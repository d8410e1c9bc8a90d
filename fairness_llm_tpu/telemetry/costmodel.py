"""Analytical per-op cost ledger: jaxpr-walked bytes+FLOPs per component,
published live at compile time for every compiled decode program.

ROADMAP item 3's headline — decode stuck at 0.4-0.5 of measured achievable
HBM bandwidth — was a single scalar (``achieved_over_achievable``) with no
live attribution: before choosing between fused multi-token steps (Kernel
Looping, arxiv 2410.23668) and tree speculation, the question is *per
compiled program*, how much of the gap is host sync, dispatch,
gather/scatter traffic, or genuinely memory-bound in-step work. That
accounting existed only as an offline TPU-only xplane tool
(``tools/account_decode_step.py``). This module makes it live, on any
backend, with no profiler capture:

- **Shared component taxonomy** — one first-match-wins classifier with two
  views: ``COMPONENTS`` (regex over XLA/xplane op names — the table
  ``tools/account_decode_step.py`` now imports instead of owning a private
  copy) and ``classify_eqn`` (jaxpr primitives, with a rank heuristic
  separating attention dots from parameter matmuls). Both emit the same
  labels: ``attention`` / ``kv_rw`` / ``weights_dma`` / ``matmuls`` /
  ``norms_elementwise`` / ``sampling`` / ``gather_scatter`` / ``control``
  / ``collectives`` (TP communication — explicit psum/all_gather prims in
  shard_map-manual jaxprs, the matching op names in xplane captures, and
  the analytic ``tp_collective_costs`` rows for GSPMD-auto programs whose
  collectives XLA inserts after partitioning, invisibly to the trace).
- **Jaxpr cost walk** — ``jaxpr_ledger`` walks EVERY equation of a compiled
  program's jaxpr (recursing through pjit/cond/scan/custom calls),
  accumulating analytical bytes (input + output aval sizes — the
  nothing-fuses upper bound on memory traffic) and FLOPs (exact for
  ``dot_general``, one-per-output-element otherwise) per component.
  Equations inside a ``while_loop`` body land in the ``per_step`` table
  (the decode loop runs them once per token); everything else is
  ``per_call`` (prefill, a donor row's gather and the extent's scatter,
  setup).
- **Compile-time hook** — ``instrument_jit`` wraps the decode-program
  builders where ``telemetry/compilestats.py`` already intercepts compiles
  (engine ``decode``/``spec_decode``/``prefix``, serving
  ``serve_prefill``/``serve_step``): the first attribution-on invocation
  traces the python function once more (``jax.make_jaxpr`` — a sliver next
  to the XLA compile happening on the same call) and publishes
  ``cost_ledger_bytes{program, component, scope}`` /
  ``cost_ledger_flops{...}`` gauges.
- **Gap attribution** — per invocation, ``note_invocation`` accumulates
  measured wall / steps / calls and the ledger's per-component min-time
  (``max(bytes/achievable_bw, flops/achievable_flops)``), and
  ``timeline.decode_chunk`` accumulates the MEASURED between-chunk host
  gap per program, so

      measured wall + host gap = host gap (measured)
                               + dispatch (calls x nominal per-dispatch)
                               + sum(component min-times)   [the floor]
                               + unattributed in-step time  [residual]

  sums exactly by construction — ``render_cost_report`` (the
  ``perf-report`` CLI subcommand, also appended to ``telemetry-report``)
  prints the decomposition per program and names the top gap contributor
  among the non-floor terms. The bytes model is a NOTHING-FUSES upper
  bound, so a negative residual means XLA fused intermediates the model
  charged for; the report says so rather than clamping.

Gated, like the whole attribution layer, on ``timeline.attribution_on()``.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import re
import time
from typing import Dict, List, Optional, Tuple

import jax

from fairness_llm_tpu.telemetry.compilestats import book_stage, program_scope
from fairness_llm_tpu.telemetry.registry import get_registry
from fairness_llm_tpu.telemetry.timeline import attribution_on

logger = logging.getLogger(__name__)

# -- the shared component taxonomy --------------------------------------------
# First-match-wins classification of XLA op names (xplane captures, fusion
# names). Moved VERBATIM in pattern and order from
# tools/account_decode_step.py (round-3/4 traces: multiply_reduce over score
# tensors, dynamic-update-slice cache writes, async slice-starts for weight
# DMA); only the labels changed, to the shared taxonomy the jaxpr walk and
# the live gauges use. The ordering is load-bearing (first match wins) and
# pinned by tests/test_costmodel.py's historical-fixture regression.
COMPONENTS: List[Tuple[str, "re.Pattern"]] = [
    # Collectives FIRST: "all-gather"/"reduce-scatter" would otherwise fall
    # into gather_scatter, and "all-reduce" must never reach any pattern
    # with a bare "reduce". No bare "reduce" HERE either —
    # "reduce_fusion"/"multiply_reduce" (attention score math) must keep
    # classifying as attention, pinned by the historical-op fixtures.
    ("collectives", re.compile(
        r"all-reduce|all-gather|reduce-scatter|collective-permute"
        r"|all-to-all|psum")),
    ("attention", re.compile(
        r"multiply_reduce|reduce_fusion|softmax|exponential|divide_fusion")),
    ("kv_rw", re.compile(r"dynamic-update-slice|update_slice")),
    ("weights_dma", re.compile(
        r"^(slice|bitcast|copy)|slice-start|copy-start|copy-done|slice_fusion")),
    ("matmuls", re.compile(r"dot|matmul|convolution|einsum")),
    ("norms_elementwise", re.compile(
        r"rsqrt|norm|add_fusion|multiply_fusion|subtract|tanh|gelu|silu|logistic")),
    ("sampling", re.compile(r"sort|argmax|rng|random|iota|cumsum|select_n|compare")),
    ("gather_scatter", re.compile(r"gather|scatter")),
    ("control", re.compile(r"while|condition|tuple|parameter|constant")),
]

# Human-readable expansions for report rendering (the labels themselves stay
# short so they fit metric label values).
COMPONENT_TITLES = {
    "collectives": "collectives (TP comm)",
    "attention": "attention (scores/softmax)",
    "kv_rw": "KV read-write (DUS)",
    "weights_dma": "weight DMA / slices",
    "matmuls": "matmuls (params)",
    "norms_elementwise": "norms/elementwise",
    "sampling": "sampling/argmax/rng",
    "gather_scatter": "gather-scatter",
    "control": "loop/control",
}


def classify(name: str) -> str:
    """Classify one XLA op name into the shared taxonomy (first match wins);
    'other' when nothing matches — identical matching behavior to the table
    ``tools/account_decode_step.py`` used to own."""
    low = name.lower()
    for label, pat in COMPONENTS:
        if pat.search(low):
            return label
    return "other"


# -- jaxpr-level classification ------------------------------------------------

_KV_PRIMS = frozenset({"dynamic_update_slice", "dynamic_slice"})
# Cross-device communication primitives. These appear in a jaxpr only where
# collectives are explicit at trace time — shard_map-manual code (QuantDense's
# psum) or hand-written pmap-era programs. GSPMD-auto programs (the serving
# step programs under a tp mesh) get their collectives inserted by XLA AFTER
# partitioning, invisibly to make_jaxpr — those programs carry the analytic
# row `tp_collective_costs` computes instead (see `instrument_jit`'s
# ``collectives=`` hook).
_COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_reduce",
    "reduce_scatter", "ppermute", "pshuffle", "all_to_all",
    "psum_scatter", "pbroadcast",
})
_SAMPLING_PRIMS = frozenset({
    "sort", "argmax", "argmin", "top_k", "threefry2x32", "random_bits",
    "random_seed", "random_wrap", "random_fold_in", "random_unwrap",
    "iota", "cumsum", "cumlogsumexp",
})
_ELEMENTWISE_PRIMS = frozenset({
    "exp", "exp2", "log", "log1p", "tanh", "logistic", "rsqrt", "sqrt",
    "erf", "add", "sub", "mul", "div", "max", "min", "neg", "abs", "pow",
    "integer_pow", "expm1", "square",
})


def _aval_items(var) -> Tuple[int, int]:
    """(element count, itemsize) of a jaxpr var/literal; (0, 0) for
    non-array avals (tokens, unit)."""
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0, 0
    n = 1
    for d in shape:
        n *= int(d)
    return n, dtype.itemsize


def _eqn_bytes(eqn) -> int:
    """The nothing-fuses memory traffic of one equation: every input and
    output aval once. An upper bound — XLA keeps fused intermediates in
    registers — which is exactly what makes the residual in the gap
    decomposition interpretable (negative residual = fusion won)."""
    total = 0
    for v in list(eqn.invars) + list(eqn.outvars):
        n, item = _aval_items(v)
        total += n * item
    return total


def _dot_flops(eqn) -> int:
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    lhs_shape = eqn.invars[0].aval.shape
    contracted = 1
    for d in lhs_c:
        contracted *= int(lhs_shape[d])
    out_elems = 1
    for d in eqn.outvars[0].aval.shape:
        out_elems *= int(d)
    return 2 * out_elems * contracted


def _eqn_flops(eqn) -> int:
    if eqn.primitive.name == "dot_general":
        return _dot_flops(eqn)
    # One op per output element — right for elementwise, an undercount for
    # reductions' intermediate adds, zero-ish for pure data movement; the
    # decode floor is bytes-dominated either way.
    total = 0
    for v in eqn.outvars:
        n, _ = _aval_items(v)
        total += n
    return total


def _max_ndim(eqn) -> int:
    nd = 0
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape is not None:
            nd = max(nd, len(shape))
    return nd


def classify_eqn(eqn) -> str:
    """Classify one jaxpr equation into the shared taxonomy.

    Attention is structural, not nominal: in this codebase hidden states are
    rank-3 ``[B, S, D]`` while attention scores (and the softmax/mask math
    over them) are rank-4 ``[B, H, S, T]`` — so a ``dot_general`` (or any
    elementwise/reduce op) touching a rank-4 operand is attention work, and
    rank-<=3 dots are parameter matmuls. KV-cache updates classify first
    (the cache is rank-4 too, but a DUS on it is KV traffic, not score
    math)."""
    name = eqn.primitive.name
    if name in _COLLECTIVE_PRIMS:
        return "collectives"
    if name in _KV_PRIMS:
        return "kv_rw"
    if name == "gather" or name.startswith("scatter"):
        return "gather_scatter"
    if name in _SAMPLING_PRIMS:
        return "sampling"
    if name in ("dot_general", "conv_general_dilated"):
        return "attention" if _max_ndim(eqn) >= 4 else "matmuls"
    if _max_ndim(eqn) >= 4:
        return "attention"
    if name in _ELEMENTWISE_PRIMS or name.startswith("reduce_"):
        return "norms_elementwise"
    return "control"


# -- the ledger ----------------------------------------------------------------


@dataclasses.dataclass
class ComponentCost:
    bytes: int = 0
    flops: int = 0

    def add(self, b: int, f: int) -> None:
        self.bytes += b
        self.flops += f

    def min_time_s(self, bytes_per_s: float, flops_per_s: float) -> float:
        """The analytic floor for this component: whichever of the memory
        and compute walls binds."""
        bt = self.bytes / bytes_per_s if bytes_per_s > 0 else 0.0
        ft = self.flops / flops_per_s if flops_per_s > 0 else 0.0
        return max(bt, ft)


@dataclasses.dataclass
class CostLedger:
    """Per-component analytical cost of one compiled program: ``per_call``
    counts equations outside any ``while_loop`` once per invocation;
    ``per_step`` counts loop-body (and loop-cond) equations once per loop
    iteration — the decode step."""

    program: str
    per_call: Dict[str, ComponentCost] = dataclasses.field(default_factory=dict)
    per_step: Dict[str, ComponentCost] = dataclasses.field(default_factory=dict)

    def _table(self, scope: str) -> Dict[str, ComponentCost]:
        return self.per_step if scope == "step" else self.per_call

    def record(self, scope: str, component: str, b: int, f: int) -> None:
        self._table(scope).setdefault(component, ComponentCost()).add(b, f)

    @property
    def has_loop(self) -> bool:
        return bool(self.per_step)

    def components(self) -> List[str]:
        return sorted(set(self.per_call) | set(self.per_step))

    def min_times_s(self, steps: float, bytes_per_s: float,
                    flops_per_s: float) -> Dict[str, float]:
        """Per-component analytic floor of one invocation that ran ``steps``
        loop iterations: per-call cost once + per-step cost x steps."""
        out: Dict[str, float] = {}
        for comp in self.components():
            t = 0.0
            c = self.per_call.get(comp)
            if c is not None:
                t += c.min_time_s(bytes_per_s, flops_per_s)
            s = self.per_step.get(comp)
            if s is not None:
                t += steps * s.min_time_s(bytes_per_s, flops_per_s)
            out[comp] = t
        return out


_SUBJAXPR_SCAN_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _walk(jaxpr, ledger: CostLedger, scope: str, repeat: int = 1) -> None:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def inner(sub, sub_scope: str, sub_repeat: int = 1) -> None:
        if isinstance(sub, ClosedJaxpr):
            sub = sub.jaxpr
        _walk(sub, ledger, sub_scope, sub_repeat)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "while":
            # The decode loop: cond + body run once per iteration. A while
            # nested inside a step body stays per_step (we never compound
            # unknown trip counts — the decode programs have exactly one
            # loop level, pinned by the six-variant ledger test).
            inner(eqn.params["cond_jaxpr"], "step", repeat)
            inner(eqn.params["body_jaxpr"], "step", repeat)
            continue
        if name == "scan":
            inner(eqn.params["jaxpr"], scope,
                  repeat * int(eqn.params.get("length", 1)))
            continue
        if name == "cond":
            # One branch executes; charge the most expensive one (the floor
            # stays a floor only if we never charge branches that didn't
            # run — max over branches is the conservative single choice).
            branches = eqn.params.get("branches") or ()
            best, best_cost = None, -1
            for br in branches:
                probe = CostLedger(program="_branch")
                b = br.jaxpr if isinstance(br, ClosedJaxpr) else br
                _walk(b, probe, "call")
                cost = sum(c.bytes for c in probe.per_call.values())
                if cost > best_cost:
                    best, best_cost = br, cost
            if best is not None:
                inner(best, scope, repeat)
            continue
        handled_sub = False
        for key in _SUBJAXPR_SCAN_KEYS:
            sub = eqn.params.get(key)
            if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                inner(sub, scope, repeat)
                handled_sub = True
                break
        if handled_sub:
            continue
        ledger.record(scope, classify_eqn(eqn),
                      repeat * _eqn_bytes(eqn), repeat * _eqn_flops(eqn))


def jaxpr_ledger(closed_jaxpr, program: str) -> CostLedger:
    """Walk a (closed) jaxpr into a :class:`CostLedger`."""
    ledger = CostLedger(program=program)
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    _walk(jaxpr, ledger, "call")
    return ledger


def tp_collective_costs(model_config, tp: int, rows: int, tokens: int = 1,
                        scope: str = "step") -> List[Tuple[str, int, int]]:
    """Analytic collective traffic of one GSPMD tensor-parallel forward —
    the ``collectives`` ledger row for programs whose jaxpr cannot show it.

    Under ``AxisType.Auto`` meshes XLA inserts the TP collectives AFTER
    partitioning, so a serving program's ``make_jaxpr`` trace has none to
    walk (only shard_map-manual code, e.g. QuantDense's psum, traces
    them). This models the megatron pattern the sharding rules produce,
    per forward of ``rows x tokens`` positions:

    - one ring all-reduce of the ``[rows, tokens, d_model]`` activation
      after each ROW-PARALLEL projection — the attention o-proj (when the
      head axis shards) and the MLP down-proj (when the ff axis shards) —
      at the ring cost of ``2 (tp-1)/tp`` bytes moved per device per
      all-reduced byte;
    - one all-gather of the ``[rows, tokens, vocab]`` logits when the lm
      head shards, at ``(tp-1)/tp`` bytes.

    Divisibility gates mirror ``parallel.sharding.make_axis_rules`` — an
    axis that falls back to replicated produces no collective. FLOPs are
    reported as 0 (comm is bandwidth, not compute). Returns ``[]`` when
    nothing shards, so an effectively-replicated "mesh" run charges
    nothing. Like the whole ledger, this is an analytic NOTHING-FUSES
    model, not a measurement — the xplane table's ``collectives`` entry is
    the measured view when a profiler capture exists.
    """
    if tp <= 1:
        return []
    itemsize = 2 if model_config.dtype == "bfloat16" else 4
    act_bytes = rows * tokens * model_config.d_model * itemsize
    all_reduces = (int(model_config.num_heads % tp == 0)
                   + int(model_config.d_ff % tp == 0))
    total = int(model_config.num_layers * all_reduces
                * act_bytes * 2 * (tp - 1) / tp)
    if model_config.vocab_size % tp == 0:
        total += int(rows * tokens * model_config.vocab_size * itemsize
                     * (tp - 1) / tp)
    if total <= 0:
        return []
    return [(scope, total, 0)]


# -- reference rates -----------------------------------------------------------
# The compute roofline comes from the same per-device table as the streaming
# one (``roofline.DEVICE_PEAKS``): a device without a row has no floor, and
# the gauges that need one are not published there. The per-dispatch host
# overhead is a nominal estimate (the report labels it so), not a peak; the
# decomposition's measured terms (wall, host gap) are exact either way.

TPU_DISPATCH_S = 5e-5
CPU_DISPATCH_S = 2e-4

_gflops_override: Optional[float] = None
_dispatch_override: Optional[float] = None


def set_achievable_gflops(gflops: Optional[float]) -> None:
    global _gflops_override
    _gflops_override = float(gflops) if gflops else None


def set_dispatch_s(seconds: Optional[float]) -> None:
    global _dispatch_override
    _dispatch_override = float(seconds) if seconds else None


def _publishable_rates() -> Optional[Tuple[float, float]]:
    """(GB/s, GFLOP/s) the floor is priced at, or None when the device has
    no published peaks and no override covers the gap."""
    from fairness_llm_tpu.telemetry.roofline import (
        known_device_peaks,
        publishable_gbps,
    )

    gbps = publishable_gbps()
    gflops = _gflops_override
    if gflops is None:
        peaks = known_device_peaks()
        gflops = peaks.bf16_gflops if peaks is not None else None
    if gbps is None or gflops is None:
        return None
    return gbps, gflops


def reference_dispatch_s() -> float:
    if _dispatch_override is not None:
        return _dispatch_override
    return TPU_DISPATCH_S if jax.default_backend() == "tpu" else CPU_DISPATCH_S


# -- publication ---------------------------------------------------------------


def publish_ledger(ledger: CostLedger) -> None:
    """Publish one program's ledger as gauges:
    ``cost_ledger_bytes{program, component, scope}`` (scope ``step`` = one
    decode-loop iteration, ``call`` = the per-invocation remainder) and the
    matching ``cost_ledger_flops``."""
    if not attribution_on():
        return
    reg = get_registry()
    for scope in ("call", "step"):
        for comp, c in ledger._table(scope).items():
            lbl = dict(program=ledger.program, component=comp, scope=scope)
            reg.gauge("cost_ledger_bytes", **lbl).set(c.bytes)
            reg.gauge("cost_ledger_flops", **lbl).set(c.flops)


def note_invocation(program: str, wall_s: float, steps: int = 0,
                    ledger: Optional[CostLedger] = None,
                    compiling: bool = False) -> None:
    """Accumulate one compiled-program invocation into the gap-attribution
    gauges: measured wall / steps / calls per program, the reference rates
    (so a report re-derives min-times from the snapshot alone), and — when
    the caller holds the program's ledger — the per-component analytic
    floor ``cost_component_min_s_total{program, component}``. Unlabeled by
    replica on purpose: the decomposition is per PROGRAM, a fleet's N
    replicas fold into one accumulation.

    ``compiling`` marks a first-call invocation whose wall is XLA-compile-
    dominated (the caller's ``first_compile`` knowledge): its wall ALSO
    accumulates into ``cost_compile_s_total`` so the decomposition reports
    compile time as its own named contributor instead of letting a cold
    run's compile wall masquerade as "unattributed in-step" work."""
    if not attribution_on():
        return
    reg = get_registry()
    lbl = dict(component="costmodel", program=program)
    reg.gauge("cost_wall_s_total", **lbl).add(max(float(wall_s), 0.0))
    reg.gauge("cost_steps_total", **lbl).add(float(steps))
    reg.gauge("cost_calls_total", **lbl).add(1.0)
    if compiling:
        # The whole compiling call's wall, an upper bound — it includes the
        # call's own floor-charged work, so the residual on a compile-only
        # program can read slightly negative; compile dominates in practice.
        reg.gauge("cost_compile_s_total", **lbl).add(
            max(float(wall_s), 0.0))
    reg.gauge("cost_dispatch_s", component="costmodel").set(
        reference_dispatch_s())
    rates = _publishable_rates()
    if rates is None:
        return  # no peaks for this device: no reference gauges, no floor
    gbps, gflops = rates
    reg.gauge("cost_reference_gbps", component="costmodel").set(gbps)
    reg.gauge("cost_reference_gflops", component="costmodel").set(gflops)
    if ledger is not None:
        for comp, sec in ledger.min_times_s(
                steps, gbps * 1e9, gflops * 1e9).items():
            reg.gauge("cost_component_min_s_total", program=program,
                      component=comp).add(sec)


# -- the compile-time hook -----------------------------------------------------


class InstrumentedJit:
    """A ``jax.jit`` wrapper that computes and publishes the program's cost
    ledger on its first attribution-on invocation.

    The ``jax.make_jaxpr`` trace runs at most once per compiled program,
    on the same call that pays the XLA compile, BEFORE the jitted call —
    donated input buffers are gone after it. JAX 0.9 keeps the jaxpr it
    traced, so the jitted call does not trace again: what the ledger adds
    is its walk, booked as the ``ledger_trace`` compile stage (0.18 s a
    program at 7B, PERF.md). A failed trace logs once and never fails the decode;
    the jitted function is untouched either way.

    ``collectives``: optional ``[(scope, bytes, flops), ...]`` rows folded
    into the ledger's ``collectives`` component after the walk — the
    analytic traffic of GSPMD-inserted collectives a tp>1 program executes
    but ``make_jaxpr`` cannot see (``tp_collective_costs`` computes them
    from the sharding rules). Skipped when the walk already found explicit
    collectives (shard_map-manual programs), so nothing double-counts."""

    def __init__(self, pyfn, program: str, collectives=None, **jit_kwargs):
        self._pyfn = pyfn
        self._jit = jax.jit(pyfn, **jit_kwargs)
        self.program = program
        self.ledger: Optional[CostLedger] = None
        self._ledger_failed = False
        self._memory_done = False
        self._memory_failed = False
        self._collectives = list(collectives or ())
        self._fn = _fun_name(pyfn)
        self._called = False

    def __call__(self, *args):
        # Every compile stage JAX reports inside this call is booked to
        # this program (telemetry/compilestats.py), whenever it happens;
        # on the first call, what the call takes beyond them as well.
        first, self._called = not self._called, True
        with program_scope(self.program, self._fn, first):
            self._first_call_instrumentation(args)
            return self._jit(*args)

    def _first_call_instrumentation(self, args) -> None:
        if self.ledger is None and not self._ledger_failed \
                and attribution_on():
            t0 = time.monotonic()
            try:
                ledger = jaxpr_ledger(
                    jax.make_jaxpr(self._pyfn)(*args), self.program
                )
                if self._collectives and not any(
                        "collectives" in ledger._table(s)
                        for s in ("call", "step")):
                    for scope, b, f in self._collectives:
                        ledger.record(scope, "collectives", int(b), int(f))
                self.ledger = ledger
                publish_ledger(self.ledger)
            except Exception as e:  # noqa: BLE001 — diagnostics only
                self._ledger_failed = True
                logger.warning("cost ledger for %s unavailable: %s: %s",
                               self.program, type(e).__name__, e)
            # What this instrumentation adds to the first call, as a stage
            # of its own beside JAX's (trace, lower, backend_compile): the
            # block's wall less the tracing JAX reported inside it.
            book_stage("ledger_trace", time.monotonic() - t0)
        if not self._memory_done and not self._memory_failed:
            # AOT memory_analysis capture (ISSUE 18): what XLA itself
            # budgeted for this program — temp/argument/output/peak — as
            # program_memory_bytes gauges. Costs a second compile, so the
            # capture flag stays off unless the exporters armed it; like
            # the walk above it runs before the jitted call (donation) and
            # inside the caller's mesh context (tp programs lower SPMD).
            from fairness_llm_tpu.telemetry.memory import (  # lazy: no cycle
                aot_memory_capture_on, capture_program_memory,
            )

            if aot_memory_capture_on():
                try:
                    capture_program_memory(self._jit, self._pyfn,
                                           self.program, args)
                    self._memory_done = True
                except Exception as e:  # noqa: BLE001 — diagnostics only
                    self._memory_failed = True
                    logger.warning(
                        "AOT memory analysis for %s unavailable: %s: %s",
                        self.program, type(e).__name__, e)


def _fun_name(fn) -> str:
    """The name JAX reports ``fn``'s tracing under (``jax._src.util``)."""
    name = getattr(fn, "__name__", None)
    if name is not None:
        return name
    if isinstance(fn, functools.partial):
        return _fun_name(fn.func)
    return "<unnamed function>"


def instrument_jit(pyfn, program: str, collectives=None,
                   **jit_kwargs) -> InstrumentedJit:
    """``jax.jit`` + cost-ledger instrumentation — the drop-in the decode
    program builders use. ``jit_kwargs`` pass through (``donate_argnums``
    for the step programs); ``collectives`` injects the analytic tp
    communication rows (see :class:`InstrumentedJit`)."""
    return InstrumentedJit(pyfn, program, collectives=collectives,
                           **jit_kwargs)


# -- gap decomposition / report ------------------------------------------------


def gap_decomposition(snap: Dict) -> Dict[str, Dict]:
    """Per-program gap attribution from a telemetry snapshot:

        wall + host_gap = floor (sum component min-times) + dispatch
                        + unattributed + host_gap

    All four right-hand terms are returned per program (summing exactly to
    the measured left side by construction — ``unattributed`` is the
    residual), plus the per-component floor table and the top gap
    contributor among the measured/estimated non-floor terms."""
    gauges = snap.get("gauges", [])

    def rows(name):
        return [g for g in gauges if g.get("name") == name]

    def val(name, **want) -> float:
        for g in rows(name):
            lb = g.get("labels", {})
            if all(lb.get(k) == v for k, v in want.items()):
                return float(g.get("value", 0.0))
        return 0.0

    dispatch_s = val("cost_dispatch_s")
    out: Dict[str, Dict] = {}
    programs = sorted({g.get("labels", {}).get("program")
                       for g in rows("cost_wall_s_total")} - {None})
    for p in programs:
        wall = val("cost_wall_s_total", program=p)
        calls = val("cost_calls_total", program=p)
        steps = val("cost_steps_total", program=p)
        host_gap = val("cost_host_gap_s_total", program=p)
        compile_s = val("cost_compile_s_total", program=p)
        comps = {
            g["labels"].get("component"): float(g.get("value", 0.0))
            for g in rows("cost_component_min_s_total")
            if g.get("labels", {}).get("program") == p
        }
        floor = sum(comps.values())
        dispatch = calls * dispatch_s
        unattributed = wall - dispatch - floor - compile_s
        total = wall + host_gap
        ledger = {}
        for g in rows("cost_ledger_bytes"):
            lb = g.get("labels", {})
            if lb.get("program") != p:
                continue
            key = (lb.get("component"), lb.get("scope"))
            ledger[key] = {"bytes": float(g.get("value", 0.0))}
        for g in rows("cost_ledger_flops"):
            lb = g.get("labels", {})
            if lb.get("program") != p:
                continue
            key = (lb.get("component"), lb.get("scope"))
            ledger.setdefault(key, {})["flops"] = float(g.get("value", 0.0))
        contributors = {"host_gap": host_gap, "dispatch": dispatch,
                        "compile": compile_s,
                        "unattributed_in_step": unattributed}
        top = max(contributors, key=lambda k: contributors[k]) \
            if total > 0 else None
        aoa = None
        for g in rows("achieved_over_achievable"):
            if g.get("labels", {}).get("program") == p:
                aoa = float(g.get("value", 0.0))
        out[p] = {
            "wall_s": wall,
            "host_gap_s": host_gap,
            "calls": calls,
            "steps": steps,
            "dispatch_s": dispatch,
            "compile_s": compile_s,
            "floor_s": floor,
            "floor_components_s": comps,
            "unattributed_s": unattributed,
            "total_s": total,
            "sum_check_s": (floor + dispatch + compile_s + unattributed
                            + host_gap),
            "achieved_over_achievable": aoa,
            "top_gap_contributor": top,
            "ledger": ledger,
        }
    return out


def has_cost_data(snap: Dict) -> bool:
    return any(g.get("name") == "cost_wall_s_total"
               for g in snap.get("gauges", []))


def _fmt_bytes(b: float) -> str:
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if b >= div:
            return f"{b / div:.2f} {unit}"
    return f"{b:.0f} B"


def _fmt_s(s: float) -> str:
    if abs(s) >= 1.0:
        return f"{s:.3f} s"
    return f"{s * 1e3:.2f} ms"


def render_cost_report(snap: Dict, width: int = 78) -> str:
    """Terminal renderer of the cost ledger + gap decomposition — the
    ``perf-report`` CLI subcommand, appended to ``telemetry-report`` when a
    run recorded the ledger."""
    lines = ["=" * width, "DECODE COST LEDGER / GAP ATTRIBUTION", "=" * width]
    decomp = gap_decomposition(snap)
    if not decomp:
        lines.append("(no cost-ledger data — was the attribution layer on?)")
        return "\n".join(lines)
    gauges = snap.get("gauges", [])

    def ref(name):
        for g in gauges:
            if g.get("name") == name:
                return float(g.get("value", 0.0))
        return 0.0

    if ref("cost_reference_gbps"):
        lines.append(
            f"references: {ref('cost_reference_gbps'):g} GB/s streaming, "
            f"{ref('cost_reference_gflops'):g} GFLOP/s, "
            f"{ref('cost_dispatch_s') * 1e6:g} us/dispatch (nominal)"
        )
    else:
        lines.append(
            "references: none (the device has no published peaks, so no "
            f"floor is priced); {ref('cost_dispatch_s') * 1e6:g} "
            "us/dispatch (nominal)"
        )
    for program, d in decomp.items():
        lines.append(f"\n[{program}]  calls={d['calls']:g} "
                     f"steps={d['steps']:g}"
                     + (f"  achieved_over_achievable="
                        f"{d['achieved_over_achievable']:.3f}"
                        if d["achieved_over_achievable"] is not None else ""))
        comp_rows = sorted(d["floor_components_s"].items(),
                           key=lambda kv: -kv[1])
        if comp_rows:
            lines.append(f"  {'component':<26} {'bytes/step':>12} "
                         f"{'flops/step':>12} {'min-time':>12} {'share':>7}")
            for comp, sec in comp_rows:
                sb = d["ledger"].get((comp, "step"), {})
                cb = d["ledger"].get((comp, "call"), {})
                by = sb.get("bytes", cb.get("bytes", 0.0))
                fl = sb.get("flops", cb.get("flops", 0.0))
                share = sec / d["floor_s"] if d["floor_s"] > 0 else 0.0
                lines.append(
                    f"  {COMPONENT_TITLES.get(comp, comp):<26} "
                    f"{_fmt_bytes(by):>12} {fl:>12.3g} "
                    f"{_fmt_s(sec):>12} {share:>6.1%}"
                )
        total = d["total_s"]

        def pct(x):
            return f"{x / total:6.1%}" if total > 0 else "     -"

        lines.append(f"  measured: chunk wall {_fmt_s(d['wall_s'])} "
                     f"+ host gap {_fmt_s(d['host_gap_s'])} "
                     f"= {_fmt_s(total)}")
        lines.append(f"    floor (sum component min-time) "
                     f"{_fmt_s(d['floor_s']):>12}  {pct(d['floor_s'])}")
        lines.append(f"    dispatch (estimated)           "
                     f"{_fmt_s(d['dispatch_s']):>12}  {pct(d['dispatch_s'])}")
        lines.append(f"    compile (first-call walls)     "
                     f"{_fmt_s(d['compile_s']):>12}  {pct(d['compile_s'])}")
        lines.append(f"    unattributed in-step           "
                     f"{_fmt_s(d['unattributed_s']):>12}  "
                     f"{pct(d['unattributed_s'])}")
        lines.append(f"    host gap (measured)            "
                     f"{_fmt_s(d['host_gap_s']):>12}  {pct(d['host_gap_s'])}")
        ok = abs(d["sum_check_s"] - total) <= max(1e-9, 1e-6 * total)
        lines.append(f"    sum check: {'OK' if ok else 'MISMATCH'} "
                     f"(components sum to the measured wall)")
        if d["unattributed_s"] < 0:
            lines.append("    note: negative residual — the nothing-fuses "
                         "byte model charged intermediates XLA fused away")
        if d["top_gap_contributor"] is not None:
            lines.append(f"  top gap contributor: {d['top_gap_contributor']}")
    return "\n".join(lines)
