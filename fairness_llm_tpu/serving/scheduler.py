"""Continuous-batching step loop: prefill/decode interleaving over a slot pool.

The static engine (``runtime/engine.py``) runs one compiled program per
batch: every row pads to the longest prompt, finished rows burn decode steps
until the whole chunk drains, and new work waits outside. This scheduler
instead keeps ONE persistent device cache of ``num_slots`` rows and runs an
admission loop:

1. expire deadlined requests (queued or mid-decode)
2. backfill free slots from the admission queue — admitted prompts prefill
   in groups bucketed by prompt length ([nb, P] compiled shapes, P a
   multiple of the engine's seq bucket), each row scattering its KV into its
   slot's row of the shared cache
3. decode every live slot ``decode_chunk`` steps in one compiled
   while_loop: per-row sampling streams (seeded on request identity),
   per-row KV ``write_offsets`` (the machinery the speculative-decoding PR
   added to the transformer), per-row EOS/budget stopping
4. evict finished rows, release their slots (device-side ``key_valid``
   invalidation before reuse), and loop

Compiled-program inventory stays bounded: one decode-step program (slot
invalidation rides on its reset mask) and one prefill program per
(batch-bucket, prompt-bucket) pair — independent of workload size or mix.

Greedy parity is the correctness contract (pinned in tests/test_serving.py):
a request decodes the SAME tokens through the server as through
``DecodeEngine.generate([prompt])`` alone. It holds by construction: each
slot reproduces the engine's batch-1 layout exactly — left-padded prompt in
cache slots [0, P), decode writes at ``P + emitted``, positions counted over
real tokens, attention masked to the row's own valid keys — and padding
/ pool composition contribute exact zeros to every reduction.

Sampled decode works too (the per-row fold_in(emitted) key stream equals the
engine's fold_in(step) stream row-for-row); only sampler SETTINGS are
per-scheduler, because sampling is baked into the compiled step program.

Fault containment (``utils/failures.py``): an injected or device-raised
decode/prefill fault releases the hit slots and requeues each request once;
a second fault surfaces as a failed ``Result``. The loop itself never dies.

Resilience (``resilience/``, opt-in via ``ResilienceConfig``): a step
watchdog classifies over-budget compiled calls as ``HangFault`` (contained
exactly like a decode fault), per-stage circuit breakers stop hammering a
persistently-failing prefill/decode (open state skips the stage until a
half-open probe), breaker trips advance a degradation ladder (drop
speculation -> halve decode chunk + soft-cap the pool -> the backend's
static-engine fallback), and a drain request (SIGTERM/SIGINT via
``GracefulDrain``, or ``request_drain()``) stops admission, gives live
slots ``drain_grace_s`` to finish, and preempts the rest — journaled
requests resume in a successor process via ``resume-serving``.

Integrity (``integrity/``): when the engine's ``numerics_guards`` flag is
set, every compiled prefill/decode program AND-reduces a finite check of
its logits into one flag per chunk; a tripped flag discards the chunk as a
containable ``NumericsFault`` (requeue-once, breaker-visible, counted in
``numerics_faults_total``) instead of delivering silently-garbage tokens.
``ScriptedFaultInjector(corruptions=...)`` poisons a request's carried
logits host-side so the guard is drillable on the CPU harness.

Tensor-parallel meshes ARE supported (``--tp N``): the scheduler accepts an
engine built over a tp-only mesh, places the persistent KV cache on the
mesh sharded along the kv-head axis (``parallel.sharding.kv_tree_shardings``)
and the carried logits along vocab, and runs every
compiled program under ``with mesh, nn.logical_axis_rules(...)`` so the
whole step lowers as one SPMD computation with XLA-inserted collectives.
Compile keys gain a ``("tp", k)`` element and telemetry programs a
``@tp<k>`` label suffix — both byte-identical to the unsharded scheme at
tp=1. dp/sp meshes are still rejected (the slot scatter would need dp-aware
placement). Multi-replica routing
IS the next layer up — ``serving/fleet.py`` drives N of these schedulers
(one per replica, each with its own slot pool, breakers, and watchdog)
through the public ``step()`` hook, with per-replica ``{"replica": name}``
labels on every instrument this loop writes.
"""

from __future__ import annotations

import logging
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from fairness_llm_tpu.config import (
    ModelSettings,
    OverloadConfig,
    ResilienceConfig,
    ServingConfig,
)
from fairness_llm_tpu.models.transformer import (
    MOE_COUNTERS,
    RowState,
    init_cache,
)
from fairness_llm_tpu.ops import pool_attention
from fairness_llm_tpu.parallel import sharding as shd
from fairness_llm_tpu.resilience.breaker import BreakerBoard
from fairness_llm_tpu.resilience.drain import (
    ServingJournal,
    drain_requested,
    take_signal_telemetry,
)
from fairness_llm_tpu.resilience.watchdog import StepWatchdog
from fairness_llm_tpu.runtime.sampling import SamplerSettings
from fairness_llm_tpu.runtime.stepbuilder import (
    build_serve_prefill,
    build_serve_step,
    cache_rings,
    compile_key,
    extend_window,
    long_bucket,
    program_label,
)
from fairness_llm_tpu.serving.overload import (
    DeadlineEstimator,
    ShedController,
    count_shed,
)
from fairness_llm_tpu.serving.queue import AdmissionQueue, ClassedAdmissionQueue
from fairness_llm_tpu.serving.request import (
    QOS_CLASSES,
    QOS_PRIORITY,
    Request,
    Result,
)
from fairness_llm_tpu.serving.slots import LiveRowPrefixes, SlotPool, SlotState
from fairness_llm_tpu.telemetry import (
    Heartbeat,
    RequestTracer,
    emit_event,
    get_registry,
)
from fairness_llm_tpu.telemetry.compilestats import record_compile, startup_stage
from fairness_llm_tpu.telemetry.costmodel import (
    instrument_jit,
    note_invocation,
    tp_collective_costs,
)
from fairness_llm_tpu.telemetry.fairness import get_fairness_monitor
from fairness_llm_tpu.telemetry.flightrecorder import get_flight_recorder
from fairness_llm_tpu.telemetry.incidents import maybe_trigger, record_decision
from fairness_llm_tpu.telemetry.memory import get_memory_ledger
from fairness_llm_tpu.telemetry.roofline import observe_decode
from fairness_llm_tpu.telemetry.timeline import get_timeline
from fairness_llm_tpu.integrity.numerics import check_finite
from fairness_llm_tpu.utils.failures import (
    DecodeFault,
    HangFault,
    NumericsFault,
)
from fairness_llm_tpu.utils.profiling import ServingStats
from fairness_llm_tpu.utils.ratelimit import RateLimiter

logger = logging.getLogger(__name__)


def _bucket_pow2(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _copyable(n: int, m: int, S: int) -> int:
    """Positions a row of ``n`` ids copies from a row that shares its first
    ``m``: all but the fewest whole windows of ``S`` that hold the rest (one
    at the least, for the row's own logits). Zero or less: nothing."""
    return n - max(1, -(-(n - m) // S)) * S


class ContinuousScheduler:
    """Drives one ``DecodeEngine``'s params/model as a continuous server.

    One scheduler = one compiled sampler (``settings`` temperature/top_k/
    top_p) + one decode-length cap. ``ServingBackend`` keeps one scheduler
    per settings tuple; direct users construct it around an engine:

        sched = ContinuousScheduler(engine, ServingConfig(num_slots=8))
        results = sched.serve([Request(prompt=p) for p in prompts])
    """

    # Memory-ledger handle sequence across scheduler instances in one
    # process (a fleet holds several; handles must not collide).
    _mem_seq = 0

    @startup_stage("scheduler")
    def __init__(
        self,
        engine,
        serving: Optional[ServingConfig] = None,
        settings: Optional[ModelSettings] = None,
        fault_injector=None,
        resilience: Optional[ResilienceConfig] = None,
        journal: Optional[ServingJournal] = None,
        breakers: Optional[BreakerBoard] = None,
        replica: Optional[str] = None,
        overload: Optional[OverloadConfig] = None,
    ):
        mesh = engine.mesh
        if mesh is not None and (mesh.shape.get("dp", 1) > 1
                                 or mesh.shape.get("sp", 1) > 1):
            raise ValueError(
                "ContinuousScheduler supports single-device and tp-only "
                "meshes (the slot scatter is not dp/sp-aware yet); build "
                "the engine with a tp-only mesh or without one"
            )
        # Tensor-parallel serving (the stepbuilder's mesh axis): every
        # compiled program runs inside ``with mesh, logical_axis_rules`` —
        # params already placed by the engine, carried KV/logits placed by
        # _place_device_state below — and keys/labels carry the mesh shape
        # (byte-identical at tp=1).
        self.mesh = mesh
        self.tp = mesh.shape.get("tp", 1) if mesh is not None else 1
        self.engine = engine
        self.serving = serving or ServingConfig(enabled=True)
        want_tp = max(1, getattr(self.serving, "tp", 1))
        if want_tp > 1 and self.tp != want_tp:
            raise ValueError(
                f"ServingConfig.tp={want_tp} but the engine's mesh is "
                f"{dict(mesh.shape) if mesh is not None else None}; build "
                "the engine over a matching tp mesh (parallel.make_mesh) — "
                "a silent single-device fallback would invalidate every "
                "mesh-labeled measurement"
            )
        self.settings = settings or ModelSettings()
        # Replica identity (serving/fleet.py): every instrument this
        # scheduler writes — tracer histograms, breaker/watchdog state,
        # fault counters — carries a {"replica": name} label so N replicas'
        # health reads apart in one registry. None (the single-engine path)
        # adds no label: metric keys are byte-identical to before.
        self.replica = replica
        self.labels = {"replica": replica} if replica else {}
        # Timeline lane (telemetry/timeline.py): fleet replicas get their
        # own track; the single-engine path shares one "serving" lane.
        self._track = replica or "serving"
        # The number of the iteration in flight (every span inside it
        # carries it as ``seq``), and the time of the earliest dispatch
        # since the last decode sync: the device is busy from then on.
        self._iter_seq = 0
        self._busy_from: Optional[float] = None
        self.sampler = SamplerSettings(
            temperature=self.settings.temperature,
            top_k=self.settings.top_k,
            top_p=self.settings.top_p,
        )
        self.fault_injector = fault_injector
        cfg = engine.config
        cap = self.serving.max_new_tokens
        if cap < 1 or cap >= cfg.max_seq_len:
            raise ValueError(
                f"max_new_tokens {cap} must be in [1, {cfg.max_seq_len})"
            )
        from fairness_llm_tpu.runtime.engine import _bucket_len

        self._bucket_len = _bucket_len
        # Per-request prompt budget: the serving knob, clamped so the longest
        # prompt + the decode cap always fit the model's position tables.
        self.prompt_budget = min(
            self.serving.max_prompt_len, cfg.max_seq_len - cap
        )
        if self.prompt_budget < 1:
            raise ValueError(
                f"no prompt budget left: max_seq_len {cfg.max_seq_len} - "
                f"max_new_tokens {cap} <= 0"
            )
        # Largest per-row prompt bucket (cache layout only — the REAL token
        # budget above is what bounds positions, so a bucket overshooting the
        # budget just leaves a few always-invalid slots per row).
        self.max_prompt_bucket = _bucket_len(self.prompt_budget, engine.seq_bucket)
        self.num_slots = self.serving.num_slots
        self.cache_len = self.max_prompt_bucket + cap
        self.pool = SlotPool(self.num_slots)
        # Overload control (serving/overload.py): with it armed, the queue
        # becomes the per-class variant and the shed controller +
        # deadline-feasibility estimator gate admission at this front door.
        # A fleet replica's scheduler is NOT the front door (the ReplicaSet
        # gates at its own intake), so the fleet passes overload=None here.
        self.overload = overload if (overload is not None and
                                     overload.enabled) else None
        rate_limiter = (RateLimiter(self.serving.admission_per_minute)
                        if self.serving.admission_per_minute else None)
        if self.overload is not None:
            self.queue: AdmissionQueue = ClassedAdmissionQueue(
                capacity=self.serving.queue_capacity,
                rate_limiter=rate_limiter, overload=self.overload,
            )
            self.shed_controller: Optional[ShedController] = ShedController(
                self.overload, labels=self.labels,
            )
            self.deadline_estimator: Optional[DeadlineEstimator] = (
                DeadlineEstimator(
                    safety=self.overload.feasibility_safety,
                    labels=self.labels,
                ) if self.overload.deadline_admission else None
            )
        else:
            self.queue = AdmissionQueue(
                capacity=self.serving.queue_capacity,
                rate_limiter=rate_limiter,
            )
            self.shed_controller = None
            self.deadline_estimator = None
        # Sheds recorded outside a drain (public submit() refusals between
        # drains) — folded into the next drain's stats like rejections.
        self._shed_untaken = 0
        # Persistent device state: the shared KV cache (one private row a
        # slot) + each slot's carried next-token logits (f32 — what the
        # sampler consumes).
        self._cache = init_cache(cfg, self.num_slots, self.cache_len,
                                 ring=True)
        # Bytes of per-row state a slot holds whatever its length (0 unless
        # a layer is recurrent): the plan takes no donor for such a model.
        self._row_state_bytes = cfg.row_state_bytes
        # What a row's per-position leaves hold, from shapes: the lengths
        # kept, and the (window, length) of each ring, a window layer's leaf
        # shorter than the row, which bounds how long a resident row stays a
        # donor (``_ring_holds``).
        kept = [jax.tree.leaves(layer) for layer in self._cache.layers
                if not isinstance(layer, RowState)]
        self._retained = sorted({leaves[0].shape[1] for leaves in kept})
        self._rings = cache_rings(cfg, self._cache)
        # The step reads the pool through ``ops/pool_attention`` (each
        # position once, an idle row not at all) where that is available,
        # and the plan (``_decode_plan``) names each row's lead. Per kind of
        # leaf that keeps something per position: (window, slots a row,
        # layers, bytes a row-position of all its leaves, the kernels'
        # blocks of a softmax leaf).
        self._pool_spans = pool_attention.available(cfg, self.tp)
        self._next_root = 0
        _, kv_heads, _ = cfg.heads_held
        kinds: Dict[tuple, int] = {}
        for mixer, w, layer in zip(cfg.mixer_kinds, cfg.layer_windows,
                                   self._cache.layers):
            if mixer in ("gqa", "mla"):
                leaves = jax.tree.leaves(layer)
                n, L = leaves[0].shape[:2]
                key = (w if mixer == "gqa" else None, L,
                       sum(x.nbytes for x in leaves) // (n * L),
                       mixer == "gqa" and leaves[0].dtype.itemsize)
                kinds[key] = kinds.get(key, 0) + 1
        self._attn_kinds = [
            (w, L, n, row_bytes, pool_attention.blocks(
                self.num_slots, L, kv_heads * cfg.head_dim, itemsize,
                cfg.heads_held[0] // kv_heads) if itemsize else None)
            for (w, L, row_bytes, itemsize), n in kinds.items()]
        self._prev_logits = jnp.zeros(
            (self.num_slots, cfg.vocab_size), jnp.float32
        )
        self._place_device_state()
        self._mem_handle = f"sched{ContinuousScheduler._mem_seq}"
        ContinuousScheduler._mem_seq += 1
        self._account_device_state()
        self._compiled: Dict[tuple, object] = {}
        # Overflow beyond queue capacity (deque: _feed pops from the head)
        self._pending: Deque[Request] = deque()
        self._results: Dict[str, Result] = {}
        # Rejections already attributed to a previous drain's stats — the
        # next drain reports only the delta, INCLUDING refusals from public
        # submit() calls made between drains.
        self._rejected_taken = 0
        self.last_stats: Optional[ServingStats] = None
        # decode_chunk: steps per compiled decode call. Larger chunks
        # amortize per-call dispatch overhead; smaller chunks backfill
        # freed slots sooner.
        self.decode_chunk = max(1, self.serving.decode_chunk)
        # fuse_steps (ISSUE 14): decode chunks folded into ONE compiled
        # dispatch — the step program runs decode_chunk x fuse_steps steps
        # before returning to the host, so the per-dispatch host gap
        # (eviction sweep, queue polls, telemetry, the device_get sync)
        # amortizes 1/fuse per token. Per-row caps/EOS stops advance
        # in-program (and the loop early-exits when every live row
        # finishes), so the token stream is identical at any fuse factor;
        # what moves to the fused boundary is eviction/backfill latency and
        # every host-side poll (drain, breaker feed, watchdog observe).
        self.fuse_steps = max(1, getattr(self.serving, "fuse_steps", 1))
        # Request-lifecycle tracing (telemetry/tracing.py): every request's
        # submitted -> admitted -> prefill_start -> first_token -> terminal
        # timeline, feeding the queue-wait/TTFT/per-token/e2e histograms in
        # the process registry. Always on — host-side timestamps only.
        self.tracer = RequestTracer(component="serving", labels=self.labels)
        self._heartbeat = Heartbeat(
            interval_s=30.0,
            name=f"serving[{replica}]" if replica else "serving",
        )
        # Resilience (resilience/): watchdog + breakers arm only when the
        # config enables them (or a shared BreakerBoard is handed in, the
        # ServingBackend case); the journal ledgers intake when present. In
        # a fault-free run these cost a few host-side timestamps per chunk
        # — the bench guard in docs/PERFORMANCE.md pins that at noise.
        self.resilience = resilience or ResilienceConfig()
        r = self.resilience
        if breakers is not None:
            self.breakers: Optional[BreakerBoard] = breakers
        elif r.enabled:
            self.breakers = BreakerBoard(
                failure_threshold=r.breaker_threshold,
                cooldown_s=r.breaker_cooldown_s,
                labels=self.labels,
            )
        else:
            self.breakers = None
        self.watchdog: Optional[StepWatchdog] = (
            StepWatchdog(r.max_step_seconds, labels=self.labels)
            if r.enabled and r.max_step_seconds > 0 else None
        )
        self.journal = journal
        # Version id stamped on this scheduler's journal records (set by
        # the fleet to its replica's rollout version; None outside a
        # fleet): resume-serving reads it to keep a resumed request's
        # token stream single-version (serving/rollout.py).
        self.journal_version: Optional[str] = None
        self._drain_flag = False
        # Per-drain grace override (request_drain(grace_s=...)): the fleet
        # fences with grace 0 — a sick replica must not keep decoding work
        # that should migrate — while signal-driven drains keep the
        # configured grace.
        self._drain_grace_override: Optional[float] = None
        # Degradation-ladder state: rung 2 halves the decode chunk and
        # soft-caps concurrent slots; both restore when the ladder retreats.
        self._base_decode_chunk = self.decode_chunk
        self._base_fuse_steps = self.fuse_steps
        self.live_cap = self.num_slots
        self._applied_level = 0

    # -- mesh placement -----------------------------------------------------

    def _place_device_state(self) -> None:
        """Pin the persistent carried state to the mesh: the KV cache
        sharded along the kv-head axis when tp divides it
        (``kv_tree_shardings``), carried logits along vocab.
        Committed placement, so the jit'd programs consume the shards
        in-place instead of re-replicating per call. No-op off-mesh."""
        if self.mesh is None:
            return
        cfg = self.engine.config
        self._cache = jax.tree.map(
            jax.device_put, self._cache,
            shd.kv_tree_shardings(cfg, self.mesh, self._cache),
        )
        self._prev_logits = jax.device_put(
            self._prev_logits, shd.logits_sharding(cfg, self.mesh))

    # -- memory ledger (ISSUE 18) -------------------------------------------

    def _account_device_state(self) -> None:
        """Register this scheduler's persistent device trees with the
        memory ledger — the slot cache under ``kv_contiguous``, the
        carried logits under ``logits_carry`` — so ``hbm_bytes{pool}``
        tracks the live trees.
        Runs at init AND after the containment rebuild (re-registering the
        same handle replaces the entry: the rebuild made new arrays of the
        same shape, and the gauges must say so rather than go stale)."""
        from fairness_llm_tpu.telemetry.memory import (  # lazy: no cycle
            get_memory_ledger,
        )

        mem = get_memory_ledger()
        # Three kinds of leaf, three pools: what a layer keeps of every
        # position (and the pool's own masks), a window layer's ring,
        # shorter than the row, and what a layer keeps per row whatever the
        # positions (a recurrent state), which no length bounds.
        per_row = [l for l in self._cache.layers if isinstance(l, RowState)]
        rings = [l for l in self._cache.layers
                 if not isinstance(l, RowState)
                 and jax.tree.leaves(l)[0].shape[1] < self.cache_len]
        mem.register("kv_contiguous", f"{self._mem_handle}:cache",
                     self._cache.replace(layers=tuple(
                         l for l in self._cache.layers
                         if not any(l is x for x in per_row + rings))),
                     replica=self.replica)
        if rings:
            mem.register("kv_window", f"{self._mem_handle}:rings", rings,
                         replica=self.replica)
        if per_row:
            mem.register("row_state", f"{self._mem_handle}:row_state",
                         per_row, replica=self.replica)
        mem.register("logits_carry", f"{self._mem_handle}:logits",
                     self._prev_logits, replica=self.replica)

    def release_memory(self) -> None:
        """Drop every ledger entry this scheduler registered — the fleet
        calls it at replica retirement (the permanent exit; fences keep
        the replica and its memory)."""
        from fairness_llm_tpu.telemetry.memory import (  # lazy: no cycle
            get_memory_ledger,
        )

        get_memory_ledger().release_matching(f"{self._mem_handle}:")

    def _run_compiled(self, fn, *args):
        """Invoke a compiled program under the mesh context: inside
        ``with mesh, nn.logical_axis_rules(...)`` the program's logical
        activation constraints resolve against the tp axis and the whole
        step lowers as ONE SPMD computation (same pattern as
        ``DecodeEngine._call``). Off-mesh this is a plain call."""
        if self.mesh is None:
            return fn(*args)
        with self.mesh, nn.logical_axis_rules(self.engine.rules):
            return fn(*args)

    # -- compiled programs --------------------------------------------------

    def _donate(self):
        # Donate the cache + carried logits, so that a decode chunk and a
        # prefill window update the pool in place instead of copying all of
        # it per call (donation is implemented on CPU too). A call that
        # raised may have consumed them: the failure paths then build the
        # device state anew (_rebuild_device_state).
        return (1, 2)

    def _guard(self) -> bool:
        """Numerics-guard flag, read from the engine (one switch for the
        static and serving paths). Part of every compiled-program key —
        guarded programs return an extra finite flag."""
        return bool(getattr(self.engine, "numerics_guards", False))

    def _step_key(self, guard: bool) -> tuple:
        """This scheduler's CURRENT decode-program key: the mutable
        ``decode_chunk`` (the degradation ladder can change it mid-run — a
        halved chunk compiles its own program and restoring reuses the
        original), the numerics-guard flag (return arity), and the fuse
        factor — the one scheme every compiled variant shares
        (``stepbuilder.compile_key``)."""
        return compile_key("serve_step", chunk=self.decode_chunk, guard=guard,
                           fuse=self.fuse_steps, tp=self.tp)

    def _step_program(self) -> str:
        """Telemetry label for the current decode program: fused dispatches
        publish their own compile stats / ledger / roofline gauges under
        ``<base>_fused`` (``validate_telemetry`` holds them to that), and
        mesh-sharded programs under a ``@tp<k>`` suffix so single-device
        and sharded measurements never mix in one series."""
        return program_label("serve_step", self.fuse_steps, tp=self.tp)

    def _collectives(self, rows: int, tokens: int, scope: str):
        """Analytic collectives rows for the cost ledger when the jaxpr
        walk can't see them (GSPMD inserts all-reduces post-partitioning,
        invisible to ``make_jaxpr``). [] at tp=1."""
        return tp_collective_costs(self.engine.config, self.tp, rows,
                                   tokens=tokens, scope=scope)

    def _prefill_fn(self, nb: int, P: int, guard: bool, copies: bool = True):
        """One window's extend of [nb] rows' ``[:P]`` extents of the shared
        cache: the builder's ``serve_prefill`` composition (see
        ``stepbuilder.build_serve_prefill`` for the program semantics;
        ``copies`` false is the program of a long bucket's batch in which no
        row copies, with its wide window). The collectives row counts the
        window's tokens."""
        key = compile_key("serve_prefill", nb=nb, P=P, guard=guard,
                          copies=copies, tp=self.tp)
        program = program_label("serve_prefill", tp=self.tp)
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        run = build_serve_prefill(
            self.engine.config, self.engine.model, nb=nb, P=P, guard=guard,
            num_slots=self.num_slots, copies=copies, rings=self._rings,
        )
        # Donated like the step: the extent's scatter writes the pool in
        # place, and the call's outputs are sound for every row it did not
        # name (it scatters at ``slots`` alone). instrument_jit = jax.jit +
        # the cost ledger (telemetry/costmodel.py) on every compiled program.
        fn = instrument_jit(
            run, program, donate_argnums=self._donate(),
            collectives=self._collectives(
                nb, extend_window(self.engine.config, P, copies, self._rings),
                "call"))
        self._compiled[key] = fn
        return fn

    def _step_fn(self):
        """The decode program: ``decode_chunk x fuse_steps`` steps in one
        while_loop — the builder's shared greedy loop entered through the
        slot pool's reset mask. See ``stepbuilder.build_serve_step``."""
        guard = self._guard()
        key = self._step_key(guard)
        program = self._step_program()
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        run = build_serve_step(
            self.engine.config, self.engine.model, self.sampler,
            self.engine.tokenizer.pad_id, self.engine.tokenizer.eos_id,
            num_slots=self.num_slots, chunk=self.decode_chunk, guard=guard,
            fuse=self.fuse_steps, pool_spans=self._pool_spans,
        )
        fn = instrument_jit(
            run, program, donate_argnums=self._donate(),
            collectives=self._collectives(self.num_slots, 1, "step"),
        )
        self._compiled[key] = fn
        return fn

    # -- submission ---------------------------------------------------------

    def _check_settings(self, request: Request) -> None:
        """Sampler-setting mismatches fail loudly — sampling is compiled
        into the step program, so a mismatched request would silently
        decode with the wrong temperature."""
        s = request.settings
        if s is None:
            return
        rs = SamplerSettings(
            temperature=s.temperature, top_k=s.top_k, top_p=s.top_p
        )
        if rs != self.sampler:
            raise ValueError(
                f"request {request.id!r} sampler settings {rs} != "
                f"scheduler sampler {self.sampler}; use a scheduler "
                "compiled for those settings"
            )

    def submit(self, request: Request, front: bool = False,
               restamp: bool = True) -> bool:
        """Queue one request; False = backpressure (queue full / rate
        quota) OR a terminal overload shed — the two read apart via
        ``take_result``: a shed leaves a claimable ``finish_reason="shed"``
        Result with a retry-after hint, backpressure leaves nothing (the
        caller may simply retry). The deadline/latency clock (re)starts
        here — a Request object built ahead of time doesn't age before the
        server sees it. ``front=True`` admits at the head of the line (the
        fleet's migration path — see ``AdmissionQueue.submit``).
        ``restamp=False`` keeps the EXISTING ``submitted_at``: the fleet
        stamped the request at its own intake, and re-stamping on routing
        (or on migration off a fenced replica) would silently extend the
        deadline and hide the fleet-queue wait from the latency — the same
        deadline-from-first-submission contract ``resume-serving``
        preserves by shrinking resumed deadlines."""
        self._check_settings(request)
        if restamp:
            request.submitted_at = time.monotonic()
        # Overload gate BEFORE acceptance: a shed request was never
        # accepted, so it carries no journal obligation (the journal's
        # zero-lost contract covers accepted work; the shed Result is the
        # explicit refusal).
        if self._overload_gate(request, journaled=False):
            return False
        accepted = self.queue.submit(request, front=front)
        if accepted:
            # Rejections are NOT recorded here: queue.rejected already counts
            # them and the next drain publishes the delta as
            # serving_rejected_total — one source of truth.
            self.tracer.record(request.id, "submitted", t=request.submitted_at)
            if self.journal is not None:
                # Ledger at ACCEPTANCE (not admission): from here on the
                # request must reach a terminal Result or survive in the
                # journal — the zero-lost contract a preemption is judged on.
                self.journal.record_submitted(request,
                                              version=self.journal_version)
        return accepted

    def take_result(self, request_id: str) -> Optional[Result]:
        """Claim (and remove) the Result of a request that terminated in an
        earlier ``serve``/``drain`` — the retrieval path for requests
        entered via ``submit()`` rather than ``serve()``."""
        return self._results.pop(request_id, None)

    def drain(self) -> ServingStats:
        """Run the loop until the queue and slot pool are empty — the
        companion to ``submit()``. Terminated requests' Results wait in
        ``take_result``."""
        stats = ServingStats(num_slots=self.num_slots)
        self._run_loop(stats)
        self.last_stats = stats
        return stats

    def serve(self, requests: Sequence[Request]) -> List[Result]:
        """Submit ``requests`` and run the loop until every one terminates.
        Overflow beyond queue capacity waits host-side and feeds in as the
        queue drains (the queue bound is admission backpressure, not a cap
        on workload size). Results come back in submission order. Requests
        already queued via ``submit()`` decode alongside; their Results stay
        claimable through ``take_result``."""
        stats = ServingStats(num_slots=self.num_slots)
        with get_timeline().span("backend.submit", "backend", self._track,
                                 stage="intake", rows=len(requests)):
            self._intake(requests)
        self._run_loop(stats)
        self.last_stats = stats
        return [self._results.pop(r.id) for r in requests]

    def _intake(self, requests: Sequence[Request]) -> None:
        # Validate the whole batch up front (same guard as submit()) so a
        # mismatched-sampler request fails loudly before any work starts,
        # and start every request's deadline/latency clock at intake.
        now = time.monotonic()
        ids = [r.id for r in requests]
        if len(set(ids)) != len(ids):
            # _results is keyed by id; a collision would overwrite one
            # request's Result and KeyError on return AFTER decoding both.
            dup = sorted({i for i in ids if ids.count(i) > 1})
            raise ValueError(f"duplicate request ids in serve() batch: {dup}")
        for r in requests:
            self._check_settings(r)
        # Spans only after the WHOLE batch validated: a mid-batch
        # _check_settings raise must not leave earlier requests' events
        # stranded in the tracer (they would never finalize).
        for r in requests:
            r.submitted_at = now
            self.tracer.record(r.id, "submitted", t=now)
            if self.journal is not None:
                self.journal.record_submitted(r, version=self.journal_version)
        self._pending = deque(requests)

    # -- internals ----------------------------------------------------------

    def request_drain(self, grace_s: Optional[float] = None) -> None:
        """Programmatic drain trigger (the signal path is ``GracefulDrain``):
        the loop stops admission at its next iteration, finishes what it can
        within ``drain_grace_s``, and preempts the rest to the journal.
        ``grace_s`` overrides the configured grace for THIS drain only —
        the fleet fences sick replicas with 0 (their live work migrates
        instead of finishing on a replica already judged unhealthy)."""
        self._drain_flag = True
        self._drain_grace_override = grace_s

    def _drain_requested(self) -> bool:
        # Own flag OR the process-wide one a GracefulDrain handler sets —
        # so one SIGTERM drains every scheduler in the process.
        return self._drain_flag or drain_requested()

    @property
    def has_work(self) -> bool:
        """Anything still owed a Result: pending overflow, queued, or live
        in a slot. The fleet router polls this to decide which replicas to
        step."""
        return bool(self._pending or len(self.queue) or self.pool.occupancy)

    def step(self, stats: ServingStats) -> bool:
        """ONE admission+decode loop iteration — the interleaving hook the
        fleet router (``serving/fleet.py``) drives so N replicas share the
        host thread instead of one ``serve()`` monopolizing it. Honors a
        pending drain request exactly as ``_run_loop`` does (executing it
        counts as not-progressed: everything preempts, nothing decodes).
        Returns True when any work moved."""
        if self._drain_requested():
            self._execute_drain(stats)
            return False
        self._apply_degradation()
        # SLO window decay (telemetry/slo.py): throttled re-evaluation so
        # the fast/slow burn gauges age out during quiet stretches instead
        # of freezing at the last terminal request's value.
        self.tracer.slo.maybe_evaluate()
        if self.shed_controller is not None:
            # Overload controller tick: one depth sample per loop
            # iteration (the self-decaying window the controller judges),
            # then a throttled ladder step — AFTER the SLO decay above so
            # the burn gauges it reads are current.
            self.shed_controller.observe_queue_depth(
                len(self.queue), self.serving.queue_capacity,
            )
            self.shed_controller.maybe_evaluate()
        progressed = self._iterate(stats)
        self._feed(stats)
        self._heartbeat.poke(
            occupancy=self.pool.occupancy, queue_depth=len(self.queue),
            completed=stats.completed, decoded_tokens=stats.decoded_tokens,
        )
        return progressed

    def finish_stats(self, stats: ServingStats) -> None:
        """Close out one drain's stats: attribute queue rejections not yet
        reported by an earlier drain — including public submit() refusals
        made BETWEEN drains (the single-threaded loop means none can occur
        during one) — and publish once (the registry accumulates process
        totals while this ServingStats object stays the per-drain
        record)."""
        stats.rejected = self.queue.rejected - self._rejected_taken
        self._rejected_taken = self.queue.rejected
        # Sheds from public submit() calls between drains (the gate runs
        # outside any drain's stats there) — same delta pattern as
        # rejections above.
        stats.shed += self._shed_untaken
        self._shed_untaken = 0
        stats.publish(labels=self.labels)
        # Reset the LIVE high-water mark to the (now drained) depth: the
        # gauge is a per-drain-window worst case for online readers (the
        # fleet router), not a lifetime one — without the reset, one
        # historical burst would discount this scheduler's placement
        # weight forever. The per-drain record keeps its own max in
        # serving_queue_depth_max.
        get_registry().gauge(
            "queue_depth_hwm", component="serving", **self.labels
        ).set(len(self.queue))
        # Step-gap cursor reset: the idle stretch between this drain and the
        # next one's first chunk is not per-step host sync.
        get_timeline().clear_track_cursor(self._track)

    def _run_loop(self, stats: ServingStats) -> None:
        self._feed(stats)
        while self.has_work:
            drained = self._drain_requested()
            if not self.step(stats) and not drained:
                # Nothing moved this iteration — rate-limited admission with
                # an empty pool, or an OPEN breaker refusing the stage while
                # work waits. Yield briefly instead of spinning the loop dry
                # (a fault-free loop with work always progresses, so this
                # never fires on the hot path). A just-executed drain is
                # exempt: it preempted everything, so the loop exits on the
                # next has_work check without sleeping.
                time.sleep(0.002)
        self.finish_stats(stats)

    def _apply_degradation(self) -> None:
        """Make the scheduler's knobs match the ladder's current rung.

        Effects-by-polling (once per loop iteration): rung 1 sheds the
        engine's speculation config (a pure-throughput feature — output is
        identical by construction, so it is the cheapest thing to lose),
        rung 2 halves the decode chunk and soft-caps concurrent slots at
        half the pool (smaller compiled steps, smaller blast radius per
        fault). Everything restores as the ladder retreats. Rung 3 (static
        fallback) is applied by ``ServingBackend``, not here — a scheduler
        cannot turn itself into the static engine mid-loop.
        """
        if self.breakers is None:
            return
        lvl = self.breakers.ladder.level
        if lvl == self._applied_level:
            return
        # Shed/restore state lives on the ENGINE (idempotent methods) and
        # is driven unconditionally by the current level: several
        # schedulers can share one engine + one board, and any per-
        # scheduler bookkeeping could capture an already-shed None or be
        # LRU-evicted before it restores.
        if lvl >= 1:
            self.engine.shed_speculation()
        else:
            self.engine.restore_speculation()
        if lvl >= 2:
            self.decode_chunk = max(1, self._base_decode_chunk // 2)
            self.live_cap = max(1, self.num_slots // 2)
            # Fused dispatch is a pure-throughput feature with a chunk-wide
            # blast radius (one fault discards fuse x chunk steps of work)
            # — rung 2's smaller-compiled-steps posture drops it to 1.
            self.fuse_steps = 1
        else:
            self.decode_chunk = self._base_decode_chunk
            self.live_cap = self.num_slots
            self.fuse_steps = self._base_fuse_steps
        logger.warning(
            "degradation rung %d (%s) applied: speculation=%s "
            "decode_chunk=%d fuse_steps=%d live_cap=%d",
            lvl, self.breakers.ladder.rung,
            "shed" if self.engine._spec_shed else "kept",
            self.decode_chunk, self.fuse_steps, self.live_cap,
        )
        self._applied_level = lvl

    def _execute_drain(self, stats: ServingStats) -> None:
        """Stop admission, give live slots ``drain_grace_s`` to finish,
        preempt everything else. Queued/pending requests never got a slot,
        so there is nothing partial to save — they preempt immediately."""
        n_queued, n_pending = len(self.queue), len(self._pending)
        n_live = self.pool.occupancy
        # Deferred signal telemetry: the SIGTERM/SIGINT handler only sets
        # flags (signal context can't safely log/emit); this is the safe
        # context that records which signals asked for the drain.
        take_signal_telemetry()
        logger.warning(
            "draining: admission stopped (%d queued, %d pending, %d live)",
            n_queued, n_pending, n_live,
        )
        emit_event("drain_started", queued=n_queued, pending=n_pending,
                   live=n_live, **self.labels)
        get_registry().counter("drains_total", component="serving",
                               **self.labels).inc()
        self.queue.close()
        try:
            for req in self._pending:
                self._preempt(req, stats)
            self._pending.clear()
            for req in self.queue.pop(len(self.queue)):
                self._preempt(req, stats)
            completed_before = stats.completed
            grace = (self._drain_grace_override
                     if self._drain_grace_override is not None
                     else self.resilience.drain_grace_s)
            t0 = time.monotonic()
            while self.pool.occupancy and time.monotonic() - t0 < grace:
                if not self._decode(stats):  # breaker may refuse the stage
                    time.sleep(0.002)
            for slot in self.pool.live_slots():
                st = self.pool.release(slot)
                self._preempt(st.request, stats)
            # A fault/hang DURING the grace loop requeues its victims
            # (requeue bypasses the closed queue by design) — sweep the
            # queue again so they preempt instead of stranding without a
            # Result (serve() would KeyError on them otherwise).
            for req in self.queue.pop(len(self.queue)):
                self._preempt(req, stats)
            # Released rows keep their pending invalidation: a later serve
            # on this scheduler resets them via the step mask (or prefill
            # re-init on realloc), same as any other release.
        finally:
            self.queue.reopen()
            # One programmatic drain per request_drain() call — the
            # scheduler stays reusable afterwards. The PROCESS-wide signal
            # flag (GracefulDrain) intentionally stays set: that process is
            # on its way out, and every later serve should drain too.
            self._drain_flag = False
            self._drain_grace_override = None
        emit_event("drain_complete", preempted=stats.preempted,
                   completed_during_drain=stats.completed - completed_before,
                   **self.labels)

    def _feed(self, stats: ServingStats) -> None:
        # Internal top-up from serve()'s pending overflow: a failed attempt
        # here is a RETRY of an already-accepted request, not a refused
        # submission, so it must not count toward stats.rejected (which
        # records public submit() backpressure).
        if self.shed_controller is None:
            while self._pending and not self.queue.full:
                if not self.queue.submit(self._pending[0],
                                         count_rejection=False):
                    break  # rate-limited; retry next iteration
                self._pending.popleft()
            return
        # QoS mode: the overload gate runs here for serve()'s intake, and a
        # bounded/quota'd CLASS must not head-of-line-block other classes'
        # pending behind it — scan the whole overflow once, keeping refused
        # requests in order and skipping a class after its first refusal
        # (per-class isolation; the scan short-circuits once every class is
        # blocked, so a deep overflow costs one pass, not one per entry).
        blocked: set = set()
        kept: Deque[Request] = deque()
        while self._pending:
            if len(blocked) == len(QOS_CLASSES):
                kept.extend(self._pending)
                self._pending.clear()
                break
            req = self._pending.popleft()
            if req.qos in blocked:
                kept.append(req)
                continue
            if self._overload_gate(req, stats=stats):
                continue  # terminally shed, Result recorded
            if not self.queue.submit(req, count_rejection=False):
                blocked.add(req.qos)
                kept.append(req)
        self._pending = kept

    def _note_fairness(self, request: Request, outcome: str, row,
                       text: str = "") -> None:
        """Feed the fairness monitor's serving side (telemetry/fairness.py)
        at every terminal outcome: per-group neutrality audit + the pair
        watch's outcome/attribution half. A no-op for untagged traffic
        (the monitor early-returns on a dict miss)."""
        get_fairness_monitor().observe_request(
            request, outcome, queue_wait_s=row.queue_wait_s,
            ttft_s=row.ttft_s, text=text, replica=self.replica,
            rung=(self.breakers.ladder.level
                  if self.breakers is not None else 0),
        )

    def _fail(self, request: Request, reason: str, error: str,
              stats: ServingStats, tokens: Optional[List[int]] = None) -> None:
        tok = self.engine.tokenizer
        ids = list(tokens or [])
        text = tok.decode([t for t in ids if t != tok.eos_id])
        outcome = "expired" if reason == "deadline" else "failed"
        row = self.tracer.finalize(request.id, outcome, tokens=len(ids))
        self._note_fairness(request, outcome, row, text=text)
        self._results[request.id] = Result(
            id=request.id, ok=False, text=text,
            tokens=np.asarray(ids, np.int32), finish_reason=reason,
            error=error, retries=request.retries,
            latency_s=time.monotonic() - request.submitted_at,
            queue_wait_s=row.queue_wait_s, ttft_s=row.ttft_s,
        )
        if self.journal is not None:
            self.journal.record_terminal(request.id, outcome)
        if reason == "deadline":
            stats.expired += 1
        else:
            stats.failed += 1

    def _shed(self, request: Request, reason: str, error: str,
              retry_after: float, stats: Optional[ServingStats] = None,
              journaled: bool = True) -> None:
        """Terminal overload refusal: an explicit ``finish_reason="shed"``
        Result with a retry-after hint — never silent loss. ``journaled``
        says whether intake already ledgered the request (serve()'s path);
        a submit()-time shed was never accepted, so there is nothing to
        close out."""
        if not self.tracer.events(request.id):
            # Lifecycle completeness for gate-at-submit sheds: the span
            # must still start at "submitted" (assert_span_order).
            self.tracer.record(request.id, "submitted",
                               t=request.submitted_at)
        row = self.tracer.finalize(request.id, "shed", tokens=0)
        self._note_fairness(request, "shed", row)
        self._results[request.id] = Result(
            id=request.id, ok=False, finish_reason="shed", error=error,
            retries=request.retries,
            latency_s=time.monotonic() - request.submitted_at,
            queue_wait_s=row.queue_wait_s, ttft_s=row.ttft_s,
            retry_after_s=retry_after,
        )
        count_shed(request.qos, reason, labels=self.labels)
        # Decision audit trail (telemetry/incidents.py): the refusal with
        # its inputs — the rung that shed it or the feasibility estimate
        # that doomed it — keyed to the refused request.
        record_decision(
            "shed", reason,
            signals={"qos": request.qos, "retry_after_s": retry_after,
                     "level": (self.shed_controller.level
                               if self.shed_controller is not None else 0)},
            request_id=request.id, replica=self.replica,
        )
        if journaled and self.journal is not None:
            self.journal.record_terminal(request.id, "shed")
        if stats is not None:
            stats.shed += 1
        else:
            self._shed_untaken += 1

    def _overload_gate(self, request: Request,
                       stats: Optional[ServingStats] = None,
                       journaled: bool = True) -> bool:
        """True when overload control terminally shed ``request`` (the
        Result is recorded — claimable via ``take_result`` or delivered by
        ``serve``). Two gates, in order: the brownout ladder's class
        admission, then deadline feasibility (see serving/overload.py)."""
        ctl = self.shed_controller
        if ctl is None:
            return False
        if request.qos == "interactive":
            # Arms the burn signal: there is now a latency-sensitive
            # tenant the brownout ladder exists to protect.
            ctl.note_interactive()
        if not ctl.admits(request.qos):
            self._shed(
                request, "overload",
                f"overload level {ctl.level} ({ctl.rung}) sheds "
                f"{request.qos}-class admissions; retry after "
                f"{ctl.retry_after()}s",
                ctl.retry_after(), stats=stats, journaled=journaled,
            )
            return True
        if self.deadline_estimator is not None and \
                request.deadline_s is not None:
            # Queued-ahead = same-or-higher-priority depth: class isolation
            # means lower classes can age past this request occasionally
            # but never systematically delay it, so they stay out of the
            # lower bound.
            if isinstance(self.queue, ClassedAdmissionQueue):
                ahead = sum(
                    d for c, d in self.queue.class_depths().items()
                    if QOS_PRIORITY[c] <= QOS_PRIORITY[request.qos]
                )
            else:
                ahead = len(self.queue)
            # Slot turnover happens at the fused-dispatch boundary, so the
            # feasibility wave is decode_chunk x fuse_steps steps wide.
            est = self.deadline_estimator.infeasible(
                request, ahead, self.num_slots,
                self.decode_chunk * self.fuse_steps,
            )
            if est is not None:
                self._shed(
                    request, "deadline_infeasible",
                    "deadline provably unmeetable at admission "
                    f"(estimated earliest first token {est:.3f}s); not "
                    "prefilling a doomed request",
                    ctl.retry_after(est), stats=stats, journaled=journaled,
                )
                return True
        return False

    def _preempt(self, request: Request, stats: ServingStats) -> None:
        """Drain outcome for a request this process will not finish: a
        ``preempted`` Result here, NO terminal journal record — the journal
        entry staying unfinished is exactly what ``resume-serving`` reads."""
        row = self.tracer.finalize(request.id, "preempted", tokens=0)
        hint = (f"resume with: resume-serving {self.journal.journal_dir}"
                if self.journal is not None
                else "no serving journal configured; request is lost at exit")
        self._results[request.id] = Result(
            id=request.id, ok=False, finish_reason="preempted",
            error=f"drained before completion ({hint})",
            retries=request.retries,
            latency_s=time.monotonic() - request.submitted_at,
            queue_wait_s=row.queue_wait_s, ttft_s=row.ttft_s,
        )
        stats.preempted += 1

    def _note_fault(self, stage: str, kind: str, request_ids: List[str],
                    error) -> None:
        """One contained fault into the incident engine: a ``fault``
        decision naming the riders the containment branch just requeued/
        failed, and — for the kinds with DIRECT evidence of a distinct
        failure mode — an incident trigger: ``watchdog_hang`` (the step
        blew its budget) and ``numerics_fault`` (the guard caught a
        non-finite chunk). Plain device/injected faults stay trigger-free
        here; a PERSISTENT storm of them opens a breaker, and the breaker
        transition is that incident's trigger."""
        record_decision(
            "fault", f"{stage}:{kind}",
            signals={"request_ids": list(request_ids),
                     "error": str(error)[:200]},
            request_id=(request_ids[0] if request_ids else None),
            replica=self.replica,
        )
        scope = self.replica or "serving"
        first = request_ids[0] if request_ids else None
        if kind == "hang":
            maybe_trigger(
                "watchdog_hang", f"{stage} step over budget: {error}",
                scope=scope, replica=self.replica, request_id=first,
                stage=stage, request_ids=list(request_ids),
            )
        elif kind == "numerics":
            maybe_trigger(
                "numerics_fault", f"{stage} chunk non-finite: {error}",
                scope=scope, replica=self.replica, request_id=first,
                stage=stage, request_ids=list(request_ids),
            )

    def _requeue_or_fail(self, request: Request, error: str,
                         stats: ServingStats, cause: str = "device") -> None:
        if request.retries < 1:
            request.retries += 1
            stats.requeued += 1
            # Cause breakdown ("injected" = ScriptedFaultInjector chaos
            # drills, "device" = a real raised prefill/decode) — the bare
            # ServingStats.requeued total can't tell a drill from an
            # incident; the registry label can.
            get_registry().counter(
                "serving_requeues_by_cause_total", component="serving",
                cause=cause, **self.labels,
            ).inc()
            self.tracer.record(request.id, "requeued")
            # Pair-watch attribution: a tagged request's requeue (and its
            # cause) shows up in the divergent-pair table. tagged= covers
            # direct-tagged requests whose pairs only auto-register at
            # terminal time — after this requeue.
            get_fairness_monitor().note_event(
                request.id, f"requeued:{cause}",
                tagged=(request.group is not None
                        or request.pair_id is not None),
            )
            self.queue.requeue(request)
        else:
            self._fail(request, "failed", error, stats)

    def _finish(self, slot: int, reason: str, stats: ServingStats) -> None:
        state = self.pool.release(slot)
        req = state.request
        tok = self.engine.tokenizer
        ids = []
        for t in state.tokens:
            ids.append(int(t))
            if t == tok.eos_id:
                break
        text = tok.decode(ids[:-1] if ids and ids[-1] == tok.eos_id else ids)
        if reason == "deadline":
            self._fail(req, "deadline", "deadline expired mid-decode",
                       stats, tokens=ids)
            return
        row = self.tracer.finalize(req.id, "completed", tokens=len(ids))
        self._note_fairness(req, "completed", row, text=text)
        self._results[req.id] = Result(
            id=req.id, ok=True, text=text,
            tokens=np.asarray(ids, np.int32), finish_reason=reason,
            prompt_tokens=state.real_len, retries=req.retries,
            latency_s=time.monotonic() - req.submitted_at,
            queue_wait_s=row.queue_wait_s, ttft_s=row.ttft_s,
        )
        if self.journal is not None:
            self.journal.record_terminal(req.id, "completed")
        stats.completed += 1

    def _cap_for(self, request: Request) -> int:
        m = (request.settings or self.settings).max_tokens
        cap = max(1, min(m, self.serving.max_new_tokens))
        if self.shed_controller is not None:
            # Brownout rung 2+: batch budgets clamp to batch_token_cap.
            # Greedy output stays a token-for-token PREFIX of the uncapped
            # stream (the cap only stops it sooner); a row already past a
            # freshly-shrunk cap finishes "length" at its next eviction
            # sweep, at most one chunk later.
            cap = self.shed_controller.batch_cap(cap, request.qos)
        return cap

    def _admit(self, stats: ServingStats) -> bool:
        """Backfill free slots from the queue until one side runs dry,
        prefilling in prompt-bucket groups (``prefill_group`` bounds one
        compiled batch, not the iteration — leaving slots empty while work
        is queued would decode below pool capacity for a whole chunk).
        Returns True when anything was admitted/attempted."""
        any_admitted = False
        while True:
            if not self._admit_once(stats):
                return any_admitted
            any_admitted = True

    def _admit_once(self, stats: ServingStats) -> bool:
        if self.breakers is not None and not self.breakers.allow("prefill"):
            return False
        free = self.pool.free_count
        if self.live_cap < self.num_slots:
            # Degradation rung 2: soft-cap concurrent slots. The pool keeps
            # its compiled size (shapes are baked in); admission just stops
            # filling it past the cap.
            free = min(free, max(0, self.live_cap - self.pool.occupancy))
        n = min(free, self.serving.prefill_group, len(self.queue))
        if n <= 0:
            return False
        tl = get_timeline()
        with tl.span("sched.admit.plan", "sched", self._track,
                     queue_depth=len(self.queue),
                     live_rows=self.pool.occupancy) as plan_span:
            admitted = self._plan_admission(n, stats)
            if admitted is None:
                return False
            plan = self._plan_prefill(admitted)
            # Rows that wait a batch for a row ahead of them go back to the
            # front in their order: before every later arrival, with their
            # deadline and their ``queue_wait`` running on.
            for req in reversed(plan["deferred"]):
                self.queue.requeue(req)
            stats.prefill_rows_deferred += len(plan["deferred"])
            plan_span.set(rows=len(plan["reqs"]), nb=plan["nb"], P=plan["P"],
                          S=plan["S"], donors=plan["donors"],
                          deferred=len(plan["deferred"]),
                          windows=len(plan["calls"]))
            if self._row_state_bytes:
                # No donor was looked for: a layer keeps a per-row state.
                plan_span.set(donorless="row_state")
            if self._rings:
                # What each kind of layer keeps of a row, and the rows whose
                # best donor's ring had moved past what they needed of it.
                plan_span.set(retained=self._retained,
                              ring_refused=plan["ring_refused"])
            # A scripted stall is consumed by the batch its request goes with.
            hang_fn = getattr(self.fault_injector, "maybe_hang", None)
            injected_hang = 0.0 if hang_fn is None else sum(
                hang_fn(req.id, "prefill") for req in plan["reqs"])
        return self._dispatch_prefill(plan, stats, injected_hang)

    def _plan_admission(self, n: int, stats: ServingStats):
        """Pop ``n`` requests and settle each one's fate before any device
        work: expired, shed, faulted by script, or admitted with its token
        ids and prompt bucket. Returns ``[(request, ids, P)]``, or None when
        nothing is left to admit."""
        popped = self.queue.pop(n)
        tok = self.engine.tokenizer
        now = time.monotonic()
        admitted = []  # (request, row ids, P)
        for req in popped:
            if req.expired(now):
                # The deadline passed between the queue's expiry sweep and
                # this pop — most often while the request sat in the
                # requeue-after-fault window. It must terminate expired
                # here, never spend a prefill on a second attempt.
                self._fail(req, "deadline", "deadline expired before prefill",
                           stats)
                continue
            if self.deadline_estimator is not None and \
                    req.deadline_s is not None:
                # Pop-time feasibility recheck (queue wait now spent, so
                # ahead=0): a request whose remaining deadline cannot even
                # cover one prefill + one decode step sheds HERE instead of
                # burning a full prefill and expiring mid-decode.
                est = self.deadline_estimator.infeasible(
                    req, 0, self.num_slots,
                    self.decode_chunk * self.fuse_steps, now=now,
                )
                if est is not None:
                    self._shed(
                        req, "deadline_infeasible",
                        "deadline provably unmeetable at prefill time "
                        f"(estimated earliest first token {est:.3f}s)",
                        self.shed_controller.retry_after(est)
                        if self.shed_controller is not None else est,
                        stats=stats,
                    )
                    continue
            if self.fault_injector is not None:
                try:
                    self.fault_injector.maybe_fail(req.id, "prefill")
                except DecodeFault as e:
                    # Fault decision FIRST, breaker feed second: a trip to
                    # OPEN dumps an incident bundle, and the bundle's trail
                    # must already name the request that faulted.
                    self._note_fault("prefill", "injected", [req.id], e)
                    # Scripted faults feed the breaker like real ones —
                    # that's what makes breaker trips chaos-drillable.
                    if self.breakers is not None:
                        self.breakers.record_failure("prefill")
                    self._requeue_or_fail(req, str(e), stats, cause="injected")
                    continue
            ids = tok.encode(req.prompt)
            if len(ids) > self.prompt_budget:
                # Keep recency, like the engine's truncation — but the
                # server budget (ServingConfig.max_prompt_len) can be
                # tighter than the engine's per-call budget, and a
                # truncated prompt decodes DIFFERENT tokens than the
                # engine alone would, so say so instead of silently
                # breaking the parity contract.
                logger.warning(
                    "request %s: prompt (%d tokens) exceeds the serving "
                    "budget (%d); left-truncating — output will differ "
                    "from the static engine's for this request",
                    req.id, len(ids), self.prompt_budget,
                )
                ids = ids[-self.prompt_budget:]
            P = min(
                self._bucket_len(max(len(ids), 1), self.engine.seq_bucket),
                self.max_prompt_bucket,
            )
            admitted.append((req, ids, P))
        return admitted or None

    def _plan_prefill(self, admitted) -> dict:
        """What one contiguous admission batch has left to compute, as the
        arguments of its ``serve_prefill`` calls (one per window, in
        order), on the device before the first call is made.

        A row whose prompt starts like a live row's copies what it can and
        extends over its last windows only: with ``m`` ids shared, ``S`` the
        window ``extend_window(config, P)`` and ``k`` the fewest windows for
        which ``n - k S <= m``, ``n - k S`` positions come from the donor. A
        row with no such donor (or fewer than ``S`` ids) runs every window
        that holds one of its tokens. Donors are rows resident before this
        call: never a slot it writes. Where a window layer keeps a ring, the
        donor is the resident row with the longest shared prefix among those
        whose rings still hold what the rider needs of them
        (``_ring_holds``), and ``ring_refused`` counts the riders whose
        longest-sharing row did not.

        In a long bucket (``stepbuilder.long_bucket``: there a row's leading
        windows are most of a batch's work) a row that found no donor looks,
        with the same arithmetic, among the rows AHEAD of it that this batch
        takes. Where one of them would save it a window the row is
        ``deferred``: it claims no slot, and ``_admit_once`` puts it back at
        the front of the queue, so the next plan of the same ``_admit`` loop
        finds the row that went ahead resident (its calls are queued on the
        device before the riders', as a batch's windows already are). Rows
        that share nothing wait for nobody, and nothing is remembered: if
        the batch of the row ahead fails, that row is requeued in front of
        the rows that waited for it and the next plan is made as if the
        batch had never been. A batch of such a bucket in which no row
        copies runs the program of that case, with its wide window
        (``stepbuilder.extend_window``: the plan's ``S``), and a batch there
        is one row or pads to ``prefill_group``: a compiled shape of a long
        bucket costs more than a padded row.

        A model of which a layer keeps a fixed state per row
        (``config.row_state_bytes``) takes no donor at all: a resident row's
        state stands at that row's own last token, not at the point where
        the prompts part, so there is nothing to copy. Its rows carry no
        ``ids`` and so are never donors (``LiveRowPrefixes`` finds none). Its
        window is the whole bucket, so a batch is one call, in which
        ``serve_prefill`` zeroes each row's state.
        """
        tok = self.engine.tokenizer
        cfg = self.engine.config
        # ONE bucket per admission batch, the largest of its rows'. Shorter
        # rows pad up to it: numerically free (pad slots are masked,
        # contributing exact zeros to every reduction; parity tests pin
        # this) and much cheaper than a compiled shape per bucket when
        # backfills trickle in one or two rows at a time. A row's ``base``
        # is therefore the bucket it was PREFILLED at, which its decode
        # write offsets continue from.
        P = max(item[2] for item in admitted)
        S = extend_window(cfg, P)  # a rider's window
        long = long_bucket(cfg, P)
        live = LiveRowPrefixes(self.pool)
        rows, deferred = [], []  # (request, ids, donor, donor_pad, copied)
        ring_refused = 0
        for req, ids, _ in admitted:
            ids = np.asarray(ids[-P:], np.int32)
            n = len(ids)
            found = (0, 0, 0)
            for at, (src, m) in enumerate(live.by_shared(ids)):
                can_copy = _copyable(n, m, S)
                if can_copy <= 0:
                    break  # a shorter prefix saves no window either
                st = self.pool.get(src)
                if not self._ring_holds(st, can_copy):
                    ring_refused += at == 0
                    continue
                found = (src, st.base - st.real_len, can_copy)
                break
            if long and not found[2] and self._waits_for(ids, rows, S):
                deferred.append(req)
                continue
            rows.append((req, ids) + found)
        n_rows = len(rows)
        cap = max(self.serving.prefill_group, n_rows)
        copies = not long or any(row[4] for row in rows)
        if not copies:
            S = extend_window(cfg, P, False, self._rings)
        if long:
            nb = 1 if n_rows == 1 else cap
        else:
            nb = _bucket_pow2(n_rows, cap)
        reqs = [row[0] for row in rows]
        tokens = np.full((nb, P), tok.pad_id, np.int32)
        lead = np.full((nb,), P, np.int32)  # left padding; P: no real token
        donor = np.zeros((nb,), np.int32)
        donor_pad = np.zeros((nb,), np.int32)
        copied = np.zeros((nb,), np.int32)
        # The window a row starts in; batch-bucket pad rows start in none.
        first = np.full((nb,), P // S, np.int32)
        for r, (_, ids, src, src_pad, n_copied) in enumerate(rows):
            n = len(ids)
            tokens[r, P - n:] = ids
            lead[r] = P - n
            donor[r], donor_pad[r], copied[r] = src, src_pad, n_copied
            # A rider runs the whole windows after what it copied; any other
            # row starts in its first token's window.
            first[r] = P // S - (n - n_copied) // S if n_copied else \
                min((P - n) // S, P // S - 1)
        # Slots are claimed after every row has looked for its donor, so
        # that no row of this batch can be another's.
        slots = []
        for r, req in enumerate(reqs):
            if copied[r]:
                src = self.pool.get(int(donor[r]))
                root, held = src.root, min(int(copied[r]), src.root_shared)
            else:
                root, held = self._next_root, int(P - lead[r])
                self._next_root += 1
            slot = self.pool.alloc(SlotState(
                request=req, base=P, real_len=int(P - lead[r]),
                ids=None if self._row_state_bytes else tokens[r, lead[r]:],
                root=root, root_shared=held,
            ))
            assert slot is not None  # admission is free-count bounded
            slots.append(slot)
            self.tracer.record(req.id, "admitted")
        slot_ids = np.full((nb,), self.num_slots, np.int32)
        slot_ids[:n_rows] = slots
        valid = np.arange(P)[None, :] >= lead[:, None]
        calls = []
        for i in range(int(first.min()), P // S):
            w = i * S
            rides = first <= i
            # A row that sits this window out (or pads the batch bucket)
            # has no valid token and an out-of-range slot id: it attends to
            # nothing (a uniform softmax over masked keys, finite), routes
            # to no expert, and its extent scatter-drops.
            calls.append(dict(w=w, rows=int(rides.sum()), args=tuple(
                jnp.asarray(x) for x in (
                    np.where(rides[:, None], tokens[:, w:w + S], tok.pad_id),
                    valid[:, w:w + S] & rides[:, None],
                    np.where(rides, slot_ids, self.num_slots),
                    np.where(rides, lead, P),
                    donor, donor_pad,
                    np.where(first == i, copied, 0),
                    np.asarray(w, np.int32),  # 0-d: a transfer, no program
                ))))
        # First use of this (batch, prompt) bucket compiles; that wall is
        # exempt from hang classification (injected stalls still classify).
        guard = self._guard()
        pf_key = compile_key("serve_prefill", nb=nb, P=P, guard=guard,
                             copies=copies, tp=self.tp)
        return dict(
            nb=nb, P=P, S=S, reqs=reqs, slots=slots, guard=guard, key=pf_key,
            first_compile=pf_key not in self._compiled,
            fn=self._prefill_fn(nb, P, guard, copies),
            program=program_label("serve_prefill", tp=self.tp),
            prompt_tokens=int((P - lead).sum()),
            reused_tokens=int(copied.sum()),
            donors=int((copied > 0).sum()), calls=calls,
            ring_refused=int(ring_refused), deferred=deferred,
            alone=not copies,
        )

    def _waits_for(self, ids: np.ndarray, ahead, S: int) -> bool:
        """Whether a row that found no donor waits a batch for one of the
        rows ``ahead`` of it that this batch takes: one that, once resident,
        saves it a window."""
        n = len(ids)
        for _, theirs, *_ in ahead:
            width = min(n, len(theirs))
            differ = np.flatnonzero(ids[:width] != theirs[:width])
            m = int(differ[0]) if differ.size else width
            can_copy = _copyable(n, m, S)
            if can_copy > 0 and self._rings_hold(len(theirs), can_copy):
                return True
        return False

    def _ring_holds(self, st: SlotState, copied: int) -> bool:
        """Whether a resident row's rings still hold what a rider that
        copies ``copied`` positions needs of them: ``_rings_hold`` of the
        ``real_len + emitted`` positions the row has written."""
        return self._rings_hold(st.real_len + st.emitted, copied)

    def _rings_hold(self, written: int, copied: int) -> bool:
        """Whether the rings of a row that has written ``written`` positions
        hold what a rider that copies ``copied`` needs of them. The rider's
        first query stands at position ``copied`` and sees ``window`` back,
        so it needs the positions from ``copied - window + 1`` (or 0) on; a
        ring of ``R`` holds the last ``R`` written. True where no layer
        keeps a ring."""
        return all(written - length <= max(0, copied - window + 1)
                   for window, length in self._rings)

    def _dispatch_prefill(self, plan: dict, stats: ServingStats,
                          injected_hang: float) -> bool:
        """The compiled calls of a contiguous admission batch, window by
        window, each as a ``sched.prefill.dispatch`` span that names the
        requests it admits. On an asynchronous backend a call returns once
        the work is queued: the span, ``prefill_wall_s`` and
        ``note_invocation`` are the DISPATCH, a batch's windows queue
        behind one another on the device (each is handed the pool the one
        before it returns, donated, so none holds a copy), and the device
        is known to be done with them only at the next
        ``sched.decode.sync``. A call that fails releases the whole batch:
        a row some earlier window wrote stays out of every decode step and
        is no donor, and its slot is reset or prefilled anew before
        anything reads it. Where the call that raised had consumed the
        pool, every live row goes with the batch and the pool is built
        anew (``_rebuild_device_state``)."""
        nb, P, reqs, slots = plan["nb"], plan["P"], plan["reqs"], plan["slots"]
        fn, guard, first_compile = plan["fn"], plan["guard"], \
            plan["first_compile"]
        pf_t0 = time.monotonic()
        for req in reqs:
            self.tracer.record(req.id, "prefill_start", t=pf_t0)
        try:
            for call in plan["calls"]:
                t0 = time.monotonic()
                if self.watchdog is not None:
                    self.watchdog.arm("prefill")
                with get_timeline().span(
                        "sched.prefill.dispatch", "prefill", self._track,
                        program=plan["program"], nb=nb, P=P, w=call["w"],
                        rows=call["rows"], donated=True,
                        request_ids=[r.id for r in reqs]):
                    self._note_dispatch(t0)
                    out = self._run_compiled(
                        fn, self.engine.params, self._cache,
                        self._prev_logits, *call["args"],
                    )
                    # Swapped BEFORE the finite check: the call consumed
                    # its inputs, and what it returns is sound for every
                    # row it did not name (the program scatters at
                    # ``slots`` alone, and at none where the flag is
                    # down), so a poisoned batch still releases only
                    # itself in the containment branch.
                    self._cache, self._prev_logits = out[0], out[1]
                    if guard:
                        check_finite(out[2], "serving", "prefill")
                if self.watchdog is not None:
                    # Post-hoc hang classification (see
                    # resilience/watchdog.py): an over-budget prefill raises
                    # HangFault INTO the containment branch below — the
                    # cache rows it wrote are released with their slots, so
                    # nothing stale survives.
                    self.watchdog.observe("prefill", extra_s=injected_hang,
                                          classify=not first_compile)
                self._note_prefill(plan["program"], time.monotonic() - t0,
                                   fn, first_compile, plan["key"])
                stats.prefill_batches += 1
                first_compile, injected_hang = False, 0.0
        except Exception as e:  # noqa: BLE001 — containment is the point
            kind = ("hang" if isinstance(e, HangFault)
                    else "numerics" if isinstance(e, NumericsFault)
                    else "device")
            logger.warning("prefill batch (%d, %d) failed: %s", nb, P, e)
            get_registry().counter(
                "faults_total", component="serving",
                kind=kind, stage="prefill", **self.labels,
            ).inc()
            # Fault decision BEFORE the breaker feed: a trip to OPEN dumps
            # a bundle whose trail must already name the riders.
            self._note_fault("prefill", kind, [r.id for r in reqs], e)
            if self.breakers is not None:
                self.breakers.record_failure("prefill")
            if self._device_state_deleted():
                # The call that raised had consumed the pool: the rows that
                # were live before the batch went with it.
                self._rebuild_device_state(
                    "prefill", f"prefill failed: {e}", stats, cause=kind)
                return True
            # The state is whole (the call raised before it launched, or
            # its outputs were swapped in): the batch alone goes.
            for slot, req in zip(slots, reqs):
                self.pool.release(slot)
                self._requeue_or_fail(req, f"prefill failed: {e}", stats,
                                      cause=kind)
            return True
        if self.breakers is not None:
            self.breakers.record_success("prefill")
        stats.prefill_tokens += plan["prompt_tokens"]
        stats.prefill_tokens_reused += plan["reused_tokens"]
        stats.prefill_donors_refused_ring += plan["ring_refused"]
        stats.prefill_leader_batches += plan["alone"]
        if self._row_state_bytes:
            # Each admitted row's state was zeroed in the batch's call.
            stats.row_state_resets += len(reqs)
        stats.admitted += len(reqs)
        return True

    def _device_state_deleted(self) -> bool:
        """Whether a donated call took the pool or the carried logits with
        it when it raised: asked of the arrays themselves."""
        return any(x.is_deleted() for x in jax.tree.leaves(
            (self._cache, self._prev_logits)))

    def _rebuild_device_state(self, stage: str, error: str,
                              stats: ServingStats, cause: str) -> None:
        """Containment where a donated call may have consumed its inputs (a
        decode chunk that failed; a prefill window that raised after it
        launched): every live row is released and requeued for a fresh
        prefill, so nothing in the pool is still needed, and the pool and
        the carried logits are built anew, placed and accounted."""
        for slot in self.pool.live_slots():
            req = self.pool.release(slot).request
            self._requeue_or_fail(req, error, stats, cause=cause)
        self._cache = init_cache(
            self.engine.config, self.num_slots, self.cache_len, ring=True
        )
        self._prev_logits = jnp.zeros(
            (self.num_slots, self.engine.config.vocab_size), jnp.float32
        )
        # Fresh host-side buffers: re-pin them to the mesh, or the next
        # compiled call would recompile against replicated layouts.
        self._place_device_state()
        self._account_device_state()
        self.pool.take_invalidations()
        self._busy_from = None
        get_registry().counter(
            "device_state_rebuilds_total", component="serving",
            stage=stage, **self.labels,
        ).inc()

    def _note_dispatch(self, t0: float) -> None:
        """The earliest dispatch since the last ``sched.decode.sync``: the
        device is taken to be busy from here until that sync returns."""
        if self._busy_from is None:
            self._busy_from = t0

    def _note_prefill(self, program: str, wall: float, fn,
                      first_compile: bool, key) -> None:
        """What a prefill call feeds once it has returned:
        ``prefill_wall_s`` (``overload.DeadlineEstimator`` reads its p50 as
        a lower bound on a prefill, which a dispatch wall still is),
        ``compiles_total`` on a first use, and the cost ledger's wall."""
        get_registry().histogram(
            "prefill_wall_s", component="serving", **self.labels
        ).observe(wall)
        if first_compile:
            record_compile(program, reason="shape", seconds=wall, key=key,
                           labels=self.labels)
        note_invocation(program, wall, ledger=getattr(fn, "ledger", None),
                        compiling=first_compile)

    def _prepare_decode(self, stats: ServingStats) -> Optional[dict]:
        """Everything a decode chunk needs before its call: scripted
        faults, the live rows' masks, seeds and caps as host arrays, the
        step program, and the call's arguments on the device. None when no
        row is live."""
        injected_hang = 0.0
        if self.fault_injector is not None:
            for slot in self.pool.live_slots():
                req = self.pool.get(slot).request
                try:
                    self.fault_injector.maybe_fail(req.id, "decode")
                except DecodeFault as e:
                    self._note_fault("decode", "injected", [req.id], e)
                    if self.breakers is not None:
                        self.breakers.record_failure("decode")
                    self.pool.release(slot)
                    self._requeue_or_fail(req, str(e), stats, cause="injected")
            hang_fn = getattr(self.fault_injector, "maybe_hang", None)
            if hang_fn is not None:
                for slot in self.pool.live_slots():
                    injected_hang += hang_fn(
                        self.pool.get(slot).request.id, "decode"
                    )
            corrupt_fn = getattr(self.fault_injector, "maybe_corrupt", None)
            if corrupt_fn is not None:
                for slot in self.pool.live_slots():
                    mode = corrupt_fn(self.pool.get(slot).request.id, "decode")
                    if mode is not None:
                        # Scripted silent corruption: poison the slot's
                        # CARRIED logits (the sample source) host-side. With
                        # the numerics guard armed the chunk faults as
                        # NumericsFault; without it, this is exactly the
                        # garbage-argmax failure the guard exists to catch.
                        bad = float("nan") if mode == "nan" else float("inf")
                        self._prev_logits = self._prev_logits.at[slot].set(bad)
        live_ids = self.pool.live_slots()
        if not live_ids:
            return None
        # Released-slot invalidation rides on the step program's reset mask
        # (no separate dispatch). Slots released and REUSED before this
        # point never enter the mask — SlotPool.alloc cancels their pending
        # invalidation because prefill re-initialized the row.
        reset = np.zeros((self.num_slots,), bool)
        reset[self.pool.take_invalidations()] = True

        B = self.num_slots
        live = np.zeros((B,), bool)
        emitted = np.zeros((B,), np.int32)
        base = np.zeros((B,), np.int32)
        caps = np.ones((B,), np.int32)
        seeds = np.zeros((B,), np.uint32)
        for slot in live_ids:
            st = self.pool.get(slot)
            live[slot] = True
            emitted[slot] = st.emitted
            base[slot] = st.base
            caps[slot] = self._cap_for(st.request)
            seed = st.request.row_seed
            seeds[slot] = np.uint32((0 if seed is None else seed) & 0xFFFFFFFF)
        guard = self._guard()
        step_key = self._step_key(guard)
        prog = self._step_program()
        first_compile = step_key not in self._compiled
        fn = self._step_fn()
        plan = self._decode_plan(live_ids) if self._pool_spans else ()
        args = (self._cache, self._prev_logits) + tuple(
            jnp.asarray(x) for x in (seeds, emitted, base, caps, live, reset)
            + plan)
        return dict(live_ids=live_ids, injected_hang=injected_hang, fn=fn,
                    args=args, prog=prog, step_key=step_key, guard=guard,
                    first_compile=first_compile, plan=plan)

    def _decode_plan(self, live_ids) -> tuple:
        """The chunk's ``(lead, shared)``, int32 a slot, for
        ``ops/pool_attention``: which row each row reads its leading
        positions from, and how many. Live rows of one ``root`` hold the
        fewer of their ``root_shared`` alike, bit for bit; a group of two
        and more reads them once, from its lead: the member that stands at
        the smallest position (the lower slot among equals), so that under a
        ring its ring still holds what every member's window needs of it
        (``_rings_hold``'s arithmetic, asked of each member). The lead reads
        its whole ``root_shared`` as its group's. A finished lead keeps its
        row until the next chunk's reset, and the next plan names another
        member; a released or recycled slot is in no plan. At most
        ``pool_attention.MAX_GROUPS`` groups, those that save the most
        positions; every other row is its own lead with ``shared`` 0."""
        B = self.num_slots
        lead = np.arange(B, dtype=np.int32)
        shared = np.zeros((B,), np.int32)
        by_root: Dict[int, list] = {}
        for slot in live_ids:
            st = self.pool.get(slot)
            if st.root >= 0:
                by_root.setdefault(st.root, []).append((
                    st.real_len + st.emitted, slot, st.root_shared))
        groups = []
        for rows in by_root.values():
            if len(rows) < 2:
                continue
            at, head, held = min(rows)
            members = [(slot, min(n, held)) for pos, slot, n in rows
                       if slot != head and self._rings_hold(at, pos)]
            if members:
                groups.append((sum(n for _, n in members), head, held,
                               members))
        groups.sort(key=lambda g: (-g[0], g[1]))
        for _, head, held, members in groups[:pool_attention.MAX_GROUPS]:
            shared[head] = held
            for slot, n in members:
                lead[slot], shared[slot] = head, n
        return lead, shared

    def _decode(self, stats: ServingStats) -> bool:
        """One compiled decode chunk over the live slots; evict finished
        rows. Returns True when any decoding happened."""
        if self.breakers is not None and not self.breakers.allow("decode"):
            return False
        tl = get_timeline()
        with tl.span("sched.decode.prepare", "decode", self._track) as prep:
            ready = self._prepare_decode(stats)
            if ready is None:
                return False
            live_ids, prog = ready["live_ids"], ready["prog"]
            prep.set(rows=len(live_ids), program=prog)
        fn, args, guard = ready["fn"], ready["args"], ready["guard"]
        step_key, first_compile = ready["step_key"], ready["first_compile"]
        injected_hang = ready["injected_hang"]
        dc_t0 = time.monotonic()
        if self.watchdog is not None:
            self.watchdog.arm("decode")
        try:
            with tl.span("sched.decode.dispatch", "decode", self._track,
                         program=prog, rows=len(live_ids)):
                self._note_dispatch(dc_t0)
                out = self._run_compiled(fn, self.engine.params, *args)
            if guard:
                (self._cache, self._prev_logits, toks, emitted_after,
                 counters, finite) = out
            else:
                self._cache, self._prev_logits, toks, emitted_after, \
                    counters = out
            # Where the host learns that the chunk, and every prefill
            # dispatched ahead of it, is done.
            with tl.span("sched.decode.sync", "decode", self._track):
                toks = np.asarray(jax.device_get(toks))
                emitted_after = np.asarray(jax.device_get(emitted_after))
                counters = np.asarray(jax.device_get(counters))
                if guard:
                    # A tripped finite flag discards the whole chunk as a
                    # NumericsFault into the containment branch below — the
                    # donated cache was already consumed, so the rebuild
                    # there is mandatory, and every rider requeues for a
                    # fresh prefill (which re-derives all activations from
                    # the prompt, healing a transient flip).
                    check_finite(finite, "serving", "decode")
            synced = time.monotonic()
            busy_from, self._busy_from = self._busy_from, None
            if self.watchdog is not None:
                # Hang classification AFTER the host sees results: a chunk
                # past max_step_seconds raises HangFault into the branch
                # below — its tokens are discarded and every rider requeues
                # for a fresh attempt, exactly like a failed chunk (a hung
                # step's outputs are unaccounted time, not trusted work).
                # A fused dispatch legitimately runs fuse_steps chunks of
                # wall, so the budget scales with it — a threshold tuned
                # for one chunk must not classify every healthy fused
                # dispatch as a hang.
                self.watchdog.observe("decode", extra_s=injected_hang,
                                      classify=not first_compile,
                                      budget_scale=self.fuse_steps)
        except Exception as e:  # noqa: BLE001 — containment is the point
            kind = ("hang" if isinstance(e, HangFault)
                    else "numerics" if isinstance(e, NumericsFault)
                    else "device")
            logger.warning("decode chunk failed: %s", e)
            get_registry().counter(
                "faults_total", component="serving",
                kind=kind, stage="decode", **self.labels,
            ).inc()
            self._note_fault(
                "decode", kind,
                [self.pool.get(s).request.id for s in live_ids], e,
            )
            if self.breakers is not None:
                self.breakers.record_failure("decode")
            self._rebuild_device_state(
                "decode", f"decode failed: {e}", stats, cause=kind)
            return True
        with tl.span("sched.decode.emit", "decode", self._track,
                     rows=len(live_ids)):
            self._emit_chunk(
                stats, live_ids, toks, emitted_after, counters, fn=fn,
                prog=prog, step_key=step_key, first_compile=first_compile,
                dc_t0=dc_t0, now=synced, busy_from=busy_from,
                plan=ready["plan"])
        return True

    def _emit_chunk(self, stats: ServingStats, live_ids, toks, emitted_after,
                    counters, *, fn, prog: str, step_key, first_compile: bool,
                    dc_t0: float, now: float,
                    busy_from: Optional[float], plan: tuple = ()) -> None:
        """What follows a chunk whose results the host holds (``now`` is the
        end of its sync): the telemetry that reads its wall, then each
        row's new tokens, first-token stamp, and eviction when done."""
        if self.breakers is not None:
            self.breakers.record_success("decode")
        steps = int(counters[0])
        stats.decode_steps += steps
        stats.occupancy_sum += int(counters[1])
        # What the model itself counted on the device since the last chunk
        # (routed experts: tokens, local pairs, the fullest expert's pairs,
        # summed over layers, steps and the prefills in between).
        for name, value in zip(MOE_COUNTERS, counters[2:]):
            setattr(stats, name, getattr(stats, name) + int(value))
        # Every step reads and writes EVERY slot's per-row state, live or
        # idle (the step program runs over the whole pool): from shapes.
        stats.row_state_bytes_stepped += \
            steps * self.num_slots * self._row_state_bytes
        self._count_reads(stats, live_ids, emitted_after, steps, plan)
        # Performance attribution (telemetry/): the chunk's span on this
        # scheduler's timeline track (the gap to the previous chunk feeds
        # the step_gap_s histogram — the per-step host sync ROADMAP item 3
        # wants to eliminate), first-use compiles under compiles_total, and
        # the live roofline gauges. The byte model streams the WHOLE pool's
        # KV per step (the compiled program does, live rows or not), so
        # batch is num_slots, not len(live_ids). ``busy_from`` is the
        # earliest dispatch since the previous sync (a prefill queued ahead
        # of this chunk): the device was busy from there to ``now``, which
        # is known only now that the sync has returned.
        dc_wall = now - dc_t0
        gap = get_timeline().decode_chunk(self._track, dc_t0, dc_wall, steps,
                                          labels=self.labels,
                                          rows=len(live_ids),
                                          program=prog, busy_from=busy_from)
        # Flight-recorder chunk ring (telemetry/flightrecorder.py): the
        # last-K decode chunks with their step gaps — the high-rate recent
        # history an incident bundle snapshots but nothing persists.
        get_flight_recorder().record(
            "chunks", program=prog, steps=steps,
            wall_s=round(dc_wall, 6),
            gap_s=(round(gap, 6) if gap is not None else None),
            rows=len(live_ids), replica=self.replica, t=dc_t0,
        )
        if first_compile:
            record_compile(
                prog,
                reason=("decode_chunk"
                        if self.decode_chunk != self._base_decode_chunk
                        else "shape"),
                seconds=dc_wall, key=step_key, labels=self.labels,
            )
        observe_decode(
            self.engine.config,
            {"batch": self.num_slots, "cache_slots": self.cache_len,
             "prefix_len": 0, "ring": True},
            steps, dc_wall, program=prog, labels=self.labels,
        )
        # Gap attribution (telemetry/costmodel.py): the chunk's measured
        # wall + trip count against the step program's analytic ledger. A
        # first-compile chunk's wall is tagged so the decomposition shows
        # compile as its own contributor, not "unattributed in-step".
        note_invocation(prog, dc_wall, steps,
                        ledger=getattr(fn, "ledger", None),
                        compiling=first_compile)
        # Per-chunk pool-pressure samples, weighted by the steps the chunk
        # actually ran (the compiled loop may exit early): live rows at
        # entry is the occupancy every one of those steps decoded at most.
        self.tracer.sample_step_gauges(
            occupancy=len(live_ids), queue_depth=len(self.queue),
            decode_steps=steps,
        )
        for slot in live_ids:
            st = self.pool.get(slot)
            n = int(emitted_after[slot]) - st.emitted
            new = [int(t) for t in toks[slot, :n]]
            if st.emitted == 0 and n > 0:
                # Earliest HOST-visible time for this row's first token: the
                # end of the chunk that produced it (see telemetry/tracing.py
                # on granularity).
                self.tracer.record(st.request.id, "first_token", t=now)
            st.tokens.extend(new)
            st.emitted += n
            stats.decoded_tokens += n
            eos = self.engine.tokenizer.eos_id in new
            if eos:
                self._finish(slot, "eos", stats)
            elif st.emitted >= self._cap_for(st.request):
                self._finish(slot, "length", stats)
            elif st.request.expired(now):
                self._finish(slot, "deadline", stats)

    def _count_reads(self, stats: ServingStats, live_ids, emitted_after,
                     steps: int, plan: tuple) -> None:
        """What the chunk's attention read of the pool, from the rows'
        positions and the plan, over its steps and the layers that keep
        something per position. ``kv_positions_attended``: the positions the
        live rows attended (a window layer's window). ``kv_positions_from_
        lead``: of those, the ones a row read through another row's lead
        span. ``kv_bytes_stepped``: the bytes of keys and values fetched:
        through ``ops/pool_attention`` every grid step's block of both
        kernels (``pool_attention.fetched_positions``); on the XLA path
        every slot's whole retained row, live or idle."""
        B = self.num_slots
        start = np.zeros((B,), np.int64)
        ran = np.zeros((B,), np.int64)
        pad = np.zeros((B,), np.int64)
        for slot in live_ids:
            st = self.pool.get(slot)
            start[slot] = st.real_len + st.emitted
            ran[slot] = int(emitted_after[slot]) - st.emitted
            pad[slot] = st.base - st.real_len
        t = np.arange(steps)[:, None]
        live = ran[None, :] > t  # [steps, B]
        pos = start[None, :] + t
        member = live & (plan[0] != np.arange(B))[None, :] if plan \
            else np.zeros_like(live)
        for window, L, layers, row_bytes, blocks in self._attn_kinds:
            lo = 0 if window is None else np.maximum(pos - window + 1, 0)
            stats.kv_positions_attended += layers * int(
                np.where(live, pos + 1 - lo, 0).sum())
            if plan:
                stats.kv_positions_from_lead += layers * int(np.where(
                    member, np.maximum(plan[1][None, :] - lo, 0), 0).sum())
            if plan and blocks:
                stats.kv_bytes_stepped += layers * row_bytes * \
                    pool_attention.fetched_positions(
                        plan[0], plan[1], live, pos, pad + pos, L=L,
                        window=window, own_blk=blocks[0], lead_blk=blocks[1],
                        leads=not self._row_state_bytes)
            else:
                stats.kv_bytes_stepped += layers * steps * B * L * row_bytes

    def _iterate(self, stats: ServingStats) -> bool:
        """One iteration, as one ``sched.iterate`` span whose children are
        the pieces a stall can hide in (docs/OBSERVABILITY.md, "Spans
        inside the iteration"): ``sched.expire``, then per admission batch
        ``sched.admit.plan`` and ``sched.prefill.dispatch``, then
        ``sched.decode.prepare``/``.dispatch``/``.sync``/``.emit``."""
        self._iter_seq += 1
        tl = get_timeline()
        with tl.span("sched.iterate", "sched", self._track,
                     seq=self._iter_seq):
            with tl.span("sched.expire", "sched", self._track):
                progressed = self._expire(stats)
            progressed |= self._admit(stats)
            progressed |= self._decode(stats)
            # The device's high-water mark, while the run still holds its
            # memory (one memory_stats() call per device).
            get_memory_ledger().note_peaks()
        return progressed

    def _expire(self, stats: ServingStats) -> bool:
        stats.loop_iterations += 1
        depth = len(self.queue)
        stats.queue_depth_sum += depth
        stats.queue_depth_max = max(stats.queue_depth_max, depth)
        # Live high-water mark, updated every loop iteration — the
        # per-decode-step queue_depth gauge (tracer.sample_step_gauges) is
        # instantaneous and the per-drain serving_queue_depth_max publishes
        # only AFTER a drain, so neither shows a mid-drain spike to an
        # online reader. The fleet router reads this (registry.read_value)
        # as its backpressure signal when scoring replicas.
        get_registry().gauge(
            "queue_depth_hwm", component="serving", **self.labels
        ).set_max(depth)
        now = time.monotonic()
        progressed = False
        for req in self.queue.drain_expired(now):
            self._fail(req, "deadline", "deadline expired in queue", stats)
            progressed = True
        for slot in self.pool.live_slots():
            if self.pool.get(slot).request.expired(now):
                self._finish(slot, "deadline", stats)
                progressed = True
        return progressed
