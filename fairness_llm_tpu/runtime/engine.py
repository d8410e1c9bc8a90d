"""Batched autoregressive decode engine (prefill + ``lax.scan`` decode, sharded).

Replaces the reference's per-profile sequential API round-trips
(``phase1_bias_detection.py:325-340`` — 45 HTTPS calls with sleep-based rate
limiting) with ONE device program per batch:

1. tokenize + **left-pad** all prompts to a bucketed [B, S] shape
2. prefill the whole batch in one forward pass (MXU-friendly big matmul)
3. decode up to ``max_new_tokens`` steps inside a single compiled
   ``lax.while_loop`` that exits as soon as every real row has sampled EOS
   (early-EOS rows emit pads and their KV writes are masked invalid)
4. detokenize host-side

Greedy decode can instead take the SPECULATIVE loop (``_spec_decode_fn``):
each iteration drafts k tokens per row by prompt lookup
(``runtime/speculative.py``) and verifies all k+1 positions in one forward
pass with per-row cache write offsets — token-for-token identical output,
1..k+1 tokens per weight-tree stream instead of exactly one. See
docs/SPECULATIVE.md.

Sharding: when a mesh is provided, params are placed with the
``parallel/sharding.py`` NamedShardings and the token batch is dp-sharded;
flax logical-axis rules + XLA GSPMD insert the TP collectives. The same
compiled function serves 1-chip TP=1 and v5e-8 DP×TP layouts.

Shape bucketing: S rounds up to a multiple of 64 (128 when the model can take
the Pallas flash path) and B to a multiple of 8 (pad rows are dropped on
output), so a sweep of odd-sized batches reuses a handful of compiled programs
instead of recompiling per shape.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from fairness_llm_tpu.config import MeshConfig, ModelSettings, SpeculationConfig
from fairness_llm_tpu.models.configs import ModelConfig
from fairness_llm_tpu.models.tokenizer import tokenizer_for
from fairness_llm_tpu.models.transformer import Transformer
from fairness_llm_tpu.parallel import sharding as shd
from fairness_llm_tpu.runtime.sampling import (
    SamplerSettings,
    speculation_applicable,
)
from fairness_llm_tpu.runtime.stepbuilder import (
    build_engine_decode,
    build_prefix,
    build_spec_decode,
    compile_key,
)
from fairness_llm_tpu.telemetry import get_registry
from fairness_llm_tpu.telemetry.compilestats import record_compile, startup_stage
from fairness_llm_tpu.telemetry.costmodel import instrument_jit, note_invocation
from fairness_llm_tpu.telemetry.roofline import observe_decode
from fairness_llm_tpu.telemetry.timeline import get_timeline
from fairness_llm_tpu.utils.profiling import SpeculationStats

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class GenerateOutput:
    texts: List[str]
    tokens: np.ndarray  # [B, max_new] int32 (pad-filled after EOS)
    steps: int  # decode-step CAP (max_new_tokens); actual trip count is
    # dynamic — the while_loop exits once every real row hits EOS
    stats: Optional[Dict[str, Any]] = None  # decode-shape diagnostics
    # (batch, prompt_len, prefix_len, cache_slots) for byte accounting,
    # plus a "speculation" sub-dict (SpeculationStats) when spec decode ran


def _bucket_len(n: int, multiple: int = 64) -> int:
    return max(multiple, ((n + multiple - 1) // multiple) * multiple)


def _token_lcp(rows) -> int:
    """Longest common token prefix across rows, capped so that every row
    keeps at least one non-prefix token. Vectorized: the python-loop version
    profiled at ~25 ms per sweep call (5% of the whole decode wall) on 45
    ~900-token rows."""
    if not rows:
        return 0
    limit = min(len(r) for r in rows) - 1
    if limit <= 0:
        return 0
    first = np.asarray(rows[0][:limit], dtype=np.int64)
    agree = np.ones(limit, dtype=bool)
    for r in rows[1:]:
        agree &= first == np.asarray(r[:limit], dtype=np.int64)
        if not agree[0]:
            return 0
    mismatch = np.flatnonzero(~agree)
    return int(mismatch[0]) if mismatch.size else limit


def _is_kernel_compile_error(e: Exception) -> bool:
    """Whether ``e`` is the fused decode-attention kernel failing to
    COMPILE (the VMEM-gate miss the XLA-path fallback exists for).

    Two conditions, both required: the exception must be a compile-/
    runtime-layer error raised by jaxlib (``XlaRuntimeError`` — Mosaic
    rejections surface through it — or any exception whose defining module
    lives under jaxlib/mosaic), AND its text must name the VMEM/Mosaic
    budget. The old substring-only match would also have absorbed an
    arbitrary Python exception that merely mentioned 'scoped', silently
    downgrading the engine for a bug that had nothing to do with the
    kernel."""
    mod = type(e).__module__ or ""
    compile_layer = (
        isinstance(e, jax.errors.JaxRuntimeError)
        or type(e).__name__ == "XlaRuntimeError"
        or mod.startswith(("jaxlib", "jax._src.pallas", "mosaic"))
    )
    if not compile_layer:
        return False
    msg = str(e).lower()
    return "vmem" in msg or "mosaic" in msg or "scoped" in msg


def _bucket_batch(n: int, mesh: Optional[jax.sharding.Mesh] = None) -> int:
    # Multiples of 8 (sublane granularity), not powers of two: decode steps
    # stream the whole [B, max_len] KV cache from HBM, so padding 45 -> 64
    # rows would inflate that traffic 42% for nothing; 45 -> 48 costs 7%.
    # With a mesh, the batch must also divide the dp axis.
    b = 8 if n <= 8 else ((n + 7) // 8) * 8
    if mesh is not None:
        dp = mesh.shape.get("dp", 1)
        b = ((b + dp - 1) // dp) * dp
    return b


class DecodeEngine:
    """Owns params + compiled decode programs for one model."""

    # Stable memory-ledger handles across engine instances in one process
    # (fleets build several engines; re-registering "engine0" from a second
    # instance would silently replace the first's params entry).
    _mem_seq = 0

    @startup_stage("engine")
    def __init__(
        self,
        model_config: ModelConfig,
        params: Optional[Any] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        mesh_config: Optional[MeshConfig] = None,
        tokenizer=None,
        tokenizer_path: Optional[str] = None,
        seed: int = 0,
        assume_sharded: bool = False,
        param_dtype: Optional[str] = None,
        speculation: Optional[SpeculationConfig] = None,
        numerics_guards: bool = False,
    ):
        """``assume_sharded=True`` skips re-placing params onto the mesh —
        for callers (weights loader) that already device_put each tensor onto
        its NamedSharding at load time. ``param_dtype`` ("float32"/"bfloat16")
        overrides the size-based storage policy. ``speculation`` sets the
        engine-wide default for ``generate`` (per-call arg overrides).
        ``numerics_guards`` folds an on-device finite check of the logits
        into every compiled decode program (integrity/numerics.py): one
        AND-reduced flag per chunk, raised host-side as a containable
        ``NumericsFault``. Guarded/unguarded programs compile under
        disjoint keys; the token stream is identical either way."""
        self.config = model_config
        self.speculation = speculation
        self.numerics_guards = bool(numerics_guards)
        # Resilience hooks (resilience/): ``breakers`` — a BreakerBoard whose
        # "speculate" stage gates the speculative path (a persistently-
        # failing spec program trips it open and generate falls back to the
        # plain path, identical output by construction); ``watchdog`` — a
        # StepWatchdog that classifies an over-budget generate call as a
        # containable HangFault. Both default off; backend_for/ServingBackend
        # install them when ResilienceConfig.enabled.
        self.breakers = None
        self.watchdog = None
        # Degradation-ladder shed state (see shed_speculation below): kept
        # ON the engine because several schedulers may share it — a
        # per-caller saved copy could capture an already-shed None and
        # "restore" speculation to permanently off.
        self._spec_shed = False
        self._spec_saved_speculation = None
        self.tokenizer = tokenizer or tokenizer_for(model_config, tokenizer_path)
        self.mesh = mesh
        if mesh is None and mesh_config is not None and mesh_config.num_devices > 1:
            self.mesh = shd.make_mesh(mesh_config)
        self.rules = (
            shd.make_axis_rules(model_config, self.mesh) if self.mesh is not None else ()
        )
        self.model = Transformer(model_config)
        # Param storage dtype policy: f32 params measure FASTER than bf16 for
        # small models on v5e (~0.45 vs 0.60 s on the gpt2 sweep — XLA handles
        # the per-fusion cast well), but a billion-param f32 tree costs 4
        # bytes/param of HBM the cache needs — so large bf16 models store
        # params in bf16.
        big = model_config.approx_param_count >= 1_000_000_000
        if param_dtype is not None:
            if param_dtype not in ("float32", "bfloat16"):
                raise ValueError(
                    f"param_dtype must be 'float32' or 'bfloat16', got {param_dtype!r}"
                )
            param_dtype = jnp.bfloat16 if param_dtype == "bfloat16" else jnp.float32
        else:
            param_dtype = (
                jnp.bfloat16 if (model_config.dtype == "bfloat16" and big) else jnp.float32
            )
        # The resolved STORAGE width. Note for byte accounting: the decode
        # loop streams params at the COMPUTE width regardless (XLA hoists the
        # storage->compute cast out of the loop, per an earlier device trace;
        # ROADMAP.md S1), so roofline models should use config.dtype, not this.
        self.param_itemsize = 2 if param_dtype == jnp.bfloat16 else 4
        if self.mesh is not None:
            pb = shd.per_device_param_bytes(
                model_config, self.mesh, self.rules,
                itemsize=2 if param_dtype == jnp.bfloat16 else 4,
            )
            logger.info(
                "%s on mesh %s: ~%.2f GB params per device",
                model_config.name, dict(self.mesh.shape), pb / 1e9,
            )
        if params is None:
            logger.info("initializing random params for %s", model_config.name)
            # Low-memory init: allocates each leaf directly in the target
            # dtype (flax's f32 init tree alone can OOM a chip for 3B+).
            from fairness_llm_tpu.models.transformer import init_params_lowmem

            params = init_params_lowmem(
                model_config, jax.random.key(seed), dtype=param_dtype
            )
        elif param_dtype == jnp.bfloat16:
            # Float leaves only: int8 kernels stay int8, and the per-channel
            # quant scales stay f32 (the kernel reads them in f32; rounding
            # them to bf16 would perturb every dequantized weight for no
            # memory win — they're one scalar per output channel).
            params = jax.tree_util.tree_map_with_path(
                lambda path, x: x
                if (path and getattr(path[-1], "key", None) == "kernel_scale")
                or not (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating))
                else x.astype(jnp.bfloat16),
                params,
            )
        if self.mesh is not None and not assume_sharded:
            shardings = shd.param_shardings(model_config, self.mesh, self.rules)
            params = shd.shard_params(params, shardings)
        self.params = params
        self._compiled: Dict[Tuple, Any] = {}
        self._mem_handle = f"engine{DecodeEngine._mem_seq}"
        DecodeEngine._mem_seq += 1
        self._account_params_memory()

    def _account_params_memory(self) -> None:
        """Params-vs-HBM preflight, ledger edition (ISSUE 18): register
        the live param tree under ``pool="params"`` — which publishes the
        ``hbm_bytes`` gauge and, through reconciliation, the limit/
        headroom gauges — and re-check the per-device fit against the
        limit the device itself reports. The old one-shot log line only
        ran at first init; this fires again on every engine rebuild (the
        VMEM-fallback path), so a rebuilt engine's accounting stays
        live."""
        from fairness_llm_tpu.telemetry.memory import (  # lazy: no cycle
            device_memory_stats,
            get_memory_ledger,
        )

        get_memory_ledger().register("params", self._mem_handle,
                                     self.params)
        limit = device_memory_stats().get("bytes_limit")
        if limit and self.mesh is not None:
            pb = shd.per_device_param_bytes(
                self.config, self.mesh, self.rules,
                itemsize=self.param_itemsize,
            )
            if pb > 0.95 * limit:
                logger.warning(
                    "per-device params (%.1f GB) likely exceed the chip's "
                    "%.1f GB HBM — use a larger tp axis or quantized "
                    "weights", pb / 1e9, limit / 1e9,
                )

    def _prefix_kv_handle(self, kv_key) -> str:
        """Ledger handle for one prefix-KV LRU entry (stable within this
        process, which is all register/release needs)."""
        return f"{self._mem_handle}:prefix:{abs(hash(kv_key)):x}"

    @property
    def seq_bucket(self) -> int:
        """Sequence bucket multiple: 128 only when this model can actually take
        the Pallas flash path (head_dim tiling + TPU) or attends over lane-
        padded XLA scores on the TPU (latent attention); otherwise 64 to halve
        padding. Shared by decode prefill and scoring so both stay eligible."""
        from fairness_llm_tpu.ops.quant_matmul import lowering_for_tpu

        flash_eligible = (
            self.config.use_flash_attention
            and self.config.head_dim % 64 == 0
            and lowering_for_tpu()
        )
        # Latent attention's prefill builds [.., S, S] scores in XLA: a key
        # axis of 192 is padded to 256 lanes on the chip anyway, and one
        # bucket for a sweep's 175-195 tokens means one prefill program.
        # The same holds for any model whose prefill attends through XLA
        # over the pool (latent layers, or softmax layers beside recurrent
        # ones).
        lane_scores = self.config.attention != "gqa" and lowering_for_tpu()
        return 128 if flash_eligible or lane_scores else 64

    # -- compiled program ---------------------------------------------------

    def _prefix_fn(self, prefix_len: int):
        """Compiled forward over the shared prompt prefix [1, Pc] -> per-layer
        (k, v) arrays [Pc, Hkv, D] every batch row reads (but never copies).
        A ``stepbuilder`` composition, like every compiled program here."""
        key = compile_key("prefix", prefix_len=prefix_len)
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        fn = instrument_jit(
            build_prefix(self.config, self.model, prefix_len=prefix_len),
            "prefix",
        )
        self._compiled[key] = fn
        return fn

    def _decode_fn(self, batch: int, prompt_len: int, max_new: int,
                   sampler_settings: SamplerSettings, prefix_len: int = 0,
                   guard: bool = False):
        # One compile-key scheme for every program (stepbuilder.compile_key).
        # The leading "decode" tag IS the speculation slot of the compile
        # key: speculative programs live under disjoint ("spec_decode", ...,
        # ngram_max, draft_len) keys (and their shapes/returns differ), so
        # toggling speculation can NEVER reuse a stale compiled step for the
        # other mode (pinned by test_spec_compile_keys_disjoint). ``guard``
        # (the numerics-guard flag) changes the return arity, so it is part
        # of the key for the same stale-program reason.
        key = compile_key("decode", batch=batch, prompt_len=prompt_len,
                          max_new=max_new, sampler=sampler_settings,
                          prefix_len=prefix_len, guard=guard)
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        # The plain program is the builder's batch entry + the SHARED greedy
        # while_loop skeleton (the same loop serve_step runs over
        # the slot pool) with a uniform cap. instrument_jit = jax.jit + the
        # cost ledger (telemetry/costmodel.py): the first attribution-on
        # call walks the program's jaxpr into cost_ledger_bytes/flops
        # {program="decode"} gauges.
        run = build_engine_decode(
            self.config, self.model, sampler_settings,
            self.tokenizer.pad_id, self.tokenizer.eos_id, batch=batch,
            prompt_len=prompt_len, max_new=max_new, prefix_len=prefix_len,
            guard=guard,
        )
        fn = instrument_jit(run, "decode")
        self._compiled[key] = fn
        return fn

    def _spec_decode_fn(self, batch: int, prompt_len: int, max_new: int,
                        prefix_len: int, spec: SpeculationConfig,
                        guard: bool = False):
        """Compiled speculative decode: greedy draft-and-verify.

        One while_loop iteration = ONE multi-token verify forward over
        ``k+1 = spec.draft_len+1`` positions per row (the greedy next token
        plus k prompt-lookup drafts), accepting the longest prefix matching
        greedy argmax — so each iteration emits 1..k+1 tokens per row while
        streaming params/KV once, vs once PER TOKEN on the plain path.
        Token-for-token identical to the plain greedy program by
        construction (parity pinned in tests/test_speculative.py).

        Rows advance at their own acceptance rates, so cache writes use
        per-row ``write_offsets`` (slot = prompt_len + tokens emitted) and
        rejected slots are re-invalidated after each step; the next step's
        window always overwrites them. The cache carries ``draft_len`` spare
        slots so the last verify window of a nearly-finished row still fits.
        """
        # ``guard`` sits mid-key (not last): the speculation knobs stay the
        # key's trailing pair, which diagnostics (and the compile-key test)
        # rely on. See stepbuilder.compile_key for the one scheme.
        key = compile_key("spec_decode", batch=batch, prompt_len=prompt_len,
                          max_new=max_new, prefix_len=prefix_len,
                          guard=guard, ngram_max=spec.ngram_max,
                          draft_len=spec.draft_len)
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        run = build_spec_decode(
            self.config, self.model, self.tokenizer.pad_id,
            self.tokenizer.eos_id, batch=batch, prompt_len=prompt_len,
            max_new=max_new, prefix_len=prefix_len,
            ngram_max=spec.ngram_max, draft_len=spec.draft_len, guard=guard,
        )
        fn = instrument_jit(run, "spec_decode")
        self._compiled[key] = fn
        return fn

    # -- public API ---------------------------------------------------------

    def shed_speculation(self) -> None:
        """Degradation rung 1 (resilience/breaker.py): disable the engine's
        default speculation, remembering the original config. Idempotent —
        the first shedding caller wins; later callers (other schedulers
        sharing this engine) are no-ops, so restore can never capture an
        already-shed None."""
        if not self._spec_shed:
            self._spec_saved_speculation = self.speculation
            self.speculation = None
            self._spec_shed = True

    def restore_speculation(self) -> None:
        """Undo ``shed_speculation`` (ladder retreat). No-op unless shed."""
        if self._spec_shed:
            self.speculation = self._spec_saved_speculation
            self._spec_saved_speculation = None
            self._spec_shed = False

    def generate(
        self,
        prompts: Sequence[str],
        settings: Optional[ModelSettings] = None,
        max_new_tokens: Optional[int] = None,
        seed: int = 0,
        row_seeds: Optional[Sequence[int]] = None,
        share_prefix: Optional[bool] = None,
        prefix_ids: Optional[Sequence[int]] = None,
        speculation: Optional[SpeculationConfig] = None,
    ) -> GenerateOutput:
        """Decode a batch of prompts; returns detokenized continuations.

        ``row_seeds`` (one per prompt) make each row's sampling independent of
        batch composition: the same (prompt, row_seed, settings) decodes the
        same text whatever else shares the batch. Default: seed + position.

        ``speculation`` overrides the engine default. It engages only for
        greedy decode (temperature <= 0) — sampled decode silently takes the
        plain path (``runtime/sampling.speculation_applicable``); the output
        stream is identical either way, speculation only changes speed.
        """
        settings = settings or ModelSettings()
        max_new = settings.max_tokens if max_new_tokens is None else max_new_tokens
        sampler = SamplerSettings(
            temperature=settings.temperature, top_k=settings.top_k, top_p=settings.top_p
        )
        spec = speculation if speculation is not None else self.speculation
        use_spec = bool(
            spec is not None and spec.enabled and spec.draft_len > 0
            and speculation_applicable(sampler) and max_new > 1
            # Breaker-gated (resilience/breaker.py): an open "speculate"
            # breaker sheds the speculative path until its half-open probe —
            # output is identical either way, so this is pure degradation.
            and (self.breakers is None or self.breakers.allow("speculate"))
        )

        # The cache (and, for learned-position models, the position table) holds
        # max_seq_len slots; out-of-range gathers clamp silently under jit, so
        # enforce the budget here and truncate prompts from the left.
        if max_new >= self.config.max_seq_len:
            raise ValueError(
                f"max_new_tokens {max_new} >= model max_seq_len {self.config.max_seq_len}"
            )
        prompt_budget = self.config.max_seq_len - max_new
        t_start = time.perf_counter()
        n = len(prompts)
        if n == 0:
            # An empty chunk (e.g. a fully-resumed sweep) must not compile and
            # run an all-pad-rows device program just to discard it.
            return GenerateOutput(
                texts=[], tokens=np.zeros((0, max_new), np.int32), steps=max_new,
                stats={"batch": 0, "prompt_len": 0, "prefix_len": 0,
                       "cache_slots": 0},
            )

        # Shared-prefix decode: the counterfactual sweep's prompts are
        # near-identical, so their longest common TOKEN prefix is most of the
        # prompt. Compute its KV once [Pc, Hkv, D] instead of per-row —
        # decode is KV-read-bound, so a shared 80% prefix cuts that traffic
        # by ~0.8*(1 - 1/B).
        #
        # ``prefix_ids`` (explicit, from the caller) is the reproducible way:
        # pipelines compute the prefix over the FULL sweep once, so resumed /
        # re-chunked batches split attention identically. Auto-detection
        # (share_prefix=None/True without prefix_ids) is composition-
        # DEPENDENT: near-tie sampled tokens can differ between a batch and
        # its resume-subset — fine for one-shot calls, not for sweeps.
        from fairness_llm_tpu.models.tokenizer import _left_pad

        if self.config.attention != "gqa":
            # A shared prefix is [Pc, Hkv, D] keys and values, asked of every
            # layer: a latent cache has no such form yet, and a per-row
            # state after the prefix would have to be restored per row
            # (stepbuilder.build_prefix refuses both), so every row prefills
            # its whole prompt.
            share_prefix, prefix_ids = False, None
        rows = [self.tokenizer.encode(p) for p in prompts]
        shared_ids: Optional[list] = None
        if share_prefix is not False and n >= 1 and prefix_ids is not None:
            pl = list(prefix_ids)
            # Contract: prefix_ids must be a STRICT prefix of every prompt.
            # A row equal to the prefix would decode from an empty remainder
            # (its first sample conditioning on a pad embedding), and quietly
            # disabling sharing for just this batch would split attention
            # differently between a sweep chunk and its resume-subset — so a
            # violation fails loudly instead of diverging numerically.
            if not all(len(r) > len(pl) and r[: len(pl)] == pl for r in rows):
                raise ValueError(
                    "prefix_ids must be a strict prefix of every prompt "
                    "(recompute it over the full sweep, e.g. via "
                    "pipeline.backends.shared_prefix_ids)"
                )
            shared_ids = pl
        elif share_prefix is not False and n >= 2 and prefix_ids is None:
            common = _token_lcp(rows)
            min_shared = 64 if share_prefix is None else 1
            if common >= min_shared:
                shared_ids = rows[0][:common]

        if shared_ids is not None:
            # Budget cap: reserve at least 64 remainder slots so the prefix
            # can never consume the whole budget. The cap is a CONSTANT (not
            # derived from this batch's rows) so the effective prefix is
            # identical for every chunk of a sweep — resumed chunks are
            # filtered subsets, and any row-dependent adjustment here would
            # split attention differently on resume. Rows longer than the
            # budget lose mid-prompt tokens to the remainder left-truncation
            # below, exactly like the plain path's recency-keeping truncation.
            shared_ids = shared_ids[: max(0, prompt_budget - 64)]
            if share_prefix is not True:
                # floor to a multiple of 64 so distinct sweeps land on shared
                # compiled programs (explicit True keeps the caller's length)
                shared_ids = shared_ids[: (len(shared_ids) // 64) * 64]
            if not shared_ids:
                shared_ids = None

        if shared_ids is not None:
            remainders = [r[len(shared_ids):] for r in rows]
            rem_budget = prompt_budget - len(shared_ids)
            tb = _left_pad(remainders, self.tokenizer.pad_id)
            # Remainder rows are short (the sweep's prompts differ only past
            # the prefix); a 32-multiple bucket keeps 32 fewer KV slots per
            # row streaming through every decode step than the default 64.
            prompt_len = _bucket_len(min(tb.tokens.shape[1], rem_budget), 32)
            if prompt_len > rem_budget:
                prompt_len = max(rem_budget, 1)
            if tb.tokens.shape[1] > prompt_len:
                tb = _left_pad(remainders, self.tokenizer.pad_id, max_len=prompt_len)
        else:
            tb = _left_pad(rows, self.tokenizer.pad_id)
            prompt_len = _bucket_len(min(tb.tokens.shape[1], prompt_budget), self.seq_bucket)
            if prompt_len > prompt_budget:
                prompt_len = prompt_budget
            if tb.tokens.shape[1] > prompt_len:
                tb = _left_pad(rows, self.tokenizer.pad_id, max_len=prompt_len)
        batch = _bucket_batch(n, self.mesh)
        tokens = np.full((batch, prompt_len), self.tokenizer.pad_id, dtype=np.int32)
        valid = np.zeros((batch, prompt_len), dtype=bool)
        s = tb.tokens.shape[1]
        assert s <= prompt_len
        tokens[:n, prompt_len - s:] = tb.tokens
        valid[:n, prompt_len - s:] = tb.valid
        # Pad rows decode garbage against an all-invalid cache; give them one
        # valid BOS-ish token so attention has something to normalize over.
        valid[n:, -1] = True

        if row_seeds is None:
            row_seeds_arr = np.asarray(
                [seed * 1_000_003 + i for i in range(batch)], dtype=np.uint32
            )
        else:
            if len(row_seeds) != n:
                raise ValueError(f"row_seeds has {len(row_seeds)} entries for {n} prompts")
            row_seeds_arr = np.zeros(batch, dtype=np.uint32)
            row_seeds_arr[:n] = np.asarray(row_seeds, dtype=np.uint64).astype(np.uint32)

        prefix_len = len(shared_ids) if shared_ids is not None else 0

        guard = self.numerics_guards

        def build_fn():
            if use_spec:
                return self._spec_decode_fn(
                    batch, prompt_len, max_new, prefix_len, spec, guard=guard
                )
            return self._decode_fn(batch, prompt_len, max_new, sampler,
                                   prefix_len, guard=guard)

        # Snapshot for the watchdog's compile exemption below: if this call
        # grows the compiled-program cache (first use of a shape, a VMEM/
        # spec fallback rebuild, a fresh prefix KV), its wall includes
        # compile time and must not classify as a hang. The KEY set (not
        # just the count) also feeds compile observability: every key the
        # call adds is one fresh compilation attributed the call's wall.
        keys_before = set(self._compiled)
        n_compiled_before = len(self._compiled)
        t0_mono = time.monotonic()
        fn = build_fn()
        tokens_j = jnp.asarray(tokens)
        valid_j = jnp.asarray(valid)
        if self.mesh is not None:
            bs = shd.batch_sharding(self.mesh)
            tokens_j = jax.device_put(tokens_j, bs)
            valid_j = jax.device_put(valid_j, bs)
            ctx_mesh = self.mesh
        else:
            ctx_mesh = None

        shared_layers = None
        if prefix_len:
            # Cache the prefix KV per sweep (every chunk passes the same ids)
            # and compute it under the same mesh/rules context as decode.
            kv_key = ("prefix_kv", tuple(shared_ids))
            shared_layers = self._compiled.get(kv_key)
            if shared_layers is not None:
                # LRU refresh: without it a recurring sweep prefix stays
                # oldest-inserted and one-off prefixes evict it.
                self._compiled[kv_key] = self._compiled.pop(kv_key)
            if shared_layers is None:
                pfn = self._prefix_fn(prefix_len)
                ids_j = jnp.asarray(shared_ids, jnp.int32)[None, :]
                if ctx_mesh is not None:
                    with ctx_mesh, nn.logical_axis_rules(self.rules):
                        shared_layers = pfn(self.params, ids_j)
                else:
                    shared_layers = pfn(self.params, ids_j)
                # Each cached prefix KV holds device memory (layers x [Pc, H, D]);
                # evict the oldest beyond a small working set so a long-lived
                # engine serving many different sweeps doesn't accumulate HBM.
                # ISSUE 18: each entry is registered with the memory ledger
                # under pool="prefix_cache" (bytes held ride hbm_bytes) and
                # released on evict; entry count and evictions get their
                # own instruments — this LRU was device memory with zero
                # telemetry before.
                from fairness_llm_tpu.telemetry.memory import (  # lazy
                    get_memory_ledger,
                )

                mem = get_memory_ledger()
                kv_keys = [k for k in self._compiled if k[0] == "prefix_kv"]
                while len(kv_keys) >= 4:
                    victim = kv_keys.pop(0)
                    del self._compiled[victim]
                    mem.release("prefix_cache", self._prefix_kv_handle(victim))
                    get_registry().counter(
                        "prefix_kv_evictions_total", component="engine"
                    ).inc()
                self._compiled[kv_key] = shared_layers
                mem.register("prefix_cache", self._prefix_kv_handle(kv_key),
                             shared_layers)
                get_registry().gauge(
                    "prefix_kv_entries", component="engine"
                ).set(len(kv_keys) + 1)

        seeds_j = jnp.asarray(row_seeds_arr)
        live = np.zeros(batch, dtype=bool)
        live[:n] = True
        live_j = jnp.asarray(live)
        pref_j = jnp.asarray(
            shared_ids if shared_ids is not None else [], jnp.int32
        )

        def call(f):
            if use_spec:
                args = (self.params, tokens_j, valid_j, live_j, shared_layers,
                        pref_j)
            else:
                args = (self.params, tokens_j, valid_j, seeds_j, live_j,
                        shared_layers)
            if ctx_mesh is not None:
                with ctx_mesh, nn.logical_axis_rules(self.rules):
                    return f(*args)
            return f(*args)

        if self.watchdog is not None:
            self.watchdog.arm("decode")
        # Set by either in-call degradation below: a call that failed once,
        # rebuilt/recompiled, and retried is by definition not a steady-state
        # step — its combined wall must not classify as a hang (the compile-
        # growth check alone can coincide when the retry's program was
        # already cached).
        degraded_in_call = False
        try:
            res = call(fn)
        except Exception as e:  # noqa: BLE001 — two in-call degradations below
            degraded_in_call = True
            if (
                self.config.use_decode_attention_kernel
                and _is_kernel_compile_error(e)
            ):
                # VMEM-gate miss fallback: the fused decode-attention
                # kernel's eligibility gate is a calibrated VMEM model
                # (ops/decode_attention._block_bytes), not an exact
                # accounting — a shape where it under-predicts passes the
                # gate and Mosaic rejects the program at compile time. That
                # must degrade to the XLA path, not fail the study: rebuild
                # this engine without the kernel and recompile once.
                logger.warning(
                    "fused decode-attention kernel failed to compile (%s); "
                    "falling back to the XLA attention path for this engine",
                    type(e).__name__,
                )
                self.config = dataclasses.replace(
                    self.config, use_decode_attention_kernel=False
                )
                self.model = Transformer(self.config)
                self._compiled = {
                    k: v for k, v in self._compiled.items()
                    if k[0] == "prefix_kv"
                }
                # Rebuild = a fresh accounting pass: the preflight fires
                # here too now, not just at first init (ISSUE 18).
                self._account_params_memory()
                fn = build_fn()
                res = call(fn)
            elif use_spec and self.breakers is not None:
                # Speculative-path failure with a breaker armed: count it
                # (enough consecutive ones trip "speculate" open, shedding
                # the path until a half-open probe) and retry THIS call on
                # the plain path — greedy output is identical by
                # construction, so the caller never sees the degradation.
                self.breakers.record_failure("speculate")
                get_registry().counter(
                    "faults_total", component="engine", kind="device",
                    stage="speculate",
                ).inc()
                logger.warning(
                    "speculative decode failed (%s: %s); retrying on the "
                    "plain path", type(e).__name__, e,
                )
                use_spec = False
                fn = build_fn()
                res = call(fn)
            else:
                raise
        spec_stats = None
        finite_dev = None
        if use_spec:
            if guard:
                toks_dev, out_len_dev, counters_dev, finite_dev = res
            else:
                toks_dev, out_len_dev, counters_dev = res
            out = np.asarray(jax.device_get(toks_dev))[:n]
            counters = np.asarray(jax.device_get(counters_dev))
            emitted = int(np.asarray(jax.device_get(out_len_dev))[:n].sum())
            spec_stats = SpeculationStats(
                drafted=int(counters[0]), accepted=int(counters[1]),
                verify_steps=int(counters[2]), emitted=emitted,
                draft_len=spec.draft_len, ngram_max=spec.ngram_max,
            )
        else:
            if guard:
                res, finite_dev = res
            out = np.asarray(jax.device_get(res))[:n]
        if finite_dev is not None:
            # Numerics guard (integrity/numerics.py): a tripped chunk flag
            # discards the chunk's tokens as a containable NumericsFault —
            # with_failure_containment retries once then sentinels, same as
            # any other decode fault. Checked before hang classification
            # (the more specific diagnosis wins).
            from fairness_llm_tpu.integrity.numerics import check_finite

            try:
                check_finite(
                    jax.device_get(finite_dev), "engine",
                    "speculate" if use_spec else "decode",
                )
            except Exception:
                if use_spec and self.breakers is not None:
                    # A numerically-sick speculative path must feed its
                    # breaker like a crashed one: enough consecutive trips
                    # shed the path until a half-open probe.
                    self.breakers.record_failure("speculate")
                raise
        # Speculate-breaker success only once the chunk is KNOWN good —
        # recording it before the finite check would let a persistently
        # NaN-poisoned verify window reset the count every call and the
        # breaker would never open.
        if use_spec and self.breakers is not None:
            self.breakers.record_success("speculate")
        if self.watchdog is not None:
            # Hang classification once the host has the tokens (post-hoc by
            # construction — a single-threaded loop can't interrupt its own
            # blocked call): an over-budget generate raises HangFault, which
            # with_failure_containment retries once and then sentinels, the
            # same containment every other decode fault gets. Calls that
            # compiled (cache grew) are exempt — compile time is not step
            # time.
            self.watchdog.observe(
                "decode",
                classify=(not degraded_in_call
                          and len(self._compiled) == n_compiled_before),
            )

        texts = []
        for row in out:
            ids = []
            for t in row:
                if t == self.tokenizer.eos_id:
                    break
                ids.append(int(t))
            texts.append(self.tokenizer.decode(ids))
        # Engine-path telemetry (component="engine"): call/token counters and
        # the per-call wall histogram. Wall time here includes any compile —
        # warmed steady-state calls dominate a sweep, and the histogram's
        # max/percentile spread is exactly how a cold compile shows up.
        reg = get_registry()
        wall = time.perf_counter() - t_start
        reg.counter("generate_calls_total", component="engine").inc()
        reg.counter("prompt_tokens_total", component="engine").inc(
            int(sum(len(r) for r in rows))
        )
        reg.counter("decoded_tokens_total", component="engine").inc(
            int(np.sum(out != self.tokenizer.pad_id))
        )
        reg.counter(
            "decode_paths_total", component="engine",
            path="speculative" if use_spec else "plain",
        ).inc()
        reg.histogram("generate_wall_s", component="engine").observe(wall)
        if spec_stats is not None:
            spec_stats.publish(reg)
        stats: Dict[str, Any] = {
            "batch": batch,
            "prompt_len": prompt_len,
            "prefix_len": prefix_len,
            # spec decode carries draft_len spare slots for the last window
            "cache_slots": prompt_len + max_new + (spec.draft_len if use_spec else 0),
            # The EFFECTIVE attention path: read from config AFTER any
            # in-call VMEM fallback, so a record produced past a gate miss
            # carries decode_kernel=False provenance even though the
            # engine was built with the kernel requested.
            "decode_kernel": bool(self.config.use_decode_attention_kernel),
        }
        if spec_stats is not None:
            stats["speculation"] = spec_stats.as_dict()
        # Performance attribution (telemetry/): the call as a span on the
        # "engine" timeline track; every compile key the call added as a
        # fresh compilation (the span's wall is the compile-dominated upper
        # bound each key gets — in practice one call compiles at most a
        # prefix program + one decode program); the live roofline gauges.
        # The span runs from t0_mono (post-tokenize, where the device work
        # starts) to NOW on the same clock — `wall` above starts at t_start
        # and would overrun the call's real end by the tokenize/pad time.
        wall_mono = time.monotonic() - t0_mono
        path = "speculative" if use_spec else "plain"
        get_timeline().record_span(
            f"generate[{batch}x{prompt_len}]",
            "speculate" if use_spec else "decode", "engine", t0_mono,
            wall_mono, path=path, prefix_len=prefix_len,
        )
        for key in set(self._compiled) - keys_before:
            if key[0] != "prefix_kv":  # cached KV arrays, not a program
                record_compile(key[0], reason="shape", seconds=wall_mono,
                               key=key)
        if use_spec:
            steps_done = spec_stats.verify_steps
        else:
            # Plain-path trip count: the while_loop runs until the slowest
            # row finishes, so steps == the max per-row emitted count.
            per_row = np.sum(out != self.tokenizer.pad_id, axis=1)
            steps_done = int(per_row.max()) if per_row.size else 0
        # wall_mono still includes prefill + detokenize, so the fraction is
        # a lower bound on steady-state decode efficiency — the serving
        # scheduler's per-chunk numbers are the precise ones.
        observe_decode(self.config, stats, steps_done, wall_mono,
                       program="spec_decode" if use_spec else "decode")
        # Gap attribution (telemetry/costmodel.py): this call's measured
        # wall + trip count against the compiled program's analytic ledger.
        # Calls that grew the compile cache are compile-dominated (the
        # watchdog-exemption condition) and tagged so the decomposition
        # names compile instead of inflating "unattributed".
        note_invocation("spec_decode" if use_spec else "decode", wall_mono,
                        steps_done, ledger=getattr(fn, "ledger", None),
                        compiling=any(k[0] != "prefix_kv" for k in
                                      set(self._compiled) - keys_before))
        return GenerateOutput(texts=texts, tokens=out, steps=max_new, stats=stats)
