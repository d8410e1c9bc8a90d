"""One step-program builder for every compiled decode variant.

The static engine's plain ``decode`` and speculative ``spec_decode`` and
the serving scheduler's ``serve_prefill``/``serve_step`` share one
while-loop skeleton, guard layering, and compile-key scheme, so a
cross-cutting feature (the numerics guard changing return arity, mutable
``decode_chunk`` compile keys, per-row write offsets) is woven through
once. The programs are compositions over four orthogonal axes:

- **token selection** — greedy/sampled single-token steps
  (:func:`make_greedy_loop`, the ONE while-loop skeleton the plain engine
  decode and ``serve_step`` both run) or the speculative
  draft-and-verify window (:func:`build_spec_decode`);
- **guard layer** — ``guard=True`` folds the on-device finite check
  (``integrity/numerics.masked_finite``) into the carry as one AND-reduced
  flag, appended to the return tuple (arity change = compile-key axis);
- **fuse factor** — ``fuse=k`` runs ``k`` decode chunks' worth of steps
  inside ONE compiled dispatch (the Kernel-Looping move: per-step host
  sync amortizes 1/k) with per-row live masks, caps, and write offsets
  advancing in-program, so continuous batching and the guard compose
  unchanged. Fused programs publish under their own
  telemetry label (:func:`program_label`) so the cost ledger, roofline
  gauges, and host-gap accounting attribute them separately;
- **mesh/sharding** — the axis PR 14 reserved: under a tp mesh every
  composition lowers as ONE SPMD computation (params placed by
  ``parallel/sharding.py`` rules, activations constrained along the model
  axis by the transformer's ``with_logical_constraint`` annotations, the
  KV cache sharded on KV heads). The programs themselves
  are mesh-agnostic — the scheduler runs them inside ``with mesh,
  nn.logical_axis_rules(...)`` and places the carried device state
  (``parallel.sharding.kv_tree_shardings``); what changes here is the key
  scheme (``tp`` appends a mesh element, :func:`compile_key`) and the
  telemetry label (``@tp<k>`` suffix, :func:`program_label`), both
  byte-identical at tp=1.

Compile keys come from ONE scheme (:func:`compile_key`) instead of
per-site tuple literals. Key invariants the rest of the stack relies on
(pinned in tests): ``key[0]`` is the program's base name — the speculation
slot, so plain/speculative programs can never alias; the guard flag is the
last element of ``decode`` keys and sits mid-key on ``spec_decode`` keys
(whose trailing pair stays ``(ngram_max, draft_len)``); step keys carry
``(chunk, guard, fuse)`` so the degradation ladder's halved chunk and a
fused dispatch each compile their own program and restoring reuses the
original.

Every builder names its closure before ``jax.jit`` sees it (the program's
label plus its bucket: ``serve_step``, ``serve_prefill_8x256``,
``serve_step_fused``, ``decode_4x64``), so the executable reads
``jit_serve_step(...)`` on the device's ``XLA Modules`` line of a profiler
trace and in JAX's compile logs, and not ``jit_run`` for all of them.

Behavioral contract: every composition is token-for-token identical to the
hand-threaded program it replaced — the whole pre-existing parity/golden/
chaos test surface is the regression net, plus the dedicated harness in
``tests/test_stepbuilder.py`` enumerating the axis grid.

Callers (``runtime/engine.py``, ``serving/scheduler.py``) keep their own
``_compiled`` dicts and host-side dispatch/telemetry; this module owns the
device-program construction and the key scheme.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from fairness_llm_tpu.models.configs import EXTEND_WINDOW
from fairness_llm_tpu.models.transformer import RowState, init_cache
from fairness_llm_tpu.parallel.sharding import require_gqa
from fairness_llm_tpu.runtime.sampling import (
    SamplerSettings,
    greedy_accept_length,
    make_sampler,
)
from fairness_llm_tpu.runtime.speculative import ngram_draft

#: The decode-loop step programs a scheduler can dispatch (plain, fused).
STEP_PROGRAMS = ("serve_step", "serve_step_fused")


def program_label(base: str, fuse: int = 1, tp: int = 1) -> str:
    """Telemetry name for a step program: fused dispatches (``fuse > 1``)
    publish under ``<base>_fused`` so their compile stats, cost ledger,
    roofline gauges, and host-gap accounting read apart from the per-chunk
    baseline (``validate_telemetry`` requires a fused program seen in
    ``compiles_total`` to publish all three). Sharded programs (``tp > 1``)
    additionally publish under ``<label>@tp<k>`` — a real-mesh program's
    roofline/ledger/collectives accounting must never fold into the
    single-device baseline it is being compared against. ``tp=1`` labels
    are byte-identical to the pre-mesh scheme."""
    label = base if fuse <= 1 else f"{base}_fused"
    return label if tp <= 1 else f"{label}@tp{tp}"


def base_program(label: str) -> str:
    """Strip the mesh suffix off a :func:`program_label` name:
    ``serve_step_fused@tp2`` -> ``serve_step_fused``. The inverse the
    telemetry gates (``validate_telemetry``'s fused-program checks) use so
    a sharded fused program is still recognized as fused."""
    return label.split("@", 1)[0]


def compile_key(program: str, *, batch: Optional[int] = None,
                prompt_len: Optional[int] = None,
                max_new: Optional[int] = None,
                sampler: Optional[SamplerSettings] = None,
                prefix_len: int = 0, guard: bool = False,
                ngram_max: Optional[int] = None,
                draft_len: Optional[int] = None,
                chunk: Optional[int] = None, fuse: int = 1,
                nb: Optional[int] = None, P: Optional[int] = None,
                copies: bool = True, tp: int = 1) -> Tuple:
    """The one compile-key scheme for every step program.

    Axes are per-program-shape (batch/prompt buckets, decode caps), plus
    the cross-cutting ones every variant shares: the guard flag (return
    arity), the mutable ``decode_chunk``, and the fuse factor. See the
    module docstring for the pinned layout invariants.

    The mesh axis: ``tp > 1`` APPENDS a ``("tp", k)`` element — a sharded
    program lowers to a different SPMD computation (GSPMD-inserted
    collectives, sharded cache layout) and must never alias the
    single-device one. ``tp=1`` keys are byte-identical to the pre-mesh
    scheme (pinned in tests), so existing baselines/goldens stay valid,
    and the tagged-tuple element can never collide with a positional int
    axis like ``fuse``.
    """
    if program == "prefix":
        key: Tuple = ("prefix", prefix_len)
    elif program == "decode":
        key = ("decode", batch, prompt_len, max_new, sampler, prefix_len,
               guard)
    elif program == "spec_decode":
        # ``guard`` sits mid-key: the speculation knobs stay the trailing
        # pair, which diagnostics (and the compile-key test) rely on.
        key = ("spec_decode", batch, prompt_len, max_new, prefix_len,
               guard, ngram_max, draft_len)
    elif program == "serve_prefill":
        key = ("serve_prefill", nb, P, guard)
        if not copies:
            # The program of a batch in which no row copies (a tagged
            # element, as the mesh axis': the keys that were stay as they
            # were).
            key += (("copies", False),)
    elif program == "serve_step":
        key = ("serve_step", chunk, guard, fuse)
    else:
        raise ValueError(f"unknown step program {program!r}")
    if tp > 1:
        key = key + (("tp", tp),)
    return key


# -- shared pieces -------------------------------------------------------------


def _masked_finite():
    # Lazy: integrity/ is only touched when a guard layer is actually
    # composed in, mirroring the pre-builder call sites.
    from fairness_llm_tpu.integrity.numerics import masked_finite

    return masked_finite


def make_batch_entry(cfg, model, *, batch: int, cache_len: int,
                     prefix_len: int = 0):
    """The left-padded batch prefill every ENGINE program starts with:
    positions from the valid cumsum (prefix-offset, pad slots clamped), a
    fresh cache of ``cache_len`` slots, one forward with ``last_only``
    logits. Returns ``entry(params, tokens, valid, shared_layers) ->
    (last_logits, cache)``."""

    def entry(params, tokens, valid, shared_layers):
        positions = prefix_len + jnp.maximum(
            jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1, 0
        )
        cache = init_cache(cfg, batch, cache_len)
        logits, cache = model.apply(
            {"params": params}, tokens, positions, valid, cache,
            left_padded=True, last_only=True, shared_layers=shared_layers,
        )
        return logits[:, -1, :], cache

    return entry


def make_greedy_loop(model, sample, pad_id: int, eos_id: int, *, batch: int,
                     steps: int, guard: bool, prefix_len: int = 0,
                     per_row_offsets: bool = False):
    """The shared greedy/sampled decode ``while_loop`` — the skeleton the
    plain engine decode, ``serve_step`` and its fused variant all run.

    Per iteration: sample from the carried logits with the row's own
    ``fold_in(emitted)`` key stream (identical to the engine's
    ``fold_in(step)`` stream — a live row's emitted count IS the step
    index), write the token at the chunk column, forward one token with
    the row's validity mask, carry the new logits, advance per-row
    ``emitted``/``done`` (EOS or the row's cap). Early exit when every
    live row finishes. ``per_row_offsets`` threads ``write_offsets =
    base + emitted`` into the cache write (the serving slot layout);
    without it the cache writes at its own lengths (the engine layout).

    Returns ``loop(params, cache, prev_logits, row_seeds, emitted0, base,
    caps, live0, shared_layers, pool_spans=None) -> final carry`` with
    layout
    ``(t, cache, prev_logits, done, emitted, toks, counters[, finite])``
    — ``toks`` is the ``[batch, steps]`` pad-filled emit buffer,
    ``counters`` is ``[steps_run, live_row_steps]``.
    """
    B, T = batch, steps
    masked_finite = _masked_finite() if guard else None

    def loop(params, cache, prev_logits, row_seeds, emitted0, base, caps,
             live0, shared_layers, pool_spans=None):
        row_keys = jax.vmap(jax.random.key)(row_seeds)
        toks0 = jnp.full((B, T), pad_id, jnp.int32)
        done0 = ~live0
        counters0 = jnp.zeros((2,), jnp.int32)  # steps, live-row-steps

        def cond(carry):
            t, done = carry[0], carry[3]
            return (t < T) & ~jnp.all(done)

        def body(carry):
            t, cache, prev_logits, done, emitted, toks, counters = carry[:7]
            live = ~done
            step_keys = jax.vmap(jax.random.fold_in)(row_keys, emitted)
            tok = sample(prev_logits, step_keys)
            tok = jnp.where(live, tok, pad_id)
            toks = jax.lax.dynamic_update_slice(
                toks, tok[:, None], (jnp.zeros((), jnp.int32), t)
            )
            pos = cache.lengths[:, None]
            if prefix_len:
                pos = prefix_len + pos
            apply_kwargs = dict(shared_layers=shared_layers)
            if per_row_offsets:
                apply_kwargs["write_offsets"] = base + emitted
            if pool_spans is not None:
                apply_kwargs["pool_spans"] = pool_spans
            logits, cache = model.apply(
                {"params": params}, tok[:, None], pos, live[:, None],
                cache, **apply_kwargs,
            )
            prev_logits = jnp.where(
                live[:, None], logits[:, -1, :], prev_logits
            )
            emitted = emitted + live.astype(jnp.int32)
            done = done | (tok == eos_id) | (emitted >= caps)
            counters = counters + jnp.stack(
                [jnp.ones((), jnp.int32), jnp.sum(live, dtype=jnp.int32)]
            )
            out = (t + 1, cache, prev_logits, done, emitted, toks, counters)
            if guard:
                out += (carry[7] & masked_finite(logits[:, -1, :], live),)
            return out

        init = (jnp.zeros((), jnp.int32), cache, prev_logits, done0,
                emitted0, toks0, counters0)
        if guard:
            # Entry check covers the CARRIED logits (the sample source —
            # where host-side NaN injection, and a poisoned prefill that
            # slipped a disabled guard, would sit). Live rows only:
            # released slots legitimately carry stale garbage.
            init += (masked_finite(prev_logits, live0),)
        return jax.lax.while_loop(cond, body, init)

    return loop


# -- engine programs (one dispatch = prefill + full decode) --------------------


def build_engine_decode(cfg, model, sampler: SamplerSettings, pad_id: int,
                        eos_id: int, *, batch: int, prompt_len: int,
                        max_new: int, prefix_len: int, guard: bool):
    """The static engine's plain program: batch entry + the shared greedy
    loop with a uniform cap (every row's budget is ``max_new``, so per-row
    caps coincide with the loop bound) and engine-layout cache writes (no
    per-row offsets — each row's KV appends at its own length)."""
    sample = make_sampler(sampler)
    entry = make_batch_entry(cfg, model, batch=batch,
                             cache_len=prompt_len + max_new,
                             prefix_len=prefix_len)
    loop = make_greedy_loop(model, sample, pad_id, eos_id, batch=batch,
                            steps=max_new, guard=guard,
                            prefix_len=prefix_len, per_row_offsets=False)

    def run(params, tokens, valid, row_seeds, row_live, shared_layers):
        last_logits, cache = entry(params, tokens, valid, shared_layers)
        c = loop(params, cache, last_logits, row_seeds,
                 jnp.zeros((batch,), jnp.int32), None,
                 jnp.full((batch,), max_new, jnp.int32), row_live,
                 shared_layers)
        if guard:
            return c[5], c[7]  # toks [B, max_new], finite
        return c[5]

    run.__name__ = f"decode_{batch}x{prompt_len}"
    return run


def build_spec_decode(cfg, model, pad_id: int, eos_id: int, *, batch: int,
                      prompt_len: int, max_new: int, prefix_len: int,
                      ngram_max: int, draft_len: int, guard: bool):
    """The speculative selection body: greedy draft-and-verify.

    One while_loop iteration = ONE multi-token verify forward over
    ``k+1 = draft_len+1`` positions per row (the greedy next token plus k
    prompt-lookup drafts), accepting the longest prefix matching greedy
    argmax — so each iteration emits 1..k+1 tokens per row while streaming
    params/KV once, vs once PER TOKEN on the greedy loop. Token-for-token
    identical to the plain greedy composition by construction (parity
    pinned in tests/test_speculative.py and tests/test_stepbuilder.py).

    Rows advance at their own acceptance rates, so cache writes use
    per-row ``write_offsets`` (slot = prompt_len + tokens emitted) and
    rejected slots are re-invalidated after each step; the next step's
    window always overwrites them. The cache carries ``draft_len`` spare
    slots so the last verify window of a nearly-finished row still fits.
    """
    if cfg.row_state_bytes:
        raise NotImplementedError(
            f"speculation rolls rejected positions back by invalidating "
            f"their slots; {cfg.name} keeps a per-row state, which a verify "
            f"window has already moved past them")
    k = draft_len
    masked_finite = _masked_finite() if guard else None
    S = k + 1
    cache_len = prompt_len + max_new + k
    gen_len = max_new + k  # emit buffer widened so a verify window never
    # needs clamped writes; sliced back to max_new on return
    entry = make_batch_entry(cfg, model, batch=batch, cache_len=cache_len,
                             prefix_len=prefix_len)

    def run(params, tokens, valid, row_live, shared_layers, prefix_toks):
        last_logits, cache = entry(params, tokens, valid, shared_layers)

        # Lookup context: [shared prefix | left-padded remainder | gen].
        # The prefix is identical across rows; pad gaps between segments
        # are masked out of n-gram matching by ctx_valid.
        pref_tile = jnp.broadcast_to(
            prefix_toks[None, :], (batch, prefix_len)
        )
        ctx_prompt = jnp.concatenate([pref_tile, tokens], axis=1)
        ctx_prompt_valid = jnp.concatenate(
            [jnp.ones((batch, prefix_len), bool), valid], axis=1
        )
        gen_start = prefix_len + prompt_len
        gpos = jnp.arange(gen_len, dtype=jnp.int32)[None, :]
        step_iota = jnp.arange(S, dtype=jnp.int32)

        gen0 = jnp.full((batch, gen_len), pad_id, jnp.int32)
        out_len0 = jnp.zeros((batch,), jnp.int32)
        done0 = ~row_live
        counters0 = jnp.zeros((3,), jnp.int32)  # drafted, accepted, steps

        def cond(carry):
            step_idx, done = carry[0], carry[3]
            return (step_idx < max_new) & ~jnp.all(done)

        def body(carry):
            step_idx, cache, prev_logits, done, gen, out_len, counters = \
                carry[:7]
            live = ~done
            # The step's guaranteed token: greedy argmax of the carried
            # logits (identical to the plain loop's sample at temp 0).
            t0 = jnp.argmax(prev_logits, axis=-1).astype(jnp.int32)
            t0 = jnp.where(live, t0, pad_id)
            # Drafts via n-gram lookup over history INCLUDING t0.
            gen_t0 = jnp.where(
                (gpos == out_len[:, None]) & live[:, None],
                t0[:, None], gen,
            )
            ctx = jnp.concatenate([ctx_prompt, gen_t0], axis=1)
            ctx_valid = jnp.concatenate(
                [ctx_prompt_valid, gpos <= out_len[:, None]], axis=1
            )
            hist_end = gen_start + out_len + 1
            drafts = ngram_draft(
                ctx, ctx_valid, hist_end, k, ngram_max, pad_id
            )
            inp = jnp.concatenate([t0[:, None], drafts], axis=1)  # [B, S]

            # Verify all S positions in one forward; per-row write slots.
            off = jnp.minimum(prompt_len + out_len, cache_len - S)
            pos = prefix_len + cache.lengths[:, None] + step_iota[None, :]
            tv = jnp.broadcast_to(live[:, None], (batch, S))
            logits, nc = model.apply(
                {"params": params}, inp, pos, tv, cache,
                shared_layers=shared_layers, write_offsets=off,
            )
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
            # g[:, i] is the model's token AFTER input position i, so
            # g[:, :k] checks drafts (= inp[:, 1:]).
            a = greedy_accept_length(drafts, g[:, :k])  # [B] in [0, k]

            # Emitted count e: accepted prefix, truncated at the first
            # EOS (inclusive — plain decode records EOS then stops) and
            # at the max_new cap; 0 for done rows.
            eos_first = jnp.min(
                jnp.where(inp == eos_id, step_iota[None, :], S), axis=1
            )
            e = jnp.minimum(a + 1, eos_first + 1)
            e = jnp.minimum(e, max_new - out_len)
            e = jnp.where(live, e, 0)

            # Scatter the emitted window into the output buffer.
            widx = gpos - out_len[:, None]  # [B, gen_len]
            wtok = jnp.take_along_axis(
                inp, jnp.clip(widx, 0, S - 1), axis=1
            )
            gen = jnp.where((widx >= 0) & (widx < e[:, None]), wtok, gen)

            # Carry logits after the LAST emitted token (the next step's
            # greedy distribution — this is what makes acceptance exact).
            pick = jnp.clip(e - 1, 0, S - 1)
            nl = jnp.take_along_axis(
                logits,
                jnp.broadcast_to(
                    pick[:, None, None], (batch, 1, logits.shape[-1])
                ),
                axis=1,
            )[:, 0]
            prev_logits = jnp.where(live[:, None], nl, prev_logits)

            # Cache fixups: invalidate rejected window slots (the next
            # window starts at off+e and always covers them) and advance
            # lengths by the ACCEPTED count, not the window width.
            slot = jnp.arange(cache_len, dtype=jnp.int32)[None, :]
            wpos = slot - off[:, None]
            in_win = (wpos >= 0) & (wpos < S)
            fixed_valid = nc.key_valid & ~(in_win & (wpos >= e[:, None]))
            nc = nc.replace(
                key_valid=fixed_valid, lengths=cache.lengths + e
            )

            out_len = out_len + e
            done = done | (live & (eos_first < e)) | (out_len >= max_new)
            counters = counters + jnp.stack([
                k * jnp.sum(live, dtype=jnp.int32),
                jnp.sum(jnp.maximum(e - 1, 0), dtype=jnp.int32),
                jnp.ones((), jnp.int32),
            ])
            out = (step_idx + 1, nc, prev_logits, done, gen, out_len,
                   counters)
            if guard:
                # The whole [B, S, V] verify window must be finite: the
                # accepted tokens AND the carried next-step logits both
                # come out of it.
                out += (carry[7] & masked_finite(logits, live),)
            return out

        init = (jnp.zeros((), jnp.int32), cache, last_logits, done0, gen0,
                out_len0, counters0)
        if guard:
            init += (masked_finite(last_logits, row_live),)
            carry_out = jax.lax.while_loop(cond, body, init)
            return (carry_out[4][:, :max_new], carry_out[5], carry_out[6],
                    carry_out[7])
        _, _, _, _, gen, out_len, counters = jax.lax.while_loop(
            cond, body, init
        )
        return gen[:, :max_new], out_len, counters

    run.__name__ = f"spec_decode_{batch}x{prompt_len}"
    return run


def build_prefix(cfg, model, *, prefix_len: int):
    """Compiled forward over the shared prompt prefix [1, Pc] -> per-layer
    (k, v) arrays [Pc, Hkv, D] every batch row reads (but never copies)."""

    require_gqa(cfg, "the shared-prefix forward")

    def run(params, tokens):
        positions = jnp.arange(prefix_len, dtype=jnp.int32)[None, :]
        cache = init_cache(cfg, 1, prefix_len)
        _, cache = model.apply(
            {"params": params}, tokens, positions,
            jnp.ones((1, prefix_len), jnp.bool_), cache,
            left_padded=True, last_only=True,
        )
        out = []
        for layer in cache.layers:
            if cfg.kv_cache_quant:
                from fairness_llm_tpu.models.transformer import _dequantize_kv

                dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
                out.append((
                    _dequantize_kv(layer.k, layer.k_scale, dtype)[0],
                    _dequantize_kv(layer.v, layer.v_scale, dtype)[0],
                ))
            else:
                out.append((layer.k[0], layer.v[0]))
        return tuple(out)

    run.__name__ = f"prefix_{prefix_len}"
    return run


# -- serving step programs (one dispatch = chunk x fuse steps) -----------------


def build_serve_step(cfg, model, sampler: SamplerSettings, pad_id: int,
                     eos_id: int, *, num_slots: int, chunk: int,
                     guard: bool, fuse: int = 1, pool_spans: bool = False):
    """The serving decode program: the shared greedy loop over the slot
    pool.

    ``pool_spans``: the program takes the scheduler's plan, ``lead`` and
    ``shared`` ``[num_slots]`` int32 after ``reset``, and each softmax layer
    attends through ``ops/pool_attention`` (``pool_attention.available``):
    a row's shared positions from its lead's row, its own from itself, an
    idle row nothing.

    Released-slot invalidation rides on the program entry's reset mask
    (rows in ``reset`` lose their key_valid/lengths before any attention
    can touch them — one program instead of a separate invalidate dispatch
    per iteration).

    ``fuse=k`` multiplies the dispatch's step budget to ``chunk * k`` —
    per-row caps, EOS stops, live masks, and write offsets all advance
    in-program (they already did), so k chunks' worth of decoding returns
    to the host in ONE call and the per-dispatch host gap amortizes 1/k.
    Eviction/backfill and every host-side poll (drain, breaker, watchdog)
    move to the fused-dispatch boundary; the loop still early-exits the
    moment every live row finishes, so a fused dispatch never burns steps
    a plain one wouldn't.
    """
    sample = make_sampler(sampler)
    B = num_slots
    T = chunk * max(1, fuse)
    loop = make_greedy_loop(model, sample, pad_id, eos_id, batch=B,
                            steps=T, guard=guard, per_row_offsets=True)

    def run(params, cache, prev_logits, row_seeds, emitted0, base, caps,
            live0, reset, *plan):
        # Fold released-slot invalidation into the step entry: rows in
        # ``reset`` lose their key_valid/lengths before any attention can
        # touch them.
        keep = ~reset
        cache = cache.replace(
            key_valid=cache.key_valid & keep[:, None],
            lengths=cache.lengths * keep.astype(cache.lengths.dtype),
        )
        c = loop(params, cache, prev_logits, row_seeds, emitted0, base,
                 caps, live0, None, plan if pool_spans else None)
        cache, counters = c[1], c[6]
        if cache.counters is not None:
            # What the model counted on the device since the last chunk
            # (prefills included) rides to the host behind the loop's own
            # two counters, and starts again from zero.
            counters = jnp.concatenate([counters, cache.counters])
            cache = cache.replace(counters=jnp.zeros_like(cache.counters))
        if guard:
            return cache, c[2], c[5], c[4], counters, c[7]
        return cache, c[2], c[5], c[4], counters

    run.__name__ = program_label("serve_step", fuse)
    return run


# -- serving prefill programs --------------------------------------------------


# ``EXTEND_WINDOW``, the new tokens a ``serve_prefill`` call computes per row,
# is ``models/configs.py``'s: a window layer's ring is sized against it.


#: Windows in a bucket from which ``serve_prefill`` takes its riders' donor
#: rows as one rotated slice of the pool each, under a ``lax.cond`` on "a
#: row of this call copies", and not as rows rolled over the whole extent.
#: A static choice on the bucket: the rolled rows cost a call ``(L + P) /
#: S`` of its window's own traffic, 3% at the 3-5 windows of a 192-320
#: bucket and two fifths at 126 (measured at those two ends alone:
#: ``PERF.md`` section 6); 16 windows is a bucket of 1024 positions.
DONOR_COND_WINDOWS = 16


#: The widest window of a batch in which no row copies: the kernels' row
#: tile (``ops/quant_matmul.matmul_blocks``: one row tile up to 512).
ALONE_WINDOW_MAX = 512


def cache_rings(cfg, cache) -> list:
    """The ``(window, length)`` of each ring a cache holds: a window layer's
    leaf shorter than the row (``KVCache``). From shapes."""
    kept = ((w, jax.tree.leaves(layer)[0].shape[1])
            for w, layer in zip(cfg.layer_windows, cache.layers)
            if not isinstance(layer, RowState))
    return sorted({(w, n) for w, n in kept if n < cache.max_len})


def long_bucket(cfg, P: int) -> bool:
    """Whether a bucket has ``DONOR_COND_WINDOWS`` windows and more of a
    model that takes donors: there a row's leading windows are most of its
    prefill, so ``serve_prefill`` builds donor rows only in a call that
    copies, and the scheduler sends a donorless row ahead of the rows of its
    batch that could copy from it (``scheduler._plan_prefill``)."""
    return not cfg.row_state_bytes and \
        P // EXTEND_WINDOW >= DONOR_COND_WINDOWS


def extend_window(cfg, P: int, copies: bool = True, rings=()) -> int:
    """New tokens a ``serve_prefill`` call of bucket ``P`` computes per row.
    A window narrower than the bucket pays where a donor saves a row its
    leading windows. A model of which a layer keeps a fixed state per row
    takes no donor (``scheduler._plan_prefill``) and would run every window
    of every row, streaming its weights once a window: its window is the
    whole bucket (on the chip: ``PERF.md`` section 6, PR 33).

    ``copies`` false: the batch is of rows that copy nothing, in a long
    bucket (``long_bucket``; elsewhere the argument changes nothing). Such a
    row runs every window that holds one of its tokens, and each call
    streams the weights again, so its window is the widest multiple of
    ``EXTEND_WINDOW`` up to ``ALONE_WINDOW_MAX`` that divides ``P`` and the
    length of every ring of the pool (``rings``: ``cache_rings``), so that a
    call's write never wraps inside the call, and that leaves the call's
    first query its whole window in every ring (``S <= R - window + 1``:
    ``configs.ring_len`` explains the slack)."""
    if cfg.row_state_bytes:
        return P
    if copies or not long_bucket(cfg, P):
        return EXTEND_WINDOW
    return max((
        S for S in range(EXTEND_WINDOW, ALONE_WINDOW_MAX + 1, EXTEND_WINDOW)
        if P % S == 0 and all(R % S == 0 and S <= R - window + 1
                              for window, R in rings)), default=EXTEND_WINDOW)


def build_serve_prefill(cfg, model, *, nb: int, P: int, guard: bool,
                        num_slots: int, copies: bool = True, rings=()):
    """Extend ``nb`` rows of the slot pool by one window of
    ``extend_window(cfg, P, copies, rings)`` tokens: prefill appends to what
    a row's ``[:P]`` extent already holds.

    A row's prompt is left-padded to ``P`` (``pad`` slots of padding, real
    token ``t`` at slot ``pad + t`` and position ``t``). A call computes the
    slots ``[w, w + S)`` of every row it names; the host walks a prompt's
    windows in order, so what lies before ``w`` is there already:

    1. each row first takes ``copied`` positions from ``donor``, a resident
       row whose prompt starts with the same ids: slots ``[donor_pad,
       donor_pad + copied)`` of the donor land in ``[pad, pad + copied)``.
       Keys are rotated at their logical positions, which do not depend on
       the slot or the padding, so they are this row's keys too. Whatever
       leaves a layer keeps per request (k and v, their scales, or a latent
       and its rotated key) and ``key_positions`` move alike; ``copied = 0``
       moves nothing;
    2. validity is constructed, never read: real slots before the window
       are visible, everything else in the extent is dark, so a recycled
       slot cannot expose its previous tenant's keys;
    3. the window goes forward at the cache's ``index = w`` (every row of
       the call writes the same slots, so the uniform write serves): query
       ``i`` sees slot ``j`` iff ``j <= w + i`` and the slot is valid, the
       small causal window against an already-valid cache
       (latent layers read the latent cache through the absorbed form);
    4. the extent scatters back at ``slots`` (slots >= num_slots, the
       batch-bucket pad rows and the rows that sit this window out, drop),
       the tail ``[P, cache_len)`` is invalidated, and the window's last
       position lands in the carried logits: the row's next-token logits
       once its last window (``w + S == P``) has run. The scheduler donates
       the pool and the logits, so these are writes in place; under
       ``guard`` a batch whose logits are not finite drops every row, and
       the pool it returns is the pool it was given.

    A per-position leaf has its OWN length: ``L`` slots, or a window layer's
    ring of ``R < L`` (``KVCache``), in which slot ``j`` lives at ``j mod R``.
    Steps 1 and 4 take each leaf at its own length: its extent is its first
    ``min(P, R)`` slots (the whole ring once the bucket outgrows it), the
    donor's row is rolled within ``R``, and a ring slot takes the donor's
    where the slot it holds just before this window, ``j_r = (w - 1) - (w -
    1 - r) mod R``, is one of the copied ones. Validity needs no ring form:
    the forward derives a ring slot's from the row's ``key_valid`` at
    ``j_r``.

    In a long bucket (``long_bucket``) step 1 is a branch on "a row of this
    call copies": where none does, the rows' own extents; where one does,
    each row of the call is one slice of one row of the pool, a rider's
    donor's whole row rotated by ``donor_pad - pad`` within the leaf's
    length (ring slot ``r`` takes the donor's ``(r + donor_pad - pad) mod
    R``, the rule above), any other row its own. No position of the rider's
    own is lost (its slot is new to it, and it copies in its first window
    only), and every position the call shows it is a copied one (its first
    window starts where the copied range ends). A short bucket's form rolls
    the donors' rows over ``[nb, n + extent]`` and masks them in; at 126
    windows that was a third of a rider call on the chip (``PERF.md``
    section 5).

    A layer that keeps a fixed state per row (``RowState``: no position
    axis) is gathered and scattered by slot alone. Such a model's window is
    its whole bucket, so every row a call names begins in it: its leaves
    start from zero, and a recycled slot never shows its previous tenant's
    state. They are zeroed here, inside the program, not by the host. Padding
    leaves them as they were (the forward's ``token_valid``); a slot the call
    does not name (a batch-bucket pad row, every other row of the pool) is
    neither read nor written. No donor is taken for such a model (the plan's
    ``copied`` is all zeros): a donor's state stands at its own last token.

    ``copies`` false, in a long bucket (``long_bucket``; elsewhere it
    changes nothing): the program of a batch in which no row copies. Step 1
    is left out (no donor's rows are built, ``copied`` is not read) and the
    window is the wide one ``extend_window`` gives over the pool's
    ``rings``; the rest is the same, so its rows stand in the pool as any
    row's and are donors from the next plan on.
    """
    masked_finite = _masked_finite() if guard else None
    long = long_bucket(cfg, P)
    copies = copies or not long
    S = extend_window(cfg, P, copies, rings)
    if P % S:
        raise ValueError(f"prompt bucket {P} is not a multiple of the "
                         f"extend window {S}")

    def run(params, cache, prev_logits, tokens, valid, slots, pad, donor,
            donor_pad, copied, w):
        L = cache.max_len
        own = jnp.minimum(slots, num_slots - 1)
        j = jnp.arange(P, dtype=jnp.int32)[None, :]
        lead = pad[:, None]
        # Each rider's donor row is sliced out of the pool by its slot. As
        # ONE gather of whole rows XLA stages the whole leaf for it in every
        # call, and where the leaf's tiling is not the gather's (4 KV
        # heads) copies the leaf into another layout first: a copy of the
        # pool a call, donated or not. A slice reads its row.
        donor_slot = [jax.lax.index_in_dim(donor, r, keepdims=False)
                      for r in range(nb)]

        def take(big, with_donor):
            # ``lax.gather`` spelled out: NumPy-style indexing and ``vmap``
            # cost several times as much to trace, per leaf, in every
            # prefill bucket's set-up.
            tail = big.shape[2:]
            offsets = tuple(range(1, big.ndim))
            # The leaf's own length and extent; ``n < L`` is a ring, whose
            # slot r holds the row's slot ``held`` just before this window.
            n = big.shape[1]
            extent = min(P, n)

            def rows_of(operand, starts, length, index_map):
                return jax.lax.gather(
                    operand, starts, jax.lax.GatherDimensionNumbers(
                        offset_dims=offsets, collapsed_slice_dims=(0,),
                        start_index_map=index_map),
                    (1, length) + tail, mode="promise_in_bounds")

            mine = rows_of(big, own[:, None], extent, (0,))
            if not with_donor:
                return mine
            held = j[:, :extent] if n == L else \
                (w - 1) - jnp.mod(w - 1 - j[:, :extent], n)
            from_donor = (held >= lead) & (held < lead + copied[:, None])
            # The donor's row rolled so that its slot donor_pad meets pad.
            # The copied range never wraps a full leaf (it lies inside the
            # donor's own extent); a ring is read round its end.
            shift = jnp.mod(donor_pad - pad, n)
            theirs = jnp.concatenate([
                jax.lax.dynamic_index_in_dim(big, d, 0) for d in donor_slot])
            theirs = jnp.concatenate([theirs, theirs[:, :extent]], axis=1)
            theirs = rows_of(
                theirs, jnp.stack([jnp.arange(nb, dtype=jnp.int32), shift],
                                  axis=1), extent, (0, 1))
            mask = from_donor.reshape(from_donor.shape
                                      + (1,) * (big.ndim - 2))
            return jnp.where(mask, theirs, mine)

        # A long bucket's call in which a row copies: each row it names is one
        # slice of one row of the pool (the docstring's step 1). The rest of
        # a rider's rotated row lies in its padding (dark for good), in this
        # window (written by it) or past it (dark until a later window
        # writes it). The rows reach the pool by the extent's own scatter, so
        # a batch the guard drops writes no copy either.
        def rotated():
            rider = copied > 0
            src = jnp.where(rider, donor, own)
            shift = jnp.where(rider, donor_pad - pad, 0)
            row_of = [jax.lax.index_in_dim(src, r, keepdims=False)
                      for r in range(nb)]
            shift_of = [jax.lax.index_in_dim(shift, r, keepdims=False)
                        for r in range(nb)]

            def per_position(big):
                # The row doubled over the extent and sliced at the shift:
                # read round the leaf's end (a ring's); a full leaf's copied
                # range never wraps (it lies inside the donor's own extent).
                n = big.shape[1]
                extent = min(P, n)
                tail = big.shape[2:]
                # One axis after the position's, so that a row's slice keeps
                # the pool's tiling (a head axis of one would make XLA lay
                # the whole leaf out anew for the slices).
                flat = big.reshape(big.shape[:2] + (-1,))

                def row(r):
                    whole = jax.lax.dynamic_index_in_dim(flat, row_of[r], 0)
                    whole = jnp.concatenate([whole, whole[:, :extent]],
                                            axis=1)
                    return jax.lax.dynamic_slice_in_dim(
                        whole, jnp.mod(shift_of[r], n), extent, axis=1)

                return jnp.concatenate([row(r) for r in range(nb)]).reshape(
                    (nb, extent) + tail)

            return extents(per_position)

        # Every row a call names begins in it where a state is kept: the
        # window of such a model is its whole bucket.
        fresh = slots < num_slots

        def take_row_state(big):
            rows = big[own]
            return jnp.where(fresh.reshape((nb,) + (1,) * (big.ndim - 1)),
                             jnp.zeros_like(rows), rows)

        def extents(per_position):
            """Every layer's leaves and ``key_positions`` over the rows'
            extents, each per-position leaf taken by ``per_position``."""
            return tuple(
                jax.tree.map(take_row_state if isinstance(layer, RowState)
                             else per_position, layer)
                for layer in cache.layers), per_position(cache.key_positions)

        def own_rows(big):
            return take(big, False)

        # A row copies in the call of its first window alone, so in a long
        # bucket most calls (every window of a batch that found no donor,
        # every later window of a rider) name no copying row, and take the
        # rows' own extents. In a bucket of a few windows the donors' rolled
        # rows are a few hundredths of a call, fused into the extent's own
        # gather, and the program stays the one it was.
        if not copies:
            gathered, own_positions = extents(own_rows)
        elif long:
            gathered, own_positions = jax.lax.cond(
                jnp.any(copied > 0), rotated, lambda: extents(own_rows))
        else:
            gathered, own_positions = extents(lambda big: take(big, True))
        view = cache.replace(
            layers=gathered,
            key_positions=own_positions,
            key_valid=(j >= lead) & (j < w),
            lengths=jnp.maximum(w - pad, 0),
            index=w,
            counters=(None if cache.counters is None
                      else jnp.zeros_like(cache.counters)),
        )
        positions = jnp.maximum(
            w + jnp.arange(S, dtype=jnp.int32)[None, :] - lead, 0)
        logits, view = model.apply(
            {"params": params}, tokens, positions, valid, view,
            last_only=True,
        )
        if guard:
            # Real rows of this window only (the others scatter-drop and
            # may hold anything): one reduced flag for the batch. A batch
            # that trips it writes NOTHING: the donated pool has no earlier
            # copy to fall back to, and a NaN left in a released slot would
            # reach its next tenant through masked positions (a weight of
            # zero times NaN).
            finite = masked_finite(logits[:, -1, :], slots < num_slots)
            slots = jnp.where(finite, slots, num_slots)

        def scat(big, rows):
            return big.at[slots, :rows.shape[1]].set(rows, mode="drop")

        def scat_layer(layer, rows):
            if isinstance(layer, RowState):
                return jax.tree.map(
                    lambda big, r: big.at[slots].set(r, mode="drop"),
                    layer, rows)
            return jax.tree.map(scat, layer, rows)

        key_valid = scat(cache.key_valid, view.key_valid)
        key_valid = key_valid.at[slots, P:].set(False, mode="drop")
        new_cache = cache.replace(
            layers=tuple(map(scat_layer, cache.layers, view.layers)),
            counters=(None if cache.counters is None
                      else cache.counters + view.counters),
            key_valid=key_valid,
            key_positions=scat(cache.key_positions, view.key_positions),
            lengths=cache.lengths.at[slots].set(view.lengths, mode="drop"),
        )
        new_logits = prev_logits.at[slots].set(
            logits[:, -1, :], mode="drop"
        )
        if guard:
            return new_cache, new_logits, finite
        return new_cache, new_logits

    run.__name__ = f"serve_prefill_{nb}x{P}"
    return run
