"""Prompt-lookup speculative decoding: draft-free n-gram drafting.

The phase-1/3 sweeps are decode-bound (BENCH_r05: ~0.5 of achievable HBM
bandwidth at ~38 ms marginal per step) and their outputs are ranked lists of
movie titles copied verbatim from the candidate list already in the prompt.
That is the ideal regime for *prompt lookup* speculation (the draft-model-free
corner of SPEED-style speculative pipelining, arxiv 2310.12072): instead of a
draft model, match the last ``n`` generated tokens against the row's own
prompt + generated suffix and propose the ``k`` tokens that followed the
match. The engine then verifies all ``k+1`` positions (the greedy next token
plus the ``k`` drafts) in ONE forward pass and accepts the longest prefix
that matches greedy argmax — token-for-token identical to plain greedy decode
by construction, because every accepted token IS the argmax of logits
computed over an identical accepted context.

Everything here is jit-friendly and runs INSIDE the engine's compiled
``while_loop`` (a host round-trip per draft would cost more than the tokens
it saves): fixed shapes, no data-dependent control flow. The lookup is
a handful of [B, C] elementwise ops + row gathers — noise next to the
verify forward.

Greedy-only: with temperature > 0, verifying a *sampled* draft requires
rejection-sampling machinery (and changes the sampled stream unless done
exactly); the engine falls back to the plain sampled path instead (see
``runtime/sampling.py:speculation_applicable``).
"""

from __future__ import annotations

import jax.numpy as jnp

from fairness_llm_tpu.config import SpeculationConfig

__all__ = ["SpeculationConfig", "ngram_draft"]


def ngram_draft(
    ctx: jnp.ndarray,  # [B, C] int32 token context (prompt layout + generated)
    ctx_valid: jnp.ndarray,  # [B, C] bool — True where ctx holds a real token
    hist_end: jnp.ndarray,  # [B] int32 — one past the last history token
    draft_len: int,
    ngram_max: int,
    pad_id: int,
) -> jnp.ndarray:
    """Draft ``draft_len`` tokens per row by suffix n-gram lookup.

    For each row, take the suffix of the last ``n`` history tokens (the
    window ending at ``hist_end``), find the EARLIEST other position where
    that n-gram occurs, and return the tokens that followed it. Tries
    ``n = ngram_max`` first, falling back to shorter n-grams (longer matches
    are more specific, so their continuations verify better). Earliest —
    not most recent — is deliberate, and is what the original prompt-lookup
    decoding does: the two regimes this serves are (a) copying from the
    prompt's candidate list, where the earliest match IS the prompt copy,
    and (b) periodic/repetitive generation, where the most recent match sits
    so close to ``hist_end`` that its continuation immediately runs out of
    history (measured: acceptance collapsed to ~1 draft/step on a perfectly
    periodic stream), while the earliest occurrence has the whole tail
    available. Rows with no match (or drafts that would run off the valid
    region) get ``pad_id`` drafts — the verify step simply rejects them, so
    a failed lookup costs nothing but the step's unused verify positions.

    Layout notes: ``ctx`` may contain pad gaps anywhere (the engine's context
    is [shared prefix | left-padded remainder | generated]); windows touching
    an invalid position never match, so n-grams cannot straddle a pad gap.
    Correctness never depends on match quality — only acceptance does.
    """
    if draft_len < 1:
        raise ValueError(f"draft_len must be >= 1, got {draft_len}")
    if ngram_max < 1:
        raise ValueError(f"ngram_max must be >= 1, got {ngram_max}")
    B, C = ctx.shape
    pos = jnp.arange(C, dtype=jnp.int32)[None, :]  # [1, C]
    hist_valid = ctx_valid & (pos < hist_end[:, None])

    best_end = jnp.full((B,), C, jnp.int32)  # match-window END position
    found = jnp.zeros((B,), bool)
    for n in range(ngram_max, 0, -1):
        # Row suffix: the last n history tokens (positions hist_end-n..hist_end-1).
        idx = hist_end[:, None] - n + jnp.arange(n, dtype=jnp.int32)[None, :]
        safe = jnp.clip(idx, 0, C - 1)
        suf = jnp.take_along_axis(ctx, safe, axis=1)  # [B, n]
        suf_ok = jnp.all(
            (idx >= 0) & jnp.take_along_axis(hist_valid, safe, axis=1), axis=1
        )
        # match[b, p]: the window of n tokens ENDING at p equals the suffix,
        # with every window token a valid history token.
        match = jnp.ones((B, C), bool)
        for i in range(n):
            shift = n - 1 - i  # window token i sits at p - shift
            eq = (ctx == suf[:, i : i + 1]) & hist_valid
            if shift:
                eq = jnp.pad(eq, ((0, 0), (shift, 0)))[:, :C]
            match &= eq
        # Exclude the suffix's own terminal position (the trivial self-match).
        match &= pos <= hist_end[:, None] - 2
        match &= suf_ok[:, None]
        m_end = jnp.min(jnp.where(match, pos, C), axis=1)  # earliest
        newly = (m_end < C) & ~found
        best_end = jnp.where(newly, m_end, best_end)
        found = found | (m_end < C)

    didx = best_end[:, None] + 1 + jnp.arange(draft_len, dtype=jnp.int32)[None, :]
    safe_d = jnp.clip(didx, 0, C - 1)
    drafts = jnp.take_along_axis(ctx, safe_d, axis=1)
    ok = found[:, None] & (didx < C) & jnp.take_along_axis(hist_valid, safe_d, axis=1)
    return jnp.where(ok, drafts, jnp.asarray(pad_id, jnp.int32))
