"""Flash attention (Pallas TPU): fused causal attention for the prefill phase.

Why a kernel: prefill attention materializes [B, H, S, S] scores in HBM under
stock XLA when S is long; the flash pattern keeps each [block_q, block_k]
score tile in VMEM, folding into an online-softmax accumulator, so memory
traffic is O(S·D) instead of O(S²). This is the one op in the pipeline where
hand-tiling beats the compiler (pallas_guide.md tiling rules: last dim 128,
fp32 accumulation on the MXU).

Layout contract:
- q: [B, H, S, D], k/v: [B, Hkv, S, D] (GQA handled by the index map — each q
  head reads its kv head directly, no jnp.repeat materialization)
- left-padded batches: row b's valid keys are exactly positions
  ``S - lengths[b] ..< S``, so the padding mask needs only a scalar per row
  (prefetched to SMEM) rather than a [B, S] mask array
- causal masking over slot indices (left-padding keeps causality aligned)
- optional sliding window (Mistral): key j visible iff q_idx - j < window

Supported when S is a multiple of the 128-lane tile and D is a multiple of
64 (a 64-lane D tail pads to the 128-lane tile at half occupancy — see
``flash_supported``); callers fall back to the XLA path otherwise.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    lengths_ref,  # SMEM [1] int32 — this batch row's real token count
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, S, D]
    v_ref,  # [1, 1, S, D]
    o_ref,  # [1, 1, block_q, D]
    *,
    seq_len: int,
    block_q: int,
    block_k: int,
    causal: bool,
    window: Optional[int],
    scale: float,
):
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale  # [bq, D]
    length = lengths_ref[pl.program_id(0)]
    pad_start = seq_len - length  # first valid slot

    q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    num_k_blocks = pl.cdiv(seq_len, block_k)
    if causal:
        # keys strictly after the last query row of this block are never visible
        num_k_blocks = jnp.minimum(num_k_blocks, pl.cdiv((qi + 1) * block_q, block_k))

    def body(kb, carry):
        m_acc, l_acc, acc = carry
        k_blk = k_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)  # [bq, bk]

        k_idx = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_idx >= pad_start
        if causal:
            mask &= k_idx <= q_idx
        if window is not None:
            mask &= (q_idx - k_idx) < window
        s = jnp.where(mask, s, NEG_INF)

        m_blk = jnp.max(s, axis=1)  # [bq]
        m_new = jnp.maximum(m_acc, m_blk)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_acc - m_new)
        l_new = l_acc * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_k_blocks, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


def flash_supported(seq_len: int, head_dim: int, block_q: int = 128, block_k: int = 128) -> bool:
    """head_dim >= 64: a 64-lane tail pads to the 128-lane tile (half-lane
    occupancy on the D axis) but the kernel stays correct and still beats the
    XLA dense path — prefill is score-matmul-bound, and the [bq, bk] score
    tiles are full 128x128 regardless of D. head_dim < 64 wastes > half the
    VMEM tile; fall back to XLA there."""
    return (
        seq_len % block_k == 0
        and seq_len >= block_q
        and seq_len % block_q == 0
        and head_dim % 64 == 0
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,  # [B, H, S, D]
    k: jnp.ndarray,  # [B, Hkv, S, D]
    v: jnp.ndarray,  # [B, Hkv, S, D]
    lengths: jnp.ndarray,  # [B] int32 real token counts (left-padded layout)
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    rep = H // Hkv
    if not flash_supported(S, D, block_q, block_k):
        raise ValueError(f"unsupported flash shape S={S} D={D}")
    scale = D ** -0.5

    grid = (B, H, S // block_q)
    kernel = functools.partial(
        _kernel,
        seq_len=S, block_q=block_q, block_k=block_k,
        causal=causal, window=window, scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, *_: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, qi, *_: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, qi, *_: (b, h // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, *_: (b, h, qi, 0)),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="flash_attention",
    )(lengths.astype(jnp.int32), q, k, v)
