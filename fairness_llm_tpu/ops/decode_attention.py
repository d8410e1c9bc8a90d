"""Fused decode-step attention (Pallas TPU): one kernel call per layer per step.

Why a kernel when decode attention is tiny: the round-3 device trace
(ROADMAP.md S1) showed XLA lowering each layer's single-token attention
into several HBM-round-tripping fusions — scores written to HBM, read back
for softmax, probabilities written again, read for the PV reduce — costing
~140 us/layer where the data (a few MB of KV in VMEM) supports ~20 us. This
kernel computes one head per grid step entirely in VMEM: QK^T, joint
(shared-prefix + own-cache) online softmax, PV — nothing intermediate
touches HBM.

int8 cache mode (round 4): with ``kv_cache_quant`` the cache stores int8
values + per-(slot, head) f32 scales; the kernel streams the int8 blocks
and dequantizes IN VMEM. The per-slot scale commutes with the head_dim
reduction, so dequant costs two [B, block] elementwise multiplies (fold
k_scale into the scores, v_scale into the probabilities), never a scaled
[B, block, D] temporary — and HBM sees half the bytes of the bf16 cache.
The XLA int8 path instead relies on fusing ``dequant -> attention``, which
the round-3 trace shows it does imperfectly (separate fusions per stage).

Layout contract (head-major, so each grid step's block is a legal TPU tile —
dynamic head indexing on the sublane dim is forbidden, so the wrapper
transposes to head-leading layouts; the transposes are step-local copies
XLA fuses into the cache-update neighborhood):
- q: [B, H, D] -> kernel sees [H, B, D], one [1, B, D] block per head
- k/v: [B, L, Hkv, D] -> [Hkv, B, L, D], GQA head h reads block h // rep
- k/v_scale (int8 mode): [B, L, Hkv] f32 -> [Hkv, B, L]
- valid: [B, L] bool — which cache slots hold real keys; for single-token
  decode this already encodes causality (slots after the write index are
  False), so it is the ONLY own-cache mask
- shared_k/v: [P, Hkv, D] -> [Hkv, P128, D] — optional prompt prefix common
  to every row, always causally visible; padded to a 128 multiple
  (loop-invariant: XLA hoists the pad+transpose out of the decode
  while_loop), masked by the true P inside the kernel. The prefix KV is
  bf16 even in int8-cache mode (it is read once per step, not per row —
  see runtime/engine._prefix_fn).

Supported when D % 64 == 0, L % 128 == 0, B % 8 == 0 (else callers fall back
to the XLA path). Sliding windows use the XLA path.

Multi-query mode (round 6 / ISSUE 1): a speculative verify step carries
q_len = k+1 queries per row, each owning cache slot ``q_offsets[b] + i``.
Pass q as [B, Q, H, D] with ``q_offsets`` [B]; the kernel applies the
causal window ``j <= q_offsets[b] + i`` on top of ``valid`` and runs QK^T /
PV as bb-batched MXU ``dot_general``s (see ``_kernel_multi``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
_BLOCK_L = 128  # own-cache block size (flash-style L iteration)


def _block_bytes(bb: int, cache_len: int, head_dim: int, shared_len: int,
                 kv_itemsize: int, q_len: int = 1) -> int:
    """Scoped-VMEM bytes one (head, batch-block) grid step needs: the
    [1, bb, L, D] k and v block refs (plus their [bb, L] f32 scales in int8
    mode), the f32 shared-prefix operands (the shared matmul is UNBLOCKED —
    sk/sv cast whole plus [bb, P128] scores), and the kernel body's f32
    temporaries — ~six [bb, 128, D] tensors live across the fori body
    (kb/vb casts, the q*kb product, p, and the PV expansion). The temp term
    is calibrated against Mosaic's own OOM report (bb=120 int8 L=256 D=64:
    predicted 27.8 MB vs reported 27.73 MB).

    ``q_len > 1`` (speculative verify windows): the per-block score/prob
    temporaries and the shared-prefix scores gain a Q axis, and the
    accumulators/q tiles scale by Q; the kb/vb casts don't. Conservative
    additive model — a gate miss degrades to the XLA path via the engine's
    compile-failure fallback, never fails a study."""
    p128 = -(-shared_len // 128) * 128
    kv = 2 * bb * cache_len * head_dim * kv_itemsize
    if kv_itemsize == 1:
        kv += 2 * bb * cache_len * 4  # the f32 scales
    shared = 2 * p128 * head_dim * 4 * 2 + bb * q_len * p128 * 4 * 3
    temps = 6 * bb * _BLOCK_L * head_dim * 4
    if q_len > 1:
        temps += 4 * bb * q_len * _BLOCK_L * 4  # [bb, Q, bl] scores/probs/mask
        temps += 3 * bb * q_len * head_dim * 4  # q tile + acc + PV output
    return kv + shared + temps


def _pick_batch_block(batch: int, cache_len: int, head_dim: int,
                      shared_len: int, kv_itemsize: int, q_len: int = 1) -> int:
    """Largest batch block (multiple of 8, dividing batch) whose grid step
    fits the 16 MB scoped-VMEM window (minus 1 MB slack); 0 if even 8 rows
    don't fit. Rows are independent, so blocking the batch is free
    parallelism — it's what keeps the kernel eligible at batch 192/360
    where a whole-batch block would blow VMEM.

    NOTE the budget is intentionally NOT more conservative: the 48-row
    sweep shape sits exactly at the 15 MiB boundary and has run whole-batch
    on v5e since round 3 — extra slack would silently split a proven-live
    configuration. Because ``_block_bytes``'s temp term is a calibrated
    model (fitted to one Mosaic OOM report), a shape where it
    under-predicts can still pass the gate and fail in Mosaic; the engine
    catches that compile failure and retries with the kernel disabled
    (DecodeEngine's VMEM-fallback), so a gate miss degrades to the XLA
    path instead of failing the study."""
    budget = 15 * 1024 * 1024
    best = 0
    for bb in range(8, batch + 1, 8):
        if batch % bb:
            continue
        if _block_bytes(bb, cache_len, head_dim, shared_len, kv_itemsize,
                        q_len) <= budget:
            best = bb
    return best


def decode_attn_supported(
    batch: int, cache_len: int, head_dim: int, shared_len: int = 0,
    kv_itemsize: int = 4, q_len: int = 1,
) -> bool:
    """Static shape gate + VMEM budget for the fused decode kernel.

    ``kv_itemsize``: bytes/element the k and v BLOCKS occupy in VMEM — 4 for
    the conservative f32-input default (bf16 callers may pass 2; int8-cache
    callers pass 1, which roughly quadruples the eligible shape envelope).
    ``q_len``: queries per row — 1 for plain decode, k+1 for a speculative
    verify window (small: capped at 16 by the model gate).
    """
    if not (batch % 8 == 0 and cache_len % _BLOCK_L == 0 and head_dim % 64 == 0):
        return False
    return _pick_batch_block(
        batch, cache_len, head_dim, shared_len, kv_itemsize, q_len
    ) > 0


def _kernel(
    q_ref,  # [1, B, D]
    k_ref,  # [1, B, L, D] (model dtype, or int8 in quant mode)
    v_ref,  # [1, B, L, D]
    valid_ref,  # [B, L] int32
    *rest,  # ([1, B, L] ks, vs when quant) + ([1, P128, D] sk, sv when shared) + o_ref
    scale: float,
    shared_len: int,
    quant: bool,
):
    rest = list(rest)
    ks_ref = vs_ref = sk_ref = sv_ref = None
    if quant:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    if shared_len:
        sk_ref, sv_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref = rest[0]

    B = q_ref.shape[1]
    D = q_ref.shape[2]
    L = k_ref.shape[2]
    q = q_ref[0, :, :].astype(jnp.float32) * scale  # [B, D]

    # Online-softmax accumulators, seeded from the shared-prefix part (one
    # [B, D] x [D, P128] MXU matmul — the prefix is read once per (head,
    # step), not once per row).
    if shared_len:
        sk = sk_ref[0, :, :].astype(jnp.float32)  # [P128, D]
        sv = sv_ref[0, :, :].astype(jnp.float32)
        s_sh = jnp.dot(q, sk.T, preferred_element_type=jnp.float32)
        sh_mask = (
            jax.lax.broadcasted_iota(jnp.int32, (1, sk.shape[0]), 1) < shared_len
        )
        s_sh = jnp.where(sh_mask, s_sh, NEG_INF)
        m0 = jnp.max(s_sh, axis=1)  # [B]
        p_sh = jnp.where(sh_mask, jnp.exp(s_sh - m0[:, None]), 0.0)
        l0 = jnp.sum(p_sh, axis=1)
        acc0 = jnp.dot(p_sh, sv, preferred_element_type=jnp.float32)  # [B, D]
    else:
        m0 = jnp.full((B,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B,), jnp.float32)
        acc0 = jnp.zeros((B, D), jnp.float32)

    # Own-cache attention in L-blocks of 128 (flash pattern): per-block f32
    # casts keep peak VMEM under the 16 MB scoped budget — a whole-cache f32
    # cast overflowed it at the sweep shape.
    def body(lb, carry):
        m_acc, l_acc, acc = carry
        kb = k_ref[0, :, pl.ds(lb * _BLOCK_L, _BLOCK_L), :].astype(jnp.float32)
        vb = v_ref[0, :, pl.ds(lb * _BLOCK_L, _BLOCK_L), :].astype(jnp.float32)
        mask = valid_ref[:, pl.ds(lb * _BLOCK_L, _BLOCK_L)] != 0  # [B, bl]
        # batched matvec as a VPU multiply-reduce, all in VMEM
        s = jnp.sum(q[:, None, :] * kb, axis=-1)  # [B, bl]
        if quant:
            # per-slot k scale commutes with the D-reduction: scale the
            # SCORES, not the [B, bl, D] key block
            s = s * ks_ref[0, :, pl.ds(lb * _BLOCK_L, _BLOCK_L)]
        s = jnp.where(mask, s, NEG_INF)
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_acc, m_blk)
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_acc - m_new)
        l_new = l_acc * alpha + jnp.sum(p, axis=1)  # normalizer: UNSCALED p
        if quant:
            # v scale likewise commutes with the slot reduction: fold it
            # into the probabilities used for PV (only), not into vb
            p = p * vs_ref[0, :, pl.ds(lb * _BLOCK_L, _BLOCK_L)]
        acc = acc * alpha[:, None] + jnp.sum(p[:, :, None] * vb, axis=1)
        return m_new, l_new, acc

    m, l, acc = jax.lax.fori_loop(0, L // _BLOCK_L, body, (m0, l0, acc0))
    o_ref[0, :, :] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _kernel_multi(
    q_ref,  # [1, B, Q, D]
    k_ref,  # [1, B, L, D] (model dtype, or int8 in quant mode)
    v_ref,  # [1, B, L, D]
    valid_ref,  # [B, L] int32
    offs_ref,  # [B, 1] int32 — per-row first-query slot index
    *rest,  # ([1, B, L] ks, vs when quant) + ([1, P128, D] sk, sv when shared) + o_ref
    scale: float,
    shared_len: int,
    quant: bool,
):
    """Speculative-verify variant of ``_kernel``: Q queries per row in one
    grid step. Query i of row b occupies cache slot ``offs[b] + i``; the
    causal rule is ``j <= offs[b] + i`` ANDed with ``valid`` (slots beyond
    the verify window are already invalid in ``valid``, slots inside it need
    the triangular window). QK^T and PV run as bb-batched MXU ``dot_general``
    ([bb, Q, D] x [bb, bl, D]) instead of the single-query VPU
    multiply-reduce; everything else (online softmax over L-blocks, the
    shared-prefix seed, int8 scale folding) matches ``_kernel``.
    """
    rest = list(rest)
    ks_ref = vs_ref = sk_ref = sv_ref = None
    if quant:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    if shared_len:
        sk_ref, sv_ref = rest[0], rest[1]
        rest = rest[2:]
    o_ref = rest[0]

    B = q_ref.shape[1]
    Q = q_ref.shape[2]
    D = q_ref.shape[3]
    L = k_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale  # [B, Q, D]
    offs = offs_ref[:, 0]  # [B]
    qi = jax.lax.broadcasted_iota(jnp.int32, (1, Q, 1), 1)  # [1, Q, 1]

    if shared_len:
        # Shared-prefix slots precede every query: always causally visible.
        sk = sk_ref[0].astype(jnp.float32)  # [P128, D]
        sv = sv_ref[0].astype(jnp.float32)
        p128 = sk.shape[0]
        s_sh = jax.lax.dot_general(
            q.reshape(B * Q, D), sk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(B, Q, p128)
        sh_mask = (
            jax.lax.broadcasted_iota(jnp.int32, (1, 1, p128), 2) < shared_len
        )
        s_sh = jnp.where(sh_mask, s_sh, NEG_INF)
        m0 = jnp.max(s_sh, axis=-1)  # [B, Q]
        p_sh = jnp.where(sh_mask, jnp.exp(s_sh - m0[..., None]), 0.0)
        l0 = jnp.sum(p_sh, axis=-1)
        acc0 = jax.lax.dot_general(
            p_sh.reshape(B * Q, p128), sv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(B, Q, D)
    else:
        m0 = jnp.full((B, Q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Q), jnp.float32)
        acc0 = jnp.zeros((B, Q, D), jnp.float32)

    def body(lb, carry):
        m_acc, l_acc, acc = carry
        kb = k_ref[0, :, pl.ds(lb * _BLOCK_L, _BLOCK_L), :].astype(jnp.float32)
        vb = v_ref[0, :, pl.ds(lb * _BLOCK_L, _BLOCK_L), :].astype(jnp.float32)
        vmask = valid_ref[:, pl.ds(lb * _BLOCK_L, _BLOCK_L)] != 0  # [B, bl]
        j = lb * _BLOCK_L + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, _BLOCK_L), 2
        )  # [1, 1, bl]
        mask = vmask[:, None, :] & (j <= offs[:, None, None] + qi)  # [B, Q, bl]
        s = jax.lax.dot_general(
            q, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [B, Q, bl]
        if quant:
            s = s * ks_ref[0, :, pl.ds(lb * _BLOCK_L, _BLOCK_L)][:, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_acc, m_blk)
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m_acc - m_new)
        l_new = l_acc * alpha + jnp.sum(p, axis=-1)
        if quant:
            p = p * vs_ref[0, :, pl.ds(lb * _BLOCK_L, _BLOCK_L)][:, None, :]
        acc = acc * alpha[..., None] + jax.lax.dot_general(
            p, vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    m, l, acc = jax.lax.fori_loop(0, L // _BLOCK_L, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(
    q: jnp.ndarray,  # [B, H, D], or [B, Q, H, D] with q_offsets (verify window)
    k: jnp.ndarray,  # [B, L, Hkv, D] (int8 when scales given)
    v: jnp.ndarray,
    valid: jnp.ndarray,  # [B, L] bool
    shared_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # ([P, Hkv, D]) x2
    k_scale: Optional[jnp.ndarray] = None,  # [B, L, Hkv] f32 (int8 cache mode)
    v_scale: Optional[jnp.ndarray] = None,
    q_offsets: Optional[jnp.ndarray] = None,  # [B] int32 first-query slot (4D q)
    interpret: bool = False,
) -> jnp.ndarray:  # [B, H, D] (3D q) or [B, Q, H, D] (4D q)
    multi = q.ndim == 4
    if multi:
        if q_offsets is None:
            raise ValueError("multi-query decode attention needs q_offsets")
        B, Q, H, D = q.shape
    else:
        B, H, D = q.shape
        Q = 1
    L = k.shape[1]
    Hkv = k.shape[2]
    rep = H // Hkv
    quant = k_scale is not None
    if quant and v_scale is None:
        raise ValueError("int8 cache mode needs both k_scale and v_scale")
    shared_true_len = 0 if shared_kv is None else shared_kv[0].shape[0]
    # Account k/v VMEM at the width actually streamed (bf16 callers get the
    # 2-byte envelope, matching the model gate's accounting).
    itemsize = 1 if quant else jnp.dtype(k.dtype).itemsize
    if not decode_attn_supported(B, L, D, shared_true_len, kv_itemsize=itemsize,
                                 q_len=Q):
        raise ValueError(
            f"unsupported decode-attention shape B={B} L={L} D={D} Q={Q}"
        )
    bb = _pick_batch_block(B, L, D, shared_true_len, itemsize, Q)
    scale = D ** -0.5

    kh = k.transpose(2, 0, 1, 3)  # [Hkv, B, L, D]
    vh = v.transpose(2, 0, 1, 3)
    if multi:
        qh = q.transpose(2, 0, 1, 3)  # [H, B, Q, D]
        args = [qh, kh, vh, valid.astype(jnp.int32),
                q_offsets.astype(jnp.int32)[:, None]]
        in_specs = [
            pl.BlockSpec((1, bb, Q, D), lambda h, b: (h, b, 0, 0)),
            pl.BlockSpec((1, bb, L, D), lambda h, b: (h // rep, b, 0, 0)),
            pl.BlockSpec((1, bb, L, D), lambda h, b: (h // rep, b, 0, 0)),
            pl.BlockSpec((bb, L), lambda h, b: (b, 0)),
            pl.BlockSpec((bb, 1), lambda h, b: (b, 0)),
        ]
    else:
        qh = q.transpose(1, 0, 2)  # [H, B, D]
        args = [qh, kh, vh, valid.astype(jnp.int32)]
        in_specs = [
            pl.BlockSpec((1, bb, D), lambda h, b: (h, b, 0)),
            pl.BlockSpec((1, bb, L, D), lambda h, b: (h // rep, b, 0, 0)),
            pl.BlockSpec((1, bb, L, D), lambda h, b: (h // rep, b, 0, 0)),
            pl.BlockSpec((bb, L), lambda h, b: (b, 0)),
        ]
    if quant:
        args += [
            k_scale.transpose(2, 0, 1).astype(jnp.float32),  # [Hkv, B, L]
            v_scale.transpose(2, 0, 1).astype(jnp.float32),
        ]
        in_specs += [
            pl.BlockSpec((1, bb, L), lambda h, b: (h // rep, b, 0)),
            pl.BlockSpec((1, bb, L), lambda h, b: (h // rep, b, 0)),
        ]

    if shared_kv is not None and shared_kv[0].shape[0] == 0:
        # A zero-length prefix is the no-prefix case; passing empty refs
        # through would desync _kernel's ref unpacking.
        shared_kv = None
    shared_len = 0
    if shared_kv is not None:
        sk, sv = shared_kv
        shared_len = sk.shape[0]
        pad = (-shared_len) % 128
        if pad:
            sk = jnp.pad(sk, ((0, pad), (0, 0), (0, 0)))
            sv = jnp.pad(sv, ((0, pad), (0, 0), (0, 0)))
        p128 = sk.shape[0]
        args += [sk.transpose(1, 0, 2), sv.transpose(1, 0, 2)]  # [Hkv, P128, D]
        # b-invariant index: consecutive batch-block grid steps revisit the
        # same prefix block, so Pallas doesn't re-DMA it per step.
        in_specs += [
            pl.BlockSpec((1, p128, D), lambda h, b: (h // rep, 0, 0)),
            pl.BlockSpec((1, p128, D), lambda h, b: (h // rep, 0, 0)),
        ]

    kernel = functools.partial(
        _kernel_multi if multi else _kernel,
        scale=scale, shared_len=shared_len, quant=quant,
    )
    if multi:
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((H, B, Q, D), q.dtype),
            grid=(H, B // bb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bb, Q, D), lambda h, b: (h, b, 0, 0)),
            interpret=interpret,
        )(*args)
        return out.transpose(1, 2, 0, 3)  # [B, Q, H, D]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((H, B, D), q.dtype),
        grid=(H, B // bb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bb, D), lambda h, b: (h, b, 0)),
        interpret=interpret,
    )(*args)
    return out.transpose(1, 0, 2)  # [B, H, D]
