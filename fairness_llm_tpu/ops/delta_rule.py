"""The delta rule's update of a fixed state per row and head, one token at
a time. Kimi Delta Attention (arXiv 2510.26692) decays per key channel:

    S' = Diag(a) S;  u = S'^T k;  S = S' + b k (v - u)^T;  o = S^T q

which is ``S <- (I - b k k^T) Diag(a) S + b k v^T`` spelled so that nothing
larger than ``S`` is ever formed. ``S`` is ``[dk, dv]`` float32 per (row,
head), ``a`` in (0, 1)^dk one decay per key channel, ``b`` in (0, 2) a scalar.
A token with ``a = 1, b = 0`` leaves ``S`` as it was, which is how padding and
idle rows pass through.

:func:`kda_update` is the plain form, used inside the prefill's scan over a
window's positions and wherever the kernel does not apply. :func:`kda_step`
is the decode step over the whole slot pool: on the TPU a Pallas kernel named
``kda_step`` that reads each state block once and writes it once in place
(input aliased to output), where XLA makes three passes (decay, the rank-one
update, the read-out) over what is the step's second-largest stream.

The kernel's layout. Heads and rows are one axis of ``N = rows x heads``
states, walked ``block`` at a time. The rank-one update needs ``k`` along the
state's sublanes and ``v - u`` along its lanes, so the ``dk``-vectors (``q``,
``k``, ``b k``, ``a``) arrive transposed, ``[N / block, dk, block]``: a static
lane slice ``[:, i]`` is then a column that broadcasts along lanes, and no
transpose happens inside the kernel. Both reductions run over sublanes and
land lane-major, as ``v`` and ``o`` are.

Gated DeltaNet (arXiv 2412.06464) decays the whole state of a head by one
scalar ``a``: the same update with ``a`` broadcast over ``dk``, and a state
that need not be square (OLMo Hybrid: 96 x 192). :func:`gdn_update` and
:func:`gdn_step` take the slot pool's layout of it, ``p`` heads side by side
(``heads_side_by_side``): ``[..., heads / p, dk, p * dv]``, in which a
192-wide state fills 384 lanes, three whole tiles, where alone it would be
padded to 256. ``v`` and ``o`` are then a reshape of ``[..., heads, dv]``, the
decay a lane vector, and ``q``, ``k`` and ``b k`` are a column per head that
a lane select spreads over that head's ``dv`` lanes. :func:`gdn_step` is the
decode step over the pool, a Pallas kernel named ``gdn_step`` that reads and
writes each state once in place; the prefill's scan runs
:func:`gdn_update` on the same leaf, so nothing is laid out anew.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fairness_llm_tpu.ops.dispatch import note_kernel_path
from fairness_llm_tpu.ops.quant_matmul import lowering_for_tpu

_LANE = 128
#: States a grid step holds: 32 x 64 KiB = 2 MiB in and as much out, double
#: buffered. A step costs a fixed 0.3 us (PERF.md section 6, PR 31), so a
#: kernel that only streams wants blocks of megabytes.
_BLOCK = 32
_VMEM_LIMIT = 48 * 1024 * 1024


def kda_update(state, q, k, v, a, b) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the delta rule, any leading axes. ``state [..., dk,
    dv]``, ``q, k, a [..., dk]``, ``v [..., dv]``, ``b [...]``, all float32
    -> ``(new state, o [..., dv])``. Sums are elementwise products reduced on
    the vector unit: a matrix unit would round the float32 state."""
    decayed = state * a[..., None]
    u = jnp.sum(decayed * k[..., None], axis=-2)
    new = decayed + (b[..., None] * k)[..., None] * (v - u)[..., None, :]
    return new, jnp.sum(new * q[..., None], axis=-2)


def kda_step_tileable(n: int, dk: int, dv: int, block: int = _BLOCK) -> bool:
    """Static gate: whole lane tiles of state and whole blocks of them."""
    return dk % _LANE == 0 and dv % _LANE == 0 and n % block == 0


def _kda_kernel(s_ref, q_ref, k_ref, kb_ref, a_ref, v_ref, s_out, o_ref, *,
                block: int):
    rows = []
    for i in range(block):  # static: a lane slice wants a constant index
        col = slice(i, i + 1)
        decayed = s_ref[i] * a_ref[0, :, col]
        u = jnp.sum(decayed * k_ref[0, :, col], axis=0, keepdims=True)
        new = decayed + kb_ref[0, :, col] * (v_ref[0, col, :] - u)
        s_out[i] = new
        rows.append(jnp.sum(new * q_ref[0, :, col], axis=0, keepdims=True))
    o_ref[0] = jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def _kda_step_pallas(state, q, k, v, a, b, interpret: bool, block: int):
    lead = state.shape[:-2]
    dk, dv = state.shape[-2:]
    n = 1
    for d in lead:
        n *= d
    groups = n // block

    def columns(x):  # [..., dk] -> [groups, dk, block]
        return x.reshape(groups, block, dk).transpose(0, 2, 1)

    vec = pl.BlockSpec((1, dk, block), lambda g: (g, 0, 0))
    row = pl.BlockSpec((1, block, dv), lambda g: (g, 0, 0))
    mat = pl.BlockSpec((block, dk, dv), lambda g: (g, 0, 0))
    new, o = pl.pallas_call(
        functools.partial(_kda_kernel, block=block),
        grid=(groups,),
        in_specs=[mat, vec, vec, vec, vec, row],
        out_specs=[mat, row],
        out_shape=[jax.ShapeDtypeStruct((n, dk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((groups, block, dv), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="kda_step",
    )(state.reshape(n, dk, dv), columns(q), columns(k),
      columns(b[..., None] * k), columns(a), v.reshape(groups, block, dv))
    return new.reshape(state.shape), o.reshape(lead + (dv,))


def kda_step(state, q, k, v, a, b, *, interpret: bool = False):
    """The decode step's update of every row's state: ``state [R, H, dk,
    dv]`` float32, ``q, k, a [R, H, dk]``, ``v [R, H, dv]``, ``b [R, H]`` ->
    ``(new state, o [R, H, dv])``. Pallas on the TPU (or under
    ``interpret=True``) where the shapes tile; otherwise :func:`kda_update`,
    to the same numbers but for the order of two sums."""
    dk, dv = state.shape[-2:]
    shape = state.shape
    n = state.size // (dk * dv)
    on_tpu = lowering_for_tpu()
    if (on_tpu or interpret) and kda_step_tileable(n, dk, dv):
        note_kernel_path("kda_step", "pallas", shape, blocks=str(_BLOCK))
        f32 = jnp.float32
        return _kda_step_pallas(
            state, q.astype(f32), k.astype(f32), v.astype(f32),
            a.astype(f32), b.astype(f32), interpret, _BLOCK)
    note_kernel_path("kda_step", "xla", shape,
                     "untileable" if on_tpu or interpret else "not_tpu")
    return kda_update(state, q, k, v, a, b)


# -- Gated DeltaNet: one decay per head, heads side by side ----------------------


def heads_side_by_side(heads: int, dv: int) -> int:
    """``p``: the fewest heads whose values together fill whole lane tiles
    and that divide ``heads``; 1 where none do (the state is then padded)."""
    return next((p for p in range(1, heads + 1)
                 if heads % p == 0 and p * dv % _LANE == 0), 1)


def _lane_heads(x, p: int, dv: int):
    """``[..., heads, dk]`` -> ``[..., heads / p, dk, p * dv]``: each head's
    vector as a column over the ``dv`` lanes that hold its state, chosen by
    a lane select from columns that broadcast along lanes, so that XLA fuses
    it into its consumer; a reshape of a broadcast to ``p * dv`` lanes would
    lay out a state-sized array anew at every token."""
    lead, (h, dk) = x.shape[:-2], x.shape[-2:]
    x = x.reshape(lead + (h // p, p, dk))[..., None]
    head = jax.lax.broadcasted_iota(jnp.int32, (1, p * dv), 1) // dv
    out = x[..., p - 1, :, :]
    for j in range(p - 2, -1, -1):
        out = jnp.where(head == j, x[..., j, :, :], out)
    return jnp.broadcast_to(out, lead + (h // p, dk, p * dv))


def gdn_update(state, q, k, v, a, b) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of Gated DeltaNet's delta rule, any leading axes, on the
    side-by-side layout: ``state [..., heads / p, dk, p * dv]``, ``q, k [...,
    heads, dk]``, ``v [..., heads, dv]``, ``a, b [..., heads]``, all float32
    -> ``(new state, o [..., heads, dv])``. The same sums as
    :func:`kda_update` with ``a`` broadcast over ``dk``, on the vector unit."""
    lead, (h, dv) = v.shape[:-2], v.shape[-2:]
    p = h // state.shape[-3]
    row = lead + (h // p, p * dv)
    a_lanes = _lane_heads(a[..., None], p, dv)[..., 0, :]  # [..., h / p, p dv]
    decayed = state * a_lanes[..., None, :]
    u = jnp.sum(decayed * _lane_heads(k, p, dv), axis=-2)
    new = decayed + _lane_heads(b[..., None] * k, p, dv) \
        * (v.reshape(row) - u)[..., None, :]
    o = jnp.sum(new * _lane_heads(q, p, dv), axis=-2)
    return new, o.reshape(lead + (h, dv))


def _gdn_block(n: int, state_bytes: int) -> int:
    """States a grid step holds: the most that divide ``n`` and stay within
    ``_BLOCK`` states' worth of ``kda_step``'s 64 KiB ones (2 MiB)."""
    budget = max(_BLOCK * 64 * 1024 // state_bytes, 1)
    return max(d for d in range(1, min(n, budget) + 1) if n % d == 0)


def gdn_step_tileable(dk: int, lanes: int) -> bool:
    """Static gate: whole sublane and lane tiles of state."""
    return dk % 8 == 0 and lanes % _LANE == 0


def _gdn_kernel(s_ref, q_ref, k_ref, kb_ref, a_ref, v_ref, s_out, o_ref, *,
                block: int, p: int, dv: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, p * dv), 1)

    def spread(ref, i):  # head j's column over lanes [j dv, (j + 1) dv)
        cols = [ref[0, :, i * p + j:i * p + j + 1] for j in range(p)]
        out = cols[-1]
        for j in range(p - 2, -1, -1):
            out = jnp.where(lane < (j + 1) * dv, cols[j], out)
        return out

    rows = []
    for i in range(block):  # static: a lane slice wants a constant index
        decayed = s_ref[i] * a_ref[0, i:i + 1, :]
        u = jnp.sum(decayed * spread(k_ref, i), axis=0, keepdims=True)
        new = decayed + spread(kb_ref, i) * (v_ref[0, i:i + 1, :] - u)
        s_out[i] = new
        rows.append(jnp.sum(new * spread(q_ref, i), axis=0, keepdims=True))
    o_ref[0] = jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def _gdn_step_pallas(state, q, k, v, a, b, interpret: bool, block: int):
    lead, (h, dv) = v.shape[:-2], v.shape[-2:]
    dk, lanes = state.shape[-2:]
    p = lanes // dv
    n = state.size // (dk * lanes)
    groups = n // block

    def columns(x):  # [..., heads, dk] -> [groups, dk, block * p]
        return x.reshape(groups, block * p, dk).transpose(0, 2, 1)

    def lane_rows(x):  # [..., heads, dv] -> [groups, block, p * dv]
        return x.reshape(groups, block, lanes)

    vec = pl.BlockSpec((1, dk, block * p), lambda g: (g, 0, 0))
    row = pl.BlockSpec((1, block, lanes), lambda g: (g, 0, 0))
    mat = pl.BlockSpec((block, dk, lanes), lambda g: (g, 0, 0))
    new, o = pl.pallas_call(
        functools.partial(_gdn_kernel, block=block, p=p, dv=dv),
        grid=(groups,),
        in_specs=[mat, vec, vec, vec, row, row],
        out_specs=[mat, row],
        out_shape=[jax.ShapeDtypeStruct((n, dk, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((groups, block, lanes), jnp.float32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gdn_step",
    )(state.reshape(n, dk, lanes), columns(q), columns(k),
      columns(b[..., None] * k),
      lane_rows(jnp.broadcast_to(a[..., None], lead + (h, dv))), lane_rows(v))
    return new.reshape(state.shape), o.reshape(lead + (h, dv))


def gdn_step(state, q, k, v, a, b, *, interpret: bool = False):
    """The decode step's update of every row's state, one decay per head:
    ``state [R, heads / p, dk, p * dv]`` float32 (the pool's side-by-side
    layout), ``q, k [R, heads, dk]``, ``v [R, heads, dv]``, ``a, b [R,
    heads]`` -> ``(new state, o [R, heads, dv])``. Pallas on the TPU (or
    under ``interpret=True``) where the state tiles; otherwise
    :func:`gdn_update`, to the same numbers but for the order of two sums."""
    dk, lanes = state.shape[-2:]
    shape = state.shape
    on_tpu = lowering_for_tpu()
    if (on_tpu or interpret) and gdn_step_tileable(dk, lanes):
        n = state.size // (dk * lanes)
        block = _gdn_block(n, dk * lanes * 4)
        note_kernel_path("gdn_step", "pallas", shape, blocks=str(block))
        f32 = jnp.float32
        return _gdn_step_pallas(
            state, q.astype(f32), k.astype(f32), v.astype(f32),
            a.astype(f32), b.astype(f32), interpret, block)
    note_kernel_path("gdn_step", "xla", shape,
                     "untileable" if on_tpu or interpret else "not_tpu")
    return gdn_update(state, q, k, v, a, b)
