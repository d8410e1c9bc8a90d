"""int8 weight-only matmul with dequantization INSIDE the Pallas tile loop.

Why a kernel instead of ``x @ (wq * scale)``: XLA hoists loop-invariant
computation out of decode loops. For int8-stored weights that "obvious"
dequant-at-use expression materializes a full bf16 copy of the parameter
tree in HBM (an earlier compile of llama3-70b tp=8: the 8.84 GB/chip of int8
arguments would fit a v5e, but not the 35.2 GB of hoisted bf16 temps).
Inside a Pallas kernel the int8->bf16 conversion happens on a chunk of the
weight block already in VMEM, so HBM only ever holds the int8 tree:
weight-only-quantized serving streams half the bytes AND fits models that
bf16 cannot.

Blocks: at the 48-192 rows of a decode step the product only streams its
weight, so what a call costs above the bytes is its grid: a fixed third of a
microsecond a step, the first block's fetch and the last block's product
unoverlapped. :func:`matmul_blocks` sizes the weight block by bytes from the
call's ``(M, K, N)`` alone (about a megabyte to eight, growing with the root
of the weight's bytes), as whole contiguous rows ``[bk, N]`` where K has the
divisor and the rows are few, else K-deep ``[K, bn]``; rows go in one tile up
to 512, so x is fetched once, and N blocks are outermost, so many rows reuse
the weight block in VMEM. ``tools/int8_block_sweep.py`` times the rule against
fixed candidates per shape on the chip (v5e: 48-row calls 51-56% of their
roofline with the 512 x 512 blocks this replaced, 85-91% with the rule).

Scheme: symmetric per-output-channel quantization. ``w ≈ wq * scale[None, :]``
with ``wq`` int8 and ``scale`` float32. Because the scale is constant along
the contraction axis it commutes with the matmul:

    x @ (wq * scale[None, :]) == (x @ wq) * scale[None, :]

so the kernel runs the MXU matmul on (bf16 x, int8->bf16 wq) chunks with a
float32 accumulator and applies the scale once, after the last K chunk. The
int8->bf16 cast is exact (|q| <= 127 << 2^8), making the kernel numerically
equivalent to a bf16 matmul against the dequantized weights.

Sharding: ``quant_matmul_sharded`` wraps the kernel in a FULL-manual
``jax.shard_map`` over every mesh axis (Mosaic kernels refuse to lower in a
partially-auto SPMD context) — column-parallel (N sharded) runs purely
locally; row-parallel (K sharded) psums f32 partial products, the same
collective GSPMD inserts for the dense equivalent; batch (dp) sharding is
encoded in the specs rather than left to GSPMD. This is the
trace-time-lowered integration (works under AOT topology compilation, where
``custom_partitioning``'s runtime callback is unavailable).

The reference has no quantization support at all (its models are remote
APIs, SURVEY.md §0); this is the capability that puts Llama-3-70B tp=8 — a
``BASELINE.json`` target config — on a single v5e-8 slice.
"""

from __future__ import annotations

import contextvars
import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from fairness_llm_tpu.ops.dispatch import note_kernel_path

__all__ = [
    "quantize_weight",
    "dequantize_weight",
    "quant_tileable",
    "quant_matmul",
    "quant_matmul_sharded",
    "grouped_quant_matmul",
    "grouped_tileable",
    "force_pallas",
    "lowering_for_tpu",
]

_LANE = 128  # TPU lane width: last-dim tiling granule for every dtype

# Dispatch override for AOT lowering: ``jax.default_backend()`` reports the
# process's live backend, not the topology being lowered FOR — a CPU-pinned
# test process AOT-compiling against a TPU topology descriptor must still
# take the Pallas path (that's the thing being proven). Context-managed, not
# an argument, because the call sites sit inside flax modules. A ContextVar
# (not a module-level flag) so a concurrent trace in another thread — e.g.
# a test runner compiling while the bench's AOT check runs — can't observe
# this thread's override.
_FORCE_PALLAS: contextvars.ContextVar[int] = contextvars.ContextVar(
    "quant_matmul_force_pallas", default=0
)


def lowering_for_tpu() -> bool:
    """Is the program being traced headed for a TPU? True on a live TPU
    backend and under :class:`force_pallas` (AOT lowering against a TPU
    topology from a CPU process). The one predicate every Pallas gate and
    the engine's flash-eligible sequence bucket share."""
    return jax.default_backend() == "tpu" or bool(_FORCE_PALLAS.get())


class force_pallas:
    """``with force_pallas():`` — treat the lowering target as TPU."""

    def __enter__(self):
        self._token = _FORCE_PALLAS.set(_FORCE_PALLAS.get() + 1)
        return self

    def __exit__(self, *exc):
        _FORCE_PALLAS.reset(self._token)
        return False


# ---------------------------------------------------------------------------
# Quantization (host/XLA side)
# ---------------------------------------------------------------------------


def quantize_weight(w: jnp.ndarray, axis: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[K, N] float -> (int8 [K, N], float32 scale [N]); symmetric per-channel.

    ``axis`` is the contraction (reduced) axis; scales live on the other one.
    """
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / jnp.expand_dims(scale, axis)), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_weight(wq: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (wq.astype(jnp.float32) * scale[None, :].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


_VMEM_LIMIT = 96 << 20  # of a v5e core's 128 MiB; what both kernels ask for
_WEIGHT_BLOCK_CAP = 8 << 20  # most bytes of one int8 block, double-buffered
_ROW_TILE = 512  # most rows of one tile: x is then fetched once a call
_K_CHUNK = 512  # rows of the weight block cast to float at a time
# Two readings of the v5e behind the block's size (PERF.md §6, PR 31): the
# bytes its HBM streams during one grid step's fixed cost (0.35 us at 819
# GB/s), and the rows at which a weight byte's FLOPs take as long as its
# fetch (197 TFLOP/s over 2 x 819 GB/s).
_STEP_BYTES = 288 << 10
_RIDGE_ROWS = 120


def _matmul_kernel(x_ref, wq_ref, scale_ref, o_ref, *acc, nk: int):
    """One (n block, row tile, K block) step: the K block walked in static
    chunks of ``_K_CHUNK``, and, where K is cut across the grid (``nk`` > 1),
    the partial sums kept in a float32 VMEM scratch until the last K block."""
    bk = wq_ref.shape[0]
    part = None
    for lo in range(0, bk, _K_CHUNK):
        ks = slice(lo, min(lo + _K_CHUNK, bk))
        # int8 -> x.dtype happens HERE, on a chunk already in VMEM:
        # the whole point of the kernel: no dequantized copy of the weight
        # ever exists in HBM, and XLA cannot hoist what it cannot see.
        d = jnp.dot(x_ref[:, ks], wq_ref[ks, :].astype(x_ref.dtype),
                    preferred_element_type=jnp.float32)
        part = d if part is None else part + d

    def _done(total):
        o_ref[:] = (total * scale_ref[:].astype(jnp.float32)).astype(o_ref.dtype)

    if nk == 1:
        _done(part)
        return
    (acc_ref,) = acc
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _first():
        acc_ref[:] = part

    @pl.when(kk > 0)
    def _add():
        acc_ref[:] += part

    @pl.when(kk == nk - 1)
    def _last():
        _done(acc_ref[:])


def _pick_block(dim: int, candidates=(512, 256, 128)) -> int:
    for c in candidates:
        if dim % c == 0:
            return c
    return 0


def quant_tileable(k: int, n: int) -> bool:
    """Static gate: can the Pallas kernel tile a [k, n] int8 weight?

    Both dims must hit a 128-multiple block (last-dim lane constraint; K
    blocks stay MXU-sized). Callers fall back to the XLA dequant matmul when
    this fails (e.g. llama's 128256 vocab sharded 8 ways -> 16032, not a
    lane multiple).
    """
    return k > 0 and n > 0 and k % _LANE == 0 and n % _LANE == 0


class MatmulBlocks(NamedTuple):
    """What :func:`matmul_blocks` chose for one call."""

    bm: int  # row tile (rows padded to a multiple of it)
    bk: int  # K block of the grid, walked inside the kernel in chunks
    bn: int  # N block of the grid, outermost

    def vmem_bytes(self, x_itemsize: int, out_itemsize: int) -> int:
        """What one grid step holds: double-buffered weight, x, scale and
        output blocks, the accumulator, one chunk's float copy and one
        product."""
        kc = min(_K_CHUNK, self.bk)
        return (2 * self.bk * self.bn + 2 * self.bm * self.bk * x_itemsize
                + 2 * 8 * self.bn * 4 + 2 * self.bm * self.bn * out_itemsize
                + 2 * self.bm * self.bn * 4 + kc * self.bn * x_itemsize)

    def __str__(self) -> str:
        return f"{self.bm}x{self.bk}x{self.bn}"


def _widest_divisor(dim: int, limit: int) -> int:
    """The largest lane-multiple divisor of ``dim`` that is at most
    ``limit``; one lane tile where none is."""
    return max((c for c in range(_LANE, min(dim, limit) + 1, _LANE)
                if dim % c == 0), default=_LANE)


def matmul_blocks(m: int, k: int, n: int, x_itemsize: int = 2,
                  out_itemsize: int = 2, *,
                  weight_block_bytes: Optional[int] = None,
                  vmem_limit: int = _VMEM_LIMIT) -> MatmulBlocks:
    """Row tile, K block and N block of ``x[m, k] @ wq[k, n]``, from the
    call's shapes alone (``k`` and ``n`` lane multiples).

    The weight block is sized by bytes. A call pays for its first block's
    fetch and its last block's product unoverlapped, and a fixed cost for
    every grid step, so the block that balances the two grows with the root
    of the weight's bytes and shrinks as rows add FLOPs to a byte
    (``weight_block_bytes``, when given, stands in for that target). Under
    it, by what the v5e showed (``tools/int8_block_sweep.py``):

    - *whole rows*, ``[bk, n]``: one contiguous burst a block, the partial
      sums in a float32 scratch across the K steps. Taken when the call
      only streams its weight (rows under the ridge), a divisor of ``k``
      lands the block within half the target, and ``bk`` is at least twice
      the rows, so that the scratch's read-modify-write stays a few bytes a
      weight byte;
    - else *K-deep*, ``[k, bn]`` with ``bn`` the widest lane-multiple
      divisor of ``n`` under the target: each output block written once.
      Only a ``k`` whose one-lane-tile column passes the cap is cut.

    Rows go in one tile up to ``_ROW_TILE``, and x is fetched once; beyond
    it in the fewest equal tiles (padded by under 8 rows a tile), innermost
    under a K-deep block at least 512 wide, so one N block's weights are
    fetched once and stay in VMEM while the x tiles stream past. What a step
    holds stays under ``vmem_limit``: ``bn`` steps down its divisors, then
    ``bm`` halves.
    """
    tiles = -(-m // _ROW_TILE)
    bm = -(-m // (8 * tiles)) * 8
    cap = weight_block_bytes or _WEIGHT_BLOCK_CAP
    target = weight_block_bytes or min(cap, math.isqrt(
        k * n * _STEP_BYTES * _RIDGE_ROWS // (_RIDGE_ROWS + max(m, _RIDGE_ROWS))))
    bk = _widest_divisor(k, target // n)
    if m <= _RIDGE_ROWS and target // 2 <= bk * n <= target and bk >= 2 * m:
        blocks = MatmulBlocks(bm, bk, n)
    else:
        bk = k if k * _LANE <= cap else _widest_divisor(k, cap // _LANE)
        wide = target // bk if tiles == 1 else max(target // bk, 512)
        blocks = MatmulBlocks(bm, bk, _widest_divisor(n, wide))
    while blocks.vmem_bytes(x_itemsize, out_itemsize) > vmem_limit:
        if blocks.bn > _LANE:
            blocks = blocks._replace(bn=_widest_divisor(n, blocks.bn - _LANE))
        elif blocks.bm > 8:
            blocks = blocks._replace(bm=-(-blocks.bm // 16) * 8)
        elif blocks.bk > _LANE:
            blocks = blocks._replace(bk=_widest_divisor(k, blocks.bk - _LANE))
        else:
            break  # one tile of each: nothing smaller to ask for
    return blocks


# Jitted so that a step program's 200-odd calls trace and lower each distinct
# (shapes, blocks) once: the kernel body's unrolled chunks cost tens of
# milliseconds a call to trace, at three stages a program, which was 13 s of
# the routed-experts cell's set-up (PERF.md §6, PR 31). XLA inlines the calls,
# so the compiled program holds the same ``int8_matmul`` custom calls.
@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype", "blocks"))
def _quant_matmul_pallas(x, wq, scale, interpret: bool, out_dtype,
                         blocks: MatmulBlocks):
    m, k = x.shape
    _, n = wq.shape
    bm, bk, bn = blocks
    mp = -(-m // bm) * bm
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))
    nk = k // bk
    # N blocks outermost, K innermost: with K whole (nk == 1) the weight
    # block's index holds while the row tiles go by, so it is fetched once.
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=(n // bn, mp // bm, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, i, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda j, i, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda j, i, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] if nk > 1 else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="int8_matmul",
    )(x, wq, scale[None, :])
    return out[:m]


def quant_matmul(
    x: jnp.ndarray,
    wq: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    interpret: bool = False,
    out_dtype=None,
) -> jnp.ndarray:
    """``x [M, K] @ dequant(wq [K, N], scale [N]) -> [M, N]``.

    Pallas on TPU (or under ``interpret=True`` anywhere); otherwise the XLA
    expression with the SAME operation order as the kernel (cast-then-matmul-
    then-scale) so both paths agree to float rounding, not just mathematically.
    """
    out_dtype = out_dtype or x.dtype
    on_tpu = lowering_for_tpu()
    if (on_tpu or interpret) and quant_tileable(*wq.shape):
        blocks = matmul_blocks(x.shape[0], *wq.shape, x.dtype.itemsize,
                               jnp.dtype(out_dtype).itemsize)
        note_kernel_path("int8_matmul", "pallas", wq.shape,
                         blocks=f"{x.shape[0]}:{blocks}")
        return _quant_matmul_pallas(x, wq, scale, interpret, out_dtype, blocks)
    # On the TPU this is the expression XLA hoists a bf16 copy of the weight
    # for (module docstring) — never taken silently.
    note_kernel_path("int8_matmul", "xla", wq.shape,
                     "untileable" if on_tpu or interpret else "not_tpu")
    y = jnp.dot(x, wq.astype(x.dtype), preferred_element_type=jnp.float32)
    return (y * scale[None, :].astype(jnp.float32)).astype(out_dtype)


# ---------------------------------------------------------------------------
# Grouped product over stacked expert kernels
# ---------------------------------------------------------------------------


def _group_blocks(k: int, n: int) -> Tuple[int, int]:
    """(K chunk of the in-kernel loop, N block of the grid): the widest
    lane-multiple divisor of ``n`` whose int8 ``[k, bn]`` block stays under
    the budget, so an expert's kernel streams in as few grid steps as fit."""
    bn = max((c for c in range(_LANE, n + 1, _LANE)
              if n % c == 0 and k * c <= _WEIGHT_BLOCK_CAP),
             default=0)
    return _pick_block(k), bn


def grouped_tileable(k: int, n: int) -> bool:
    """Static gate of the grouped kernel, as :func:`quant_tileable`."""
    return quant_tileable(k, n) and all(_group_blocks(k, n))


def _grouped_kernel(te_ref, tb_ref, used_ref, x_ref, wq_ref, scale_ref, o_ref,
                    *, kb: int, nk: int):
    """One (n block, row tile) step. A row tile holds rows of ONE expert
    (``te_ref``); tiles past ``used_ref[0]`` hold no row and are skipped:
    their block indices repeat the last used tile's, so nothing is fetched
    for them and the output block is written once."""
    del te_ref, tb_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _tile():
        # K in static chunks: the int8 -> float cast of a [kb, bn] piece is
        # the only float copy of the weights there ever is.
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for c in range(nk):
            ks = slice(c * kb, (c + 1) * kb)
            acc += jnp.dot(
                x_ref[:, ks], wq_ref[ks, :].astype(x_ref.dtype),
                preferred_element_type=jnp.float32)
        o_ref[:] = (acc * scale_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _grouped_pallas(x, wq, scale, tile_expert, tile_block, tiles_used, bm,
                    interpret: bool, out_dtype):
    m, k = x.shape
    e, _, n = wq.shape
    kb, bn = _group_blocks(k, n)
    # N blocks outermost: consecutive row tiles of one expert then reuse the
    # weight block already in VMEM, and only x tiles (small) are re-read.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // bn, m // bm),
        in_specs=[
            pl.BlockSpec((bm, k), lambda j, i, te, tb, used: (tb[i], 0)),
            pl.BlockSpec((None, k, bn),
                         lambda j, i, te, tb, used: (te[i], 0, j)),
            pl.BlockSpec((None, 1, bn),
                         lambda j, i, te, tb, used: (te[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda j, i, te, tb, used: (tb[i], j)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel, kb=kb, nk=k // kb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="grouped_int8_matmul",
    )(tile_expert, tile_block, tiles_used, x, wq, scale[:, None, :])


def grouped_quant_matmul(
    x: jnp.ndarray,
    wq: jnp.ndarray,
    scale: Optional[jnp.ndarray],
    tile_expert: jnp.ndarray,
    tile_block: jnp.ndarray,
    tiles_used: jnp.ndarray,
    *,
    bm: int,
    interpret: bool = False,
    out_dtype=None,
) -> jnp.ndarray:
    """``y[rows of tile t] = x[rows of tile t] @ dequant(wq[tile_expert[t]])``.

    ``x [M, K]`` holds the rows sorted by expert in whole tiles of ``bm``
    rows (ragged groups padded to the tile, never cut: no row is dropped);
    ``wq [E, K, N]`` int8 with ``scale [E, N]``, or a float ``wq`` with
    ``scale`` None. ``tile_expert [M // bm]`` names each tile's expert,
    ``tiles_used [1]`` how many tiles hold rows, and ``tile_block`` is
    ``min(t, tiles_used - 1)``; ``tile_expert`` past the used tiles repeats
    the last used one. Rows of unused tiles come back unwritten: the caller
    reads only the rows it placed.

    Pallas on TPU for int8 kernels (the dequantisation stays in VMEM, as in
    :func:`quant_matmul`: XLA would hoist a bf16 copy of every expert out of
    the decode loop); otherwise one batched XLA product over the tiles.
    """
    out_dtype = out_dtype or x.dtype
    m, k = x.shape
    _, _, n = wq.shape
    on_tpu = lowering_for_tpu()
    if scale is not None and (on_tpu or interpret):
        if grouped_tileable(k, n):
            note_kernel_path("grouped_int8_matmul", "pallas", wq.shape)
            return _grouped_pallas(x, wq, scale, tile_expert, tile_block,
                                   tiles_used, bm, interpret, out_dtype)
        reason = "untileable"
    else:
        reason = "not_tpu" if scale is not None else "float_kernel"
    note_kernel_path("grouped_int8_matmul", "xla", wq.shape, reason)
    y = jnp.einsum("tbk,tkn->tbn", x.reshape(m // bm, bm, k),
                   wq[tile_expert].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    if scale is not None:
        y = y * scale[tile_expert][:, None, :].astype(jnp.float32)
    return y.reshape(m, n).astype(out_dtype)


def quant_matmul_sharded(
    x: jnp.ndarray,
    wq: jnp.ndarray,
    scale: jnp.ndarray,
    mesh: jax.sharding.Mesh,
    k_axis: Optional[str],
    n_axis: Optional[str],
    b_axis: Optional[str] = None,
    *,
    interpret: bool = False,
    out_dtype=None,
) -> jnp.ndarray:
    """The kernel under ``shard_map``, manual over EVERY mesh axis.

    ``k_axis``/``n_axis``: mesh axis (or None) sharding the weight's
    contraction / output dim; ``b_axis``: the axis sharding x's rows (dp).
    Column-parallel (n_axis) is purely local; row-parallel (k_axis) psums
    partial products — exactly the collective GSPMD inserts for the dense
    row-parallel matmul. The row-parallel psum accumulates in float32 (each
    shard's kernel output stays f32 until after the all-reduce) to match
    the dense GSPMD path, which all-reduces the f32 dot output before the
    downcast — casting shards to bf16 pre-psum would add avoidable
    accumulation error at tp=8 (e.g. the 70B down_proj).

    Why full-manual: Mosaic kernels refuse to lower in a partially-auto
    SPMD context (``tpu_custom_call.py`` requires manual_axes == all mesh
    axes), so the wrap names every axis and encodes batch sharding in the
    specs instead of leaving it to GSPMD. Axes that shard nothing here are
    manual-but-unused (their spec entries are None == replicated).
    """
    out_dtype = out_dtype or x.dtype

    def local(xl, wql, scalel):
        if k_axis is not None:
            y = quant_matmul(
                xl, wql, scalel, interpret=interpret, out_dtype=jnp.float32
            )
            return jax.lax.psum(y, k_axis).astype(out_dtype)
        return quant_matmul(xl, wql, scalel, interpret=interpret, out_dtype=out_dtype)

    from fairness_llm_tpu.parallel.sharding import compat_shard_map

    return compat_shard_map(
        local,
        mesh,
        in_specs=(P(b_axis, k_axis), P(k_axis, n_axis), P(n_axis)),
        out_specs=P(b_axis, n_axis),
    )(x, wq, scale)
