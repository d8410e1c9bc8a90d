"""Single-token attention over the serving slot pool, each position read
from HBM once (Pallas TPU).

The serving decode step (``runtime/stepbuilder.build_serve_step``) attends
every slot of the pool: one row per request, ``[B, L, Hkv, D]`` keys and
values a layer, and in a window layer a ring of ``L`` slots. Many rows hold
the same leading positions bit for bit: a rider copied them from its donor
in ``serve_prefill``. The scheduler's plan (``serving/scheduler.py``
``_decode_plan``) says so with two vectors a slot:

- ``lead[b]``: the row that row ``b`` reads its shared positions from. A
  row that shares with no other row is its own lead with ``shared`` 0.
- ``shared[b]``: how many leading positions row ``b`` holds as ``lead[b]``
  holds them. A lead (``lead[l] == l``, ``shared[l] > 0``) reads them from
  itself.

Each live row ``b`` at position ``pos`` (writing slot ``last``, so its
prompt starts at slot ``pad = last - pos``) attends the positions ``[lo,
pos]``, ``lo`` 0 or, under a window ``W``, ``pos - W + 1``. They split in
two spans:

- the LEAD span ``[lo, shared)``, read once per group from the lead's row
  (``_lead_kernel``): one grid step per block of the lead's row, every
  query of the pool scored against it and masked to the group's members;
- the OWN span ``[max(lo, shared), pos]``, read from the row itself
  (``_own_kernel``): one grid step per block of a tile of ``tile_rows``
  rows, the tile's union of spans.

A row that is not live reads nothing, and a tile of such rows has no grid
step. Both kernels leave float32 softmax statistics ``(max, sum, acc)``,
merged exactly here. Keys and values are read as stored, in the pool's
own layout (never transposed); scores and softmax are float32. The work
lists come in through scalar prefetch and set the grid's length: no grid
step is spent on a block no row needs. A ring layer's slot ``s`` lives at
``s mod L``, so a block of slots maps to the ring block that holds it.

What the mask admits is what the XLA branch of ``Attention`` admits for a
live row (its key-valid slots up to the one it writes, within the window):
a live row's slots ``[pad, last]`` are exactly those, and the plan names
only positions its lead holds, so the kernel reads no mask from the pool.
"""

from __future__ import annotations

import contextvars
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fairness_llm_tpu.ops.dispatch import note_kernel_path
from fairness_llm_tpu.ops.quant_matmul import lowering_for_tpu

NEG = -1e30
#: Rows a grid step of the own spans takes (``tile_rows``).
TILE_ROWS = 8
#: Groups of rows that share a lead in one step (``scheduler._decode_plan``
#: names no more: the rest read their own rows).
MAX_GROUPS = 4
#: A block's bytes of keys: an own block of ``TILE_ROWS`` rows, a lead
#: block of one.
_OWN_BLOCK_BYTES = 512 * 1024
_LEAD_BLOCK_BYTES = 512 * 1024
#: The lead kernel's float32 scores of every row against one block.
_LEAD_SCORE_BYTES = 2 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024

_INTERPRET = contextvars.ContextVar("pool_attention_interpret", default=0)


class interpreted:
    """``with interpreted():`` the kernels run in Pallas interpret mode off
    the TPU, wherever the step would take them on the chip (the tests'
    way to the kernel path on the CPU)."""

    def __enter__(self):
        self._token = _INTERPRET.set(_INTERPRET.get() + 1)
        return self

    def __exit__(self, *exc):
        _INTERPRET.reset(self._token)
        return False


def available(cfg, tp: int = 1) -> bool:
    """Whether the serving step of ``cfg`` attends through the kernels: a
    model with softmax layers over a bfloat16/float32 cache, one device,
    and the TPU (or ``interpreted``). On the chip, lane-tiled heads and a
    power of two of KV heads (the own kernel splits a block's rows into
    position and head by shifts)."""
    if tp != 1 or cfg.kv_cache_quant or cfg.attention == "mla":
        return False
    if "gqa" not in cfg.mixer_kinds:
        return False
    if _INTERPRET.get():
        return True
    kv_heads = cfg.heads_held[1]
    return lowering_for_tpu() and cfg.head_dim % 128 == 0 and \
        kv_heads & (kv_heads - 1) == 0


def tile_rows(B: int) -> int:
    """Rows a grid step of the own spans takes in a pool of ``B``: the
    most, up to ``TILE_ROWS``, that divide it."""
    return max(d for d in range(1, TILE_ROWS + 1) if B % d == 0)


def _pow2_at_most(n: float) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _block(L: int, limit: float, floor: int) -> int:
    """The largest power of two up to ``limit`` (and ``floor`` at the least)
    that divides ``L``."""
    b = max(floor, _pow2_at_most(limit))
    while b > 1 and L % b:
        b //= 2
    return b


def blocks(B: int, L: int, F: int, itemsize: int, rep: int):
    """``(own block, lead block)`` positions for a leaf ``[B, L, F]``: an
    own block of ``TILE_ROWS`` rows and a lead block of one near their
    bytes, the lead's scores of every row's ``rep`` queries in bounds."""
    floor = 16 if itemsize == 2 else 8
    own = _block(L, min(128, _OWN_BLOCK_BYTES / (tile_rows(B) * F *
                                                 itemsize)), floor)
    lead = _block(L, min(_LEAD_BLOCK_BYTES / (F * itemsize),
                         _LEAD_SCORE_BYTES / (4 * B * rep)), floor)
    return own, lead


class PoolPlan:
    """One decode step's plan, as the program holds it: ``lead``, ``shared``
    (the scheduler's), ``live`` (rows that decode this step), ``pos`` (the
    position each row's query stands at), ``last`` (the slot it writes),
    ``max_len`` (a row's slots; a shorter leaf is a ring), ``leads`` (False
    where the plan can name no lead: every row is its own and no lead
    kernel runs). The work lists of a leaf's kind are made once a step and
    shared by its layers."""

    def __init__(self, lead, shared, live, pos, last, max_len: int,
                 leads: bool = True):
        self.lead, self.shared, self.live = lead, shared, live
        self.pos, self.last, self.max_len = pos, last, max_len
        self.leads = leads
        self._tables = {}

    def tables(self, L: int, window: Optional[int], own_blk: int,
               lead_blk: int):
        key = (L, window, own_blk, lead_blk)
        if key not in self._tables:
            self._tables[key] = _work_lists(
                self.lead, self.shared, self.live, self.pos, self.last,
                max_len=self.max_len, L=L, window=window, own_blk=own_blk,
                lead_blk=lead_blk)
        return self._tables[key]


def spans(xp, lead, shared, live, pos, last, window):
    """Per row, in positions: the first one attended, the lead span's end
    and the own span's start; and the row's pad (``last - pos``). Rows that
    are not live attend nothing. Arrays ``[..., B]``."""
    lo = xp.zeros_like(pos) if window is None else \
        xp.maximum(pos - window + 1, 0)
    lead_hi = xp.where(live, xp.maximum(shared, lo), lo)
    own_lo = xp.where(live, xp.maximum(lo, shared), pos + 1)
    return lo, lead_hi, own_lo, last - pos


def own_tiles(xp, live, own_lo_slot, hi_slot, blk: int):
    """Per tile of ``tile_rows`` rows: the first block of the union of its
    live rows' own spans (slots) and how many blocks it takes."""
    rows = tile_rows(live.shape[-1])
    tiles = live.shape[:-1] + (live.shape[-1] // rows, rows)
    big = np.iinfo(np.int32).max
    lo = xp.where(live, own_lo_slot, big).reshape(tiles).min(axis=-1)
    hi = xp.where(live, hi_slot, 0).reshape(tiles).max(axis=-1)
    has = live.reshape(tiles).any(axis=-1)
    first = xp.where(has, lo // blk, 0)
    count = xp.where(has, (hi - 1) // blk - first + 1, 0)
    return first, count


def lead_groups(xp, lead, live, lo, lead_hi, pad, blk: int, leads):
    """Per group (``leads`` ``[G]``, -1 for none): the first block of the
    lead's row that a live member's lead span touches, and how many. A
    member's span is ``[lo, lead_hi)`` in positions, at slot ``pad[lead]``
    onward in the lead's row."""
    member = (lead[..., None, :] == leads[:, None]) & \
        (leads[:, None] >= 0) & live[..., None, :] & \
        (lo < lead_hi)[..., None, :]  # [..., G, B]
    big = np.iinfo(np.int32).max
    g_lo = xp.where(member, lo[..., None, :], big).min(axis=-1)
    g_hi = xp.where(member, lead_hi[..., None, :], 0).max(axis=-1)
    has = member.any(axis=-1)
    g_pad = xp.take(pad, xp.clip(leads, 0, lead.shape[-1] - 1), axis=-1)
    first = xp.where(has, (g_pad + g_lo) // blk, 0)
    count = xp.where(has, (g_pad + g_hi - 1) // blk - first + 1, 0)
    return first, count, g_pad


def fetched_positions(lead, shared, live, pos, last, *, L: int,
                      window: Optional[int], own_blk: int, lead_blk: int,
                      leads: bool = True) -> int:
    """Row-positions of one leaf that the two kernels fetch, on the host,
    summed over steps: every grid step's block (a kernel runs one step at
    the least; the lead kernel none where ``leads`` is False), as
    ``_work_lists`` lays them out in the program. ``live``, ``pos`` and
    ``last`` are ``[steps, B]``, or ``[B]`` for one step."""
    lead, shared, live, pos, last = (np.asarray(x) for x in (
        lead, shared, live, pos, last))
    lo, lead_hi, own_lo, pad = spans(np, lead, shared, live, pos, last,
                                     window)
    _, count = own_tiles(np, live, pad + own_lo, last + 1, own_blk)
    heads = np.full((MAX_GROUPS,), -1, np.int32)
    found = np.flatnonzero((lead == np.arange(lead.shape[0])) & (shared > 0))
    heads[:min(len(found), MAX_GROUPS)] = found[:MAX_GROUPS]
    _, g_count, _ = lead_groups(np, lead, live, lo, lead_hi, pad, lead_blk,
                                heads)
    own_steps = np.maximum(count.sum(axis=-1), 1)
    lead_steps = np.maximum(g_count.sum(axis=-1), 1) if leads else 0
    return int(np.sum(own_steps * tile_rows(lead.shape[0]) * own_blk +
                      lead_steps * lead_blk))


def _expand(first, count, size: int):
    """Work items of groups laid end to end: per item its group, block and
    whether it is its group's first and its last. ``size`` bounds the
    items; the count of real ones is returned beside them."""
    end = jnp.cumsum(count)
    start = end - count
    i = jnp.arange(size, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(i[:, None] >= end[None, :], axis=1),
                        count.shape[0] - 1).astype(jnp.int32)
    blk = (first[group] + i - start[group]).astype(jnp.int32)
    is_first = (i == start[group]).astype(jnp.int32)
    is_last = (i == end[group] - 1).astype(jnp.int32)
    return group, blk, is_first, is_last, end[-1].astype(jnp.int32)


def _work_lists(lead, shared, live, pos, last, *, max_len, L, window,
                own_blk, lead_blk):
    B = lead.shape[0]
    lo, lead_hi, own_lo, pad = spans(jnp, lead, shared, live, pos, last,
                                     window)
    first, count = own_tiles(jnp, live, pad + own_lo, last + 1, own_blk)
    own = _expand(first, count, (B // tile_rows(B)) * (max_len // own_blk + 1))
    is_lead = (lead == jnp.arange(B)) & (shared > 0)
    leads = jnp.nonzero(is_lead, size=MAX_GROUPS, fill_value=-1)[0]
    leads = leads.astype(jnp.int32)
    g_first, g_count, g_pad = lead_groups(jnp, lead, live, lo, lead_hi, pad,
                                          lead_blk, leads)
    group, blk, _, _, n_lead = _expand(
        g_first, g_count, MAX_GROUPS * (max_len // lead_blk + 1))
    lead_items = (leads[group], blk, g_pad[group], n_lead)
    own_bounds = (pad + own_lo, jnp.where(live, last + 1, 0))
    lead_bounds = (lo, lead_hi)
    return own, own_bounds, lead_items, lead_bounds


def _heads(ref):
    """Each KV head of a lead block ``[1, blk, Hkv, d]``: ``[blk, d]``.
    Fewer than 8 heads of bfloat16 share a packed tile: a head is picked in
    float32 and cast back, exactly (Mosaic takes a minute to compile the
    packed pick)."""
    n = ref.shape[2]
    if n % 8 and ref.dtype != jnp.float32:
        x = ref[0].astype(jnp.float32)
        return [x[:, g, :].astype(ref.dtype) for g in range(n)]
    return [ref[0, :, g, :] for g in range(n)]


def _online(m_ref, l_ref, acc_ref, s, mask, v, pv):
    """One block's scores ``s`` (float32, ``mask`` where they count) into
    the running ``(max, sum, acc)``; ``pv(p)`` is the block's values
    weighed by ``p``."""
    s = jnp.where(mask, s, NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + pv(p.astype(v.dtype))
    m_ref[...] = m_new


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _own_kernel(tile_ref, blk_ref, first_ref, last_ref, lo_ref, hi_ref,
                q_ref, k_ref, v_ref, *refs, blk, hkv, rep, scale):
    """A tile of rows against a block of their own rows. The block is the
    leaf's ``[rows, blk * Hkv, d]`` view (position-major, a head a row), so
    every head's queries meet every KV head in one product and a mask keeps
    query head ``h`` to KV head ``h // rep``. A tile starts from the lead
    spans' answer and its log-sum-exp (``o1``, ``lse``: the same statistics
    as ``(max, sum, acc)`` with the sum 1), where a pool has lead spans,
    and its last block writes the output."""
    i = pl.program_id(0)
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]

    @pl.when(first_ref[i] == 1)
    def _():
        if len(refs) == 4:
            _init(m_ref, l_ref, acc_ref)
            return
        lse_ref, o1_ref = refs[:2]
        lse = lse_ref[...]
        m_ref[...] = lse
        l_ref[...] = jnp.where(lse > NEG / 2, 1.0, 0.0)
        acc_ref[...] = o1_ref[...]

    H, cols = q_ref.shape[1], k_ref.shape[1]
    c = jax.lax.broadcasted_iota(jnp.int32, (1, 1, cols), 2)
    if hkv & (hkv - 1) == 0:
        shift = hkv.bit_length() - 1
        pos, head = c >> shift, c & (hkv - 1)
    else:
        pos, head = c // hkv, c % hkv
    h = jax.lax.broadcasted_iota(jnp.int32, (1, H, 1), 1)
    slot = blk_ref[i] * blk + pos
    mask = (head * rep <= h) & (h < head * rep + rep) & \
        (slot >= lo_ref[...]) & (slot < hi_ref[...])  # [rows, H, cols]
    v = v_ref[...]
    s = jax.lax.dot_general(
        q_ref[...], k_ref[...], (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale
    _online(m_ref, l_ref, acc_ref, s, mask, v, lambda p: jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32))

    @pl.when(last_ref[i] == 1)
    def _():
        den = l_ref[...]
        o_ref[...] = jnp.where(den > 0, acc_ref[...] / jnp.where(
            den > 0, den, 1.0), 0.0).astype(o_ref.dtype)


def _lead_kernel(row_ref, blk_ref, pad_ref, lead_ref, lo_ref, hi_ref, q_ref,
                 k_ref, v_ref, m_ref, acc_ref, l_ref, *, blk, hkv, rep,
                 row_major, scale):
    """Every row's queries against a block of a lead's row, a KV head at a
    time: its ``rep`` query heads of all rows are one ``[rep * B, d]``
    operand, and the rows' mask is laid out to match: each row's ``rep``
    times in a row where ``row_major`` (row ``b * rep + r``: a multiple of 8
    of them, whole tiles), else the whole mask ``rep`` times (row ``r * B +
    b``). The last grid step leaves each row's answer over its lead span
    and its log-sum-exp (``NEG`` where the row has none) in ``acc`` and
    ``m``."""
    i = pl.program_id(0)
    pl.when(i == 0)(lambda: _init(m_ref, l_ref, acc_ref))
    B = lead_ref.shape[0]
    p_of = blk_ref[i] * blk - pad_ref[i] + jax.lax.broadcasted_iota(
        jnp.int32, (B, blk), 1)  # the block's positions in the lead's row
    mask = (lead_ref[...] == row_ref[i]) & (p_of >= lo_ref[...]) & \
        (p_of < hi_ref[...])  # [B, blk]
    if row_major:
        mask = jnp.broadcast_to(mask[:, None, :], (B, rep, blk)).reshape(
            B * rep, blk)
    else:
        mask = jnp.concatenate([mask] * rep, axis=0)  # [rep * B, blk]
    ks = [k_ref[0]] if len(k_ref.shape) == 3 else _heads(k_ref)
    vs = [v_ref[0]] if len(v_ref.shape) == 3 else _heads(v_ref)
    for g in range(hkv):
        k, v = ks[g], vs[g]  # [blk, d]
        s = jax.lax.dot_general(
            q_ref[g], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rep * B, blk]
        _online(m_ref.at[g], l_ref.at[g], acc_ref.at[g], s, mask, v,
                lambda p, v=v: jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        den = l_ref[...]
        some = den > 0
        m_ref[...] = jnp.where(some, m_ref[...] + jnp.log(
            jnp.where(some, den, 1.0)), NEG)
        acc_ref[...] = acc_ref[...] / jnp.where(some, den, 1.0)


def _lead_pass(q, keys, values, lead_items, lead_bounds, lead, *, ring,
               lead_blk, params, interpret):
    """Every row's statistics over its lead span, ``(lse, o1)`` as
    ``[B, H, 1]`` and ``[B, H, D]``: one block of a lead's row per grid
    step."""
    B, H, D = q.shape
    _, L, hkv, _ = keys.shape
    rep, n_lead_blk, f32 = H // hkv, L // lead_blk, jnp.float32
    scale = D ** -0.5
    # One head's leaf is viewed ``[B, L, D]`` (a ``(positions, 128)`` tile,
    # where ``[..., 1, D]`` would pad a position to a tile of its own);
    # several keep their axis, a head picked in the kernel.
    kv_block = (D,) if hkv == 1 else (hkv, D)
    kl, vl = (x.reshape((B, L) + kv_block) for x in (keys, values))
    row, lblk, lpad, n_lead = lead_items

    def lead_kv(i, row_r, blk_r, pad_r):
        b = blk_r[i]
        return (jnp.maximum(row_r[i], 0),
                b % n_lead_blk if ring else b) + (0,) * len(kv_block)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    # One KV head with a multiple of 8 query heads: the rows' own order,
    # so that the statistics are ``[B, H, .]`` as they stand.
    row_major = hkv == 1 and rep % 8 == 0
    order = (1, 0, 2, 3) if row_major else (1, 2, 0, 3)
    n = rep * B
    lse, o1 = pl.pallas_call(
        functools.partial(_lead_kernel, blk=lead_blk, hkv=hkv, rep=rep,
                          row_major=row_major, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(n_lead, 1),),
            in_specs=[
                whole((B, 1)), whole((B, 1)), whole((B, 1)),
                whole((hkv, n, D)),
                pl.BlockSpec((1, lead_blk) + kv_block, lead_kv),
                pl.BlockSpec((1, lead_blk) + kv_block, lead_kv),
            ],
            out_specs=[whole((hkv, n, 1)), whole((hkv, n, D))],
            scratch_shapes=[pltpu.VMEM((hkv, n, 1), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((hkv, n, 1), f32),
                   jax.ShapeDtypeStruct((hkv, n, D), f32)],
        compiler_params=params, interpret=interpret,
        name="pool_attention_lead",
    )(jnp.where(n_lead > 0, row, -1), lblk, lpad, lead.reshape(B, 1),
      lead_bounds[0].reshape(B, 1), lead_bounds[1].reshape(B, 1),
      q.reshape(B, hkv, rep, D).transpose(order).reshape(hkv, n, D),
      kl, vl)

    def rows_first(x):  # [hkv, rep * B, .] -> [B, H, .]
        if row_major:
            return x.reshape(B, H, -1)
        return x.reshape(hkv, rep, B, -1).transpose(2, 0, 1, 3).reshape(
            B, H, -1)

    return rows_first(lse), rows_first(o1)


@functools.partial(jax.jit, static_argnames=(
    "window", "max_len", "own_blk", "lead_blk", "leads", "interpret"))
def _attend(q, keys, values, own, own_bounds, lead_items, lead_bounds,
            lead, live, *, window, max_len, own_blk, lead_blk, leads,
            interpret):
    B, H, D = q.shape
    _, L, hkv, _ = keys.shape
    rep = H // hkv
    ring = L < max_len
    f32 = jnp.float32
    scale = D ** -0.5
    n_own_blk = L // own_blk
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                  vmem_limit_bytes=_VMEM_LIMIT)

    # -- lead spans, where the plan can name a lead (``PoolPlan.leads``) ----
    from_lead = _lead_pass(
        q, keys, values, lead_items, lead_bounds, lead, ring=ring,
        lead_blk=lead_blk, params=params, interpret=interpret) if leads else ()

    # -- own spans: a tile of rows, a block of slots, per grid step, from
    # the lead spans' statistics to the output ------------------------------
    # The leaf as ``[B, L * Hkv, D]``: a bitcast of the pool's layout (a
    # position's heads are one tile's rows), where ``[B, L, Hkv * D]``
    # would lay the leaf out anew.
    k3, v3 = (x.reshape(B, L * hkv, D) for x in (keys, values))
    tile, blk, first, last, n_own = own
    rows = tile_rows(B)

    def own_kv(i, tile_r, blk_r, first_r, last_r):
        b = blk_r[i]
        return tile_r[i], b % n_own_blk if ring else b, 0

    def own_rows(i, tile_r, blk_r, first_r, last_r):
        return tile_r[i], 0, 0

    def own_spec(shape):
        return pl.BlockSpec(shape, own_rows)

    out = pl.pallas_call(
        functools.partial(_own_kernel, blk=own_blk, hkv=hkv, rep=rep,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(jnp.maximum(n_own, 1),),
            in_specs=[
                own_spec((rows, 1, 1)), own_spec((rows, 1, 1)),
                own_spec((rows, H, D)),
                pl.BlockSpec((rows, own_blk * hkv, D), own_kv),
                pl.BlockSpec((rows, own_blk * hkv, D), own_kv),
            ] + ([own_spec((rows, H, 1)), own_spec((rows, H, D))]
                 if from_lead else []),
            out_specs=own_spec((rows, H, D)),
            scratch_shapes=[pltpu.VMEM((rows, H, 1), f32),
                            pltpu.VMEM((rows, H, 1), f32),
                            pltpu.VMEM((rows, H, D), f32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=params, interpret=interpret,
        name="pool_attention_own",
    )(jnp.minimum(tile, B // rows - 1), blk, first, last,
      own_bounds[0].reshape(B, 1, 1), own_bounds[1].reshape(B, 1, 1), q,
      k3, v3, *from_lead)
    # A tile with no live row has no grid step: its rows' output is zeros.
    return jnp.where(live[:, None, None], out, 0)


def pool_attention(q, keys, values, plan: PoolPlan, *,
                   window: Optional[int], block_sizes=None):
    """``q [B, H, D]`` of the step's one token against a pool leaf ``keys,
    values [B, L, Hkv, D]`` as the step left it (its token written), under
    ``plan``. Returns ``[B, H, D]`` in ``q``'s dtype; a row that is not live
    gets zeros. ``block_sizes``: ``(own, lead)`` positions a block, in place
    of ``blocks``' choice."""
    B, H, D = q.shape
    _, L, hkv, _ = keys.shape
    own_blk, lead_blk = block_sizes or blocks(
        B, L, hkv * D, keys.dtype.itemsize, H // hkv)
    own, own_bounds, lead_items, lead_bounds = plan.tables(
        L, window, own_blk, lead_blk)
    interpret = not lowering_for_tpu()
    note_kernel_path("pool_attention", "pallas", (B, L, hkv, D),
                     blocks=f"own:{tile_rows(B)}x{own_blk} lead:{lead_blk}")
    return _attend(q, keys, values, own, own_bounds, lead_items, lead_bounds,
                   plan.lead, plan.live, window=window, max_len=plan.max_len,
                   own_blk=own_blk, lead_blk=lead_blk, leads=plan.leads,
                   interpret=interpret)
