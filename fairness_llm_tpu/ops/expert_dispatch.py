"""Routed experts on one chip: the chosen (token, expert) pairs whose expert
is held here, sorted by expert into whole row tiles, through the grouped
product (``ops/quant_matmul.grouped_quant_matmul``), and summed back per
token with the router's weights.

Shapes are static and no pair is dropped. A round takes up to ``pairs`` local
pairs (twice what even routing sends here); the rounds are a loop whose trip
count is read from the routing, so the usual step runs the body once and a
step in which every token chose this chip's experts runs it ``k`` times. The
device ops of a layer are a fixed number: one cumulative sum, one scatter of
row ids, one row gather in, three grouped products, one row gather out; none
is per row or per expert.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from fairness_llm_tpu.ops.quant_matmul import grouped_quant_matmul

_MIN_TILE, _MAX_TILE = 16, 256


def round_sizes(tokens: int, k: int, num_experts: int,
                held: int) -> Tuple[int, int, int]:
    """(row tile, pairs per round, row tiles per round) for ``tokens`` rows:
    the tile is four times an expert's even share of the rows, as a power of
    two between 16 and 256; a round holds twice the chip's even share of the
    pairs, in whole tiles; and every held expert may end in a part tile."""
    share = tokens * k / num_experts
    bm = _MIN_TILE
    while bm < min(4 * share, _MAX_TILE):
        bm *= 2
    pairs = -(-int(2 * share * held) // bm) * bm
    pairs = max(bm, min(pairs, -(-tokens * min(k, held) // bm) * bm))
    return bm, pairs, pairs // bm + held


def routed_experts(
    x: jnp.ndarray,  # [T, d]
    chosen: jnp.ndarray,  # [T, k] int32: expert ids over ALL experts
    weights: jnp.ndarray,  # [T, k] float32
    valid: jnp.ndarray,  # [T] bool: padding and idle rows route nowhere
    gate, up, down,  # (kernel [E_l, ., .], scale [E_l, .] or None)
    first: int, num_experts: int, activation,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum over the held chosen experts of ``w * E(x)`` as float32 [T, d],
    int32 [3] counters: valid tokens, local pairs, the fullest held expert's
    pairs)."""
    T, d = x.shape
    k = chosen.shape[1]
    held = gate[0].shape[0]
    bm, pairs, n_tiles = round_sizes(T, k, num_experts, held)
    m_cap = n_tiles * bm
    P = T * k

    local = ((chosen >= first) & (chosen < first + held)
             & valid[:, None]).reshape(P)
    le = jnp.clip(chosen - first, 0, held - 1).reshape(P)
    grank = jnp.cumsum(local.astype(jnp.int32)) - 1  # rank among local pairs
    n_local = jnp.sum(local, dtype=jnp.int32)
    of_expert = le[:, None] == jnp.arange(held)[None, :]  # [P, held]
    per_expert = jnp.sum(of_expert & local[:, None], axis=0, dtype=jnp.int32)
    counters = jnp.stack([jnp.sum(valid, dtype=jnp.int32), n_local,
                          jnp.max(per_expert)])
    token_of_pair = jnp.arange(P, dtype=jnp.int32) // k
    tiles = jnp.arange(n_tiles, dtype=jnp.int32)

    def one_round(r, y):
        in_round = local & (grank >= r * pairs) & (grank < (r + 1) * pairs)
        csum = jnp.cumsum((of_expert & in_round[:, None]).astype(jnp.int32),
                          axis=0)  # [P, held]: ranks within each group
        rank = jnp.take_along_axis(csum, le[:, None], axis=1)[:, 0] - 1
        group_tiles = (csum[-1] + bm - 1) // bm
        tile_end = jnp.cumsum(group_tiles)
        used = tile_end[-1]
        pos = jnp.where(in_round, (tile_end - group_tiles)[le] * bm + rank,
                        m_cap)
        row_token = jnp.full((m_cap,), T, jnp.int32).at[pos].set(
            token_of_pair, mode="drop", unique_indices=True)
        row_ok = row_token < T
        xs = jnp.where(row_ok[:, None],
                       x[jnp.minimum(row_token, T - 1)], 0).astype(x.dtype)
        last = jnp.maximum(used - 1, 0)
        tile_block = jnp.minimum(tiles, last)
        tile_expert = jnp.minimum(
            jnp.searchsorted(tile_end, tile_block, side="right"),
            held - 1).astype(jnp.int32)
        plan = (tile_expert, tile_block, used[None])

        def product(rows, leaf):
            return grouped_quant_matmul(rows, leaf[0], leaf[1], *plan, bm=bm,
                                        interpret=interpret)

        h = activation(product(xs, gate)) * product(xs, up)
        out = product(h, down)  # [m_cap, d]
        picked = out[jnp.minimum(pos, m_cap - 1)].reshape(T, k, d)
        return y + jnp.sum(
            jnp.where(in_round.reshape(T, k, 1),
                      picked.astype(jnp.float32) * weights[..., None], 0.0),
            axis=1)

    rounds = (n_local + pairs - 1) // pairs
    y = jax.lax.fori_loop(0, rounds, one_round,
                          jnp.zeros((T, d), jnp.float32))
    return y, counters
