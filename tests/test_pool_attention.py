"""The slot pool's decode attention (``ops/pool_attention.py``) in Pallas
interpret mode, float32, against the XLA branch of ``Attention``: every key
a live row's mask admits (its valid slots up to the one it writes, within
the window; in a ring, what each ring slot holds), softmax over them.

A pool of 16 rows in two tiles of 8: two groups whose members copied their
lead's leading positions at other pads, a lone row, idle rows; full rows of
96 slots and rings of 32 under a window of 12; blocks of 8 positions, so a
lead span ends inside a block. The kernel reads a member's shared positions
from its lead: garbage in the member's own copy of them moves nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fairness_llm_tpu.ops import pool_attention as pa

TOL = 1e-4
B, HKV, REP, D = 16, 2, 2, 32
MAX_LEN = 96
# slot -> (pad, prompt length, emitted, lead, shared); None: idle
ROWS = {
    3: (5, 40, 3, 3, 40),  # group A's lead: the smallest position
    0: (2, 41, 3, 3, 33),
    7: (9, 44, 2, 3, 29),
    9: (0, 45, 1, 3, 37),
    12: (4, 30, 6, 12, 30),  # group B's lead
    14: (1, 33, 4, 12, 26),
    5: (3, 31, 7, 12, 30),
    10: (6, 50, 9, 10, 0),  # a lone row
}


def _scenario(seed, hkv=HKV):
    """(q, logical rows [B, MAX_LEN, hkv, D] k and v, lead, shared, live,
    pos, last). A member's first ``shared`` positions are its lead's bits."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(B, MAX_LEN, hkv, D)).astype(np.float32)
    v = rng.normal(size=(B, MAX_LEN, hkv, D)).astype(np.float32)
    q = rng.normal(size=(B, hkv * REP, D)).astype(np.float32)
    lead = np.arange(B, dtype=np.int32)
    shared = np.zeros(B, np.int32)
    live = np.zeros(B, bool)
    pos = np.zeros(B, np.int32)
    last = np.zeros(B, np.int32)
    for b, (pad, n, e, ld, sh) in ROWS.items():
        lead[b], shared[b], live[b] = ld, sh, True
        pos[b], last[b] = n + e, pad + n + e
    for b, (pad, *_rest) in ROWS.items():
        l_pad = ROWS[lead[b]][0]
        for p in range(shared[b]):
            k[b, pad + p], v[b, pad + p] = k[lead[b], l_pad + p], \
                v[lead[b], l_pad + p]
    return q, k, v, lead, shared, live, pos, last


def _ring(rows, last, R):
    """The ring of ``R`` each row holds once it has written through slot
    ``last``: slot ``j`` at ``j mod R``, the newest ``R``."""
    out = np.zeros((B, R) + rows.shape[2:], rows.dtype)
    for b in range(B):
        for j in range(max(0, last[b] - R + 1), last[b] + 1):
            out[b, j % R] = rows[b, j]
    return out


def _xla_branch(q, k, v, live, pos, last, window, R=None):
    """The XLA branch's masks for a live row over a leaf of ``R`` slots (a
    ring) or of every slot."""
    L = k.shape[1]
    slots = np.arange(L)[None, :]
    pad = (last - pos)[:, None]
    if R is None:
        held = np.broadcast_to(slots, (B, L))
    else:
        held = last[:, None] - np.mod(last[:, None] - slots, R)
    ok = (held >= pad) & (held <= last[:, None])
    if window is not None:
        ok &= (pos[:, None] - (held - pad)) < window
    rep = q.shape[1] // k.shape[2]
    kk, vv = np.repeat(k, rep, axis=2), np.repeat(v, rep, axis=2)
    s = np.einsum("bhd,blhd->bhl", q, kk) * D ** -0.5
    s = np.where(ok[:, None, :], s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(axis=-1, keepdims=True)
    out = np.einsum("bhl,blhd->bhd", p, vv)
    return np.where(live[:, None, None], out, 0.0)


def _kernel(q, k, v, lead, shared, live, pos, last, window, blocks,
            leads=True):
    plan = pa.PoolPlan(*(jnp.asarray(x) for x in (lead, shared, live, pos,
                                                   last)), MAX_LEN,
                       leads=leads)
    with jax.default_matmul_precision("highest"):
        return np.asarray(pa.pool_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), plan,
            window=window, block_sizes=blocks))


CASES = {
    # leaf slots (None: every slot), window, (own, lead) block positions
    "full": (None, None, (8, 8)),
    "full_window": (None, 12, (8, 16)),
    "ring_window": (32, 12, (8, 8)),
    "full_whole_blocks": (None, None, (32, 32)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_attends_what_the_xla_branch_attends(case):
    R, window, blocks = CASES[case]
    q, k, v, lead, shared, live, pos, last = _scenario(1)
    want = _xla_branch(q, _ring(k, last, R) if R else k,
                       _ring(v, last, R) if R else v, live, pos, last,
                       window, R)
    leaf_k, leaf_v = (_ring(x, last, R) if R else x for x in (k, v))
    got = _kernel(q, leaf_k, leaf_v, lead, shared, live, pos, last, window,
                  blocks)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # idle rows get zeros, live rows something
    assert not np.any(got[~live]) and np.all(np.abs(got[live]).sum(-1) > 0)


@pytest.mark.parametrize("case", ["full", "ring_window"])
def test_a_member_reads_its_shared_positions_from_its_lead(case):
    """Garbage in a member's own copy of what it shares moves the XLA
    branch's answer and not the kernel's: each is read from the lead."""
    R, window, blocks = CASES[case]
    q, k, v, lead, shared, live, pos, last = _scenario(2)
    spoilt_k, spoilt_v = k.copy(), v.copy()
    for b, (pad, *_rest) in ROWS.items():
        if lead[b] != b:
            spoilt_k[b, pad:pad + shared[b]] = 50.0
            spoilt_v[b, pad:pad + shared[b]] = -50.0

    def leaf(x):
        return _ring(x, last, R) if R else x

    clean = _kernel(q, leaf(k), leaf(v), lead, shared, live, pos, last,
                    window, blocks)
    spoilt = _kernel(q, leaf(spoilt_k), leaf(spoilt_v), lead, shared, live,
                     pos, last, window, blocks)
    np.testing.assert_allclose(spoilt, clean, atol=TOL, rtol=0)
    moved = _xla_branch(q, leaf(spoilt_k), leaf(spoilt_v), live, pos, last,
                        window, R)
    members = [b for b in ROWS if lead[b] != b]
    assert np.abs(moved[members] - clean[members]).max() > 1.0


def test_one_kv_head_is_read_as_a_row_of_positions():
    """One KV head (``[B, L, D]`` view), and every row its own lead: with
    the lead kernel, and without it (a plan that can name no lead)."""
    q, k, v, lead, shared, live, pos, last = _scenario(3, hkv=1)
    lone, none = np.arange(B, dtype=np.int32), np.zeros(B, np.int32)
    want = _xla_branch(q, k, v, live, pos, last, None)
    for lead_, shared_, leads in ((lead, shared, True), (lone, none, True),
                                  (lone, none, False)):
        got = _kernel(q, k, v, lead_, shared_, live, pos, last, None, (8, 8),
                      leads)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_host_counts_what_the_program_fetches():
    """``fetched_positions`` (the scheduler's count) against the work lists
    the program makes: the blocks of every grid step of both kernels."""
    q, k, v, lead, shared, live, pos, last = _scenario(4)
    for L, window in ((MAX_LEN, None), (32, 12)):
        own, _, lead_items, _ = pa.PoolPlan(
            *(jnp.asarray(x) for x in (lead, shared, live, pos, last)),
            MAX_LEN).tables(L, window, 8, 16)
        n_own, n_lead = int(own[-1]), int(lead_items[-1])
        want = max(n_own, 1) * pa.tile_rows(B) * 8 + max(n_lead, 1) * 16
        got = pa.fetched_positions(lead, shared, live, pos, last, L=L,
                                   window=window, own_blk=8, lead_blk=16)
        assert got == want and n_lead > 0 and n_own > 0


def test_the_blocks_divide_the_leaf_and_bound_the_scores():
    # Trinity's full leaf and ring, Mistral's and Qwen2's rows of 384
    assert pa.blocks(64, 8192, 128, 2, 8) == (128, 1024)
    assert pa.blocks(64, 3072, 128, 2, 8) == (128, 1024)
    assert pa.blocks(48, 384, 1024, 2, 4) == (32, 128)
    assert pa.blocks(96, 384, 512, 2, 7) == (64, 128)


def test_the_lead_read_share_file_reads_the_counters():
    import json
    import os

    from fairness_llm_tpu.utils.profiling import ServingStats
    from perfbench import run as bench_run

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "decode_lead_read_share.req")
    assert entry == {
        "name": "decode_lead_read_share.req", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "step programs",
        "moves": "req_per_s",
        "workloads": ["mistral-7b-int8.sweep", "qwen2-7b-int8.sweep",
                      "trinity-mini-int8.catalog8k"]}
    read, args = bench_run._reader("perfbench", entry["name"])

    class Ctx:
        cfg = {}

        def __init__(self, counters):
            self.window = type("W", (), {"counters": counters})

    stats = ServingStats(kv_positions_attended=8000,
                         kv_positions_from_lead=7600)
    assert read(Ctx(stats.as_dict()), **args) == pytest.approx(95.0)
    # the parent's stats have no such counter: the line leaves the metric out
    assert read(Ctx({"decoded_tokens": 10}), **args) is None


@pytest.mark.parametrize("seed", range(6))
def test_random_plans_attend_what_the_xla_branch_attends(seed):
    """Pools of 8-16 rows with random pads, lengths, idle rows, one or two
    groups whose members share random prefixes, a ring or none, a window or
    none, blocks of 8-32 positions."""
    rng = np.random.default_rng(seed)
    b, hkv, rep, d = (int(rng.choice(x)) for x in ([8, 12, 16], [1, 2],
                                                    [1, 2, 8], [16]))
    ring = int(rng.choice([0, 32]))
    window = 12 if ring else (int(rng.integers(4, 20))
                              if rng.random() < 0.5 else None)
    k = rng.normal(size=(b, MAX_LEN, hkv, d)).astype(np.float32)
    v = rng.normal(size=(b, MAX_LEN, hkv, d)).astype(np.float32)
    q = rng.normal(size=(b, hkv * rep, d)).astype(np.float32)
    live = rng.random(b) < 0.7
    live[0] = True
    n = rng.integers(5, 30, size=b)
    pad = rng.integers(0, 10, size=b)
    pos = (n + rng.integers(0, 20, size=b)).astype(np.int32)
    last = (pad + pos).astype(np.int32)
    lead = np.arange(b, dtype=np.int32)
    shared = np.zeros(b, np.int32)
    rows = rng.permutation(np.flatnonzero(live))
    for group in (rows[0::2], rows[1::2]):
        if len(group) < 2:
            continue
        head = min(group, key=lambda r: (pos[r], r))
        shared[head] = n[head]
        for r in group:
            if r != head:
                lead[r] = head
                shared[r] = rng.integers(1, min(n[r], n[head]) + 1)
                k[r, pad[r]:pad[r] + shared[r]] = \
                    k[head, pad[head]:pad[head] + shared[r]]
                v[r, pad[r]:pad[r] + shared[r]] = \
                    v[head, pad[head]:pad[head] + shared[r]]

    def leaf(x):
        if not ring:
            return x
        out = np.zeros((b, ring) + x.shape[2:], x.dtype)
        for r in range(b):
            for j in range(max(0, last[r] - ring + 1), last[r] + 1):
                out[r, j % ring] = x[r, j]
        return out

    kk, vv = leaf(k), leaf(v)
    slots = np.arange(kk.shape[1])[None, :]
    held = np.broadcast_to(slots, (b, kk.shape[1])) if not ring else \
        last[:, None] - np.mod(last[:, None] - slots, ring)
    ok = (held >= pad[:, None]) & (held <= last[:, None])
    if window is not None:
        ok &= (pos[:, None] - (held - pad[:, None])) < window
    s = np.einsum("bhd,blhd->bhl", q, np.repeat(kk, rep, axis=2)) * d ** -0.5
    s = np.where(ok[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    want = np.einsum("bhl,blhd->bhd", p / p.sum(axis=-1, keepdims=True),
                     np.repeat(vv, rep, axis=2))
    want = np.where(live[:, None, None], want, 0.0)
    plan = pa.PoolPlan(*(jnp.asarray(x) for x in (lead, shared, live, pos,
                                                   last)), MAX_LEN)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(pa.pool_attention(
            jnp.asarray(q), jnp.asarray(kk), jnp.asarray(vv), plan,
            window=window, block_sizes=(int(rng.choice([8, 16])),
                                        int(rng.choice([8, 16, 32])))))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
