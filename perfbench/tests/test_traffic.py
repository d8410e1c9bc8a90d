"""The stratified generator: a seed permutes, it does not resample."""

import os

import numpy as np

from perfbench.lib import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIX = os.path.join(os.path.dirname(HERE), "traffic")


def _open(seed, rate=3.3):
    mix = traffic.load_mix(os.path.join(MIX, "replay.json"),
                           {"rate_per_s": rate})
    return mix, traffic.open_schedule(mix, seed, 51, 152064)


def test_two_seeds_same_counts_and_lengths_other_order():
    mix, a = _open(11)
    _, b = _open(2**31 + 12345)
    per_s = lambda rs: np.bincount([int(r.due_s) for r in rs])  # noqa: E731
    assert (per_s(a) == per_s(b)).all()
    la, lb = [len(r.ids) for r in a], [len(r.ids) for r in b]
    assert sorted(la) == sorted(lb) and la != lb
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))


def test_fraction_is_carried_not_drawn():
    counts = traffic.stratum_counts(3.3, 1.0, 10)
    assert counts == [3, 3, 3, 4, 3, 3, 4, 3, 3, 4] and sum(counts) == 33


def test_same_seed_same_bytes():
    _, a = _open(7)
    _, b = _open(7)
    assert b"".join(r.to_bytes() for r in a) == \
        b"".join(r.to_bytes() for r in b)
    mix = traffic.load_mix(os.path.join(MIX, "sweep.json"))
    p = traffic.closed_pass(mix, 7, 3, 32000)
    q = traffic.closed_pass(mix, 7, 3, 32000)
    assert b"".join(r.to_bytes() for r in p) == \
        b"".join(r.to_bytes() for r in q)


def test_closed_pass_shape_and_sharing():
    mix = traffic.load_mix(os.path.join(MIX, "sweep.json"))
    p = traffic.closed_pass(mix, 5, 0, 32000)
    q = traffic.closed_pass(mix, 6, 0, 32000)
    assert len(p) == 45
    assert sorted(len(r.ids) for r in p) == sorted(len(r.ids) for r in q)
    shared = int(round(0.905 * 175))
    assert all((r.ids[:shared] == p[0].ids[:shared]).all() for r in p)
    assert len({tuple(r.ids[shared:shared + 8]) for r in p}) == 45
    assert all(r.ids.min() >= traffic.FIRST_DRAWN_ID for r in p)
    assert traffic.closed_pass(mix, 5, 1, 32000)[0].ids[0] != p[0].ids[0]
