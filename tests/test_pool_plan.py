"""The scheduler's plan for the slot pool's decode attention
(``scheduler._decode_plan``, ``ops/pool_attention.py``), through the
window-hybrid toy (rings of 128 under a window of 16 beside full layers) and
the dense toy, float32, the kernels in Pallas interpret mode.

A donor row, then three riders that copy its leading windows; the rider at
the smallest position is the group's lead and finishes first, so a later
plan names another member; an unrelated row then takes its recycled slot.
Every plan is checked against the pool as the step will read it: what a
member shares with its lead is the same bits in both rows."""

import contextlib
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.config import ModelSettings, ServingConfig  # noqa: E402
from fairness_llm_tpu.ops import pool_attention  # noqa: E402
from fairness_llm_tpu.serving import ContinuousScheduler  # noqa: E402
from fairness_llm_tpu.utils.profiling import ServingStats  # noqa: E402
from toys import IdPrompts, assert_same_tokens, toy_engine  # noqa: E402

NEW = 24
GREEDY = ModelSettings(temperature=0.0, max_tokens=NEW)
SHORT = ModelSettings(temperature=0.0, max_tokens=5)
TOYS = {"window": "trinity-mini-int8", "dense": "mistral-7b-int8"}


def _serve(engine, cut, kernel: bool):
    """The traffic through a 4-slot scheduler: (tokens by request, the
    stats, the plans as ``(live slots, lead, shared, slot states, the
    pool's leaves)``, copied positions by slot and request)."""
    serving = ServingConfig(enabled=True, num_slots=4, queue_capacity=32,
                            max_prompt_len=384, max_new_tokens=NEW,
                            decode_chunk=4, prefill_group=4)
    sched = ContinuousScheduler(engine, serving, settings=GREEDY)
    assert sched._pool_spans == kernel
    plans, copied = [], {}
    make_plan, plan_prefill = sched._decode_plan, sched._plan_prefill

    def watched_plan(live_ids):
        lead, shared = make_plan(live_ids)
        states = {s: sched.pool.get(s) for s in live_ids}
        leaves = [[np.asarray(x) for x in jax.tree.leaves(layer)[:2]]
                  for layer in sched._cache.layers]
        plans.append((list(live_ids), lead, shared,
                      {s: (st.request.id, st.root, st.root_shared,
                           st.real_len + st.emitted, st.base - st.real_len)
                       for s, st in states.items()}, leaves))
        return lead, shared

    def watched_prefill(admitted):
        plan = plan_prefill(admitted)
        for r, (slot, req) in enumerate(zip(plan["slots"], plan["reqs"])):
            copied[req.id] = max(int(c["args"][6][r]) for c in plan["calls"])
        return plan

    sched._decode_plan = watched_plan
    sched._plan_prefill = watched_prefill
    prompts = IdPrompts(engine, cut, 42, GREEDY, tag="p")
    donor = prompts.draw(150)
    stats = ServingStats(num_slots=4)
    reqs = [prompts.request(donor)]
    assert sched.submit(reqs[0])
    sched._admit(stats)
    sched._decode(stats)
    riders = [(donor[:140], 8, SHORT), (donor[:140], 12, GREEDY),
              (donor[:130], 25, GREEDY)]
    for head, n, settings in riders:
        reqs.append(prompts.request(np.concatenate([head, prompts.draw(n)]),
                                    settings))
        assert sched.submit(reqs[-1])
    late = prompts.request(prompts.draw(60))
    while sched.has_work or late is not None:
        if late is not None and sched.pool.free_count:
            assert sched.submit(late)
            reqs.append(late)
            late = None
        sched._iterate(stats)
    sched.finish_stats(stats)
    tokens = {r.id: np.asarray(sched.take_result(r.id).tokens) for r in reqs}
    return tokens, stats, plans, copied, sched


@pytest.fixture(scope="module", params=sorted(TOYS))
def runs(request):
    with jax.default_matmul_precision("highest"):
        engine, cut = toy_engine(TOYS[request.param], 7)
        xla = _serve(engine, cut, kernel=False)
        with pool_attention.interpreted():
            kernel = _serve(engine, cut, kernel=True)
    return engine, cut, xla, kernel


def _members(plan):
    live, lead, shared, states, _ = plan
    return [(s, int(lead[s]), int(shared[s])) for s in live if lead[s] != s]


def test_greedy_tokens_through_the_kernel_are_the_xla_paths(runs):
    engine, cut, xla, kernel = runs
    assert sorted(xla[0]) == sorted(kernel[0])
    for name, toks in kernel[0].items():
        ids = engine.tokenizer.encode(name)
        with jax.default_matmul_precision("highest"):
            assert_same_tokens(engine, name, ids, toks, xla[0][name])
    # the same positions attended; only the kernel reads through a lead
    assert kernel[1].kv_positions_attended == xla[1].kv_positions_attended
    assert xla[1].kv_positions_from_lead == 0
    assert 0 < kernel[1].kv_positions_from_lead < \
        kernel[1].kv_positions_attended
    # fetched: blocks of live rows' spans, against every slot's whole rows
    # (the window toy's rings of 128 are a block or two: no fewer bytes)
    assert 0 < kernel[1].kv_bytes_stepped
    if kernel[4]._rings == []:
        assert kernel[1].kv_bytes_stepped < xla[1].kv_bytes_stepped / 2


def test_shared_never_exceeds_what_the_row_copied_and_is_the_leads_bits(runs):
    engine, cut, xla, kernel = runs
    plans, copied, sched = kernel[2], kernel[3], kernel[4]
    windows = engine.config.layer_windows
    checked = 0
    for plan in plans:
        live, lead, shared, states, leaves = plan
        for s, ld, n in _members(plan):
            rid, root, held, pos, pad = states[s]
            # a rider holds no more than it copied; the row that copied
            # nothing (the donor) holds its own prompt
            assert 0 < n <= held, (rid, n, held)
            assert held <= copied[rid] or copied[rid] == 0, (rid, held)
            assert states[ld][1] == root and lead[ld] == ld
            assert shared[ld] >= n
            l_pad = states[ld][4]
            for w, (k, v) in zip(windows, leaves):
                L = k.shape[1]
                lo = 0 if L == sched.cache_len else max(0, pos - w + 1)
                p = np.arange(lo, n)
                mine, theirs = (pad + p) % L, (l_pad + p) % L
                for x in (k, v):
                    np.testing.assert_array_equal(x[s, mine], x[ld, theirs])
                checked += len(p)
    assert checked > 0


def test_a_lead_that_finishes_is_replaced_by_a_live_member(runs):
    plans = runs[3][2]
    leads_seen = []
    for plan in plans:
        live, lead, shared, states, _ = plan
        heads = {ld for _, ld, _ in _members(plan)}
        leads_seen.append({states[h][0] for h in heads})
    # the first lead (the shortest rider) leaves, and a later plan's group
    # is led by another of its members
    first = next(s for s in leads_seen if s)
    gone = next(iter(first))
    later = [s for s in leads_seen if s and gone not in s]
    assert later, leads_seen
    assert any(not any(states[s][0] == gone for s in live)
               for live, _, _, states, _ in plans)


def test_a_released_or_recycled_slot_is_never_a_lead(runs):
    plans = runs[3][2]
    recycled = False
    for plan in plans:
        live, lead, shared, states, _ = plan
        for s in live:
            assert int(lead[s]) in live
            assert states[int(lead[s])][1] == states[s][1]
        for s in set(range(len(lead))) - set(live):
            assert lead[s] == s and shared[s] == 0
        recycled |= any(states[s][0] == "p4" for s in live)
    assert recycled


def test_a_model_that_keeps_a_row_state_reads_only_its_own_rows():
    """The linear-hybrid toy takes no donor, so every plan names each row
    its own lead and no lead kernel runs; the tokens are the XLA path's."""
    with jax.default_matmul_precision("highest"):
        engine, cut = toy_engine("solar-open2-int8", 7)
        got = {}
        for kernel in (False, True):
            serving = ServingConfig(enabled=True, num_slots=4,
                                    queue_capacity=8, max_prompt_len=256,
                                    max_new_tokens=8, decode_chunk=4,
                                    prefill_group=4)
            with pool_attention.interpreted() if kernel else \
                    contextlib.nullcontext():
                sched = ContinuousScheduler(engine, serving, settings=GREEDY)
                assert sched._pool_spans == kernel
                plans = []
                make_plan = sched._decode_plan
                sched._decode_plan = lambda live: plans.append(
                    make_plan(live)) or plans[-1]
                prompts = IdPrompts(engine, cut, 43, GREEDY, tag="s")
                head = prompts.draw(90)
                reqs = [prompts.request(np.concatenate([head, prompts.draw(n)]))
                        for n in (5, 9, 20)]
                results = sched.serve(reqs)
            got[kernel] = ({r.id: np.asarray(r.tokens) for r in results},
                           sched.last_stats, plans)
    (xla, _, _), (toks, stats, plans) = got[False], got[True]
    assert plans and all((lead == np.arange(4)).all() and not shared.any()
                         for lead, shared in plans)
    assert stats.kv_positions_from_lead == 0 < stats.kv_positions_attended
    for name, served in toks.items():
        with jax.default_matmul_precision("highest"):
            assert_same_tokens(engine, name, engine.tokenizer.encode(name),
                               served, xla[name])
