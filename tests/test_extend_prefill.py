"""Prefill appends to what a row's cache already holds (ISSUE 29): the
contiguous ``serve_prefill`` is an extend program of one window, and admission
copies a shared prefix from a live row.

The program runs the benchmark families' toys (``dense_gqa`` from Mistral's
file, ``latent_moe`` from A.X-K1's) in float32 on int8 weights made from a
seed, with prompts longer than the window, so what is compared is the
mathematics: a row extended window by window, and a row that copied its
prefix and extended once, against ONE forward over the whole prompt and
against ``DecodeEngine`` alone. Readings are float32 rounding (about 1e-6 on
logits of size 1); the tolerance is 1e-5. Tokens are compared between two
different programs, so a disagreement counts only where the whole-prompt
logits do not call it a near-tie (ROADMAP D9)."""

import importlib.util
import json
import os
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toys import (
    ROOT,
    IdPrompts,
    alone,
    assert_same_tokens,
    prefill_aliasing,
    prefill_fault,
    toy_engine,
    whole_prompt_logits as _whole_prompt_logits,
)

from fairness_llm_tpu.config import (  # noqa: E402
    FleetConfig,
    ModelSettings,
    ServingConfig,
)
from fairness_llm_tpu.models.tokenizer import ByteTokenizer  # noqa: E402
from fairness_llm_tpu.runtime import stepbuilder  # noqa: E402
from fairness_llm_tpu.runtime.stepbuilder import (  # noqa: E402
    EXTEND_WINDOW,
    extend_window,
)
from fairness_llm_tpu.serving import (  # noqa: E402
    ContinuousScheduler,
    ReplicaSet,
    Request,
)
from fairness_llm_tpu.serving.slots import (  # noqa: E402
    LiveRowPrefixes,
    SlotPool,
    SlotState,
)
from fairness_llm_tpu.telemetry import use_registry  # noqa: E402
from fairness_llm_tpu.telemetry.timeline import use_timeline  # noqa: E402
from fairness_llm_tpu.utils.failures import ScriptedFaultInjector  # noqa: E402
from fairness_llm_tpu.utils.profiling import ServingStats  # noqa: E402

S = EXTEND_WINDOW
TOL = 1e-5
SEED = 29
NEW = 6
GREEDY = ModelSettings(temperature=0.0, max_tokens=NEW)


def _toy(config: str):
    return toy_engine(config, SEED)


@pytest.fixture(scope="module", params=["mistral-7b-int8", "ax-k1-int8"],
                ids=["dense_gqa", "latent_moe"])
def toy(request):
    with jax.default_matmul_precision("highest"):
        yield _toy(request.param)


def _scheduler(engine, **kw):
    serving = ServingConfig(**dict(dict(
        enabled=True, num_slots=4, queue_capacity=32, max_prompt_len=192,
        max_new_tokens=NEW, decode_chunk=4, prefill_group=2), **kw))
    return ContinuousScheduler(engine, serving, settings=GREEDY)


def _Prompts(engine, cut, seed=SEED, tag="r"):
    return IdPrompts(engine, cut, seed, GREEDY, tag)


def _assert_same_greedy(engine, name, served_tokens, ids):
    """The engine alone decodes the same tokens; where the two programs
    part, the whole-sequence logits must call the step a near-tie."""
    assert len(served_tokens) == NEW
    assert_same_tokens(engine, name, ids, served_tokens,
                       alone(engine, name, GREEDY, NEW))


def _slot_of(sched, name):
    return next(s for s in sched.pool.live_slots()
                if sched.pool.get(s).request.id == name)


# -- the program: window by window, and copy then extend ------------------------


def test_extended_and_copied_rows_carry_the_whole_prompts_logits(toy):
    engine, cut = toy
    with jax.default_matmul_precision("highest"):
        sched = _scheduler(engine)
        prompts = _Prompts(engine, cut)
        first = prompts.draw(150)  # bucket 192: windows 0, 1 and 2
        second = np.concatenate([first[:100], prompts.draw(40)])
        stats = ServingStats(num_slots=sched.num_slots)

        a = prompts.request(first)
        assert sched.submit(a)
        sched._admit(stats)
        assert (stats.prefill_batches, stats.prefill_tokens,
                stats.prefill_tokens_reused) == (3, 150, 0)
        carried = np.asarray(sched._prev_logits[_slot_of(sched, a.id)])
        assert np.abs(carried - _whole_prompt_logits(engine, first)).max() \
            < TOL

        # 100 ids shared, a tail of 40: 140 - 64 positions copied from the
        # first row (another left padding: 52 against 42), then one window.
        b = prompts.request(second)
        assert sched.submit(b)
        sched._admit(stats)
        assert (stats.prefill_batches, stats.prefill_tokens,
                stats.prefill_tokens_reused) == (4, 290, 140 - S)
        carried = np.asarray(sched._prev_logits[_slot_of(sched, b.id)])
        assert np.abs(carried - _whole_prompt_logits(engine, second)).max() \
            < TOL
        # the donor's own logits were not touched by the second call
        kept = np.asarray(sched._prev_logits[_slot_of(sched, a.id)])
        assert np.abs(kept - _whole_prompt_logits(engine, first)).max() < TOL

        sched._run_loop(stats)
        assert stats.completed == 2
        for req, ids in ((a, first), (b, second)):
            res = sched.take_result(req.id)
            assert res.ok and res.prompt_tokens == len(ids)
            _assert_same_greedy(engine, req.id, res.tokens, ids)


@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
def test_the_compiled_prefill_writes_the_pool_in_place(toy, guard):
    """``serve_prefill`` is donated as ``serve_step`` is: the compiled module
    aliases every leaf of the pool and the carried logits to an output and
    copies no whole leaf, so the extent's scatter writes where the pool is
    (ISSUE 34; the same question of the described v5e:
    ``tools/aot_preflight.py``'s ``pool_copies``)."""
    engine, _ = toy
    sched = _scheduler(engine)
    aliased, donated, copies = prefill_aliasing(sched, 2, 192, guard)
    assert aliased == donated and len(donated) >= 9
    assert copies == 0


# -- the plan's edges ---------------------------------------------------------------


@pytest.fixture(scope="module")
def dense():
    with jax.default_matmul_precision("highest"):
        engine, cut = _toy("mistral-7b-int8")
        yield engine, cut, _scheduler(engine)


def _no_live_sharer(p):
    # 150 ids in bucket 192, 42 of padding: windows 0, 1, 2
    return [[p.draw(150)]], dict(batches=3, tokens=150, reused=0)


def _one_id_short_of_a_boundary(p):
    # shares n - S - 1 = 85 ids: one window cannot hold the 65 that differ
    a = p.draw(150)
    b = np.concatenate([a[:85], p.draw(65)])
    return [[a], [b]], dict(batches=3 + 2, tokens=300, reused=150 - 2 * S)


def _replay_length_two_windows(p):
    # a tail of 90 ids, as the replay's longest prompts have
    a = p.draw(160)
    b = np.concatenate([a[:100], p.draw(90)])
    return [[a], [b]], dict(batches=3 + 2, tokens=350, reused=190 - 2 * S)


def _shorter_than_a_window(p):
    # n < S: nothing to copy into, the one window holds the whole prompt
    a = p.draw(150)
    b = np.concatenate([a[:30], p.draw(10)])
    return [[a], [b]], dict(batches=3 + 1, tokens=190, reused=0)


def _donor_from_earlier_in_the_iteration(p):
    # three rows arrive together, two to a batch: the third finds the first
    a = p.draw(150)
    b = p.draw(150)
    c = np.concatenate([a[:120], p.draw(30)])
    return [[a, b, c]], dict(batches=3 + 1, tokens=450, reused=150 - S)


def _donor_in_another_bucket(p):
    # donor 150 ids in bucket 192 (padding 42); the row 100 ids in bucket 128
    # (padding 28): the copy moves between paddings and buckets
    a = p.draw(150)
    b = np.concatenate([a[:90], p.draw(10)])
    return [[a], [b]], dict(batches=3 + 1, tokens=250, reused=100 - S)


def _never_a_slot_this_call_writes(p):
    # both rows of one batch share 120 ids and the pool is empty: neither
    # copies from the other
    a = p.draw(150)
    b = np.concatenate([a[:120], p.draw(30)])
    return [[a, b]], dict(batches=3, tokens=300, reused=0)


def _identical_prompt(p):
    # everything shared: the last window is still computed, for its logits
    a = p.draw(150)
    return [[a], [a.copy()]], dict(batches=3 + 1, tokens=300, reused=150 - S)


@pytest.mark.parametrize("case", [
    _no_live_sharer, _one_id_short_of_a_boundary, _replay_length_two_windows,
    _shorter_than_a_window, _donor_from_earlier_in_the_iteration,
    _donor_in_another_bucket, _never_a_slot_this_call_writes,
    _identical_prompt,
], ids=lambda f: f.__name__.strip("_"))
def test_the_plan_computes_only_what_no_live_row_holds(dense, case):
    """Each case: waves of prompts, a wave admitted while the waves before it
    are still live (their cap is not reached: they are submitted first and
    admitted by hand); then the counters say what the plan did, and every
    row's tokens are the engine's."""
    engine, cut, sched = dense
    with jax.default_matmul_precision("highest"):
        prompts = _Prompts(engine, cut, seed=zlib.crc32(case.__name__.encode()))
        waves, want = case(prompts)
        stats = ServingStats(num_slots=sched.num_slots)
        served = []
        for wave in waves:
            reqs = [prompts.request(ids) for ids in wave]
            for r in reqs:
                assert sched.submit(r)
            sched._admit(stats)
            served += list(zip(reqs, wave))
        assert (stats.prefill_batches, stats.prefill_tokens,
                stats.prefill_tokens_reused) == (
                    want["batches"], want["tokens"], want["reused"])
        for req, ids in served:
            carried = np.asarray(sched._prev_logits[_slot_of(sched, req.id)])
            assert np.abs(carried - _whole_prompt_logits(engine, ids)).max() \
                < TOL, req.id
        sched._run_loop(stats)
        assert stats.completed == len(served) and sched.pool.occupancy == 0
        for req, ids in served:
            _assert_same_greedy(engine, req.id,
                                sched.take_result(req.id).tokens, ids)


def test_a_failed_extend_call_releases_its_group_and_nothing_else(dense):
    """The second window of a batch raises before the call is made (what a
    trace or compile error does): the donated pool was never handed over,
    so the batch's slots are released and its requests retried; the row
    that was live before keeps decoding the engine's tokens, and the
    retried rows come out right too (their half-written slots were
    prefilled anew)."""
    engine, cut, sched = dense
    with jax.default_matmul_precision("highest"), use_registry() as reg:
        prompts = _Prompts(engine, cut, seed=7)
        ids = [prompts.draw(150), prompts.draw(140), prompts.draw(130)]
        reqs = [prompts.request(x) for x in ids]
        stats = ServingStats(num_slots=sched.num_slots)
        assert sched.submit(reqs[0])
        sched._admit(stats)
        live_before = sched.pool.live_slots()

        with prefill_fault(sched, at=2) as calls:
            for r in reqs[1:]:
                assert sched.submit(r)
            sched._admit_once(stats)
        assert len(calls) == 2 and stats.requeued == 2
        assert sched.pool.live_slots() == live_before
        assert stats.prefill_batches == 3 + 1  # the call that ran is counted
        assert stats.prefill_tokens == 150  # the failed batch admitted none
        assert reg.peek("device_state_rebuilds_total", component="serving",
                        stage="prefill") is None

        sched._run_loop(stats)
        assert stats.completed == 3 and stats.failed == 0
        for req, x in zip(reqs, ids):
            res = sched.take_result(req.id)
            assert res.ok
            _assert_same_greedy(engine, req.id, res.tokens, x)


def test_a_call_that_fails_after_launch_takes_every_live_row_with_it(dense):
    """The second window of a batch raises once the call has consumed the
    donated pool: the scheduler asks the arrays, finds them deleted, and
    does what a failed decode chunk does: the row that was live before
    goes back to the queue with the batch, the pool and the carried logits
    are built anew, and every request still finishes with the engine's
    tokens."""
    engine, cut, sched = dense
    with jax.default_matmul_precision("highest"), use_registry() as reg:
        prompts = _Prompts(engine, cut, seed=8, tag="late")
        ids = [prompts.draw(150), prompts.draw(140), prompts.draw(130)]
        reqs = [prompts.request(x) for x in ids]
        stats = ServingStats(num_slots=sched.num_slots)
        assert sched.submit(reqs[0])
        sched._admit(stats)

        with prefill_fault(sched, at=2, how="launched") as calls:
            for r in reqs[1:]:
                assert sched.submit(r)
            sched._admit_once(stats)
        assert len(calls) == 2 and stats.requeued == 3
        assert sched.pool.live_slots() == [] and len(sched.queue) == 3
        assert not sched._device_state_deleted()
        assert not np.asarray(sched._cache.key_valid).any()
        assert reg.peek("device_state_rebuilds_total", component="serving",
                        stage="prefill").value == 1
        assert reg.peek("faults_total", component="serving", kind="device",
                        stage="prefill").value == 1

        sched._run_loop(stats)
        assert stats.completed == 3 and stats.failed == 0
        for req, x in zip(reqs, ids):
            res = sched.take_result(req.id)
            assert res.ok and res.retries == 1
            _assert_same_greedy(engine, req.id, res.tokens, x)


def test_a_batchs_windows_are_dispatched_without_a_wait_between_them(
        dense, monkeypatch):
    """Three windows of one donorless batch: each call is handed the pool
    the one before it returned, so the host waits for none of them (no
    ``block_until_ready`` between the dispatches), and each dispatch span
    says the call was donated."""
    engine, cut, sched = dense
    waits = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(1) or x)
    with jax.default_matmul_precision("highest"), use_timeline() as tl:
        prompts = _Prompts(engine, cut, seed=9, tag="win")
        ids = prompts.draw(150)
        req = prompts.request(ids)
        stats = ServingStats(num_slots=sched.num_slots)
        assert sched.submit(req)
        sched._admit_once(stats)
        spans = [e for e in tl.events() if e["type"] == "span"
                 and e["name"] == "sched.prefill.dispatch"]
        assert stats.prefill_batches == 3 and waits == []
        assert [e["args"]["w"] for e in spans] == [0, S, 2 * S]
        assert all(e["args"]["donated"] is True for e in spans)
        _drained(sched, stats, 1)
        _assert_same_greedy(engine, req.id, sched.take_result(req.id).tokens,
                            ids)


def test_admission_runs_no_program_but_its_own(dense, caplog):
    """What the benchmark's trace matching relies on: between two decode
    chunks the device runs the named step programs and nothing else (a NumPy
    scalar handed to ``jnp.asarray`` is a ``convert_element_type`` program of
    its own; a 0-d array is a transfer)."""
    import logging
    import re

    engine, cut, sched = dense
    with jax.default_matmul_precision("highest"):
        prompts = _Prompts(engine, cut, seed=11)
        a = prompts.draw(150)
        reqs = [prompts.request(x) for x in
                (a, np.concatenate([a[:100], prompts.draw(40)]))]
        jax.clear_caches()  # every program this loop runs compiles again
        with jax.log_compiles(), caplog.at_level(logging.WARNING, "jax"):
            stats = ServingStats(num_slots=sched.num_slots)
            for r in reqs:
                assert sched.submit(r)
                sched._admit(stats)
            sched._run_loop(stats)
        assert stats.completed == 2 and stats.prefill_tokens_reused == 140 - S
    compiled = set(re.findall(r"Compiling jit\((\w+)\)", caplog.text))
    assert compiled == {"serve_prefill_1x192", "serve_step"}, compiled


# -- a long bucket: one row ahead, alone, in a wide window (ISSUE 36) -----------------

LONG = 1024  # 16 windows: the first bucket ``stepbuilder.long_bucket`` names
WIDE = 512


@pytest.fixture(scope="module")
def long_dense():
    """The dense toy behind a pool of 8 slots x a bucket of 1024, four rows
    to a batch: its programs are ``1 x 1024`` and ``4 x 1024``, each for a
    batch that copies (window 64) and for one that does not (window 512)."""
    with jax.default_matmul_precision("highest"):
        engine, cut = _toy("mistral-7b-int8")
        yield engine, cut, _scheduler(engine, num_slots=8, prefill_group=4,
                                      max_prompt_len=LONG)


def _plans(tl):
    return [e["args"] for e in tl.events() if e["type"] == "span"
            and e["name"] == "sched.admit.plan"]


def _plan_rows(tl):
    return [(p["rows"], p["nb"], p["S"], p["donors"], p["deferred"],
             p["windows"]) for p in _plans(tl)]


def _near_duplicates(p):
    # six prompts of 1000-1005 ids of which 990 are common: the first goes
    # ahead in two windows of 512, the five others copy all but their last
    # window from it, four and then one to a batch
    rows = _family(p, 6, stem=990, tails=(10, 15))
    return rows, dict(
        plans=[(1, 1, WIDE, 0, 3, 2), (4, 4, S, 4, 0, 1), (1, 1, S, 1, 0, 1)],
        leaders=1, deferred=3, batches=4,
        reused=sum(len(r) - S for r in rows[1:]))


def _unrelated(p):
    # nothing shared: nobody waits, and the batch of three that copies
    # nothing runs the wide window in the group's shape
    rows = [p.draw(n) for n in (1000, 990, 980)]
    return rows, dict(plans=[(3, 4, WIDE, 0, 0, 2)], leaders=1, deferred=0,
                      batches=2, reused=0)


def _two_groups(p):
    # two families in one batch: a row of each goes ahead, in one batch
    a, b = (_family(p, 2, stem=990, tails=(10, 12)) for _ in range(2))
    rows = [a[0], b[0], a[1], b[1]]
    return rows, dict(plans=[(2, 4, WIDE, 0, 2, 2), (2, 4, S, 2, 0, 1)],
                      leaders=1, deferred=2, batches=3,
                      reused=len(a[1]) + len(b[1]) - 2 * S)


def _a_stem_too_short_to_save_a_window(p):
    # 30 ids shared of 1000: the second row could copy none of its windows
    # from the first, so it does not wait for it
    a = p.draw(1000)
    rows = [a, np.concatenate([a[:30], p.draw(970)])]
    return rows, dict(plans=[(2, 4, WIDE, 0, 0, 2)], leaders=1, deferred=0,
                      batches=2, reused=0)


def _a_short_fellow(p):
    # the fellow shares 200 ids of its 230 with the row ahead: it waits,
    # copies 166 and runs one window; the batch's bucket is the leader's
    a = p.draw(1000)
    rows = [a, np.concatenate([a[:200], p.draw(30)])]
    return rows, dict(plans=[(1, 1, WIDE, 0, 1, 2), (1, 1, S, 1, 0, 1)],
                      leaders=1, deferred=1, batches=3, reused=230 - S)


@pytest.mark.parametrize("case", [
    _near_duplicates, _unrelated, _two_groups,
    _a_stem_too_short_to_save_a_window, _a_short_fellow,
], ids=lambda f: f.__name__.strip("_"))
def test_in_a_long_bucket_a_donorless_row_goes_ahead_and_its_fellows_copy(
        long_dense, case):
    """A pass arrives at once in an empty pool. What the plans, the span and
    the counters say; then every row carries the whole prompt's logits and
    decodes the engine's tokens."""
    engine, cut, sched = long_dense
    with jax.default_matmul_precision("highest"), use_timeline() as tl:
        prompts = _Prompts(engine, cut, tag=case.__name__,
                           seed=zlib.crc32(case.__name__.encode()))
        rows, want = case(prompts)
        reqs = [prompts.request(ids) for ids in rows]
        stats = ServingStats(num_slots=sched.num_slots)
        for r in reqs:
            assert sched.submit(r)
        sched._admit(stats)
        assert _plan_rows(tl) == want["plans"]
        assert (stats.prefill_leader_batches, stats.prefill_rows_deferred,
                stats.prefill_batches, stats.prefill_tokens_reused) == (
            want["leaders"], want["deferred"], want["batches"],
            want["reused"])
        assert stats.admitted == len(rows) and len(sched.queue) == 0
        assert stats.prefill_tokens == sum(map(len, rows))
        for req, ids in zip(reqs, rows):
            carried = np.asarray(sched._prev_logits[_slot_of(sched, req.id)])
            assert np.abs(carried - _whole_prompt_logits(engine, ids)).max() \
                < TOL, req.id
        _drained(sched, stats, len(rows))
        for req, ids in zip(reqs, rows):
            _assert_same_greedy(engine, req.id,
                                sched.take_result(req.id).tokens, ids)


def test_rows_that_wait_keep_their_place_their_deadline_and_their_wait(
        long_dense):
    """Six fellows and four to a batch: the three that wait for the first go
    back in front of the two that were not popped, in their order, not
    admitted and without a slot; their ``queue_wait`` runs until their own
    batch; one whose deadline passes while it waits expires, unprefilled."""
    engine, cut, sched = long_dense
    with jax.default_matmul_precision("highest"):
        prompts = _Prompts(engine, cut, seed=36, tag="wait")
        rows = _family(prompts, 6, stem=990, tails=(10, 15))
        reqs = [prompts.request(ids) for ids in rows]
        reqs[2].deadline_s = 0.2
        stats = ServingStats(num_slots=sched.num_slots)
        for r in reqs:
            assert sched.submit(r)
        stamps = [r.submitted_at for r in reqs]
        sched._admit_once(stats)
        assert [r.id for r in sched.queue._q] == [r.id for r in reqs[1:]]
        assert [r.submitted_at for r in reqs] == stamps
        assert stats.admitted == 1 and sched.pool.occupancy == 1
        assert (stats.prefill_leader_batches, stats.prefill_rows_deferred) \
            == (1, 3)
        events = {r.id: [e.event for e in sched.tracer.events(r.id)]
                  for r in reqs}
        assert "admitted" in events[reqs[0].id]
        assert all("admitted" not in events[r.id] for r in reqs[1:])
        time.sleep(0.25)
        sched._admit_once(stats)
        assert stats.expired == 1 and stats.admitted == 4
        assert [r.id for r in sched.queue._q] == [reqs[5].id]
        sched._run_loop(stats)
        assert stats.completed == 5 and stats.prefill_rows_deferred == 3
        gone = sched.take_result(reqs[2].id)
        assert not gone.ok and gone.finish_reason == "deadline"
        waits = [sched.take_result(r.id).queue_wait_s
                 for r in reqs[:2] + reqs[3:]]
        # the row that went ahead, then its fellows in their order
        assert waits[0] < waits[1] <= waits[4] and waits[1] >= 0.25


@pytest.mark.parametrize("how", ["unlaunched", "launched"])
def test_a_failed_call_of_the_row_ahead_loses_none_of_its_fellows(
        long_dense, how):
    """The second wide window of the row that went ahead fails: that row is
    requeued in front of the fellows that waited for it, goes ahead again,
    and all of them finish with the engine's tokens. Where the call had
    consumed the pool it is built anew, and nothing else changes."""
    engine, cut, sched = long_dense
    with jax.default_matmul_precision("highest"):
        prompts = _Prompts(engine, cut, seed=37, tag=how)
        rows = _family(prompts, 4, stem=990, tails=(10, 13))
        reqs = [prompts.request(ids) for ids in rows]
        stats = ServingStats(num_slots=sched.num_slots)
        for r in reqs:
            assert sched.submit(r)
        with prefill_fault(sched, at=2, how=how) as calls:
            sched._admit_once(stats)
        assert len(calls) == 2 and stats.requeued == 1
        assert [r.id for r in sched.queue._q] == [r.id for r in reqs]
        assert sched.pool.occupancy == 0 and stats.admitted == 0
        assert (stats.prefill_leader_batches, stats.prefill_rows_deferred) \
            == (0, 3)
        sched._admit(stats)
        assert (stats.prefill_leader_batches, stats.prefill_rows_deferred) \
            == (1, 6)
        assert stats.prefill_tokens_reused == sum(
            len(r) - S for r in rows[1:])
        _drained(sched, stats, 4)
        for req, ids in zip(reqs, rows):
            res = sched.take_result(req.id)
            assert res.ok and res.retries == (req is reqs[0])
            _assert_same_greedy(engine, req.id, res.tokens, ids)


@pytest.mark.parametrize("rings, P, copies, want", [
    ([(2048, 3072)], 8064, False, 384),  # the cell: 21 calls, 8 to a ring
    ([(2048, 3072)], 8064, True, S),
    ([], 8064, False, 448),              # 18 x 448; 512 does not divide it
    ([], 1024, False, 512),
    ([], 1024, True, S),
    ([], 960, False, S),                 # 15 windows: not a long bucket
    ([(16, 128)], 1024, False, S),       # 128 - 16 + 1 holds one window
    ([(256, 384)], 1024, False, 128),
    ([(256, 384)], 1152, False, 128),    # 384 divides both; too wide a write
    ([(4096, 6144)], 6144, False, 512),
    ([(256, 384), (2048, 3072)], 3072, False, 128),
], ids=str)
def test_the_window_of_a_batch_that_copies_nothing(rings, P, copies, want):
    """The widest multiple of 64 up to 512 that divides the bucket and every
    ring and leaves a call's first query its whole window; 64 where a row
    copies and in every bucket under ``DONOR_COND_WINDOWS`` windows."""
    from fairness_llm_tpu.models.configs import get_model_config

    got = extend_window(get_model_config("tiny-test"), P, copies, rings)
    assert got == want and P % got == 0
    assert all(R % got == 0 and got <= R - w + 1 for w, R in rings)


def test_the_program_of_a_batch_that_copies_nothing_builds_no_donor_rows(
        long_dense):
    """The two programs of the long bucket as lowered: the one that copies
    branches on "a row of this call copies" and takes 64 positions a row;
    the other takes 512, has no branch, and still writes the pool in
    place. Their compile keys differ; a short bucket's key is as it was."""
    engine, cut, sched = long_dense
    row = jnp.zeros((1,), jnp.int32)

    def lowered(copies, width):
        return sched._prefill_fn(1, LONG, False, copies)._jit.lower(
            engine.params, sched._cache, sched._prev_logits,
            jnp.zeros((1, width), jnp.int32), jnp.zeros((1, width), bool),
            row, row, row, row, row, jnp.zeros((), jnp.int32)).as_text()

    assert "stablehlo.case" in lowered(True, S)
    assert "stablehlo.case" not in lowered(False, WIDE)
    aliased, donated, copies = prefill_aliasing(sched, 1, LONG, copies=False)
    assert aliased == donated and copies == 0
    key = stepbuilder.compile_key
    assert key("serve_prefill", nb=1, P=LONG, guard=False) \
        == ("serve_prefill", 1, LONG, False)
    assert key("serve_prefill", nb=1, P=LONG, guard=False, copies=False) \
        == ("serve_prefill", 1, LONG, False, ("copies", False))


# -- the bypass: under 16 windows, and where a row keeps a state -----------------------


def _parents_plan(sched, admitted):
    """``_plan_prefill`` as it stood before ISSUE 36, from the same pool:
    (nb, P, [(w, rows, the call's eight arguments)]). Claims no slot: the
    rows take the lowest free slots in their order."""
    tok = sched.engine.tokenizer
    P = max(item[2] for item in admitted)
    W = P if sched._row_state_bytes else EXTEND_WINDOW
    n_rows = len(admitted)
    nb = 1
    while nb < n_rows:
        nb *= 2
    nb = min(nb, max(sched.serving.prefill_group, n_rows))
    live = LiveRowPrefixes(sched.pool)
    tokens = np.full((nb, P), tok.pad_id, np.int32)
    lead = np.full((nb,), P, np.int32)
    donor, donor_pad, copied = (np.zeros((nb,), np.int32) for _ in range(3))
    first = np.full((nb,), P // W, np.int32)
    for r, (_, ids, _) in enumerate(admitted):
        ids = np.asarray(ids[-P:], np.int32)
        n = len(ids)
        tokens[r, P - n:] = ids
        lead[r] = P - n
        first[r] = min((P - n) // W, P // W - 1)
        for src, m in live.by_shared(ids):
            k = max(1, -(-(n - m) // W))
            if n - k * W <= 0:
                break
            st = sched.pool.get(src)
            if not sched._ring_holds(st, n - k * W):
                continue
            donor[r], donor_pad[r] = src, st.base - st.real_len
            copied[r], first[r] = n - k * W, P // W - k
            break
    slot_ids = np.full((nb,), sched.num_slots, np.int32)
    slot_ids[:n_rows] = sorted(sched.pool._free)[:n_rows]
    valid = np.arange(P)[None, :] >= lead[:, None]
    calls = []
    for i in range(int(first.min()), P // W):
        w = i * W
        rides = first <= i
        calls.append((w, int(rides.sum()), (
            np.where(rides[:, None], tokens[:, w:w + W], tok.pad_id),
            valid[:, w:w + W] & rides[:, None],
            np.where(rides, slot_ids, sched.num_slots),
            np.where(rides, lead, P), donor, donor_pad,
            np.where(first == i, copied, 0), np.asarray(w, np.int32))))
    return nb, P, calls


def _assert_the_parents_plan(sched, rows, prompts):
    """The plan of a batch of ``rows`` is the parent's, argument for
    argument, and nobody waits. Leaves the pool as it found it."""
    reqs = [prompts.request(ids) for ids in rows]
    admitted = [(r, list(ids), min(
        sched._bucket_len(len(ids), sched.engine.seq_bucket),
        sched.max_prompt_bucket)) for r, ids in zip(reqs, rows)]
    nb, P, calls = _parents_plan(sched, admitted)
    plan = sched._plan_prefill(admitted)
    try:
        assert (plan["nb"], plan["P"]) == (nb, P)
        assert plan["deferred"] == [] and not plan["alone"]
        assert plan["S"] == extend_window(sched.engine.config, P)
        assert plan["key"] == ("serve_prefill", nb, P, False)
        assert [(c["w"], c["rows"]) for c in plan["calls"]] \
            == [c[:2] for c in calls]
        for mine, (_, _, theirs) in zip(plan["calls"], calls):
            assert len(mine["args"]) == len(theirs) == 8
            for a, b in zip(mine["args"], theirs):
                a = np.asarray(a)
                assert a.dtype == b.dtype and np.array_equal(a, b)
    finally:
        for slot in plan["slots"]:
            sched.pool.release(slot)
        sched.pool.take_invalidations()
    return plan


@pytest.mark.parametrize("case", [
    "fellows_and_no_resident_row", "riders_of_a_resident_row",
    "three_rows_in_a_batch_of_four", "one_row", "two_buckets"])
def test_under_sixteen_windows_the_plan_is_the_parents(dense, case):
    """Buckets of 64-192 (every older cell's are 3-5 windows): fellows of
    one batch all compute, riders copy from the resident row, the batch
    pads to a power of two, and no row waits."""
    engine, cut, sched = dense
    with jax.default_matmul_precision("highest"):
        prompts = _Prompts(engine, cut, seed=zlib.crc32(case.encode()),
                           tag=case)
        a = prompts.draw(150)
        fellow = np.concatenate([a[:120], prompts.draw(30)])
        stats = ServingStats(num_slots=sched.num_slots)
        if case == "riders_of_a_resident_row":
            assert sched.submit(prompts.request(a))
            sched._admit(stats)
        rows = {"fellows_and_no_resident_row": [a, fellow],
                "riders_of_a_resident_row": [fellow, a.copy()],
                "three_rows_in_a_batch_of_four": [a, fellow, a[:100]],
                "one_row": [a],
                "two_buckets": [a[:60], fellow]}[case]
        if len(rows) > 2:
            sched = _scheduler(engine, prefill_group=4)
        plan = _assert_the_parents_plan(sched, rows, prompts)
        assert plan["donors"] == 2 * (case == "riders_of_a_resident_row")
        if stats.admitted:
            _drained(sched, stats, 1)
        assert (stats.prefill_leader_batches, stats.prefill_rows_deferred) \
            == (0, 0)


@pytest.mark.parametrize("budget", [256, LONG])
def test_a_model_that_keeps_a_state_per_row_plans_as_the_parents(budget):
    """Such a model takes no donor, so nobody can go ahead for anybody: its
    fellows run their whole bucket in one call, at 4 windows a bucket and
    at 16."""
    with jax.default_matmul_precision("highest"):
        engine, cut = _toy("solar-open2-int8")
        assert engine.config.row_state_bytes
        assert not stepbuilder.long_bucket(engine.config, LONG)
        sched = _scheduler(engine, num_slots=8, prefill_group=4,
                           max_prompt_len=budget)
        prompts = _Prompts(engine, cut, seed=budget, tag=f"state{budget}")
        rows = _family(prompts, 3, stem=budget - 60, tails=(10, 30))
        plan = _assert_the_parents_plan(sched, rows, prompts)
        assert len(plan["calls"]) == 1 and plan["S"] == plan["P"] == budget


# -- the host's match --------------------------------------------------------------


def _longest(live, ids):
    """The row the plan tries first: ``by_shared``'s head."""
    return next(iter(live.by_shared(ids)), (None, 0))


def test_live_row_prefixes_take_the_longest_share_and_the_lowest_slot():
    pool = SlotPool(4)
    rows = [np.arange(10, 30), np.arange(10, 40), np.arange(10, 40),
            np.arange(50, 60)]
    for i, ids in enumerate(rows):
        pool.alloc(SlotState(request=Request(prompt=f"p{i}"), base=64,
                             real_len=len(ids), ids=ids))
    match = LiveRowPrefixes(pool)
    assert _longest(match, np.arange(10, 35)) == (1, 25)  # slots 1 and 2 tie
    assert _longest(match, np.arange(10, 20)) == (0, 10)  # capped by the row
    assert _longest(match, np.arange(10, 60)) == (1, 30)  # capped by the donor
    assert _longest(match, np.arange(90, 99)) == (None, 0)
    assert _longest(match, np.asarray([10, 11, 99, 13])) == (0, 2)
    # every sharing row, most shared first, the lower slot first among equals
    assert match.by_shared(np.arange(10, 35)) == [(1, 25), (2, 25), (0, 20)]
    pool.release(1)
    assert _longest(LiveRowPrefixes(pool), np.arange(10, 35)) == (2, 25)


def _pool_of(*rows):
    pool = SlotPool(len(rows) + 1)
    for i, ids in enumerate(rows):
        pool.alloc(SlotState(request=Request(prompt=f"p{i}"), base=192,
                             real_len=150 if ids is None else len(ids),
                             ids=ids))
    return pool


@pytest.mark.parametrize("case", [
    "the_copy_stops_a_window_short_of_the_whole_prompt",
    "never_a_slot_the_call_writes",
    "never_a_row_without_ids",
    "a_released_slot_drops_out",
])
def test_live_row_prefixes_invariants(case):
    ids = np.arange(10, 160)
    if case == "the_copy_stops_a_window_short_of_the_whole_prompt":
        # an identical resident prompt is matched whole; what the plan copies
        # of it leaves the last window to run, so that its logits exist
        assert _longest(LiveRowPrefixes(_pool_of(ids)), ids) == (0, len(ids))
        assert _tool("prefix_stats").expected_reuse([ids, ids]) == \
            [0, len(ids) - S]
    elif case == "never_a_slot_the_call_writes":
        pool = _pool_of(ids[:80])
        live = LiveRowPrefixes(pool)  # built before the batch claims slots
        assert pool.alloc(SlotState(request=Request(prompt="new"), base=192,
                                    real_len=len(ids), ids=ids)) == 1
        assert _longest(live, ids) == (0, 80)
    elif case == "never_a_row_without_ids":
        assert _longest(LiveRowPrefixes(_pool_of(None)), ids) == (None, 0)
        assert _longest(LiveRowPrefixes(_pool_of(None, ids[:30])), ids) == \
            (1, 30)
    else:
        pool = _pool_of(ids, ids[:40])
        pool.release(0)
        assert LiveRowPrefixes(pool).slots == [1]
        assert _longest(LiveRowPrefixes(pool), ids) == (1, 40)


# -- what prefix sharing is asked of, for both kinds of cache -------------------


@pytest.fixture(scope="module")
def shared(toy):
    """One 4-slot scheduler per kind of cache, its programs compiled once
    for every scenario below. Each scenario leaves the pool empty."""
    engine, cut = toy
    return engine, cut, _scheduler(engine)


def _family(prompts, n, stem=100, tails=(40, 60)):
    """``n`` prompts of one bucket that share their first ``stem`` ids."""
    head = prompts.draw(stem)
    return [np.concatenate([head, prompts.draw(int(t))])
            for t in np.linspace(tails[0], tails[1], n)]


def _drained(sched, stats, n):
    sched._run_loop(stats)
    assert stats.completed == n and stats.failed == 0
    assert sched.pool.occupancy == 0


def test_shared_prefix_under_churn_and_a_requeue_is_serving_alone(shared):
    """Eight near-duplicates through four slots, one of them faulted while it
    decodes beside the rows that copied from the same donor."""
    engine, cut, sched = shared
    prompts = _Prompts(engine, cut, seed=101, tag="churn")
    fam = _family(prompts, 8)
    reqs = [prompts.request(x) for x in fam]
    sched.fault_injector = ScriptedFaultInjector({(reqs[5].id, "decode"): 1})
    try:
        results = sched.serve(reqs)
    finally:
        sched.fault_injector = None
    stats = sched.last_stats
    assert all(r.ok for r in results) and sched.pool.occupancy == 0
    assert stats.requeued == 1 and results[5].retries == 1
    assert stats.prefill_tokens_reused > 0
    for req, res, ids in zip(reqs, results, fam):
        _assert_same_greedy(engine, req.id, res.tokens, ids)


def test_a_request_decodes_the_same_among_other_neighbours_and_slots(shared):
    """First in slot 0 with nothing to copy, then last of a serve in slot 3
    behind a live row that holds its first hundred ids."""
    engine, cut, sched = shared
    prompts = _Prompts(engine, cut, seed=102, tag="nb")
    target = prompts.draw(150)
    kin = [np.concatenate([target[:100], prompts.draw(45)]) for _ in (0, 1)]
    strangers = [prompts.draw(n) for n in (140, 155, 135)]
    first = sched.serve([prompts.request(x) for x in [target] + strangers])
    assert sched.last_stats.prefill_tokens_reused == 0
    again = prompts.request(target)
    second = sched.serve([prompts.request(x) for x in kin + strangers[:1]]
                         + [again])
    # the kin share a group and so nothing; the target copies from one
    assert sched.last_stats.prefill_tokens_reused == 150 - S
    assert first[0].ok and second[-1].ok
    assert_same_tokens(engine, "target", target, second[-1].tokens,
                       first[0].tokens)
    _assert_same_greedy(engine, again.id, second[-1].tokens, target)


def test_a_donor_decodes_its_solo_tokens_while_a_sharer_is_admitted(shared):
    engine, cut, sched = shared
    prompts = _Prompts(engine, cut, seed=103, tag="mid")
    donor = prompts.draw(150)
    sharer = np.concatenate([donor[:100], prompts.draw(40)])
    stats = ServingStats(num_slots=sched.num_slots)
    d = prompts.request(donor)
    assert sched.submit(d)
    sched.step(stats)  # admitted, and one chunk decoded
    assert 0 < sched.pool.get(_slot_of(sched, d.id)).emitted < NEW
    s = prompts.request(sharer)
    assert sched.submit(s)
    sched._admit(stats)
    assert stats.prefill_tokens_reused == 140 - S
    _drained(sched, stats, 2)
    for req, ids in ((d, donor), (s, sharer)):
        _assert_same_greedy(engine, req.id, sched.take_result(req.id).tokens,
                            ids)


def test_a_released_donor_changes_nothing_and_is_no_donor_again(shared):
    """The donor ends after two tokens and leaves the pool while its copier
    decodes on; the next sharer lands in the freed slot and copies from the
    copier (70 ids shared), not from what the slot held (140)."""
    engine, cut, sched = shared
    prompts = _Prompts(engine, cut, seed=104, tag="rel")
    donor = prompts.draw(150)
    copier = np.concatenate([donor[:70], prompts.draw(75)])
    late = np.concatenate([donor[:140], prompts.draw(10)])
    stats = ServingStats(num_slots=sched.num_slots)
    d = prompts.request(donor, ModelSettings(temperature=0.0, max_tokens=2))
    assert sched.submit(d)
    sched._admit(stats)
    freed = _slot_of(sched, d.id)
    c = prompts.request(copier)
    assert sched.submit(c)
    sched.step(stats)  # the copier copies, and a chunk ends the donor
    assert stats.prefill_tokens_reused == 145 - 2 * S
    assert sched.pool.live_slots() == [_slot_of(sched, c.id)]
    assert freed not in LiveRowPrefixes(sched.pool).slots
    e = prompts.request(late)
    assert sched.submit(e)
    sched._admit(stats)
    assert _slot_of(sched, e.id) == freed
    assert stats.prefill_tokens_reused == (145 - 2 * S) + (150 - 2 * S)
    _drained(sched, stats, 3)
    assert_same_tokens(engine, d.id, donor, sched.take_result(d.id).tokens,
                       alone(engine, d.id, GREEDY, 2))
    for req, ids in ((c, copier), (e, late)):
        _assert_same_greedy(engine, req.id, sched.take_result(req.id).tokens,
                            ids)


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_reuse_on_the_studys_pairs_is_what_prefix_stats_predicts(shared):
    """Two of phase 1's counterfactual pairs (their last 150-192 byte ids,
    from one offset, so that they part where the pair parts), admitted one
    by one into a pool that keeps every earlier row: the share
    ``tools/prefix_stats.py`` reckons before a run is the share the drain
    counts."""
    from fairness_llm_tpu.config import default_config
    from fairness_llm_tpu.data.movielens import load_movielens
    from fairness_llm_tpu.data.profiles import (
        create_base_preferences,
        create_profile_grid,
        profile_pairs,
    )
    from fairness_llm_tpu.pipeline.prompts import recommendation_prompt

    engine, cut, sched = shared
    config = default_config()
    data = load_movielens(config.data_dir, seed=config.random_seed)
    profiles = create_profile_grid(
        create_base_preferences(data, seed=config.random_seed), config, 1)
    text = {p.id: recommendation_prompt(p) for p in profiles}
    picked = []
    for a, b in profile_pairs(profiles):
        if a not in picked and b not in picked:
            picked += [a, b]
        if len(picked) == sched.num_slots:
            break
    byte_ids = [ByteTokenizer(512).encode(text[i]) for i in picked]
    start = min(map(len, byte_ids)) - 150
    rows = [3 + np.asarray(ids[start:], np.int32) % (cut["vocab_size"] - 3)
            for ids in byte_ids]
    assert all(150 <= len(r) <= 192 for r in rows)
    expected = _tool("prefix_stats").expected_reuse(rows)
    assert expected[0] == 0 and sum(expected) > 0

    prompts = _Prompts(engine, cut, seed=105, tag="pair")
    stats = ServingStats(num_slots=sched.num_slots)
    for row in rows:
        assert sched.submit(prompts.request(row))
        sched._admit(stats)
    assert stats.prefill_tokens == sum(map(len, rows))
    assert stats.prefill_tokens_reused == sum(expected)
    _drained(sched, stats, len(rows))


def _float_rows(fn, *caches):
    """``fn`` over the leaves that hold a row's keys, values or latents."""
    return jax.tree.map(
        lambda x, *rest: fn(x, *rest)
        if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim >= 3 else x,
        *caches)


def test_a_poisoned_donor_trips_the_guard_for_its_readers_only(shared):
    """NaN in a live row's cache: a stranger admitted beside it is untouched,
    the two rows that copy from it fail the finite check of their own call
    and are requeued, and no row that was live is released."""
    engine, cut, sched = shared
    prompts = _Prompts(engine, cut, seed=106, tag="nan")
    donor, stranger = prompts.draw(150), prompts.draw(140)
    readers = [np.concatenate([donor[:100], prompts.draw(n)])
               for n in (40, 45)]
    stats = ServingStats(num_slots=sched.num_slots)
    engine.numerics_guards = True
    try:
        with use_registry() as reg:
            d = prompts.request(donor)
            assert sched.submit(d)
            sched._admit(stats)
            slot = _slot_of(sched, d.id)
            clean = sched._cache
            sched._cache = _float_rows(
                lambda x: x.at[slot].set(jnp.nan), clean)
            u = prompts.request(stranger)
            assert sched.submit(u)
            sched._admit_once(stats)
            assert stats.requeued == 0
            live = sched.pool.live_slots()
            assert len(live) == 2
            rs = [prompts.request(x) for x in readers]
            for r in rs:
                assert sched.submit(r)
            sched._admit_once(stats)
            assert stats.requeued == 2 and sched.pool.live_slots() == live
            assert reg.peek("faults_total", component="serving",
                            kind="numerics", stage="prefill").value == 1
            # the poisoned call's outputs were swapped in before its flag
            # was read: the pool is whole, and nothing was built anew; the
            # guarded program wrote nothing, so no released slot keeps a
            # NaN that its next tenant would meet behind a mask
            assert not sched._device_state_deleted()
            clean_slots = [s for s in range(sched.num_slots) if s != slot]
            for x in jax.tree.leaves(sched._cache):
                if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim >= 3:
                    assert np.isfinite(np.asarray(x)[clean_slots]).all()
            assert reg.peek("device_state_rebuilds_total",
                            component="serving", stage="prefill") is None
            # the row heals; the requeued readers find it whole
            sched._cache = _float_rows(
                lambda now, was: now.at[slot].set(was[slot]),
                sched._cache, clean)
    finally:
        engine.numerics_guards = False
    _drained(sched, stats, 4)
    for req, ids, retries in ((d, donor, 0), (u, stranger, 0),
                              (rs[0], readers[0], 1), (rs[1], readers[1], 1)):
        res = sched.take_result(req.id)
        assert res.ok and res.retries == retries
        _assert_same_greedy(engine, req.id, res.tokens, ids)


def test_a_migrated_request_decodes_the_same_with_or_without_a_donor(toy):
    """A request decoding on r1 when r1 is retired starts anew on r0, beside
    a live row that holds its first hundred ids or beside a stranger."""
    engine, cut = toy
    prompts = _Prompts(engine, cut, seed=107, tag="mig")
    target = prompts.draw(150)
    beside = {"donor": np.concatenate([target[:100], prompts.draw(45)]),
              "stranger": prompts.draw(145)}
    serving = ServingConfig(
        enabled=True, num_slots=2, queue_capacity=8, max_prompt_len=192,
        max_new_tokens=NEW, decode_chunk=4, prefill_group=2)
    tokens = {}
    for case, ids in beside.items():
        with use_registry():
            fleet = ReplicaSet(engine, serving, settings=GREEDY,
                               fleet=FleetConfig(replicas=2))
            a, t = prompts.request(ids), prompts.request(target)
            assert fleet.submit(a) and fleet.submit(t)
            fleet.tick()  # one to each replica, and a chunk decoded on both
            r0, r1 = fleet.replicas
            assert a.id in r0.assigned and t.id in r1.assigned
            assert fleet.retire_replica(r1) == 1
            stats = fleet.drain()
            assert stats.prefill_tokens_reused == \
                (150 - S if case == "donor" else 0)
            res = fleet.take_result(t.id)
            assert res.ok and fleet.take_result(a.id).ok
            _assert_same_greedy(engine, t.id, res.tokens, target)
            tokens[case] = res.tokens
    assert_same_tokens(engine, "migrated", target, tokens["donor"],
                       tokens["stranger"])


def test_a_second_serve_takes_no_row_of_the_first_as_donor(shared):
    engine, cut, sched = shared
    prompts = _Prompts(engine, cut, seed=108, tag="twice")
    ids = prompts.draw(150)
    first = sched.serve([prompts.request(ids)])[0]
    assert LiveRowPrefixes(sched.pool).slots == []
    again = prompts.request(ids)
    second = sched.serve([again])[0]
    assert (sched.last_stats.prefill_batches,
            sched.last_stats.prefill_tokens_reused) == (3, 0)
    assert_same_tokens(engine, "twice", ids, second.tokens, first.tokens)
    _assert_same_greedy(engine, again.id, second.tokens, ids)


# -- the benchmark's new layer metrics -----------------------------------------------


@pytest.mark.parametrize("metric, cells", [
    ("prefill_reuse_share.req", ["mistral-7b-int8.sweep",
                                 "qwen2-7b-int8.sweep",
                                 "ax-k1-int8.sweep180"]),
    ("prefill_reuse_share.tpot", ["qwen2-7b-int8.replay"]),
])
def test_the_reuse_share_files_load_and_read_the_counters(metric, cells):
    from perfbench import run as bench_run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    # what later PRs appended (PR 35's cell) comes after
    assert entry["workloads"][:len(cells)] == cells and entry["unit"] == "%"
    assert entry["source"] == "program_counter"
    read, args = bench_run._reader("perfbench", metric)

    class Ctx:
        cfg = {}

        def __init__(self, counters):
            self.window = type("W", (), {"counters": counters})

    stats = ServingStats(prefill_tokens=8000, prefill_tokens_reused=4400)
    assert read(Ctx(stats.as_dict()), **args) == pytest.approx(55.0)
    # the parent's stats have no such counter: the line leaves the metric out
    assert read(Ctx({"prefill_tokens": 8000}), **args) is None


# -- the prompt layout the reuse rides on ---------------------------------------------


def test_recommendation_prompt_pairs_diverge_late():
    """The layout contract the hit rate rides on: counterfactual pairs
    share most of their bytes as a prefix (demographics last)."""
    from fairness_llm_tpu.data.profiles import Profile
    from fairness_llm_tpu.pipeline.prompts import (
        divergence_stats,
        recommendation_prompt,
    )

    movies = [f"Movie {i}" for i in range(10)]
    pairs = []
    for g1, g2 in (("male", "female"), ("female", "non-binary")):
        a = Profile(id="a", gender=g1, age="25-34", occupation="pro",
                    watched_movies=movies, favorite_genres=["drama"])
        b = Profile(id="b", gender=g2, age="25-34", occupation="pro",
                    watched_movies=movies, favorite_genres=["drama"])
        pairs.append((recommendation_prompt(a), recommendation_prompt(b)))
    stats = divergence_stats(pairs)
    assert stats["pairs"] == 2
    assert stats["min_frac"] > 0.7, stats


def test_divergence_stats_math():
    from fairness_llm_tpu.pipeline.prompts import divergence_stats, lcp_len

    assert lcp_len("abcd", "abXd") == 2
    assert lcp_len("abc", "abc") == 3
    s = divergence_stats([("aaaa", "aaXX"), ("bb", "bb")])
    assert s["min_frac"] == pytest.approx(0.5)
    assert s["max_frac"] == pytest.approx(1.0)
    assert divergence_stats([])["pairs"] == 0
