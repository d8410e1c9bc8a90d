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

import dataclasses
import json
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.config import ModelSettings, ServingConfig  # noqa: E402
from fairness_llm_tpu.runtime.engine import DecodeEngine  # noqa: E402
from fairness_llm_tpu.runtime.stepbuilder import EXTEND_WINDOW  # noqa: E402
from fairness_llm_tpu.serving import ContinuousScheduler, Request  # noqa: E402
from fairness_llm_tpu.serving.slots import (  # noqa: E402
    LiveRowPrefixes,
    SlotPool,
    SlotState,
)
from fairness_llm_tpu.utils.profiling import ServingStats  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench.lib import modelcfg  # noqa: E402
from perfbench.lib import weights as W  # noqa: E402
from perfbench.lib.sut import IdTokenizer  # noqa: E402

S = EXTEND_WINDOW
TOL = 1e-5
NEAR_TIE = 1e-4
SEED = 29
NEW = 6
GREEDY = ModelSettings(temperature=0.0, max_tokens=NEW)


def _toy(config: str):
    """(engine, rng) for one family's toy, float32, ids as prompts."""
    cut = modelcfg.rehearsal_cut(modelcfg.load_config(
        os.path.join(ROOT, "perfbench", "configs", config + ".json")))
    cut["eos_token_id"] = 1  # never drawn; a row may still end on it
    family = families.of(cut)
    make_layer, make_globals = W.jitted_makers(cut)
    words = W.seed_words(SEED)
    tree = family.program_tree(cut, make_globals(words), [
        make_layer(words, i) for i in range(cut["num_hidden_layers"])])
    mc = dataclasses.replace(family.model_config(cut, "toy"), dtype="float32")
    tok = IdTokenizer(cut["vocab_size"], cut["pad_token_id"],
                      cut["eos_token_id"])
    return DecodeEngine(mc, params=tree, tokenizer=tok, seed=0), cut


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


class _Prompts:
    """Prompts as ids the tokenizer hands back by name."""

    def __init__(self, engine, cut, seed=SEED):
        self.tok, self.vocab = engine.tokenizer, cut["vocab_size"]
        self.rng = np.random.default_rng(seed)
        self.count = 0

    def draw(self, n):
        return self.rng.integers(3, self.vocab, size=n).astype(np.int32)

    def request(self, ids):
        name = f"r{self.count}"
        self.count += 1
        self.tok.register(name, np.asarray(ids, np.int32))
        return Request(prompt=name, id=name, settings=GREEDY)


def _whole_prompt_logits(engine, ids):
    """Next-token logits of ONE forward over the whole prompt, no cache."""
    ids = jnp.asarray(ids, jnp.int32)[None]
    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
    logits, _ = jax.jit(
        lambda p, t, q: engine.model.apply({"params": p}, t, q)
    )(engine.params, ids, pos)
    return np.asarray(logits[0, -1])


def _assert_same_greedy(engine, name, served_tokens, ids):
    """The engine alone decodes the same tokens; where the two programs
    part, the whole-sequence logits must call the step a near-tie."""
    alone = np.asarray(engine.generate([name], GREEDY).tokens[0])
    served = np.asarray(served_tokens)
    assert len(served) == NEW
    for t in range(NEW):
        if served[t] != alone[t]:
            ref = _whole_prompt_logits(
                engine, np.concatenate([ids, served[:t]]))
            assert abs(ref[served[t]] - ref[alone[t]]) < NEAR_TIE, (
                f"{name} token {t}: served {served[t]}, engine {alone[t]}, "
                "and the reference's logits are not a tie")
            break


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
    """The second window of a batch raises: the batch's slots are released
    and its requests retried; the row that was live before keeps decoding
    the engine's tokens, and the retried rows come out right too (their
    half-written slots were prefilled anew)."""
    engine, cut, sched = dense
    with jax.default_matmul_precision("highest"):
        prompts = _Prompts(engine, cut, seed=7)
        ids = [prompts.draw(150), prompts.draw(140), prompts.draw(130)]
        reqs = [prompts.request(x) for x in ids]
        stats = ServingStats(num_slots=sched.num_slots)
        assert sched.submit(reqs[0])
        sched._admit(stats)
        live_before = sched.pool.live_slots()

        inner, calls = sched._run_compiled, []

        def failing(fn, *args):
            if getattr(fn, "program", "") == "serve_prefill":
                calls.append(1)
                if len(calls) == 2:
                    raise RuntimeError("scripted device fault")
            return inner(fn, *args)

        sched._run_compiled = failing
        try:
            for r in reqs[1:]:
                assert sched.submit(r)
            sched._admit_once(stats)
        finally:
            sched._run_compiled = inner
        assert len(calls) == 2 and stats.requeued == 2
        assert sched.pool.live_slots() == live_before
        assert stats.prefill_batches == 3 + 1  # the call that ran is counted
        assert stats.prefill_tokens == 150  # the failed batch admitted none

        sched._run_loop(stats)
        assert stats.completed == 3 and stats.failed == 0
        for req, x in zip(reqs, ids):
            res = sched.take_result(req.id)
            assert res.ok
            _assert_same_greedy(engine, req.id, res.tokens, x)


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


# -- the host's match --------------------------------------------------------------


def test_live_row_prefixes_take_the_longest_share_and_the_lowest_slot():
    pool = SlotPool(4)
    rows = [np.arange(10, 30), np.arange(10, 40), np.arange(10, 40),
            np.arange(50, 60)]
    for i, ids in enumerate(rows):
        pool.alloc(SlotState(request=Request(prompt=f"p{i}"), base=64,
                             real_len=len(ids), ids=ids))
    match = LiveRowPrefixes(pool)
    assert match.longest(np.arange(10, 35)) == (1, 25)  # slots 1 and 2 tie
    assert match.longest(np.arange(10, 20)) == (0, 10)  # capped by the row
    assert match.longest(np.arange(10, 60)) == (1, 30)  # capped by the donor
    assert match.longest(np.arange(90, 99)) == (None, 0)
    assert match.longest(np.asarray([10, 11, 99, 13])) == (0, 2)
    pool.release(1)
    assert LiveRowPrefixes(pool).longest(np.arange(10, 35)) == (2, 25)
    # a row without ids (the paged layout keeps none) is never a donor
    bare = SlotPool(1)
    bare.alloc(SlotState(request=Request(prompt="q"), base=64, real_len=5))
    assert LiveRowPrefixes(bare).longest(np.arange(5)) == (None, 0)


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
    assert entry["workloads"] == cells and entry["unit"] == "%"
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
