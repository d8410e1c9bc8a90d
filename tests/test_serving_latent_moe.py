"""Latent attention and routed experts on the serving path: DecodeEngine ->
ContinuousScheduler -> the stepbuilder programs, prefill into the slot pool's
latent cache and absorbed decode out of it, against the plain float32
reference's full forward over the same tokens.

The program runs the benchmark family's toy in float32 on int8 weights made
from a seed, so what is compared is the mathematics: the readings are float32
rounding (2e-6 on logits of size 1), the tolerance 1e-4. Dropping the
``q_rope . k_r`` term or the routed scaling of 2.5 from the reference moves
the same number by twenty tolerances and more, and the tests say so."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.config import ModelSettings, ServingConfig  # noqa: E402
from fairness_llm_tpu.serving import ContinuousScheduler, Request  # noqa: E402
from fairness_llm_tpu.utils.profiling import ServingStats  # noqa: E402
from perfbench.families import latent_moe  # noqa: E402
from perfbench.lib import correct, reference  # noqa: E402
from perfbench.lib.traffic import RequestSpec  # noqa: E402
from toys import toy_engine  # noqa: E402

TOL = 1e-4
SEED = 77
LENGTHS = [5, 33, 18, 40, 9, 27]
NEW = [10, 6, 10, 8, 10, 7]


@pytest.fixture(scope="module")
def served():
    """The toy through a 2-slot scheduler (most rows ride recycled slots):
    (cut cfg, specs, their served tokens, the drain's stats)."""
    engine, cut = toy_engine("ax-k1-int8", SEED)
    tok = engine.tokenizer
    rng = np.random.default_rng(SEED)
    specs = [RequestSpec(id=f"r{i}", ids=rng.integers(
        3, cut["vocab_size"], size=n).astype(np.int32), max_new=m, row_seed=i)
        for i, (n, m) in enumerate(zip(LENGTHS, NEW))]
    for s in specs:
        tok.register(s.id, s.ids)
    serving = ServingConfig(enabled=True, num_slots=2, queue_capacity=16,
                            max_prompt_len=64, max_new_tokens=10,
                            decode_chunk=4, prefill_group=2)
    with jax.default_matmul_precision("highest"):
        sched = ContinuousScheduler(engine, serving, settings=ModelSettings(
            temperature=0.0, max_tokens=10))
        results = sched.serve([Request(
            prompt=s.id, id=s.id, settings=ModelSettings(
                temperature=0.0, max_tokens=s.max_new)) for s in specs])
    assert all(r.ok for r in results), [r.error for r in results]
    return cut, specs, [np.asarray(r.tokens) for r in results], \
        sched.last_stats, sched


def _gaps(cut, specs, tokens):
    sample = [correct.Served(s, t, True) for s, t in zip(specs, tokens)]
    return correct.gaps(cut, SEED, sample)


def test_served_tokens_are_the_references_within_float32_rounding(served):
    cut, specs, tokens, _, _ = served
    sample = [correct.Served(s, t, True) for s, t in zip(specs, tokens)]
    assert correct.length_faults(sample, cut["eos_token_id"]) == 0
    assert sum(len(t) == m for t, m in zip(tokens, NEW)) >= len(NEW) - 1
    got = _gaps(cut, specs, tokens)
    assert got["served_tokens"] == sum(len(t) for t in tokens)
    assert got["widest_gap"] < TOL


@pytest.mark.parametrize("broken", ["no_rope_term", "no_routed_scaling"])
def test_the_tolerance_catches_a_missing_term(served, monkeypatch, broken):
    cut, specs, tokens, _, _ = served
    if broken == "no_rope_term":
        monkeypatch.setattr(latent_moe, "_rotate", lambda x, f: x * 0.0)
    else:
        route = latent_moe.route
        monkeypatch.setattr(
            latent_moe, "route",
            lambda y, router, k, scaling, ops: route(y, router, k, 1.0, ops))
    jax.clear_caches()
    try:
        assert _gaps(cut, specs, tokens)["widest_gap"] > 10 * TOL
    finally:
        jax.clear_caches()


def test_carried_logits_are_the_references(served):
    """The logits the scheduler carries for the last tenants of its slots:
    the reference's at the position after each one's last token."""
    cut, specs, tokens, _, sched = served
    carried = np.asarray(sched._prev_logits)
    hits = 0
    for s, toks in zip(specs, tokens):
        seq = np.concatenate([s.ids, toks]).astype(np.int32)[None]
        pad = np.zeros((1, 64 - seq.shape[1]), np.int32)
        ref = reference.logits_at(
            cut, SEED, np.concatenate([seq, pad], axis=1),
            np.asarray([[seq.shape[1] - 1]], np.int32))[0, 0]
        close = np.abs(carried - ref[None]).max(axis=1) < TOL
        hits += int(close.any())
    assert hits == 2  # one per slot: the last request each slot served


def test_the_expert_counters_ride_with_the_chunks(served):
    """Two expert layers see every prefilled and every decoded token once;
    the toy holds 4 of 16 experts and chooses 4, so a token has between 0
    and 4 local pairs."""
    _, _, served_tokens, stats, _ = served
    assert isinstance(stats, ServingStats)
    tokens = 2 * (sum(LENGTHS) + sum(len(t) for t in served_tokens))
    assert stats.moe_tokens == tokens
    assert 0 < stats.moe_local_pairs <= 4 * tokens
    assert 0 < stats.moe_fullest_expert_pairs <= stats.moe_local_pairs
    assert stats.as_dict()["moe_local_pairs"] == stats.moe_local_pairs
