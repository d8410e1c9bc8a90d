"""Delta-rule linear-attention layers beside gated softmax layers on the
serving path: DecodeEngine -> ContinuousScheduler -> the stepbuilder
programs, a fixed state per row in the slot pool beside keys and values per
position, prefill and then decode, against the plain float32 reference's ONE
forward over the whole sequence. A model that takes no donor prefills a
batch in one call as wide as its bucket (``extend_window``), so the rules for
a per-row state are a call's: zeroed for every row the call names, untouched
in every slot it does not name, carried through a row's padding.

The program runs the benchmark family's toy (one period: a softmax layer and
three linear layers, 4 of 16 experts and half the heads held) in float32 on
int8 weights made from a seed, so what is compared is the mathematics. The
readings are float32 rounding: 3.2e-6 and 4.4e-6 on logits of size 1 (the
state is a sum over up to 146 tokens, in another order than the
reference's). The tolerance is 1e-4, as ``test_serving_latent_moe.py``'s. A
bfloat16 state moves the same logits by 1.3e-2 (130 tolerances), a skipped
output gate by 0.28, beta without its factor 2 by 0.55 and a dropped decay by
0.9, and a test says so of each.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.config import ModelSettings, ServingConfig  # noqa: E402
from fairness_llm_tpu.models.transformer import RowState  # noqa: E402
from fairness_llm_tpu.runtime.stepbuilder import (  # noqa: E402
    EXTEND_WINDOW,
    build_prefix,
    build_spec_decode,
    extend_window,
)
from fairness_llm_tpu.serving import ContinuousScheduler  # noqa: E402
from fairness_llm_tpu.telemetry import use_registry  # noqa: E402
from fairness_llm_tpu.telemetry.timeline import use_timeline  # noqa: E402
from fairness_llm_tpu.utils.profiling import ServingStats  # noqa: E402
from perfbench.families import linear_hybrid_moe  # noqa: E402
from perfbench.lib import correct, reference  # noqa: E402
from perfbench.lib.traffic import RequestSpec  # noqa: E402
from toys import (  # noqa: E402
    IdPrompts,
    alone,
    assert_same_tokens,
    prefill_aliasing,
    prefill_fault,
    toy_engine,
)

TOL = 1e-4
SEED = 33
NEW = 6
GREEDY = ModelSettings(temperature=0.0, max_tokens=NEW)
# Buckets of 64, 128 and 192, each prefilled in one call: rows with 59 to 52
# slots of padding in front of their first token.
LENGTHS = [5, 70, 18, 100, 9, 140]


@pytest.fixture(scope="module")
def toy():
    with jax.default_matmul_precision("highest"):
        yield toy_engine("solar-open2-int8", SEED)


def _scheduler(engine, **kw):
    serving = ServingConfig(**dict(dict(
        enabled=True, num_slots=4, queue_capacity=32, max_prompt_len=192,
        max_new_tokens=NEW, decode_chunk=4, prefill_group=2), **kw))
    return ContinuousScheduler(engine, serving, settings=GREEDY)


def _row_state(sched, slot=None):
    """The per-row leaves of the pool (of one slot), as NumPy."""
    leaves = [np.asarray(x) for layer in sched._cache.layers
              if isinstance(layer, RowState)
              for x in (layer.state, layer.conv)]
    return leaves if slot is None else [x[slot] for x in leaves]


def _slot_of(sched, name):
    return next(s for s in sched.pool.live_slots()
                if sched.pool.get(s).request.id == name)


def _reference_logits(cut, sequences):
    """The plain reference's logits after the whole of each sequence, in
    one right-padded forward (causal: padding reaches no real position)."""
    width = -(-max(map(len, sequences)) // 64) * 64
    tokens = np.zeros((len(sequences), width), np.int32)
    for r, seq in enumerate(sequences):
        tokens[r, :len(seq)] = seq
    last = np.asarray([[len(seq) - 1] for seq in sequences], np.int32)
    return reference.logits_at(cut, SEED, tokens, last)[:, 0]


@pytest.fixture(scope="module")
def served(toy):
    """The toy through a 2-slot scheduler (four of six rows ride recycled
    slots): (cut, specs, their served tokens, the drain's stats, the
    scheduler, the timeline's events)."""
    engine, cut = toy
    rng = np.random.default_rng(SEED)
    specs = [RequestSpec(id=f"h{i}", ids=rng.integers(
        3, cut["vocab_size"], size=n).astype(np.int32), max_new=NEW,
        row_seed=i) for i, n in enumerate(LENGTHS)]
    prompts = IdPrompts(engine, cut, SEED, GREEDY, tag="h")
    requests = [prompts.request(s.ids) for s in specs]
    with jax.default_matmul_precision("highest"), use_registry(), \
            use_timeline() as tl:
        sched = _scheduler(engine, num_slots=2)
        results = sched.serve(requests)
        events = tl.events()
    assert all(r.ok for r in results), [r.error for r in results]
    return cut, specs, [np.asarray(r.tokens) for r in results], \
        sched.last_stats, sched, events


# -- the mathematics --------------------------------------------------------------


def test_served_tokens_are_the_references_within_float32_rounding(served):
    cut, specs, tokens, _, _, _ = served
    sample = [correct.Served(s, t, True) for s, t in zip(specs, tokens)]
    assert correct.length_faults(sample, cut["eos_token_id"]) == 0
    got = correct.gaps(cut, SEED, sample)
    assert got["served_tokens"] == sum(len(t) for t in tokens)
    assert got["widest_gap"] < TOL


def _carried_errors(served):
    """Per request, how far the nearest slot's carried logits lie from the
    reference's logits after the request's last token."""
    cut, specs, tokens, _, sched, _ = served
    carried = np.asarray(sched._prev_logits)
    want = _reference_logits(
        cut, [np.concatenate([s.ids, t]) for s, t in zip(specs, tokens)])
    return [np.abs(carried - w[None]).max(axis=1).min() for w in want]


def test_carried_logits_are_the_references(served):
    """The logits each slot carries for its last tenant, after windows of
    prefill and six steps of decode through the pool: the reference's at the
    position after that request's last token, for one request a slot."""
    errors = _carried_errors(served)
    assert sum(e < TOL for e in errors) == 2, errors


def _bf16_state(q, k, v, a, b):
    def token(state, t):
        q_t, k_t, v_t, a_t, b_t = t
        state = a_t[..., None] * state
        u = jnp.sum(state * k_t[..., None], axis=-2)
        state = state + (b_t[..., None] * k_t)[..., None] \
            * (v_t - u)[..., None, :]
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    n, _, h, dk = q.shape
    _, o = jax.lax.scan(token, jnp.zeros((n, h, dk, dk), jnp.float32),
                        tuple(t.swapaxes(0, 1) for t in (q, k, v, a, b)))
    return o.swapaxes(0, 1)


@pytest.mark.parametrize(
    "broken", ["bf16_state", "no_decay", "no_output_gate", "beta_without_2"])
def test_the_tolerance_catches_a_lower_precision_or_a_missing_term(
        served, monkeypatch, broken):
    whole = linear_hybrid_moe.delta_rule
    if broken == "bf16_state":
        monkeypatch.setattr(linear_hybrid_moe, "delta_rule", _bf16_state)
    elif broken == "no_decay":
        monkeypatch.setattr(
            linear_hybrid_moe, "delta_rule",
            lambda q, k, v, a, b: whole(q, k, v, jnp.ones_like(a), b))
    elif broken == "beta_without_2":
        monkeypatch.setattr(
            linear_hybrid_moe, "delta_rule",
            lambda q, k, v, a, b: whole(q, k, v, a, 0.5 * b))
    else:
        monkeypatch.setattr(linear_hybrid_moe, "_output_gate",
                            lambda y, layer, ops: 1.0)
    jax.clear_caches()
    try:
        errors = _carried_errors(served)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert min(errors) > 20 * TOL, (broken, errors)


def test_the_engine_alone_decodes_the_same_tokens(served, toy):
    engine, _ = toy
    _, specs, tokens, _, _, _ = served
    with jax.default_matmul_precision("highest"):
        for spec, toks in zip(specs, tokens):
            assert_same_tokens(engine, spec.id, spec.ids, toks,
                               alone(engine, spec.id, GREEDY, NEW))


# -- the pool: what the counters, the plan and the ledger say ---------------------


def test_the_counters_count_resets_and_streamed_state(served, toy):
    engine, _ = toy
    _, _, tokens, stats, sched, _ = served
    assert isinstance(stats, ServingStats)
    row = engine.config.row_state_bytes
    # three linear layers x (4 heads x 32 x 32 float32 + 3 x 384 float32)
    assert row == 3 * (4 * 32 * 32 * 4 + 3 * 384 * 4)
    assert stats.row_state_resets == stats.admitted == len(LENGTHS)
    assert stats.row_state_bytes_stepped == stats.decode_steps * 2 * row
    assert stats.prefill_tokens == sum(LENGTHS)
    assert stats.prefill_tokens_reused == 0
    assert stats.as_dict()["row_state_resets"] == len(LENGTHS)
    # the experts count every prefilled and decoded token in all four layers
    assert stats.moe_tokens == 4 * (sum(LENGTHS) + sum(map(len, tokens)))


def test_the_plan_takes_no_donor_and_says_why(toy):
    """Two prompts that share 100 leading ids: the second copies nothing
    (a dense model would copy 76 positions and run one window of 64) and
    runs its whole bucket of 192 in one call, the plan's span names the
    reason, and the live row is no donor."""
    engine, cut = toy
    assert extend_window(engine.config, 192) == 192 != EXTEND_WINDOW
    with jax.default_matmul_precision("highest"), use_registry(), \
            use_timeline() as tl:
        sched = _scheduler(engine)
        prompts = IdPrompts(engine, cut, SEED + 1, GREEDY, tag="d")
        first = prompts.draw(150)
        second = np.concatenate([first[:100], prompts.draw(40)])
        stats = ServingStats(num_slots=sched.num_slots)
        a, b = prompts.request(first), prompts.request(second)
        assert sched.submit(a)
        sched._admit(stats)
        assert sched.pool.get(_slot_of(sched, a.id)).ids is None
        assert sched.submit(b)
        sched._admit(stats)
        assert (stats.prefill_batches, stats.prefill_tokens,
                stats.prefill_tokens_reused) == (2, 290, 0)
        carried = np.asarray(sched._prev_logits[_slot_of(sched, b.id)])
        assert np.abs(carried - _reference_logits(cut, [second])[0]).max() \
            < TOL
        plans = [e for e in tl.events() if e["type"] == "span"
                 and e["name"] == "sched.admit.plan"]
    assert len(plans) == 2
    assert all(p["args"]["donors"] == 0 for p in plans)
    assert all(p["args"]["windows"] == 1 for p in plans)
    assert all(p["args"]["donorless"] == "row_state" for p in plans)


def test_the_ledger_holds_the_per_row_state_under_a_pool_of_its_own(toy):
    from fairness_llm_tpu.telemetry.memory import (
        get_memory_ledger,
        tree_device_bytes,
    )

    engine, _ = toy
    with use_registry():
        mem = get_memory_ledger()
        # other schedulers of this process may still hold their pools
        before = mem.pool_bytes("row_state"), mem.pool_bytes("kv_contiguous")
        sched = _scheduler(engine)
        per_row = [l for l in sched._cache.layers if isinstance(l, RowState)]
        assert len(per_row) == 3
        want = sched.num_slots * engine.config.row_state_bytes
        assert tree_device_bytes(per_row) == want
        assert mem.pool_bytes("row_state") - before[0] == want
        assert mem.pool_bytes("kv_contiguous") - before[1] == \
            tree_device_bytes(sched._cache) - want
        sched.release_memory()
        assert mem.pool_bytes("row_state") == before[0]


# -- the pool: whose state a call may touch -----------------------------------------


def test_a_recycled_slot_starts_from_a_zero_state(toy):
    """The same prompt into a slot a finished request left dirty and into a
    fresh pool: the row's state, convolution inputs and carried logits are
    bit-identical, zeroed inside ``serve_prefill`` and by nothing else."""
    engine, cut = toy
    with jax.default_matmul_precision("highest"):
        prompts = IdPrompts(engine, cut, SEED + 2, GREEDY, tag="z")
        ids = prompts.draw(90)
        fresh, dirty = _scheduler(engine), _scheduler(engine)
        assert all(r.ok for r in dirty.serve([prompts.request(
            prompts.draw(120))]))
        assert any(np.abs(x).max() > 0 for x in _row_state(dirty, 0))
        out = []
        for sched in (fresh, dirty):
            req = prompts.request(ids)
            stats = ServingStats(num_slots=sched.num_slots)
            assert sched.submit(req)
            sched._admit(stats)
            slot = _slot_of(sched, req.id)
            assert slot == 0 and stats.row_state_resets == 1
            out.append(_row_state(sched, slot)
                       + [np.asarray(sched._prev_logits[slot])])
    for a, b in zip(*out):
        assert np.array_equal(a, b)
    assert all(np.abs(x).max() > 0 for x in out[0])


def _watch_calls(sched):
    """Wrap the scheduler's compiled calls: per call (program, the slots it
    names, every per-row leaf before, after)."""
    inner, seen = sched._run_compiled, []

    def watching(fn, *args):
        before = _row_state(sched)
        out = inner(fn, *args)
        program = getattr(fn, "program", "")
        # serve_prefill: args are (params, cache, logits, tokens, valid,
        # slots, ...); serve_step: (..., live, reset), the new cache first.
        named = np.asarray(args[5]) if program == "serve_prefill" else \
            np.flatnonzero(np.asarray(args[-2]))
        after = [np.asarray(x) for layer in out[0].layers
                 if isinstance(layer, RowState)
                 for x in (layer.state, layer.conv)]
        seen.append((program, named, before, after))
        return out

    sched._run_compiled = watching
    return seen


def test_a_call_moves_only_the_rows_it_names(toy):
    """A live row decodes; then a batch of three is admitted beside it in
    one call (rows of 150, 20 and 80 ids in a bucket of 192, and a
    batch-pad row), and everything decodes on. In every call, prefill or
    step, every slot the call does not name (the live row during prefill,
    idle and released slots during decode) keeps its state and convolution
    inputs bit-identical."""
    engine, cut = toy
    with jax.default_matmul_precision("highest"):
        sched = _scheduler(engine, prefill_group=4)
        prompts = IdPrompts(engine, cut, SEED + 3, GREEDY, tag="m")
        stats = ServingStats(num_slots=sched.num_slots)
        first = prompts.request(prompts.draw(100))
        assert sched.submit(first)
        sched._admit(stats)
        seen = _watch_calls(sched)
        sched._decode(stats)
        ids = [prompts.draw(n) for n in (150, 20, 80)]
        for x in ids:
            assert sched.submit(prompts.request(x))
        sched._admit_once(stats)
        sched._run_loop(stats)
    assert stats.completed == 4 and stats.failed == 0
    prefills = [c for c in seen if c[0] == "serve_prefill"]
    # bucket 192, nb 4: one call, three riders and a pad row
    assert [int((c[1] < sched.num_slots).sum()) for c in prefills] == [3]
    assert len(prefills[0][1]) == 4
    assert sum(c[0] == "serve_step" for c in seen) >= 3
    unnamed = {"serve_prefill": 0, "serve_step": 0}
    for program, named, before, after in seen:
        others = np.setdiff1d(np.arange(sched.num_slots), named)
        unnamed[program] += len(others)
        for b, a in zip(before, after):
            assert np.array_equal(b[others], a[others]), program
            if program == "serve_prefill":
                moved = named[named < sched.num_slots]
                assert not np.array_equal(b[moved], a[moved])
    assert unnamed["serve_prefill"] == 1 and unnamed["serve_step"] >= 3


def _one_live_then_a_batch(engine, cut, seed, tag):
    """A scheduler with one row admitted, and two more requests to come."""
    sched = _scheduler(engine)
    prompts = IdPrompts(engine, cut, seed, GREEDY, tag=tag)
    ids = [prompts.draw(150), prompts.draw(140), prompts.draw(130)]
    reqs = [prompts.request(x) for x in ids]
    stats = ServingStats(num_slots=sched.num_slots)
    assert sched.submit(reqs[0])
    sched._admit(stats)
    return sched, stats, reqs, ids


def _all_decode_the_engines_tokens(engine, sched, stats, reqs, ids, retries):
    sched._run_loop(stats)
    assert stats.completed == len(reqs) and stats.failed == 0
    for req, x, n in zip(reqs, ids, retries):
        res = sched.take_result(req.id)
        assert res.ok and res.retries == n
        assert_same_tokens(engine, req.id, x, res.tokens,
                           alone(engine, req.id, GREEDY, NEW))


def test_a_failed_prefill_call_leaves_the_other_rows_states_intact(toy):
    """A batch's call raises before it is made (what a trace or compile
    error does): the donated pool was never handed over, so the live row's
    state is what it was, the batch's slots are released and its requests
    retried, and every request still decodes the engine's tokens."""
    engine, cut = toy
    with jax.default_matmul_precision("highest"), use_registry() as reg:
        sched, stats, reqs, ids = _one_live_then_a_batch(
            engine, cut, SEED + 4, "f")
        live = _slot_of(sched, reqs[0].id)
        before = _row_state(sched)
        with prefill_fault(sched, at=1) as calls:
            for r in reqs[1:]:
                assert sched.submit(r)
            sched._admit_once(stats)
        assert len(calls) == 1 and stats.requeued == 2
        assert sched.pool.live_slots() == [live]
        for b, a in zip(before, _row_state(sched)):
            assert np.array_equal(b[live], a[live])
        assert stats.row_state_resets == 1  # the failed batch admitted none
        assert reg.peek("device_state_rebuilds_total", component="serving",
                        stage="prefill") is None
        _all_decode_the_engines_tokens(engine, sched, stats, reqs, ids,
                                       (0, 1, 1))


def test_a_call_that_fails_after_launch_rebuilds_every_rows_state(toy):
    """The batch's call raises once it has consumed the donated pool: the
    live row's state went with it, so every live row is requeued, the pool
    (per-row states too) is built anew from zeros, the counter says the
    wider path ran once, and every request still decodes the engine's
    tokens."""
    engine, cut = toy
    with jax.default_matmul_precision("highest"), use_registry() as reg:
        sched, stats, reqs, ids = _one_live_then_a_batch(
            engine, cut, SEED + 5, "g")
        assert any(np.abs(x).max() > 0 for x in _row_state(sched))
        with prefill_fault(sched, at=1, how="launched") as calls:
            for r in reqs[1:]:
                assert sched.submit(r)
            sched._admit_once(stats)
        assert len(calls) == 1 and stats.requeued == 3
        assert sched.pool.live_slots() == []
        assert not sched._device_state_deleted()
        assert all(np.abs(x).max() == 0 for x in _row_state(sched))
        assert reg.peek("device_state_rebuilds_total", component="serving",
                        stage="prefill").value == 1
        _all_decode_the_engines_tokens(engine, sched, stats, reqs, ids,
                                       (1, 1, 1))


def test_a_poisoned_batch_is_released_alone_and_the_live_row_decodes_on(toy):
    """Guard on, and a batch's call comes back with its finite flag down:
    the call consumed the pool and its outputs are already the scheduler's
    when the flag is read, sound for the row the call did not name. Only the
    batch is released; the live row's state is bit for bit what it was and
    it decodes the engine's tokens without a retry."""
    engine, cut = toy
    engine.numerics_guards = True
    try:
        with jax.default_matmul_precision("highest"), use_registry() as reg:
            sched, stats, reqs, ids = _one_live_then_a_batch(
                engine, cut, SEED + 6, "p")
            live = _slot_of(sched, reqs[0].id)
            before = _row_state(sched)
            with prefill_fault(sched, at=1, how="flag_down") as calls:
                for r in reqs[1:]:
                    assert sched.submit(r)
                sched._admit_once(stats)
            assert len(calls) == 1 and stats.requeued == 2
            assert sched.pool.live_slots() == [live]
            assert not sched._device_state_deleted()
            for b, a in zip(before, _row_state(sched)):
                assert np.array_equal(b[live], a[live])
            assert reg.peek("faults_total", component="serving",
                            kind="numerics", stage="prefill").value == 1
            assert reg.peek("device_state_rebuilds_total",
                            component="serving", stage="prefill") is None
            _all_decode_the_engines_tokens(engine, sched, stats, reqs, ids,
                                           (0, 1, 1))
    finally:
        engine.numerics_guards = False


@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
def test_the_compiled_prefill_writes_the_per_row_states_in_place(toy, guard):
    """Donated like ``serve_step``: keys, values, per-row states, masks and
    the carried logits are each aliased to an output, and no whole leaf of
    the pool is copied (ISSUE 34)."""
    engine, _ = toy
    sched = _scheduler(engine)
    aliased, donated, copies = prefill_aliasing(sched, 2, 192, guard)
    assert aliased == donated and copies == 0
    per_row = sum(isinstance(l, RowState) for l in sched._cache.layers)
    assert per_row == 3 and len(donated) >= 2 * per_row + 1


# -- what is refused, with a message ---------------------------------------------------


def test_tensor_parallel_heads_are_refused(toy):
    from fairness_llm_tpu.config import MeshConfig
    from fairness_llm_tpu.parallel import make_mesh
    from fairness_llm_tpu.parallel.sharding import make_axis_rules

    engine, _ = toy
    mesh = make_mesh(MeshConfig(dp=1, tp=2))
    with pytest.raises(NotImplementedError, match="per-row state"):
        make_axis_rules(engine.config, mesh)


def test_a_shared_prefix_is_refused_and_the_engine_prefills_every_row(toy):
    engine, cut = toy
    assert engine.config.attention == "hybrid"  # three layers are GQA: not it
    with pytest.raises(NotImplementedError, match="dense GQA layers only"):
        build_prefix(engine.config, engine.model, prefix_len=64)
    with jax.default_matmul_precision("highest"):
        prompts = IdPrompts(engine, cut, SEED + 5, GREEDY, tag="p")
        head = prompts.draw(70)
        names = [prompts.request(np.concatenate([head, prompts.draw(9)])).id
                 for _ in range(2)]
        shared = engine.generate(names, GREEDY, share_prefix=True)
        assert shared.stats["prefix_len"] == 0
        apart = engine.generate(names, GREEDY, share_prefix=False)
    assert np.array_equal(np.asarray(shared.tokens), np.asarray(apart.tokens))


def test_a_shared_prefix_reaching_the_mixer_is_refused(toy):
    engine, _ = toy
    cfg = engine.config
    tokens = jnp.zeros((1, 4), jnp.int32)
    pos = jnp.arange(4, dtype=jnp.int32)[None]
    hd, kv = cfg.head_dim, cfg.heads_held[1]
    shared = tuple((jnp.zeros((2, kv, hd)), jnp.zeros((2, kv, hd)))
                   for _ in range(cfg.num_layers))
    with pytest.raises(NotImplementedError, match="per-row state"):
        jax.eval_shape(lambda p: engine.model.apply(
            {"params": p}, tokens, pos, shared_layers=shared), engine.params)


def test_speculation_is_refused(toy):
    engine, _ = toy
    with pytest.raises(NotImplementedError, match="per-row state"):
        build_spec_decode(engine.config, engine.model, 0, 1, batch=2,
                          prompt_len=64, max_new=8, prefix_len=0,
                          ngram_max=3, draft_len=4, guard=False)
