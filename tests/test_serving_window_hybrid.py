"""Window layers that keep a ring beside full layers that keep every
position, on the serving path: DecodeEngine -> ContinuousScheduler -> the
stepbuilder programs, prefill window by window and then decode through the
slot pool, against the plain float32 reference's ONE forward over the whole
sequence (no cache, no ring, a mask).

The program runs the benchmark family's toy (``window_hybrid_moe``: two
periods of three window layers of 16 and one full layer, layers 0-1 dense,
then 4 of 16 experts held, half the heads; QK-norm, output gate, four norms,
a selection bias, a scaled embedding) in float32 on int8 weights made from a
seed, so what is compared is the mathematics. The pool keeps a ring of 128
positions (``configs.ring_len(16)``) of each window layer and rows run to
324 and 394 positions, so the rings come round two and three times. The
readings are float32 rounding, 1.4e-6 to 2.2e-6 on logits of size 1.3 (eight
layers of sums in another order than the reference's); the tolerance is
1e-4, as ``test_serving_linear_hybrid.py``'s. Keys and values rounded to
bfloat16 move the same logits by 4.4e-3 to 0.28 (44 tolerances at the least),
the bias used to weigh by 1.3e-2 to 2.1e-2, rotation in a full layer by 0.26
to 0.38, a window off by one by 0.42 to 0.58, no QK-norm by 0.8 to 1.1, a
post-norm dropped by 1.0 to 1.3 and the window ignored by 1.3 to 1.5: a test
says so of each.
"""

import dataclasses
import functools
import inspect
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.config import ModelSettings, ServingConfig  # noqa: E402
from fairness_llm_tpu.models import transformer  # noqa: E402
from fairness_llm_tpu.models.configs import (  # noqa: E402
    EXTEND_WINDOW,
    WindowHybridConfig,
    get_model_config,
    ring_len,
)
from fairness_llm_tpu.models.transformer import (  # noqa: E402
    Transformer,
    init_cache,
)
from fairness_llm_tpu.runtime import stepbuilder  # noqa: E402
from fairness_llm_tpu.serving import ContinuousScheduler  # noqa: E402
from fairness_llm_tpu.telemetry import use_registry  # noqa: E402
from fairness_llm_tpu.telemetry.timeline import use_timeline  # noqa: E402
from fairness_llm_tpu.utils.profiling import ServingStats  # noqa: E402
from perfbench.families import window_hybrid_moe  # noqa: E402
from perfbench.lib import correct, reference  # noqa: E402
from perfbench.lib.traffic import RequestSpec  # noqa: E402
from toys import (  # noqa: E402
    IdPrompts,
    alone,
    assert_same_tokens,
    prefill_aliasing,
    prefill_hlo,
    rolled_rows,
    toy_engine,
)

TOL = 1e-4
SEED = 35
NEW = 24
GREEDY = ModelSettings(temperature=0.0, max_tokens=NEW)
WINDOW, RING = 16, 128
# Buckets of 320, 64, 192 and 384; with 24 tokens out, rows of 324 and 394
# positions: the ring of 128 comes round twice and three times.
LENGTHS = [300, 20, 150, 370, 90]


@pytest.fixture(scope="module")
def toy():
    """(engine, cut). The reference jits its weight makers anew in every
    call; for this module's one toy they are made once (a third of the
    module's time)."""
    from perfbench.lib import weights

    makers, memo = weights.jitted_makers, {}
    weights.jitted_makers = lambda cfg: memo.get(id(cfg)) or memo.setdefault(
        id(cfg), makers(cfg))
    try:
        with jax.default_matmul_precision("highest"):
            yield toy_engine("trinity-mini-int8", SEED)
    finally:
        weights.jitted_makers = makers


def _scheduler(engine, **kw):
    serving = ServingConfig(**dict(dict(
        enabled=True, num_slots=4, queue_capacity=32, max_prompt_len=384,
        max_new_tokens=NEW, decode_chunk=4, prefill_group=2), **kw))
    return ContinuousScheduler(engine, serving, settings=GREEDY)


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
def pool(toy):
    """ONE scheduler for the tests that admit and decode (its compiled
    programs are most of their time); each leaves it drained, so every later
    test also rides recycled slots."""
    with jax.default_matmul_precision("highest"):
        return _scheduler(toy[0])


@pytest.fixture(scope="module")
def served(toy, pool):
    """The toy through the 4-slot scheduler (the fifth row rides a recycled
    slot): (cut, specs, their served tokens, the drain's stats, the
    scheduler, the timeline's events)."""
    engine, cut = toy
    rng = np.random.default_rng(SEED)
    specs = [RequestSpec(id=f"h{i}", ids=rng.integers(
        3, cut["vocab_size"], size=n).astype(np.int32), max_new=NEW,
        row_seed=i) for i, n in enumerate(LENGTHS)]
    prompts = IdPrompts(engine, cut, SEED, GREEDY, tag="h")
    requests = [prompts.request(s.ids) for s in specs]
    with jax.default_matmul_precision("highest"), use_timeline() as tl:
        sched = pool
        results = sched.serve(requests)
        events = tl.events()
    assert all(r.ok for r in results), [r.error for r in results]
    return cut, specs, [np.asarray(r.tokens) for r in results], \
        sched.last_stats, sched, events, np.asarray(sched._prev_logits)


# -- the mathematics --------------------------------------------------------------


def test_the_toy_is_the_model_in_small(toy):
    engine, cut = toy
    cfg = engine.config
    assert isinstance(cfg, WindowHybridConfig)
    assert cfg.layer_windows == (16, 16, 16, None) * 2
    assert cfg.layer_rotary == (True, True, True, False) * 2
    assert cfg.layer_kinds == ("dense",) * 2 + ("moe",) * 6
    assert cfg.heads_held == (4, 2, 0) and cfg.experts_held[1] == 4
    assert cfg.qk_norm and cfg.post_norms and cfg.attention_gate \
        and cfg.router_bias and cfg.embed_scale
    assert ring_len(WINDOW) == RING and ring_len(2048) == 3072
    assert RING % EXTEND_WINDOW == 0 and RING >= WINDOW + EXTEND_WINDOW - 1
    assert cfg.retained_len(424) == (RING, RING, RING, 424) * 2
    assert cfg.retained_len(100) == (100,) * 8  # a short pool has no ring
    # the selection bias changes choices: it is no zero buffer
    bias = np.asarray(engine.params["layer_2"]["mlp"]["router_bias"])
    assert bias.dtype == np.float32 and np.abs(bias).max() > 0.01


def test_the_pool_keeps_a_ring_of_each_window_layer(served):
    sched = served[4]
    lengths = [layer.k.shape[1] for layer in sched._cache.layers]
    assert sched.cache_len == 384 + NEW
    assert lengths == [RING, RING, RING, sched.cache_len] * 2
    assert all(layer.v.shape == layer.k.shape for layer in sched._cache.layers)
    assert sched._cache.key_valid.shape == (4, sched.cache_len)
    assert sched._rings == [(WINDOW, RING)]


def test_served_tokens_are_the_references_within_float32_rounding(served):
    cut, specs, tokens = served[:3]
    sample = [correct.Served(s, t, True) for s, t in zip(specs, tokens)]
    assert correct.length_faults(sample, cut["eos_token_id"]) == 0
    got = correct.gaps(cut, SEED, sample)
    assert got["served_tokens"] == sum(len(t) for t in tokens) == 5 * NEW
    assert got["widest_gap"] < TOL


def _carried_errors(served):
    """Per request, how far the nearest slot's carried logits lie from the
    reference's logits after the request's last token."""
    cut, specs, tokens, carried = served[:3] + served[6:]
    want = _reference_logits(
        cut, [np.concatenate([s.ids, t]) for s, t in zip(specs, tokens)])
    assert np.abs(want).max() > 1.0
    return [np.abs(carried - w[None]).max(axis=1).min() for w in want]


def test_carried_logits_are_the_references(served):
    """The logits each slot carries for its last tenant, after windows of
    prefill and 24 steps of decode round the ring: the reference's at the
    position after that request's last token, for one request a slot (the
    first request's slot was taken again by the fifth)."""
    errors = _carried_errors(served)
    assert sum(e < TOL for e in errors) == 4, errors
    assert errors[3] < TOL and errors[4] < TOL  # 394 positions; recycled


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("broken", [
    "bf16_keys_and_values", "window_off_by_one", "rotation_in_a_full_layer",
    "no_qk_norm", "bias_used_to_weigh", "a_post_norm_dropped",
    "window_ignored"])
def test_the_tolerance_catches_a_lower_precision_or_a_wrong_term(
        served, monkeypatch, broken):
    F = window_hybrid_moe
    attend, block = F.attend, F.block
    rotate_all = broken == "rotation_in_a_full_layer"

    def retraced(cfg, kind):
        # a function of its own, so that the reference traces the layer
        # anew with what is patched below, and forgets nothing it had
        fn, spec = block(cfg, kind)
        if rotate_all:
            spec = spec[:2] + (True,) + spec[3:]
        return (lambda x, layer, spec, ops: fn(x, layer, spec, ops)), spec

    monkeypatch.setattr(F, "block", retraced)
    if broken == "bf16_keys_and_values":
        monkeypatch.setattr(F, "attend", lambda q, k, v, w: attend(
            q, _bf16(k), _bf16(v), w))
    elif broken == "window_off_by_one":
        monkeypatch.setattr(F, "attend", lambda q, k, v, w: attend(
            q, k, v, w + 1 if w else 0))
    elif broken == "window_ignored":
        monkeypatch.setattr(F, "attend",
                            lambda q, k, v, w: attend(q, k, v, 0))
    elif broken == "no_qk_norm":
        monkeypatch.setattr(F, "_qk_norm", lambda z, scale, eps: z)
    elif broken == "bias_used_to_weigh":
        def weighed(y, layer, top_k, scale, ops):
            s = jax.nn.sigmoid(
                ops.lower(y) @ layer["router"].astype(jnp.float32)) \
                + layer["router_bias"]
            top, chosen = jax.lax.top_k(s, top_k)
            return chosen, scale * top / jnp.sum(top, axis=-1, keepdims=True)
        monkeypatch.setattr(F, "route", weighed)
    elif broken == "a_post_norm_dropped":
        monkeypatch.setattr(
            F, "_post_norm", lambda z, layer, name, eps: z
            if name == "mlp_post_norm" else reference.rmsnorm(
                z, layer[name], eps))
    errors = _carried_errors(served)
    assert min(errors[1:]) > 20 * TOL, (broken, errors)


def test_the_engine_alone_decodes_the_same_tokens(served, toy):
    """The static engine keeps every position of every layer and masks the
    window: the same tokens as the pool's rings give."""
    engine, _ = toy
    specs, tokens = served[1:3]
    with jax.default_matmul_precision("highest"):
        for spec, toks in list(zip(specs, tokens))[3:]:
            assert_same_tokens(engine, spec.id, spec.ids, toks,
                               alone(engine, spec.id, GREEDY, NEW))


# -- the pool: what the counters, the plan and the ledger say ---------------------


def test_the_counters_count_what_each_kind_of_layer_retains(served, toy):
    engine, _ = toy
    tokens, stats, sched = served[2:5]
    # per position and layer: 2 held KV heads x 32 x (k, v) x float32
    per = 2 * 32 * 2 * 4
    row = 6 * RING * per + 2 * sched.cache_len * per
    assert sum(n * L * row_bytes
               for _, L, n, row_bytes, _ in sched._attn_kinds) == row
    assert stats.kv_bytes_stepped == stats.decode_steps * 4 * row
    assert stats.prefill_tokens == sum(LENGTHS)
    assert stats.prefill_donors_refused_ring == 0
    assert stats.as_dict()["kv_bytes_stepped"] == stats.kv_bytes_stepped
    # the experts count every prefilled and decoded token in six layers
    assert stats.moe_tokens == 6 * (sum(LENGTHS) + sum(map(len, tokens)))


def test_the_ledger_holds_the_rings_under_a_pool_of_their_own(toy):
    from fairness_llm_tpu.telemetry.memory import (
        get_memory_ledger,
        tree_device_bytes,
    )

    engine, _ = toy
    with use_registry():
        mem = get_memory_ledger()
        before = mem.pool_bytes("kv_window"), mem.pool_bytes("kv_contiguous")
        sched = _scheduler(engine)
        rings = [l for l in sched._cache.layers if l.k.shape[1] == RING]
        want = 6 * 2 * 4 * RING * 2 * 32 * 4
        assert len(rings) == 6 and tree_device_bytes(rings) == want
        assert mem.pool_bytes("kv_window") - before[0] == want
        assert mem.pool_bytes("kv_contiguous") - before[1] == \
            tree_device_bytes(sched._cache) - want
        sched.release_memory()
        assert mem.pool_bytes("kv_window") == before[0]


@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
def test_the_compiled_prefill_writes_rings_and_rows_in_place(toy, guard):
    """``tests/test_extend_prefill.py``'s check, for the new leaf: the
    compiled ``serve_prefill`` of a bucket longer than the ring aliases every
    leaf of the pool, ring or row, and the carried logits to an output and
    copies none of either length."""
    engine, _ = toy
    sched = _scheduler(engine)
    aliased, donated, copies = prefill_aliasing(sched, 2, 384, guard)
    assert aliased == donated and len(donated) == 8 * 2 + 4 + 1 + 1
    assert copies == 0


# -- donors under a ring -------------------------------------------------------------


def _admit_after(sched, engine, cut, seed, first_len, steps, share, tail):
    """A row of ``first_len`` ids has decoded ``steps`` chunks in the
    (drained) scheduler when a second arrives that shares its first
    ``share`` ids."""
    assert sched.pool.occupancy == 0
    prompts = IdPrompts(engine, cut, seed, GREEDY, tag=f"d{seed}-")
    stats = ServingStats(num_slots=sched.num_slots)
    first = prompts.draw(first_len)
    a = prompts.request(first)
    assert sched.submit(a)
    sched._admit(stats)
    for _ in range(steps):
        sched._decode(stats)
    second = np.concatenate([first[:share], prompts.draw(tail)])
    b = prompts.request(second)
    assert sched.submit(b)
    sched._admit(stats)
    return stats, (a, first), (b, second)


def _plans(tl):
    return [e["args"] for e in tl.events() if e["type"] == "span"
            and e["name"] == "sched.admit.plan"]


def _all_decode_the_engines_tokens(engine, sched, stats, *rows, check=True):
    sched._run_loop(stats)
    assert stats.completed == len(rows) and stats.failed == 0
    for req, ids in rows:
        res = sched.take_result(req.id)
        assert res.ok
        if check:
            assert_same_tokens(engine, req.id, ids, res.tokens,
                               alone(engine, req.id, GREEDY, NEW))


def test_a_donor_whose_ring_still_holds_the_window_is_taken(toy, pool):
    """A resident row of 370 ids, 8 tokens on; a rider sharing 350 of them
    copies 316 positions (its rings take the donor's ring rolled by the
    difference of their paddings, its rows the donor's rows) and computes one
    window: the whole prompt's logits, and both rows the engine's tokens."""
    (engine, cut), sched = toy, pool
    with jax.default_matmul_precision("highest"), use_timeline() as tl:
        stats, a, b = _admit_after(sched, engine, cut, 41, 370, 2, 350, 30)
        assert sched.pool.get(_slot_of(sched, a[0].id)).emitted == 8
        assert stats.prefill_tokens_reused == 380 - EXTEND_WINDOW
        assert stats.prefill_donors_refused_ring == 0
        carried = np.asarray(sched._prev_logits[_slot_of(sched, b[0].id)])
        assert np.abs(carried - _reference_logits(cut, [b[1]])[0]).max() < TOL
        plans = _plans(tl)
        assert [p["donors"] for p in plans] == [0, 1]
        assert all(p["retained"] == [RING, sched.cache_len] for p in plans)
        assert [p["ring_refused"] for p in plans] == [0, 0]
        _all_decode_the_engines_tokens(engine, sched, stats, a, b)


def _prefill_text(sched, nb, P, guard):
    """The scheduler's ``serve_prefill`` of bucket ``(nb, P)`` as lowered."""
    S = EXTEND_WINDOW
    row = jnp.zeros((nb,), jnp.int32)
    return sched._prefill_fn(nb, P, guard)._jit.lower(
        sched.engine.params, sched._cache, sched._prev_logits,
        jnp.zeros((nb, S), jnp.int32), jnp.zeros((nb, S), bool),
        row, row, row, row, row, jnp.zeros((), jnp.int32)).as_text()


#: What a short bucket's copying program rolls over the toy's bucket of 384
#: (rows of 408 and rings of 128, two rows a batch): each donor's row
#: doubled over the extent, as a value of its own.
ROLLED = re.compile(r"tensor<2x(?:792|256)x2x32xf32>|tensor<2x792xi32>")


def _bits(tree, slots=4):
    """Every leaf of a tree that has a slot axis, as host arrays of its raw
    bits (NaN equals NaN): the pool's rows and the carried logits."""
    return [np.asarray(x).view(np.uint8) for x in jax.tree.leaves(tree)
            if np.ndim(x) and np.shape(x)[0] == slots]


def _watch_prefill(sched, rolled, poison=None):
    """Wraps the scheduler's calls: every ``serve_prefill`` call first runs
    through ``rolled(nb)`` (the same call in a short bucket's program, not
    donated) and is recorded as (slots of its rows that copy, its pool and
    logits before, its outputs, ``rolled``'s outputs). ``poison`` replaces
    the parameters of the first call in which a row copies."""
    inner, seen = sched._run_compiled, []

    def watching(fn, *args):
        if getattr(fn, "program", "") != "serve_prefill":
            return inner(fn, *args)
        slots, copied = np.asarray(args[5]), np.asarray(args[9])
        if poison is not None and copied.any() and not seen:
            args = (poison(args[0]),) + args[1:]
        before = _bits(args[1:3])
        theirs = jax.device_get(rolled(len(slots))(*args))
        out = inner(fn, *args)
        seen.append((slots[copied > 0], before, jax.device_get(out), theirs))
        return out

    sched._run_compiled = watching
    return seen


def _nan_final_norm(params):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.full_like(x, jnp.nan)
        if "final_norm" in jax.tree_util.keystr(path) else x, params)


@pytest.mark.parametrize("case", [
    "rider_at_another_padding", "a_fellow_a_rider_and_a_pad_row",
    "guarded_batch_trips_the_guard"])
def test_a_long_buckets_calls_build_donor_rows_only_where_a_row_copies(
        toy, monkeypatch, case):
    """From ``DONOR_COND_WINDOWS`` windows a bucket, a call of
    ``serve_prefill`` in which a row copies takes each row as one slice of
    the pool (a rider's donor's row rotated by the difference of their
    paddings), under a ``lax.cond`` on "a row of this call copies", and
    builds no rolled rows over the whole extent; below it (every bucket but
    Trinity's cell's) it is the program it was. With the threshold at one
    window a resident row of 370 ids (padding 14) has decoded 8 tokens when
    a rider of 380 (padding 4, so every ring slot takes the donor's ten
    further on, round the ring's end) copies 316 positions. Beside it, a
    fellow of 90 ids that copies nothing and a pad row (a batch of three),
    or the guard on and the rider's first call made not finite. In every
    call every slot but a rider's is, bit for bit, what the short bucket's
    rolled rows give; a call the guard drops writes nothing at all. The
    riders carry the reference's logits and every row decodes the engine's
    tokens."""
    (engine, cut) = toy
    args = dict(nb=2, P=384, guard=False)
    guard = case == "guarded_batch_trips_the_guard"
    group = 3 if case == "a_fellow_a_rider_and_a_pad_row" else 2
    with jax.default_matmul_precision("highest"):
        plain = _scheduler(engine)
        assert 384 // EXTEND_WINDOW < stepbuilder.DONOR_COND_WINDOWS
        text = _prefill_text(plain, **args)
        assert "stablehlo.case" not in text and ROLLED.search(text)
        rolled = functools.lru_cache()(lambda nb: jax.jit(
            stepbuilder.build_serve_prefill(
                engine.config, engine.model, nb=nb, P=384, guard=guard,
                num_slots=4, rings=plain._rings)))
        monkeypatch.setattr(stepbuilder, "DONOR_COND_WINDOWS", 1)
        monkeypatch.setattr(engine, "numerics_guards", guard)
        sched = _scheduler(engine, prefill_group=group)
        text = _prefill_text(sched, **args)
        assert "stablehlo.case" in text and not ROLLED.search(text)
        prompts = IdPrompts(engine, cut, 43, GREEDY, tag=case[:6])
        stats = ServingStats(num_slots=sched.num_slots)
        first = prompts.draw(370)
        a = (prompts.request(first), first)
        assert sched.submit(a[0])
        sched._admit(stats)
        for _ in range(2):
            sched._decode(stats)
        rows = [np.concatenate([first[:350], prompts.draw(30)])]
        if group == 3:
            rows.append(prompts.draw(90))
        batch = [(prompts.request(ids), ids) for ids in rows]
        seen = _watch_prefill(sched, rolled,
                              _nan_final_norm if guard else None)
        try:
            for req, _ in batch:
                assert sched.submit(req)
            sched._admit(stats)
        finally:
            del sched._run_compiled
        assert stats.prefill_tokens_reused == 380 - EXTEND_WINDOW
        assert [len(riders) for riders, *_ in seen] == \
            [1] * guard + [0] * (group == 3) + [1]
        for riders, before, out, theirs in seen:
            others = np.setdiff1d(np.arange(sched.num_slots), riders)
            mine = _bits(out[:2])
            for m, t in zip(mine, _bits(theirs[:2])):
                assert np.array_equal(m[others], t[others])
            if len(out) == 3 and not out[2]:  # the guard dropped the call
                assert all(map(np.array_equal, mine, before))
        assert stats.requeued == guard
        for req, ids in batch:
            carried = np.asarray(sched._prev_logits[_slot_of(sched, req.id)])
            assert np.abs(carried - _reference_logits(cut, [ids])[0]).max() \
                < TOL
        _all_decode_the_engines_tokens(engine, sched, stats, a, *batch)


@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
def test_a_long_buckets_copying_program_writes_in_place_and_rolls_no_row(
        toy, monkeypatch, guard):
    """The compiled program of a long bucket's call in which rows copy
    (``DONOR_COND_WINDOWS`` at one window): every leaf of the pool and the
    carried logits aliased to an output, no copy of either length, and no
    value of a rolled shape (``[2, 408 + 384, ...]``, ``[2, 2 x 128, ...]``),
    which a short bucket's copying program holds."""
    engine, _ = toy
    short = _scheduler(engine)
    assert rolled_rows(short, 2, 384, prefill_hlo(short, 2, 384, guard)) > 0
    monkeypatch.setattr(stepbuilder, "DONOR_COND_WINDOWS", 1)
    sched = _scheduler(engine)
    hlo = prefill_hlo(sched, 2, 384, guard)
    aliased, donated, copies = prefill_aliasing(sched, 2, 384, hlo=hlo)
    assert aliased == donated and len(donated) == 8 * 2 + 4 + 1 + 1
    assert copies == 0 and rolled_rows(sched, 2, 384, hlo) == 0


def test_a_donor_whose_ring_moved_on_is_refused_and_the_row_computes(
        toy, pool):
    """A resident row of 370 ids, 20 tokens on, holds positions 262 and up in
    its rings; a rider sharing its first 100 ids would copy 86 and needs 71
    and up: the plan refuses the donor, says so, and computes the row. With
    the check switched off the same rider copies 86 positions out of rings
    that no longer hold them and its logits are wrong by thousands of
    tolerances: the refusal is what keeps the row right."""
    (engine, cut), sched = toy, pool
    with jax.default_matmul_precision("highest"), use_timeline() as tl:
        stats, a, b = _admit_after(sched, engine, cut, 42, 370, 5, 100, 50)
        st = sched.pool.get(_slot_of(sched, a[0].id))
        assert st.emitted == 20 and not sched._ring_holds(st, 86)
        assert sched._ring_holds(st, 370 + 20 - RING + WINDOW - 1)
        assert not sched._ring_holds(st, 370 + 20 - RING + WINDOW - 2)
        assert stats.prefill_tokens_reused == 0
        assert stats.prefill_donors_refused_ring == 1
        carried = np.asarray(sched._prev_logits[_slot_of(sched, b[0].id)])
        assert np.abs(carried - _reference_logits(cut, [b[1]])[0]).max() < TOL
        plans = _plans(tl)
        assert [p["ring_refused"] for p in plans] == [0, 1]
        assert [p["donors"] for p in plans] == [0, 0]
        _all_decode_the_engines_tokens(engine, sched, stats, a, b)

        sched._ring_holds = lambda st, copied: True
        try:
            stats, a, b = _admit_after(sched, engine, cut, 42, 370, 5, 100,
                                       50)
        finally:
            del sched._ring_holds
        assert stats.prefill_tokens_reused == 86
        carried = np.asarray(sched._prev_logits[_slot_of(sched, b[0].id)])
        assert np.abs(carried - _reference_logits(cut, [b[1]])[0]).max() \
            > 100 * TOL
        _all_decode_the_engines_tokens(engine, sched, stats, a, b,
                                       check=False)


def test_the_second_best_donor_is_taken_where_the_best_is_refused(toy, pool):
    """Two resident rows share a rider's prompt: the longer share's ring has
    moved on, the shorter share's still holds its window, and the plan takes
    that one and counts the refusal."""
    (engine, cut), sched = toy, pool
    with jax.default_matmul_precision("highest"):
        assert sched.pool.occupancy == 0
        prompts = IdPrompts(engine, cut, 43, GREEDY, tag="s")
        stats = ServingStats(num_slots=sched.num_slots)
        stem = prompts.draw(120)
        long_ = np.concatenate([stem, prompts.draw(250)])   # 370 ids
        near = np.concatenate([stem[:110], prompts.draw(20)])  # 130 ids
        rows = [(prompts.request(x), x) for x in (long_, near)]
        for req, _ in rows:
            assert sched.submit(req)
            sched._admit(stats)
        # the near row itself was refused the long one, and computed
        assert (stats.prefill_donors_refused_ring,
                stats.prefill_tokens_reused) == (1, 0)
        for _ in range(5):
            sched._decode(stats)
        # shares 120 with the long row (needs 71 and up of 390 - 128) and 110
        # with the near one (150 written: its rings hold 22 and up)
        rider = np.concatenate([stem, prompts.draw(30)])
        req = prompts.request(rider)
        assert sched.submit(req)
        sched._admit(stats)
        assert stats.prefill_donors_refused_ring == 2
        assert stats.prefill_tokens_reused == 150 - EXTEND_WINDOW
        carried = np.asarray(sched._prev_logits[_slot_of(sched, req.id)])
        assert np.abs(carried - _reference_logits(cut, [rider])[0]).max() \
            < TOL
        _all_decode_the_engines_tokens(engine, sched, stats, *rows,
                                       (req, rider), check=False)


# -- the pool: whose positions a call may touch ---------------------------------------


def _leaves(sched):
    return [np.asarray(x) for layer in sched._cache.layers
            for x in (layer.k, layer.v)]


def test_a_recycled_slot_does_not_show_its_last_tenant(toy, pool):
    """A short prompt into the slot whose last tenant ran three times round
    the ring: the whole prompt's logits after prefill and the engine's
    tokens out. The rings still hold the old tenant's keys in slots the new
    row has not reached; their validity follows from the new row's length,
    so nothing sees them."""
    (engine, cut), sched = toy, pool
    with jax.default_matmul_precision("highest"):
        prompts = IdPrompts(engine, cut, SEED + 2, GREEDY, tag="z")
        assert sched.pool.occupancy == 0
        assert all(r.ok for r in sched.serve([prompts.request(
            prompts.draw(370))]))
        old = [x[0].copy() for x in _leaves(sched)]
        assert all(np.abs(x).max() > 0 for x in old)
        ids = prompts.draw(90)
        req = prompts.request(ids)
        stats = ServingStats(num_slots=sched.num_slots)
        assert sched.submit(req)
        sched._admit(stats)
        assert _slot_of(sched, req.id) == 0
        carried = np.asarray(sched._prev_logits[0])
        assert np.abs(carried - _reference_logits(cut, [ids])[0]).max() < TOL
        # a bucket of 128 wrote every ring slot: decode on, then look past
        # the row's reach in a row-long leaf, where the old keys still lie
        _all_decode_the_engines_tokens(engine, sched, stats, (req, ids))
        now = [x[0] for x in _leaves(sched)]
        reach = 128 + NEW
        assert any(x.shape[0] > reach and np.abs(x[reach:]).max() > 0
                   and np.array_equal(x[reach:], o[reach:])
                   for x, o in zip(now, old))


def test_a_prefill_call_moves_only_the_rows_it_names(toy, pool):
    """A live row that has decoded; then a batch of two is admitted beside
    it over six windows. In every prefill call every leaf, ring or row, of
    every slot the call does not name (the live row, the idle slot) is bit
    for bit what it was."""
    (engine, cut), sched = toy, pool
    with jax.default_matmul_precision("highest"):
        assert sched.pool.occupancy == 0
        prompts = IdPrompts(engine, cut, SEED + 3, GREEDY, tag="m")
        stats = ServingStats(num_slots=sched.num_slots)
        first = prompts.request(prompts.draw(370))
        assert sched.submit(first)
        sched._admit(stats)
        sched._decode(stats)
        inner, seen = sched._run_compiled, []

        def watching(fn, *args):
            before = _leaves(sched)
            out = inner(fn, *args)
            if getattr(fn, "program", "") == "serve_prefill":
                after = [np.asarray(x) for layer in out[0].layers
                         for x in (layer.k, layer.v)]
                seen.append((np.asarray(args[5]), before, after))
            return out

        sched._run_compiled = watching
        try:
            for n in (380, 150):
                assert sched.submit(prompts.request(prompts.draw(n)))
            sched._admit_once(stats)
        finally:
            sched._run_compiled = inner
        sched._run_loop(stats)
        assert stats.completed == 3
    assert len(seen) == 6
    for named, before, after in seen:
        others = np.setdiff1d(np.arange(sched.num_slots), named)
        assert len(others) >= 2 and 0 in others
        for b, a in zip(before, after):
            assert np.array_equal(b[others], a[others])
    moved = seen[-1][0][seen[-1][0] < sched.num_slots]
    assert any(not np.array_equal(b[moved], a[moved])
               for b, a in zip(seen[-1][1], seen[-1][2]))


# -- a long bucket: one row ahead in a window wider than 64 (ISSUE 36) -----------------

WIDE_WINDOW, WIDE_RING, LONG = 256, 384, 1024


@pytest.fixture(scope="module")
def wide():
    """The toy with a window of 256: a ring of 384 holds a call of 128
    positions (128 <= 384 - 256 + 1), so in the bucket of 1024 a row that
    copies nothing runs 8 calls of 128 and its writes come round the ring
    twice. (engine, cut, a scheduler of 8 slots, four rows to a batch.)"""
    with jax.default_matmul_precision("highest"):
        engine, cut = toy_engine("trinity-mini-int8", SEED,
                                 sliding_window=WIDE_WINDOW)
        assert ring_len(WIDE_WINDOW) == WIDE_RING
        sched = _scheduler(engine, num_slots=8, prefill_group=4,
                           max_prompt_len=LONG)
        assert sched._rings == [(WIDE_WINDOW, WIDE_RING)]
        assert stepbuilder.extend_window(
            engine.config, LONG, False, sched._rings) == 128
        yield engine, cut, sched


def test_a_pass_of_near_duplicates_is_the_references_and_the_engines(wide):
    """Six prompts of 1000-1005 ids, 990 of them common, at once in an empty
    pool: the first goes ahead alone in 8 calls of 128 positions, round the
    ring of 384 twice; the others wait a batch, copy all but their last 64
    positions from it (rows and rolled rings) and run one window. Every row
    carries the reference's logits after its prompt, and serves the
    reference's and the static engine's tokens."""
    engine, cut, sched = wide
    with jax.default_matmul_precision("highest"), use_timeline() as tl:
        prompts = IdPrompts(engine, cut, SEED + 36, GREEDY, tag="nd")
        stem = prompts.draw(990)
        rows = [np.concatenate([stem, prompts.draw(10 + i)])
                for i in range(6)]
        reqs = [prompts.request(ids) for ids in rows]
        stats = ServingStats(num_slots=sched.num_slots)
        for r in reqs:
            assert sched.submit(r)
        sched._admit(stats)
        plans = _plans(tl)
        assert [(p["rows"], p["nb"], p["S"], p["donors"], p["deferred"],
                 p["windows"]) for p in plans] == [
            (1, 1, 128, 0, 3, 8), (4, 4, EXTEND_WINDOW, 4, 0, 1),
            (1, 1, EXTEND_WINDOW, 1, 0, 1)]
        assert all(p["ring_refused"] == 0 for p in plans)
        spans = [e["args"] for e in tl.events() if e["type"] == "span"
                 and e["name"] == "sched.prefill.dispatch"]
        assert [s["w"] for s in spans] == list(range(0, LONG, 128)) \
            + [LONG - EXTEND_WINDOW] * 2
        assert {s["program"] for s in spans} == {"serve_prefill"}
        assert (stats.prefill_leader_batches, stats.prefill_rows_deferred,
                stats.prefill_batches, stats.prefill_donors_refused_ring) \
            == (1, 3, 10, 0)
        assert stats.prefill_tokens_reused == sum(
            len(r) - EXTEND_WINDOW for r in rows[1:])
        carried = np.asarray(sched._prev_logits)
        want = _reference_logits(cut, rows)
        for req, w in zip(reqs, want):
            assert np.abs(carried[_slot_of(sched, req.id)] - w).max() < TOL
        sched._run_loop(stats)
        assert stats.completed == 6 and stats.failed == 0
        tokens = [np.asarray(sched.take_result(r.id).tokens) for r in reqs]
        sample = [correct.Served(RequestSpec(
            id=r.id, ids=ids, max_new=NEW, row_seed=0), t, True)
            for r, ids, t in zip(reqs, rows, tokens)]
        got = correct.gaps(cut, SEED, sample)
        assert got["served_tokens"] == 6 * NEW and got["widest_gap"] < TOL
        for req, ids, toks in list(zip(reqs, rows, tokens))[:2]:
            assert_same_tokens(engine, req.id, ids, toks,
                               alone(engine, req.id, GREEDY, NEW))


def test_a_recycled_slot_shows_no_tenant_of_before_after_a_wide_window(wide):
    """Slot 0 held a row of 1000 ids and 24 tokens (the test before left the
    pool drained, every leaf full of old keys). A prompt of 965 ids takes it
    alone, in 8 calls of 128: its rings come round twice over the old
    tenant's, and past the bucket the row-long leaves keep the old keys
    until the row decodes over them. The reference's logits after prefill
    and the engine's tokens out."""
    engine, cut, sched = wide
    with jax.default_matmul_precision("highest"), use_timeline() as tl:
        assert sched.pool.occupancy == 0
        old = [x[0].copy() for x in _leaves(sched)]
        assert all(np.abs(x).max() > 0 for x in old)
        prompts = IdPrompts(engine, cut, SEED + 37, GREEDY, tag="rc")
        ids = prompts.draw(965)
        req = prompts.request(ids)
        stats = ServingStats(num_slots=sched.num_slots)
        assert sched.submit(req)
        sched._admit(stats)
        assert _slot_of(sched, req.id) == 0
        (plan,) = _plans(tl)
        assert (plan["S"], plan["windows"], plan["nb"]) == (128, 8, 1)
        carried = np.asarray(sched._prev_logits[0])
        assert np.abs(carried - _reference_logits(cut, [ids])[0]).max() < TOL
        # past the bucket a row-long leaf still holds the old tenant's keys
        now = [x[0] for x in _leaves(sched)]
        assert any(x.shape[0] > LONG and np.abs(x[LONG:]).max() > 0
                   and np.array_equal(x[LONG:], o[LONG:])
                   for x, o in zip(now, old))
        _all_decode_the_engines_tokens(engine, sched, stats, (req, ids))


@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
def test_the_wide_program_writes_rings_and_rows_in_place(wide, guard):
    """The compiled program of a batch that copies nothing, over rings of
    384 in a bucket of 1024: every leaf of the pool and the carried logits
    aliased to an output, no copy of either length."""
    engine, _, sched = wide
    aliased, donated, copies = prefill_aliasing(sched, 1, LONG, guard,
                                                copies=False)
    assert aliased == donated and len(donated) == 8 * 2 + 4 + 1 + 1
    assert copies == 0


# -- what is refused, and what is as it was -------------------------------------------


def test_a_ring_refuses_what_is_not_built_over_it():
    """A shared prefix and a multi-token verify step read or roll back
    positions a ring may no longer hold: refused in ``Attention`` with a
    message, where the cache handed in has a ring. The static engine, whose
    cache keeps every position, runs both."""
    cfg = get_model_config("tiny-window-moe")
    model = Transformer(cfg)
    B, L = 2, 256
    params = jax.eval_shape(lambda: transformer.init_params_lowmem(
        cfg, jax.random.key(0), jnp.float32))
    ring = jax.eval_shape(lambda: init_cache(cfg, B, L, ring=True))
    whole = jax.eval_shape(lambda: init_cache(cfg, B, L))
    assert ring.layers[0].k.shape[1] == RING < whole.layers[0].k.shape[1]
    assert ring.layers[3].k.shape[1] == L
    toks = jnp.zeros((B, 3), jnp.int32)
    offsets = jnp.zeros((B,), jnp.int32)
    shared = tuple((jnp.zeros((8, 2, 16)), jnp.zeros((8, 2, 16)))
                   for _ in range(cfg.num_layers))

    def apply(cache, tokens, **kw):
        return jax.eval_shape(lambda p, c: model.apply(
            {"params": p}, tokens, jnp.zeros(tokens.shape, jnp.int32),
            None, c, **kw), params, cache)

    with pytest.raises(NotImplementedError, match="no speculation over a "
                                                  "ring"):
        apply(ring, toks, write_offsets=offsets)
    with pytest.raises(NotImplementedError, match="shared prefix"):
        apply(ring, toks[:, :1], write_offsets=offsets, shared_layers=shared)
    with pytest.raises(ValueError, match="would wrap inside the call"):
        apply(ring, toks)  # three positions do not divide the ring
    apply(whole, toks, write_offsets=offsets)
    apply(whole, toks[:, :1], write_offsets=offsets, shared_layers=shared)
    apply(ring, toks[:, :1], write_offsets=offsets)  # the decode step


def test_tensor_parallel_serving_is_refused_or_shards_the_ring_by_head():
    """``--tp`` > 1: the new model has routed experts, which the sharding
    rules refuse with their message; a dense model whose window layers keep
    a ring (Mistral's pattern at a long pool) shards the ring's KV head axis
    as it shards a row's."""
    from jax.sharding import Mesh

    from fairness_llm_tpu.parallel import sharding as shd

    tp2 = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
               ("dp", "tp", "sp"))
    with pytest.raises(NotImplementedError, match="dense GQA layers only"):
        shd.make_axis_rules(get_model_config("tiny-window-moe"), tp2)
    dense = dataclasses.replace(get_model_config("tiny-test"),
                                sliding_window=WINDOW)
    cache = jax.eval_shape(lambda: init_cache(dense, 4, 256, ring=True))
    assert cache.layers[0].k.shape == (4, RING, 2, 16)
    specs = shd.kv_tree_shardings(dense, tp2, cache)
    assert specs.layers[0].k.spec == specs.layers[0].v.spec
    assert tuple(specs.layers[0].k.spec) == (None, None, "tp", None)
    assert tuple(specs.key_valid.spec) == ()


def test_cli_refuses_tp_for_routed_experts_over_gqa_layers():
    from fairness_llm_tpu.cli import main as cli

    with pytest.raises(SystemExit, match="no expert axis"):
        cli.main(["--phase", "1", "--quick", "--model", "tiny-window-moe",
                  "--continuous", "--tp", "2", "--no-save"])


@pytest.mark.parametrize("config", ["mistral-7b-int8", "qwen2-7b-int8"])
def test_the_dense_cells_keep_the_pool_and_the_answers_they_had(config):
    """Mistral is the case "every layer, window 4096" through the per-layer
    answer, Qwen2 the case "none"; at the benchmark's pools (384 positions a
    row) and up to 6144 neither keeps a ring, so their programs are the
    ones they had: every leaf as long as the row, the window a mask."""
    from perfbench.families import dense_gqa
    from perfbench.lib import modelcfg

    cfg = dense_gqa.model_config(modelcfg.load_config(os.path.join(
        ROOT, "perfbench", "configs", config + ".json")), config)
    window = 4096 if config.startswith("mistral") else None
    assert cfg.sliding_window == window
    assert cfg.layer_windows == (window,) * cfg.num_layers
    assert cfg.layer_rotary == (True,) * cfg.num_layers
    assert not (cfg.qk_norm or cfg.post_norms or cfg.attention_gate)
    for cache_len in (384, 1280, 6144):
        assert cfg.retained_len(cache_len) == (cache_len,) * cfg.num_layers
    long = cfg.retained_len(8192)
    assert long == ((6144 if window else 8192),) * cfg.num_layers
    pool = jax.eval_shape(lambda: init_cache(cfg, 48, 384, ring=True))
    same = jax.eval_shape(lambda: init_cache(cfg, 48, 384))
    assert jax.tree.map(lambda x: x.shape, pool) == \
        jax.tree.map(lambda x: x.shape, same)


def test_no_layer_in_hand_reads_the_models_one_window():
    """``cfg.sliding_window`` is the every-layer case of ``layer_windows``
    and is read nowhere a layer is in hand: not in the model, not in the
    step programs, not in the scheduler."""
    from fairness_llm_tpu.serving import scheduler

    for module in (transformer, stepbuilder, scheduler):
        assert "sliding_window" not in inspect.getsource(module), module
