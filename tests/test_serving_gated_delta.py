"""Gated DeltaNet layers beside full NoPE layers in OLMo's post-norm block, on
the serving path: DecodeEngine -> ContinuousScheduler -> the stepbuilder
programs, a per-row state of ``dk x dv`` with ``dv`` no multiple of 128 kept
two heads side by side in the slot pool, prefill and then decode, against
the plain float32 reference's ONE forward over the whole sequence
(``perfbench/families/gated_delta_hybrid.py``).

The program runs the family's toy (one period: three linear layers with a
32 x 64 state a head, then one full layer; dense MLPs) in float32 on int8
weights made from a seed, so what is compared is the mathematics. The
readings are float32 rounding, a few 1e-6 on logits of size 1 (the state is
a sum over up to 146 tokens, in another order than the reference's). The
tolerance is 1e-4, as the other hybrids' serving tests have it. A bfloat16
state, a decay made per key channel, a sigmoid output gate or a pre-norm
block moves the same logits by far more than the tolerance, and a test says
so of each.
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
from fairness_llm_tpu.models.configs import get_model_config  # noqa: E402
from fairness_llm_tpu.models.transformer import RowState  # noqa: E402
from fairness_llm_tpu.serving import ContinuousScheduler  # noqa: E402
from fairness_llm_tpu.telemetry import use_registry  # noqa: E402
from perfbench.families import gated_delta_hybrid as family  # noqa: E402
from perfbench.families import linear_hybrid_moe  # noqa: E402
from perfbench.lib import correct, reference  # noqa: E402
from perfbench.lib.traffic import RequestSpec  # noqa: E402
from toys import IdPrompts, toy_engine  # noqa: E402

TOL = 1e-4
SEED = 43
NEW = 6
GREEDY = ModelSettings(temperature=0.0, max_tokens=NEW)
# Buckets of 64, 128 and 192, each prefilled in one call.
LENGTHS = [5, 70, 18, 100, 9, 140]


@pytest.fixture(scope="module")
def toy():
    with jax.default_matmul_precision("highest"):
        yield toy_engine("olmo-hybrid-7b-int8", SEED)


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
    slots): (cut, specs, their served tokens, the scheduler)."""
    engine, cut = toy
    rng = np.random.default_rng(SEED)
    specs = [RequestSpec(id=f"o{i}", ids=rng.integers(
        3, cut["vocab_size"], size=n).astype(np.int32), max_new=NEW,
        row_seed=i) for i, n in enumerate(LENGTHS)]
    prompts = IdPrompts(engine, cut, SEED, GREEDY, tag="o")
    requests = [prompts.request(s.ids) for s in specs]
    serving = ServingConfig(
        enabled=True, num_slots=2, queue_capacity=32, max_prompt_len=192,
        max_new_tokens=NEW, decode_chunk=4, prefill_group=2)
    with jax.default_matmul_precision("highest"), use_registry():
        sched = ContinuousScheduler(engine, serving, settings=GREEDY)
        results = sched.serve(requests)
    assert all(r.ok for r in results), [r.error for r in results]
    return cut, specs, [np.asarray(r.tokens) for r in results], sched


def test_the_pool_keeps_two_heads_side_by_side(served, toy):
    engine, cut = toy
    cfg = engine.config
    assert (cfg.linear_head_dim, cfg.value_head_dim) == (32, 64)
    assert cfg.row_state_shape == (2, 32, 128)
    states = [l.state for l in served[3]._cache.layers
              if isinstance(l, RowState)]
    assert len(states) == 3
    assert all(s.shape == (2, 2, 32, 128) for s in states)
    # three linear layers x (4 heads x 32 x 64 + 3 x 4 x 128 inputs) x 4 B
    assert cfg.row_state_bytes == 3 * (4 * 32 * 64 * 4 + 3 * 512 * 4)


def test_served_tokens_are_the_references_within_float32_rounding(served):
    cut, specs, tokens, _ = served
    sample = [correct.Served(s, t, True) for s, t in zip(specs, tokens)]
    assert correct.length_faults(sample, cut["eos_token_id"]) == 0
    got = correct.gaps(cut, SEED, sample)
    assert got["served_tokens"] == sum(len(t) for t in tokens)
    assert got["widest_gap"] < TOL


def _carried_errors(served):
    """Per request, how far the nearest slot's carried logits lie from the
    reference's logits after the request's last token."""
    cut, specs, tokens, sched = served
    carried = np.asarray(sched._prev_logits)
    want = _reference_logits(
        cut, [np.concatenate([s.ids, t]) for s, t in zip(specs, tokens)])
    return [np.abs(carried - w[None]).max(axis=1).min() for w in want]


def test_carried_logits_are_the_references(served):
    """The logits each slot carries for its last tenant, after a prefill
    call and six steps of decode through the pool: the reference's at the
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
        # reduce_precision: the TPU compiler drops a round trip of casts
        state = jax.lax.reduce_precision(state, exponent_bits=8,
                                         mantissa_bits=7)
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    n, _, h, dk = q.shape
    _, o = jax.lax.scan(token, jnp.zeros((n, h, dk, v.shape[-1]),
                                         jnp.float32),
                        tuple(t.swapaxes(0, 1) for t in (q, k, v, a, b)))
    return o.swapaxes(0, 1)


def _per_channel(q, k, v, a, b):
    """The head's decay spread over its key channels, from a^0.5 to a^1.5."""
    spread = jnp.linspace(0.5, 1.5, a.shape[-1])
    return linear_hybrid_moe.delta_rule(q, k, v, a ** spread, b)


def _pre_norm(mixer):
    def layer_fn(x, layer, spec, ops):
        eps = spec[0]
        h = x + mixer(reference.rmsnorm(x, layer["attn_post_norm"], eps),
                      layer, spec, ops)
        y = reference.rmsnorm(h, layer["mlp_post_norm"], eps)
        return h + family._swiglu(y, layer["gate_proj"], layer["up_proj"],
                                  layer["down_proj"], ops)

    return layer_fn


@pytest.mark.parametrize("broken", [
    "bf16_state", "decay_per_channel", "sigmoid_gate", "pre_norm_block"])
def test_the_tolerance_catches_a_lower_precision_or_another_layer(
        served, monkeypatch, broken):
    if broken == "bf16_state":
        monkeypatch.setattr(family, "delta_rule", _bf16_state)
    elif broken == "decay_per_channel":
        monkeypatch.setattr(family, "delta_rule", _per_channel)
    elif broken == "sigmoid_gate":
        monkeypatch.setattr(family, "_output_gate", lambda x, layer, ops:
                            jax.nn.sigmoid(ops.dense(x, layer["g_proj"])))
    else:
        monkeypatch.setattr(family, "_LINEAR_LAYER",
                            _pre_norm(family._linear_mixer))
        monkeypatch.setattr(family, "_FULL_LAYER",
                            _pre_norm(family._full_mixer))
    jax.clear_caches()
    try:
        errors = _carried_errors(served)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert min(errors) > 20 * TOL, (broken, errors)


def test_the_preset_holds_the_published_widths():
    cfg = get_model_config("olmo-hybrid-7b-int8")
    assert (cfg.d_model, cfg.d_ff, cfg.num_layers, cfg.vocab_size) \
        == (3840, 11008, 32, 100352)
    assert cfg.softmax_layers == (3, 7, 11, 15, 19, 23, 27, 31)
    assert (cfg.linear_heads, cfg.linear_head_dim, cfg.value_head_dim,
            cfg.conv_width) == (30, 96, 192, 4)
    assert cfg.row_state_shape == (15, 96, 384)
    assert set(cfg.layer_kinds) == {"dense"} and cfg.weight_quant == "int8"
    assert (cfg.pre_norms, cfg.post_norms, cfg.qk_norm_whole,
            cfg.attention_gate, cfg.pos_emb) == (False, True, True, False,
                                                  "none")
    # 53.08 MB of state and 1.66 MB of convolution inputs a row
    assert cfg.row_state_bytes == 24 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    # what the configuration file's bytes say: 6.66e9 in the layers
    layers = sum(cfg.mixer_param_count(m) + cfg.mlp_param_count(k)
                 for m, k in zip(cfg.mixer_kinds, cfg.layer_kinds))
    assert 6.65e9 < layers < 6.67e9


def test_the_tiny_preset_decodes_through_its_cache_as_one_forward():
    """``tiny-gdn`` (the preset of the same kinds of layer): a prompt and
    then three tokens one at a time through the cache give the logits of
    one forward over the whole sequence with no cache."""
    from fairness_llm_tpu.models.transformer import (
        Transformer,
        init_cache,
        init_params,
    )

    cfg = get_model_config("tiny-gdn")
    assert cfg.row_state_shape == (2, 16, 128)  # 64 values, two heads a row
    model = Transformer(cfg)
    params = init_params(cfg, jax.random.key(SEED))
    ids = jnp.asarray(np.random.default_rng(SEED).integers(
        3, cfg.vocab_size, size=(2, 12)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(12, dtype=jnp.int32), (2, 12))
    apply = jax.jit(lambda p, t, q, c: model.apply({"params": p}, t, q,
                                                     cache=c))
    with jax.default_matmul_precision("highest"):
        whole, _ = jax.jit(lambda p: model.apply({"params": p}, ids, pos))(
            params)
        got, cache = apply(params, ids[:, :9], pos[:, :9],
                           init_cache(cfg, 2, 12))
        steps = [got[:, -1]]
        for t in range(9, 12):
            got, cache = apply(params, ids[:, t:t + 1], pos[:, t:t + 1],
                               cache)
            steps.append(got[:, -1])
    want = whole[:, 8:]
    assert float(jnp.max(jnp.abs(jnp.stack(steps, 1) - want))) < TOL
