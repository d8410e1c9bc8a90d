"""Latent attention (MLA): its two compositions and the plain reference.

The program runs the benchmark family's toy (``perfbench/families/latent_moe``
``rehearsal_cut``: 1 dense + 2 expert layers) in float32 on the weights the
harness makes from a seed, three ways: expanded over the new tokens (no
cache), expanded prefill then absorbed decode through the latent cache, and
absorbed throughout. Each must give the plain float32 reference's logits.
The tolerance is float32 rounding through three layers (readings near 2e-6),
with room; a missing term moves logits by 1e-2 and more.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.models.configs import get_model_config  # noqa: E402
from fairness_llm_tpu.models.transformer import (  # noqa: E402
    LatentCache,
    Transformer,
    init_cache,
    rope_interleaved,
    yarn_inv_freq,
)
from perfbench import families  # noqa: E402
from perfbench.lib import modelcfg, reference  # noqa: E402
from perfbench.lib import weights as W  # noqa: E402

TOL = 5e-5
SEED = 20261002
N, S, SPLIT = 2, 48, 29


@pytest.fixture(scope="module")
def toy():
    """(cut cfg, float32 ModelConfig, served tree, tokens, reference logits
    at every position)."""
    full = modelcfg.load_config(
        os.path.join(ROOT, "perfbench", "configs", "ax-k1-int8.json"))
    cut = modelcfg.rehearsal_cut(full)
    family = families.of(cut)
    make_layer, make_globals = W.jitted_makers(cut)
    words = W.seed_words(SEED)
    tree = family.program_tree(cut, make_globals(words), [
        make_layer(words, i) for i in range(cut["num_hidden_layers"])])
    mc = dataclasses.replace(family.model_config(cut, "toy"), dtype="float32")
    toks = np.random.default_rng(5).integers(
        3, cut["vocab_size"], (N, S)).astype(np.int32)
    take = np.tile(np.arange(S, dtype=np.int32)[None], (N, 1))
    ref = reference.logits_at(cut, SEED, toks, take)
    return cut, mc, tree, toks, ref


def _positions():
    return jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (N, 1))


def test_expanded_forward_matches_the_reference(toy):
    _, mc, tree, toks, ref = toy
    model = Transformer(mc)
    with jax.default_matmul_precision("highest"):
        got, cache = jax.jit(lambda p, t, pos: model.apply(
            {"params": p}, t, pos))(tree, jnp.asarray(toks), _positions())
    assert cache is None
    assert np.abs(np.asarray(got) - ref).max() < TOL


@pytest.mark.parametrize("absorbed_prefill", [False, True])
def test_decode_through_the_latent_cache_matches_the_reference(
        toy, absorbed_prefill):
    """Prefill (expanded, or absorbed when the caller does not mark a first
    prefill) then one absorbed step a token: the cache's two leaves are all
    that carries the prompt."""
    _, mc, tree, toks, ref = toy
    model = Transformer(mc)
    pos = _positions()

    def prefill(p, t, po):
        cache = init_cache(mc, N, S)
        return model.apply({"params": p}, t, po, jnp.ones_like(t, bool),
                           cache, left_padded=not absorbed_prefill)

    step = jax.jit(lambda p, t, po, c: model.apply(
        {"params": p}, t, po, jnp.ones_like(t, bool), c))
    with jax.default_matmul_precision("highest"):
        logits, cache = jax.jit(prefill)(
            tree, jnp.asarray(toks[:, :SPLIT]), pos[:, :SPLIT])
        worst = np.abs(np.asarray(logits) - ref[:, :SPLIT]).max()
        for i in range(SPLIT, S):
            logits, cache = step(tree, jnp.asarray(toks[:, i:i + 1]),
                                 pos[:, i:i + 1], cache)
            worst = max(worst, np.abs(np.asarray(logits[:, 0])
                                      - ref[:, i]).max())
    assert worst < TOL
    for layer in cache.layers:
        assert isinstance(layer, LatentCache)
        assert layer.c_kv.shape == (N, S, mc.kv_lora_rank)
        assert layer.k_rope.shape == (N, S, mc.qk_rope_head_dim)
    assert mc.cache_values_per_token == mc.kv_lora_rank + mc.qk_rope_head_dim


def test_the_comparison_sees_a_dropped_rope_term(toy, monkeypatch):
    """The tolerance is tight: a reference whose q_rope . k_r term is gone
    differs from the one above by a thousand tolerances."""
    from perfbench.families import latent_moe

    cut, _, _, toks, ref = toy
    monkeypatch.setattr(latent_moe, "_rotate", lambda x, f: x * 0.0)
    jax.clear_caches()
    take = np.tile(np.arange(S, dtype=np.int32)[None], (N, 1))
    broken = reference.logits_at(cut, SEED, toks, take)
    jax.clear_caches()
    assert np.abs(broken - ref).max() > 1000 * TOL


def test_cache_of_the_published_shape_is_576_values_a_token():
    cfg = families.get("latent_moe").model_config(modelcfg.load_config(
        os.path.join(ROOT, "perfbench", "configs", "ax-k1-int8.json")), "x")
    cache = jax.eval_shape(lambda: init_cache(cfg, 4, 32))
    assert [(l.c_kv.shape, l.k_rope.shape) for l in cache.layers] \
        == [((4, 32, 512), (4, 32, 64))] * 15
    assert cfg.cache_values_per_token == 576
    assert cache.counters.shape == (3,)
    assert abs(cfg.softmax_scale - 192 ** -0.5 * 1.3466 ** 2) < 2e-5
    with pytest.raises(NotImplementedError):
        init_cache(dataclasses.replace(cfg, kv_cache_quant=True), 1, 8)


def test_yarn_frequencies_against_a_hand_written_table():
    """A.X-K1's rotary settings (64 dims, theta 1e4, factor 32 over 4096,
    beta 32 / 1). By hand: a pair turns 32 times in 4096 positions at index
    64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 -> 10, once at 22.5 -> 23;
    pairs up to 10 keep 10^(-i/8), pairs from 23 are that over 32, and pair
    16 is 6/13 of the way: 0.01 * (7/13) + (0.01 / 32) * (6/13)."""
    table = {0: 1.0, 10: 10 ** -1.25, 16: 0.01 * 7 / 13 + 0.01 / 32 * 6 / 13,
             23: 10 ** -2.875 / 32, 31: 10 ** -3.875 / 32}
    got = yarn_inv_freq(64, 10000.0, 32.0, 32.0, 1.0, 4096)
    assert got.shape == (32,)
    for i, want in table.items():
        assert abs(got[i] - want) < 1e-6 * want + 1e-12, (i, got[i], want)
    plain = yarn_inv_freq(64, 10000.0, 1.0, 32.0, 1.0, 4096)
    assert np.allclose(plain, 10.0 ** (-np.arange(32) / 8.0), rtol=1e-6)
    from perfbench.families.latent_moe import _yarn_inv_freq

    assert np.allclose(_yarn_inv_freq(64, 10000.0, 32, 32, 1, 4096), got,
                       rtol=1e-6)


def test_rotary_pairs_are_neighbours():
    """Rotating pair (x[2i], x[2i+1]) by position * freq[i]: at position 1
    with one pair and freq pi/2, (1, 0) turns into (0, 1); the result lists
    the rotated firsts, then the seconds."""
    x = jnp.asarray([[[1.0, 0.0, 2.0, 0.0]]])  # [B=1, S=1, D=4]
    pos = jnp.asarray([[1]])
    out = rope_interleaved(x, pos, np.asarray([np.pi / 2, np.pi]))
    assert np.allclose(np.asarray(out[0, 0]), [0.0, -2.0, 1.0, 0.0],
                       atol=1e-6)


def test_the_tiny_preset_decodes_through_the_engine():
    """tiny-mla-moe through DecodeEngine.generate (the static engine's
    program, engine-layout cache writes): greedy tokens equal the argmax of
    the no-cache forward over the same sequence."""
    from fairness_llm_tpu.config import ModelSettings
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    cfg = get_model_config("tiny-mla-moe")
    eng = DecodeEngine(cfg, seed=3)
    out = eng.generate(["hello there", "hi"], ModelSettings(
        temperature=0.0, max_tokens=6), share_prefix=False)
    model = Transformer(cfg)
    for r, prompt in enumerate(["hello there", "hi"]):
        ids = eng.tokenizer.encode(prompt)
        seq = np.asarray(ids + [int(t) for t in out.tokens[r][:5]], np.int32)
        logits, _ = jax.jit(lambda p, t: model.apply(
            {"params": p}, t, jnp.arange(t.shape[1])[None]))(
                eng.params, jnp.asarray(seq[None]))
        greedy = np.asarray(jnp.argmax(logits[0, len(ids) - 1:], axis=-1))
        np.testing.assert_array_equal(greedy, np.asarray(out.tokens[r][:6]))
