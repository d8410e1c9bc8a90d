"""Model forward tests: cached vs uncached equivalence, padding invariance,
family-flag paths (GPT-2-style, sliding window, GQA)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fairness_llm_tpu.models import Transformer, get_model_config, init_params
from fairness_llm_tpu.models.configs import MODEL_CONFIGS
from fairness_llm_tpu.models.transformer import init_cache


def _forward_uncached(config, params, tokens, token_valid=None, positions=None):
    model = Transformer(config)
    B, S = tokens.shape
    if positions is None:
        positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))
    apply = jax.jit(lambda p, t, po, tv: model.apply({"params": p}, t, po, token_valid=tv))
    logits, _ = apply(params, tokens, positions, token_valid)
    return logits


@pytest.mark.parametrize("name", ["tiny-test", "tiny-gpt2"])
def test_prefill_decode_matches_uncached(name):
    config = get_model_config(name)
    params = init_params(config, jax.random.key(0))
    model = Transformer(config)
    B, S = 2, 10
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, config.vocab_size)

    full_logits = _forward_uncached(config, params, tokens)

    # prefill S-1 tokens, then decode one step
    apply_cached = jax.jit(lambda p, t, po, c: model.apply({"params": p}, t, po, cache=c))
    cache = init_cache(config, B, max_len=S + 4)
    positions = jnp.tile(jnp.arange(S - 1, dtype=jnp.int32)[None], (B, 1))
    prefill_logits, cache = apply_cached(params, tokens[:, : S - 1], positions, cache)
    np.testing.assert_allclose(
        np.asarray(prefill_logits), np.asarray(full_logits[:, : S - 1]), atol=2e-4
    )
    step_pos = jnp.full((B, 1), S - 1, jnp.int32)
    step_logits, cache = apply_cached(params, tokens[:, S - 1 :], step_pos, cache)
    # S=1 vs S=10 take different XLA kernels; the ~7e-5 f32 reassociation noise is
    # amplified ~50x/layer by RMSNorm over tiny-init (0.02-scale) activations.
    # Verified: cache contents and any same-shape compare match exactly.
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0]), np.asarray(full_logits[:, -1]), atol=5e-3
    )
    assert int(cache.index) == S
    assert np.all(np.asarray(cache.lengths) == S)


def test_left_padding_invariance():
    """A left-padded row must produce the same last-token logits as unpadded."""
    config = get_model_config("tiny-test")
    params = init_params(config, jax.random.key(0))
    model = Transformer(config)
    S, pad = 6, 3
    tokens = jax.random.randint(jax.random.key(2), (1, S), 0, config.vocab_size)

    plain = _forward_uncached(config, params, tokens)

    padded = jnp.concatenate([jnp.zeros((1, pad), jnp.int32), tokens], axis=1)
    valid = jnp.concatenate(
        [jnp.zeros((1, pad), bool), jnp.ones((1, S), bool)], axis=1
    )
    positions = jnp.clip(jnp.cumsum(valid, axis=1) - 1, 0).astype(jnp.int32)
    logits = _forward_uncached(config, params, padded, token_valid=valid, positions=positions)
    np.testing.assert_allclose(
        np.asarray(logits[:, -1]), np.asarray(plain[:, -1]), atol=2e-4
    )


def test_sliding_window_changes_attention():
    base = get_model_config("tiny-test")
    windowed = dataclasses.replace(base, name="tiny-swa", sliding_window=4)
    params = init_params(base, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(3), (1, 12), 0, base.vocab_size)
    full = _forward_uncached(base, params, tokens)
    swa = _forward_uncached(windowed, params, tokens)
    # Early positions (inside window) agree; late positions differ.
    np.testing.assert_allclose(np.asarray(full[:, 2]), np.asarray(swa[:, 2]), atol=2e-4)
    assert not np.allclose(np.asarray(full[:, -1]), np.asarray(swa[:, -1]), atol=1e-3)


def test_gqa_head_counts():
    config = get_model_config("tiny-test")
    assert config.num_heads % config.num_kv_heads == 0
    params = init_params(config, jax.random.key(0))
    k_kernel = params["layer_0"]["attn"]["k_proj"]["kernel"]
    assert k_kernel.shape == (config.d_model, config.num_kv_heads * config.head_dim)
    q_kernel = params["layer_0"]["attn"]["q_proj"]["kernel"]
    assert q_kernel.shape == (config.d_model, config.num_heads * config.head_dim)


def test_all_registered_configs_are_consistent():
    for name, cfg in MODEL_CONFIGS.items():
        assert cfg.num_heads % cfg.num_kv_heads == 0, name
        assert cfg.q_dim == cfg.num_heads * cfg.head_dim
        # "none": no positions at all, only beside recurrent layers, which
        # carry the order themselves
        assert cfg.pos_emb in ("rope", "learned") or (
            cfg.pos_emb == "none" and cfg.row_state_bytes > 0), name
        assert cfg.norm in ("rmsnorm", "layernorm")
