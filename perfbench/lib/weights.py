"""Weights from the seed. One rule per kind of leaf, one key per leaf, so the
served tree (made on the device, in the types it is served in) and the reference's per-layer float32 copies are the same numbers made
twice, not handed from one to the other.

Kinds: ``int8`` kernels are uniform int8 with a per-output-channel float32
scale (dequantised values have the configuration's ``initializer_range`` as
their standard deviation, scales vary by a quarter so a dropped scale shows);
``norm`` scales are 1 + 0.1 N(0,1); biases and the embedding are N(0,1) times
the initializer range; all float leaves are bfloat16, as served.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

_UNIFORM_INT8_STD = 73.9  # std of a uniform draw from -128..127


def layer_leaves(cfg: dict) -> List[Tuple[str, str, tuple]]:
    """(name, kind, shape) of one block's leaves, in key order."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    leaves = [("attn_norm", "norm", (d,)),
              ("q_proj", "int8", (d, q)), ("k_proj", "int8", (d, kv)),
              ("v_proj", "int8", (d, kv)), ("o_proj", "int8", (q, d)),
              ("mlp_norm", "norm", (d,)),
              ("gate_proj", "int8", (d, ff)), ("up_proj", "int8", (d, ff)),
              ("down_proj", "int8", (ff, d))]
    if cfg["qkv_bias"]:
        leaves += [("q_bias", "bias", (q,)), ("k_bias", "bias", (kv,)),
                   ("v_bias", "bias", (kv,))]
    return leaves


def global_leaves(cfg: dict) -> List[Tuple[str, str, tuple]]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return [("embedding", "embed", (v, d)), ("final_norm", "norm", (d,)),
            ("lm_head", "int8", (d, v))]


def base_key(seed_words):
    """``seed_words``: uint32[2] (low, high) so that any whole-number seed
    is a traced argument, not a constant that recompiles per seed. The
    generator is XLA's own (``rbg``): the chip makes 7 GB of weights in under a
    second with it, against six with threefry. Its stream is fixed for a
    platform, which is all a run needs: the served tree and the reference's
    copies are made on the same device."""
    return jax.random.fold_in(
        jax.random.key(seed_words[0], impl="rbg"), seed_words[1])


def seed_words(seed: int):
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def make_leaf(key, kind: str, shape: tuple, sigma: float):
    if kind == "int8":
        kq, ks = jax.random.split(key)
        wq = jax.lax.bitcast_convert_type(
            jax.random.bits(kq, shape, jnp.uint8), jnp.int8)
        scale = (sigma / _UNIFORM_INT8_STD) * (
            1.0 + 0.25 * jax.random.uniform(ks, shape[-1:], jnp.float32,
                                            -1.0, 1.0))
        return wq, scale
    if kind == "norm":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    if kind in ("bias", "embed"):
        return (sigma * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)
    raise ValueError(kind)


def _leaf_key(base, layer: int, index: int):
    """Layer -1 holds the global leaves."""
    return jax.random.fold_in(jax.random.fold_in(base, layer + 1), index)


def make_layer(cfg: dict, base, layer: int) -> Dict[str, object]:
    sigma = float(cfg.get("initializer_range", 0.02))
    return {name: make_leaf(_leaf_key(base, layer, i), kind, shape, sigma)
            for i, (name, kind, shape) in enumerate(layer_leaves(cfg))}


def make_globals(cfg: dict, base) -> Dict[str, object]:
    sigma = float(cfg.get("initializer_range", 0.02))
    return {name: make_leaf(_leaf_key(base, -1, i), kind, shape, sigma)
            for i, (name, kind, shape) in enumerate(global_leaves(cfg))}
