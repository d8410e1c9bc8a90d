"""Weights from the seed. One rule per kind of leaf, one key per leaf, so the
served tree (made on the device, in the types it is served in) and the reference's per-layer float32 copies are the same numbers made
twice, not handed from one to the other. Which leaves a layer has, and in what
order, is its family's (``perfbench/families``); the key rule and the
library's leaf kinds are here.

Kinds: ``int8`` kernels are uniform int8 with a per-output-channel float32
scale (dequantised values have the configuration's ``initializer_range`` as
their standard deviation, scales vary by a quarter so a dropped scale shows);
``norm`` scales are 1 + 0.1 N(0,1); biases and the embedding are N(0,1) times
the initializer range; all float leaves are bfloat16, as served.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from perfbench import families

_UNIFORM_INT8_STD = 73.9  # std of a uniform draw from -128..127


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


def make_leaf(key, kind: str, shape: tuple, sigma: float, makers=None):
    """``makers``: the family's own leaf kinds, looked in first."""
    if makers and kind in makers:
        return makers[kind](key, shape, sigma)
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


def _make(cfg: dict, base, layer: int, leaves, makers) -> Dict[str, object]:
    sigma = float(cfg.get("initializer_range", 0.02))
    return {name: make_leaf(_leaf_key(base, layer, i), kind, shape, sigma,
                            makers)
            for i, (name, kind, shape) in enumerate(leaves)}


def jitted_makers(cfg: dict):
    """The jitted makers of a configuration's weights: one small program per
    layer kind, the layer index and the seed's words as data (a single jitted
    call over all layers takes 80 s to compile at 32 unrolled layers, these
    take 3 s and run as fast), and one for the globals. Returns
    ``layer(words, i)`` and ``globals_(words)``."""
    family = families.of(cfg)
    kinds = family.layer_kinds(cfg)
    makers = getattr(family, "LEAF_MAKERS", None)

    def maker(leaves):
        return jax.jit(lambda w, i: _make(cfg, base_key(w), i, leaves, makers))

    make = {kind: maker(family.layer_leaves(cfg, kind))
            for kind in dict.fromkeys(kinds)}
    globals_ = jax.jit(lambda w: _make(
        cfg, base_key(w), -1, family.global_leaves(cfg), makers))

    def layer(words, i: int):
        return make[kinds[i]](words, jnp.int32(i))

    return layer, globals_
