"""The plain reference: a full forward pass in float32 ``jax.numpy`` with
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching tricks; it imports nothing of the program and makes its own weights
from the seed, layer by layer, so only one layer's float32 copy is ever live.
The block of each kind of layer is its family's (``perfbench/families``); the
embedding, the loop over the layers, the final norm, the head and the control
are here, the same for every family.

``act_format`` is the control: the same forward with the input of every
linear layer rounded to a lower-precision format per token ("int8": symmetric
per-row int8; "fp8": float8_e4m3), the step below the bfloat16 activations the
configurations state. A block reaches it through ``Ops``, which is the only
way it is given to multiply.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import families

from . import weights as W


def _lower(x, act_format: Optional[str]):
    if act_format is None:
        return x
    if act_format == "int8":
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if act_format == "fp8":
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
        s = jnp.where(s == 0, 1.0, s)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(act_format)


def _dense(x, leaf, act_format):
    wq, scale = leaf
    w = wq.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    return _lower(x, act_format) @ w


@dataclasses.dataclass(frozen=True)
class Ops:
    """How a family's block multiplies, with the control inside."""

    act_format: Optional[str] = None

    def lower(self, x):
        """``x`` as the input of a matrix product."""
        return _lower(x, self.act_format)

    def dense(self, x, leaf):
        """``x @ dequantised(leaf)`` for an ``int8`` leaf (kernel, scale)."""
        return _dense(x, leaf, self.act_format)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("fn", "spec", "act_format"))
def _block(x, layer, fn, spec, act_format):
    """One layer: its family's ``fn`` under the shared ``Ops``."""
    return fn(x, layer, spec, Ops(act_format))


@functools.partial(jax.jit, static_argnames=("eps", "act_format"))
def _head(x, final_norm, lm_head, eps, act_format):
    return _dense(rmsnorm(x, final_norm, eps), lm_head, act_format)


def logits_at(cfg: dict, seed: int, tokens: np.ndarray, take: np.ndarray,
              act_format: Optional[str] = None) -> np.ndarray:
    """Full forward over ``tokens`` [N, S] (right-padded; causal attention
    keeps padding from reaching real positions) and the logits at positions
    ``take`` [N, T] -> float32 [N, T, vocab]."""
    family = families.of(cfg)
    kinds = family.layer_kinds(cfg)
    blocks = {kind: family.block(cfg, kind) for kind in dict.fromkeys(kinds)}
    words = W.seed_words(seed)
    make_layer, make_globals = W.jitted_makers(cfg)
    with jax.default_matmul_precision("highest"):
        glob = make_globals(words)
        x = glob["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
        for i, kind in enumerate(kinds):
            x = _block(x, make_layer(words, i), *blocks[kind], act_format)
        picked = jnp.take_along_axis(
            x, jnp.asarray(take)[:, :, None], axis=1)
        out = _head(picked, glob["final_norm"], glob["lm_head"],
                    float(cfg["rms_norm_eps"]), act_format)
        return np.asarray(out)
