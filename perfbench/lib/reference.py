"""The plain reference: the published decoder block (pre-norm RMSNorm, rotary
GQA attention, SwiGLU) as a full forward pass in float32 ``jax.numpy`` with
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching tricks; it imports nothing of the program and makes its own weights
from the seed, layer by layer, so only one layer's float32 copy is ever live.

``act_format`` is the control: the same forward with the input of every
linear layer rounded to a lower-precision format per token ("int8": symmetric
per-row int8; "fp8": float8_e4m3), the step below the bfloat16 activations the
configurations state.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

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


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: [S, H, D]; rotate_half convention of the published code."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attend_row(q, k, v, window):
    """One sequence. q: [S, H, D]; k, v: [S, Hkv, D]. Causal, windowed."""
    s, h, d = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    ok = j <= i
    if window is not None:
        ok &= (i - j) < window
    scores = jnp.where(ok[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


@functools.partial(jax.jit, static_argnames=("spec",))
def _block(x, layer, spec):
    """x: [N, S, d] float32 -> [N, S, d]. ``spec`` is a hashable tuple of the
    block's constants."""
    (h, hkv, hd, eps, theta, window, act_format) = spec
    n, s, _ = x.shape
    y = _rmsnorm(x, layer["attn_norm"], eps)
    q = _dense(y, layer["q_proj"], act_format)
    k = _dense(y, layer["k_proj"], act_format)
    v = _dense(y, layer["v_proj"], act_format)
    if "q_bias" in layer:
        q = q + layer["q_bias"].astype(jnp.float32)
        k = k + layer["k_bias"].astype(jnp.float32)
        v = v + layer["v_bias"].astype(jnp.float32)
    q = q.reshape(n, s, h, hd)
    k = k.reshape(n, s, hkv, hd)
    v = v.reshape(n, s, hkv, hd)

    def row(args):
        qr, kr, vr = args
        return _attend_row(_rope(qr, theta), _rope(kr, theta), vr, window)

    a = jax.lax.map(row, (q, k, v)).reshape(n, s, h * hd)
    x = x + _dense(a, layer["o_proj"], act_format)
    y = _rmsnorm(x, layer["mlp_norm"], eps)
    g = _dense(y, layer["gate_proj"], act_format)
    u = _dense(y, layer["up_proj"], act_format)
    return x + _dense(jax.nn.silu(g) * u, layer["down_proj"], act_format)


@functools.partial(jax.jit, static_argnames=("eps", "act_format"))
def _head(x, final_norm, lm_head, eps, act_format):
    return _dense(_rmsnorm(x, final_norm, eps), lm_head, act_format)


def logits_at(cfg: dict, seed: int, tokens: np.ndarray, take: np.ndarray,
              act_format: Optional[str] = None) -> np.ndarray:
    """Full forward over ``tokens`` [N, S] (right-padded; causal attention
    keeps padding from reaching real positions) and the logits at positions
    ``take`` [N, T] -> float32 [N, T, vocab]."""
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements the SwiGLU block only")
    words = W.seed_words(seed)
    spec = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]), cfg["window"], act_format)
    make_layer = jax.jit(lambda w, i: W.make_layer(cfg, W.base_key(w), i))
    with jax.default_matmul_precision("highest"):
        glob = jax.jit(lambda w: W.make_globals(cfg, W.base_key(w)))(words)
        x = glob["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = _block(x, make_layer(words, jnp.int32(i)), spec)
        picked = jnp.take_along_axis(
            x, jnp.asarray(take)[:, :, None], axis=1)
        out = _head(picked, glob["final_norm"], glob["lm_head"],
                    float(cfg["rms_norm_eps"]), act_format)
        return np.asarray(out)
