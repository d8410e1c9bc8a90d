"""The dense decoder the two configurations share: every layer the published
block (pre-norm RMSNorm, rotary GQA attention, SwiGLU), int8 weight-only
kernels with float32 per-channel scales, bfloat16 norms, biases and embedding.
The code that ``lib/`` held before families, moved: the same leaves in the same
order under the same keys, the same block, the same counts."""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib.reference import rmsnorm

# -- the configuration --------------------------------------------------------


def load(cfg: dict) -> dict:
    assumed = cfg.get("assumed", {})
    cfg["head_dim"] = assumed.get(
        "head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
    cfg["qkv_bias"] = bool(assumed.get("qkv_bias", False))
    window = cfg.get("sliding_window")
    if cfg.get("use_sliding_window") is False:
        window = None
    cfg["window"] = window
    cfg["pad_token_id"] = assumed.get("pad_token_id", 0)
    return cfg


def rehearsal_cut(cfg: dict) -> dict:
    """The CPU rehearsal's toy: same family flags, sizes a CPU decodes in
    seconds. Never used for a measurement."""
    cut = dict(cfg)
    cut.update(num_hidden_layers=2, hidden_size=256, num_attention_heads=8,
               num_key_value_heads=2 if cfg["num_key_value_heads"]
               < cfg["num_attention_heads"] else 8,
               intermediate_size=512, vocab_size=1024, head_dim=32)
    cut["eos_token_id"] = min(cfg["eos_token_id"], 2)
    return cut


# -- the weights ----------------------------------------------------------------


def layer_kinds(cfg: dict) -> List[str]:
    return ["block"] * cfg["num_hidden_layers"]


def layer_leaves(cfg: dict, kind: str) -> List[Tuple[str, str, tuple]]:
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


# -- the program's names ----------------------------------------------------------


def model_config(cfg: dict, name: str):
    from fairness_llm_tpu.models.configs import ModelConfig

    served = cfg.get("served", {})
    return ModelConfig(
        name=name, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), qkv_bias=cfg["qkv_bias"],
        activation=cfg.get("hidden_act", "silu"),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        sliding_window=cfg["window"], eos_token_id=cfg["eos_token_id"],
        pad_token_id=cfg["pad_token_id"],
        dtype=served.get("activation_dtype", "bfloat16"),
        weight_quant=served.get("weight_quant", "int8"),
    )


def program_tree(cfg: dict, glob: dict, layers: List[dict]) -> dict:
    """The made leaves under the names the program's model looks them up by."""

    def dense(leaf, bias=None):
        out = {"kernel_q": leaf[0], "kernel_scale": leaf[1]}
        if bias is not None:
            out["bias"] = bias
        return out

    tree = {"embedding": glob["embedding"],
            "final_norm": {"scale": glob["final_norm"]},
            "lm_head": dense(glob["lm_head"])}
    for i, lw in enumerate(layers):
        tree[f"layer_{i}"] = {
            "attn_norm": {"scale": lw["attn_norm"]},
            "mlp_norm": {"scale": lw["mlp_norm"]},
            "attn": {p: dense(lw[p], lw.get(p[0] + "_bias"))
                     for p in ("q_proj", "k_proj", "v_proj")}
            | {"o_proj": dense(lw["o_proj"])},
            "mlp": {p: dense(lw[p])
                    for p in ("gate_proj", "up_proj", "down_proj")},
        }
    return tree


# -- the reference's block ----------------------------------------------------------


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


def _block(x, layer, spec, ops):
    """x: [N, S, d] float32 -> [N, S, d]."""
    (h, hkv, hd, eps, theta, window) = spec
    n, s, _ = x.shape
    y = rmsnorm(x, layer["attn_norm"], eps)
    q = ops.dense(y, layer["q_proj"])
    k = ops.dense(y, layer["k_proj"])
    v = ops.dense(y, layer["v_proj"])
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
    x = x + ops.dense(a, layer["o_proj"])
    y = rmsnorm(x, layer["mlp_norm"], eps)
    g = ops.dense(y, layer["gate_proj"])
    u = ops.dense(y, layer["up_proj"])
    return x + ops.dense(jax.nn.silu(g) * u, layer["down_proj"])


def block(cfg: dict, kind: str):
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements the SwiGLU block only")
    return _block, (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"], float(cfg["rms_norm_eps"]),
                    float(cfg["rope_theta"]), cfg["window"])


# -- the counts -----------------------------------------------------------------------


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in matrix products for one token: every
    projection of every layer (the embedding is a lookup, not a product; the
    output head is counted apart)."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def model_flops(cfg: dict, *, prompt_tokens: int, rows: int, decoded: int,
                prefill_context_sum: float, decode_context_sum: float,
                **_) -> float:
    """Model FLOPs of a stretch of serving: 2 x matmul parameters x tokens,
    the output head once per prefilled row (last position only) and once per
    decoded token, and attention's 4*S*D per head and token, where S is the
    mean number of keys a token attends to (half the prompt while it is
    prefilled, the whole context while decoding). Real tokens only: padding
    and idle slots are not work."""
    prefill_context = prefill_context_sum / max(prompt_tokens, 1)
    decode_context = decode_context_sum / max(decoded, 1)
    body = 2 * matmul_params(cfg)
    head = 2 * head_params(cfg)
    attn = 4 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * cfg["num_hidden_layers"]
    return (prompt_tokens * (body + attn * prefill_context)
            + rows * head
            + decoded * (body + head + attn * decode_context))
