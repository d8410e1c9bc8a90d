"""Gated DeltaNet layers that keep a fixed state per row, beside full softmax
layers without rotary positions, in OLMo 2's post-norm block with a dense
SwiGLU MLP: the model ``Olmo-Hybrid-7B`` publishes
(``configs/olmo-hybrid-7b-int8.json``), whole, on one chip. int8 weight-only
kernels with float32 per-channel scales; bfloat16 decay and beta
projections, convolution taps, norms and embedding; float32 ``A_log`` and
``dt_bias``.

The reference's layers, as equations (``x`` is the layer's input ``[T,
d]``, float32 throughout, ``highest`` matmul precision, no cache, no kernel;
``N`` an RMSNorm with a learned scale at ``rms_norm_eps``).

*Block* (OLMo 2, all layers). ``h = x + N_a(Mixer_i(x))``, ``y = h +
N_f(W_down(silu(W_gate h) * W_up h))``: no norm on the input of either.
Layer ``i`` is ``layer_types[i]``.

*Linear layer* (Gated DeltaNet, arXiv 2412.06464, as the ``fla`` library's
``GatedDeltaNet`` spells it). ``q~, k~ = W_q x, W_k x`` (``dk`` a head),
``v~ = W_v x`` (``dv`` a head); a causal depthwise convolution of
``linear_conv_kernel_dim`` taps over time on each channel (tap ``W - 1`` on
the current token, zeros before the sequence, no bias), then SiLU; ``q =
l2norm(q^)_h / sqrt(dk)``, ``k = l2norm(k^)_h``, ``v = v^_h`` (``l2norm(z) = z
/ sqrt(sum z^2 + 1e-6)``); ``a = exp(-exp(A_log[h]) softplus(w_a,h . x +
dt_bias[h]))``, one scalar per head and token; ``b = 2 sigmoid(w_b,h . x)`` in
(0, 2) (``linear_allow_neg_eigval``); ``S_t = a (I - b k k^T) S_{t-1} + b k
v^T``, ``S`` ``[dk, dv]`` from ``S_0 = 0``, computed as ``S' = a S; u = S'^T
k; S = S' + b k (v - u)^T``, a plain scan over the sequence; ``o = S^T q``;
``y = W_o concat_h(N_o(o_h) * silu(W_g x)_h)``, ``N_o`` over ``dv``.

*Full layer* (``rope_theta`` null). ``q = N_q(W_q x)``, ``k = N_k(W_k x)``,
each norm over the whole projection (every head at once), ``v = W_v x``;
causal softmax per head at ``head_dim^-1/2``, one KV head a query head;
``y = W_o attn``.

Departures from the published code, each deliberate:

- the config has no key for ``head_dim``, the l2 norms' epsilon, the
  output norm's epsilon, the convolution's bias and activation, the decay's
  form or the query scale: each is set as ``assumed`` in the configuration
  file says (hidden / heads = 128; 1e-6; ``rms_norm_eps``; none and SiLU;
  Gated DeltaNet's; ``dk^-1/2``);
- q, k and v of a linear layer are made as ONE int8 leaf ``[d, 2 dk h + dv
  h]``, its columns in that order: with a scale per output channel that is
  the same numbers as three leaves, and the program multiplies it so;
- the recurrence is a scan over single tokens, not the chunked form.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from perfbench.families.latent_moe import _swiglu
from perfbench.families.linear_hybrid_moe import (
    _a_log,
    _conv_taps,
    _dt_bias,
    _l2norm,
    _short_conv,
    delta_rule,
)
from perfbench.lib.reference import rmsnorm

_KINDS = {"linear_attention": "linear_layer", "full_attention": "full_layer"}

# -- the configuration ----------------------------------------------------------


def load(cfg: dict) -> dict:
    assumed = cfg.get("assumed", {})
    cfg["pad_token_id"] = assumed.get("pad_token_id", 0)
    cfg["eos_token_id"] = assumed["eos_token_id"]
    cfg["head_dim"] = assumed["head_dim"]
    cfg["linear_heads"] = cfg["linear_num_value_heads"]
    cfg["linear_head_dim"] = cfg["linear_key_head_dim"]
    cfg["linear_value_dim"] = cfg["linear_value_head_dim"]
    cfg["conv_width"] = cfg["linear_conv_kernel_dim"]
    if not 0 < cfg["eos_token_id"] < cfg["vocab_size"]:
        raise ValueError("the end-of-sequence id lies outside the vocabulary")
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"] \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or not cfg["linear_allow_neg_eigval"] or cfg["attention_bias"] \
            or cfg["rope_parameters"].get("rope_theta") is not None \
            or set(cfg["layer_types"][:cfg["num_hidden_layers"]]) - set(_KINDS):
        raise ValueError(
            "the reference takes as many key as value heads in both mixers, "
            "beta in (0, 2), no biases, no rotation, and linear_attention or "
            "full_attention layers")
    return cfg


def rehearsal_cut(cfg: dict) -> dict:
    """The CPU rehearsal's toy: one period (three linear layers, then a full
    one), a state 32 x 64 a head, every mechanism present. Never measured."""
    cut = dict(cfg)
    cut.update(num_hidden_layers=4, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=4, head_dim=64, linear_heads=4,
               linear_head_dim=32, linear_value_dim=64,
               intermediate_size=512, vocab_size=1024,
               eos_token_id=min(cfg["eos_token_id"], 2))
    return cut


# -- the weights ------------------------------------------------------------------


def layer_kinds(cfg: dict) -> List[str]:
    return [_KINDS[t] for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def _gate_proj(key, shape, sigma):
    """The decay's and beta's projections read the un-normed stream, whose
    size grows with depth: at a tenth of the library's spread ``a`` ranges
    over 0.15..0.99 and ``b`` stays off its ends, where 0.02 would forget the
    state at every token."""
    return (0.1 * sigma * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


LEAF_MAKERS = {"conv": _conv_taps, "a_log": _a_log, "dt_bias": _dt_bias,
               "gate_proj": _gate_proj}


def _mlp_leaves(cfg: dict) -> List[Tuple[str, str, tuple]]:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return [("attn_post_norm", "norm", (d,)),
            ("gate_proj", "int8", (d, ff)), ("up_proj", "int8", (d, ff)),
            ("down_proj", "int8", (ff, d)), ("mlp_post_norm", "norm", (d,))]


def layer_leaves(cfg: dict, kind: str) -> List[Tuple[str, str, tuple]]:
    """(name, kind, shape) of one layer's leaves, in key order."""
    d = cfg["hidden_size"]
    if kind == "full_layer":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        mixer = [("q_proj", "int8", (d, q)), ("k_proj", "int8", (d, kv)),
                 ("v_proj", "int8", (d, kv)), ("o_proj", "int8", (q, d)),
                 ("q_norm", "norm", (q,)), ("k_norm", "norm", (kv,))]
    else:
        h, w = cfg["linear_heads"], cfg["conv_width"]
        ck = h * cfg["linear_head_dim"]
        cv = h * cfg["linear_value_dim"]
        mixer = [("qkv_proj", "int8", (d, 2 * ck + cv)),
                 ("conv_q", "conv", (w, ck)), ("conv_k", "conv", (w, ck)),
                 ("conv_v", "conv", (w, cv)),
                 ("a_proj", "gate_proj", (d, h)), ("A_log", "a_log", (h,)),
                 ("dt_bias", "dt_bias", (h,)),
                 ("beta_proj", "gate_proj", (d, h)),
                 ("g_proj", "int8", (d, cv)),
                 ("o_norm", "norm", (cfg["linear_value_dim"],)),
                 ("o_proj", "int8", (cv, d))]
    return mixer + _mlp_leaves(cfg)


def global_leaves(cfg: dict) -> List[Tuple[str, str, tuple]]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return [("embedding", "embed", (v, d)), ("final_norm", "norm", (d,)),
            ("lm_head", "int8", (d, v))]


# -- the program's names ------------------------------------------------------------


def model_config(cfg: dict, name: str):
    from fairness_llm_tpu.models.configs import LinearHybridConfig

    served = cfg.get("served", {})
    return LinearHybridConfig(
        name=name, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        norm_eps=float(cfg["rms_norm_eps"]), pos_emb="none",
        activation=cfg.get("hidden_act", "silu"),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        eos_token_id=cfg["eos_token_id"], pad_token_id=cfg["pad_token_id"],
        dtype=served.get("activation_dtype", "bfloat16"),
        weight_quant=served.get("weight_quant", "int8"),
        use_flash_attention=False,
        softmax_layers=tuple(i for i, kind in enumerate(layer_kinds(cfg))
                             if kind == "full_layer"),
        linear_heads=cfg["linear_heads"],
        linear_head_dim=cfg["linear_head_dim"],
        linear_value_head_dim=cfg["linear_value_dim"],
        conv_width=cfg["conv_width"], linear_mixer="gdn", block="olmo2",
    )


_DENSE = ("q_proj", "k_proj", "v_proj", "o_proj", "qkv_proj", "g_proj")
_RAW = ("conv_q", "conv_k", "conv_v", "A_log", "dt_bias")


def program_tree(cfg: dict, glob: dict, layers: List[dict]) -> dict:
    """The made leaves under the names the program's model looks them up by."""

    def dense(leaf):
        return {"kernel_q": leaf[0], "kernel_scale": leaf[1]}

    def norm(leaf):
        return {"scale": leaf}

    tree = {"embedding": glob["embedding"],
            "final_norm": norm(glob["final_norm"]),
            "lm_head": dense(glob["lm_head"])}
    for i, lw in enumerate(layers):
        attn = {p: dense(lw[p]) for p in _DENSE if p in lw}
        attn.update({p: lw[p] for p in _RAW if p in lw})
        attn.update({p: norm(lw[p]) for p in ("q_norm", "k_norm", "o_norm")
                     if p in lw})
        attn.update({p: {"kernel": lw[p]} for p in ("a_proj", "beta_proj")
                     if p in lw})
        tree[f"layer_{i}"] = {
            "attn": attn, "attn_post_norm": norm(lw["attn_post_norm"]),
            "mlp": {p: dense(lw[p])
                    for p in ("gate_proj", "up_proj", "down_proj")},
            "mlp_post_norm": norm(lw["mlp_post_norm"])}
    return tree


# -- the reference's blocks ----------------------------------------------------------


def _full_mixer(x, layer, spec, ops):
    eps, h, hd = spec[0], spec[1], spec[2]
    n, s, _ = x.shape
    q = rmsnorm(ops.dense(x, layer["q_proj"]), layer["q_norm"], eps)
    k = rmsnorm(ops.dense(x, layer["k_proj"]), layer["k_norm"], eps)
    v = ops.dense(x, layer["v_proj"])
    q, k, v = (t.reshape(n, s, h, hd) for t in (q, k, v))
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) * hd ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(scores, axis=-1), v)
    return ops.dense(attn.reshape(n, s, h * hd), layer["o_proj"])


def _decay(x, layer, ops):
    """``a [N, S, heads]``: one scalar per head and token."""
    f = ops.lower(x) @ layer["a_proj"].astype(jnp.float32)
    return jnp.exp(-jnp.exp(layer["A_log"]) * jax.nn.softplus(
        f + layer["dt_bias"]))


def _output_gate(x, layer, ops):
    return jax.nn.silu(ops.dense(x, layer["g_proj"]))


def _linear_mixer(x, layer, spec, ops):
    eps, (h, dk, dv) = spec[0], spec[1:4]
    n, s, _ = x.shape
    ck = h * dk
    qkv = ops.dense(x, layer["qkv_proj"])

    def conv(lo, hi, name, width):
        mixed = _short_conv(qkv[..., lo:hi], layer["conv_" + name])
        return jax.nn.silu(mixed).reshape(n, s, h, width)

    q = _l2norm(conv(0, ck, "q", dk)) * dk ** -0.5
    k = _l2norm(conv(ck, 2 * ck, "k", dk))
    v = conv(2 * ck, 2 * ck + h * dv, "v", dv)
    a = jnp.broadcast_to(_decay(x, layer, ops)[..., None], (n, s, h, dk))
    b = 2.0 * jax.nn.sigmoid(
        ops.lower(x) @ layer["beta_proj"].astype(jnp.float32))
    o = rmsnorm(delta_rule(q, k, v, a, b), layer["o_norm"], eps)
    return ops.dense(o.reshape(n, s, h * dv) * _output_gate(x, layer, ops),
                     layer["o_proj"])


def _post_norm_block(mixer):
    def layer_fn(x, layer, spec, ops):
        eps = spec[0]
        h = x + rmsnorm(mixer(x, layer, spec, ops), layer["attn_post_norm"],
                        eps)
        y = _swiglu(h, layer["gate_proj"], layer["up_proj"],
                    layer["down_proj"], ops)
        return h + rmsnorm(y, layer["mlp_post_norm"], eps)

    return layer_fn


_FULL_LAYER = _post_norm_block(_full_mixer)
_LINEAR_LAYER = _post_norm_block(_linear_mixer)


def block(cfg: dict, kind: str):
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements SwiGLU only")
    eps = float(cfg["rms_norm_eps"])
    if kind == "full_layer":
        return _FULL_LAYER, (eps, cfg["num_attention_heads"], cfg["head_dim"])
    return _LINEAR_LAYER, (eps, cfg["linear_heads"], cfg["linear_head_dim"],
                           cfg["linear_value_dim"])


# -- the counts ------------------------------------------------------------------------


def matmul_params(cfg: dict, kind: str) -> int:
    """Parameters of one layer that take part in matrix products."""
    d = cfg["hidden_size"]
    mlp = 3 * d * cfg["intermediate_size"]
    if kind == "full_layer":
        hd = cfg["head_dim"]
        return mlp + 2 * d * hd * (cfg["num_attention_heads"]
                                   + cfg["num_key_value_heads"])
    h = cfg["linear_heads"]
    ck, cv = h * cfg["linear_head_dim"], h * cfg["linear_value_dim"]
    # q, k, v; a and b; the output gate; o
    return mlp + d * (2 * ck + cv) + 2 * d * h + d * cv + cv * d


def model_flops(cfg: dict, *, prompt_tokens: int, rows: int, decoded: int,
                prefill_context_sum: float, decode_context_sum: float,
                **_) -> float:
    """Model FLOPs of a stretch of serving, real tokens only. Per token: 2 x
    every layer's matmul parameters; in a linear layer the recurrence at 7
    x dk x dv a head (the decay, ``u``, the rank-one update, the read-out)
    and the convolution's taps, 2 a tap and channel; in a full layer ``4 x
    heads x head_dim`` per (query, key) pair; the head once per prefilled
    row and decoded token."""
    d = cfg["hidden_size"]
    kinds = layer_kinds(cfg)
    h, dk, dv = cfg["linear_heads"], cfg["linear_head_dim"], \
        cfg["linear_value_dim"]
    n_lin = kinds.count("linear_layer")
    per_token = (2 * sum(matmul_params(cfg, kind) for kind in kinds)
                 + n_lin * (7 * h * dk * dv
                            + 2 * h * (2 * dk + dv) * cfg["conv_width"]))
    pair = 4 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * kinds.count("full_layer")
    return ((prompt_tokens + decoded) * per_token
            + (rows + decoded) * 2 * d * cfg["vocab_size"]
            + (prefill_context_sum + decode_context_sum) * pair)
