"""Delta-rule linear-attention layers that keep a fixed state per row, beside
gated softmax GQA layers without rotary positions, every layer with routed
experts and a shared expert, as ONE CHIP'S SHARE of a stated deployment: the
block that ``Solar-Open2-250B`` publishes (``configs/solar-open2-int8.json``).
int8 weight-only kernels with float32 per-channel scales, the routed experts'
kernels stacked ``[held, K, N]``; bfloat16 router, beta projection,
convolution taps, norms and embedding; float32 ``A_log`` and ``dt_bias``.

The reference's layers, as equations (``x`` is the RMS-normed input ``[T,
d]``, float32 throughout, ``highest`` matmul precision, no cache, no kernel,
no dispatch; ``d_k = d_v = head_dim``, heads ``h`` of the share held).

*Block.* ``x += Mixer_i(norm(x))``; ``x += MoE(norm(x))``. Layer ``i`` is a
softmax layer where ``i`` is in ``gqa_layers`` and a linear layer otherwise.

*Linear layer* (Kimi Delta Attention, arXiv 2510.26692). ``q~, k~, v~ = W
x``; a causal depthwise convolution of ``short_conv_kernel_size`` taps over
time on each channel (tap ``W - 1`` on the current token, zeros before the
sequence), then SiLU; ``q = l2norm(q^)_h / sqrt(d_k)``, ``k = l2norm(k^)_h``,
``v = v^_h`` (``l2norm(z) = z / sqrt(sum z^2 + 1e-6)``); ``a = exp(-exp(A_log[h])
softplus(W_fb (W_fa x) + dt_bias)_h)`` in (0, 1)^d_k, one decay per key
channel; ``b = 2 sigmoid(w_beta,h . x)`` in (0, 2); ``S_t = (I - b k k^T)
Diag(a) S_{t-1} + b k v^T`` with ``S_0 = 0``, computed as ``S' = Diag(a) S; u
= S'^T k; S = S' + b k (v - u)^T``, a plain scan over the sequence; ``o =
S^T q``; ``y = W_o concat_h(RMSNorm(o) * sigmoid(W_gb (W_ga x))_h)``.

*Softmax layer* (``use_rope`` false, ``use_gqa_gate`` true). ``q, k, v = W
x``, no rotary, no QK norm; causal softmax at ``head_dim^-1/2``, ``q heads /
kv heads`` queries per KV head; ``y = W_o (attn * sigmoid(W_gate x))``, the
gate elementwise over heads x head_dim.

*Experts* (every layer). ``p = sigmoid(x W_r)`` over ALL published experts;
the ``num_experts_per_tok`` largest (ties to the lower index); ``w = p_sel /
sum(p_sel)`` (``norm_topk_prob``, ``routed_scaling_factor`` 1); ``y =
Shared(x) + sum_i w_i E_i(x)``, ``E(x) = W_d (silu(W_g x) * W_u x)``.

*The chip's share.* The layer holds heads ``[rank * h, rank * h + h)`` of
each mixer and experts ``[rank * n, rank * n + n)`` of the published counts.
It computes its own heads' part of ``W_o``'s sum, routes over all experts,
normalises over all the chosen, and adds only the chosen experts it holds to
the shared expert; that partial result goes on to the next layer. Nothing
stands in for the absent chips or their exchange.

Departures from the published code, each deliberate:

- the config has no key for the router's score function, a correction bias,
  the shared expert's width, the gates' low-rank width, the factor 2 on beta,
  the activation after the convolution, a convolution bias or the form of
  the softmax gate: each is set as ``assumed`` in the configuration file says
  (sigmoid and none; ``n_shared_experts x moe_intermediate_size``; 128;
  2 (``kda_allow_neg_eigval``); SiLU; none; elementwise);
- ``kda_use_full_proj`` false: the decay's and the output gate's projections
  are low-rank pairs, as above;
- every expert is computed for every token and weighted by zero where it was
  not chosen: the same sum, no dispatch;
- the recurrence is a scan over single tokens, not the chunked form.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from perfbench.families.latent_moe import _int8_stack, _swiglu, route
from perfbench.lib.reference import rmsnorm

STRETCH_COUNTERS = ("moe_local_pairs",)
_L2_EPS = 1e-6

# -- the configuration ----------------------------------------------------------


def load(cfg: dict) -> dict:
    assumed, share = cfg.get("assumed", {}), cfg["deployment"]
    linear = cfg["linear_attn_config"]
    cfg["pad_token_id"] = assumed.get("pad_token_id", 0)
    cfg["eos_token_id"] = assumed["eos_token_id"]
    cfg["router_experts"] = cfg["published"]["n_routed_experts"]
    cfg["expert_shards"] = cfg["head_shards"] = share["chips_sharing_a_layer"]
    cfg["expert_rank"] = cfg["head_rank"] = share["rank"]
    cfg["linear_heads"] = linear["num_heads"]
    cfg["linear_head_dim"] = linear["head_dim"]
    cfg["conv_width"] = linear["short_conv_kernel_size"]
    cfg["gate_rank"] = assumed["gate_low_rank"]
    if cfg["router_experts"] != cfg["n_routed_experts"] * cfg["expert_shards"]:
        raise ValueError("experts held x chips sharing a layer is not the "
                         "published count")
    for held, key in ((cfg["num_attention_heads"], "num_attention_heads"),
                      (cfg["num_key_value_heads"], "num_key_value_heads"),
                      (cfg["linear_heads"], "linear_attn_num_heads")):
        if cfg["published"][key] != held * cfg["head_shards"]:
            raise ValueError(f"{key}: heads held x chips sharing a layer is "
                             "not the published count")
    if not 0 < cfg["eos_token_id"] < cfg["vocab_size"]:
        raise ValueError("the end-of-sequence id lies outside the slice")
    if cfg["use_rope"] or not cfg["use_gqa_gate"] or cfg["kda_use_full_proj"] \
            or not cfg["kda_allow_neg_eigval"] or cfg["first_k_dense_replace"] \
            or float(cfg["routed_scaling_factor"]) != 1.0 \
            or not cfg["norm_topk_prob"]:
        raise ValueError(
            "the reference takes softmax layers without rotary positions and "
            "with an output gate, low-rank delta-rule gates with beta in "
            "(0, 2), no leading dense layer, and normalised top-k weights "
            "at scale 1")
    return cfg


def rehearsal_cut(cfg: dict) -> dict:
    """The CPU rehearsal's toy: one period (a softmax layer and three linear
    layers), 4 of 16 experts held, half the heads, every mechanism present.
    Never measured."""
    cut = dict(cfg)
    cut.update(num_hidden_layers=4, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, linear_heads=4,
               linear_head_dim=32, gate_rank=16, head_shards=2,
               head_rank=min(cfg["head_rank"], 1), moe_intermediate_size=128,
               n_routed_experts=4, router_experts=16, expert_shards=4,
               expert_rank=min(cfg["expert_rank"], 3), num_experts_per_tok=4,
               vocab_size=1024, eos_token_id=min(cfg["eos_token_id"], 2))
    return cut


# -- the weights ------------------------------------------------------------------


def layer_kinds(cfg: dict) -> List[str]:
    softmax = set(cfg["gqa_layers"])
    return ["softmax_layer" if i in softmax else "linear_layer"
            for i in range(cfg["num_hidden_layers"])]


def _conv_taps(key, shape, sigma):
    """Taps of order one: with the library's 0.02 the convolution's output
    would vanish under the norms' epsilons."""
    del sigma
    return (0.5 * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


def _a_log(key, shape, sigma):
    """``exp(A_log)`` uniform in 1..16, as state-space layers are started."""
    del sigma
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape, sigma):
    """The inverse softplus of a step log-uniform in 0.001..0.1, so that a
    channel's decay ``a`` ranges from 0.2 to 0.999 over heads and tokens."""
    del sigma
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


LEAF_MAKERS = {"int8_stack": _int8_stack, "conv": _conv_taps,
               "a_log": _a_log, "dt_bias": _dt_bias}


def _expert_leaves(cfg: dict) -> List[Tuple[str, str, tuple]]:
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, sf = cfg["n_routed_experts"], ff * cfg["n_shared_experts"]
    return [("mlp_norm", "norm", (d,)),
            ("router", "embed", (d, cfg["router_experts"])),
            ("shared_gate", "int8", (d, sf)), ("shared_up", "int8", (d, sf)),
            ("shared_down", "int8", (sf, d)),
            ("experts_gate", "int8_stack", (held, d, ff)),
            ("experts_up", "int8_stack", (held, d, ff)),
            ("experts_down", "int8_stack", (held, ff, d))]


def layer_leaves(cfg: dict, kind: str) -> List[Tuple[str, str, tuple]]:
    """(name, kind, shape) of one layer's leaves, in key order."""
    d = cfg["hidden_size"]
    if kind == "softmax_layer":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        mixer = [("q_proj", "int8", (d, q)), ("k_proj", "int8", (d, kv)),
                 ("v_proj", "int8", (d, kv)), ("gate_proj", "int8", (d, q)),
                 ("o_proj", "int8", (q, d))]
    else:
        h, dk = cfg["linear_heads"], cfg["linear_head_dim"]
        c, w, r = h * dk, cfg["conv_width"], cfg["gate_rank"]
        mixer = [("q_proj", "int8", (d, c)), ("k_proj", "int8", (d, c)),
                 ("v_proj", "int8", (d, c)), ("conv_q", "conv", (w, c)),
                 ("conv_k", "conv", (w, c)), ("conv_v", "conv", (w, c)),
                 ("f_a_proj", "int8", (d, r)), ("f_b_proj", "int8", (r, c)),
                 ("A_log", "a_log", (h,)), ("dt_bias", "dt_bias", (c,)),
                 ("beta_proj", "embed", (d, h)),
                 ("g_a_proj", "int8", (d, r)), ("g_b_proj", "int8", (r, c)),
                 ("o_norm", "norm", (dk,)), ("o_proj", "int8", (c, d))]
    return [("attn_norm", "norm", (d,))] + mixer + _expert_leaves(cfg)


def global_leaves(cfg: dict) -> List[Tuple[str, str, tuple]]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return [("embedding", "embed", (v, d)), ("final_norm", "norm", (d,)),
            ("lm_head", "int8", (d, v))]


# -- the program's names ------------------------------------------------------------


def model_config(cfg: dict, name: str):
    from fairness_llm_tpu.models.configs import LinearHybridConfig

    served, shards = cfg.get("served", {}), cfg["head_shards"]
    return LinearHybridConfig(
        name=name, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"] * shards,
        num_kv_heads=cfg["num_key_value_heads"] * shards,
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
        softmax_layers=tuple(i for i in cfg["gqa_layers"]
                             if i < cfg["num_hidden_layers"]),
        linear_heads=cfg["linear_heads"] * shards,
        linear_head_dim=cfg["linear_head_dim"],
        conv_width=cfg["conv_width"], gate_rank=cfg["gate_rank"],
        head_shards=shards, head_rank=cfg["head_rank"],
        num_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_d_ff=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        expert_shards=cfg["expert_shards"], expert_rank=cfg["expert_rank"],
    )


_DENSE = ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj", "f_a_proj",
          "f_b_proj", "g_a_proj", "g_b_proj")
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
        if "o_norm" in lw:
            attn.update(o_norm=norm(lw["o_norm"]),
                        beta_proj={"kernel": lw["beta_proj"]})
        mlp = {"router": {"kernel": lw["router"]},
               "shared": {p + "_proj": dense(lw["shared_" + p])
                          for p in ("gate", "up", "down")}}
        mlp.update({p: dense(lw[p]) for p in (
            "experts_gate", "experts_up", "experts_down")})
        tree[f"layer_{i}"] = {"attn_norm": norm(lw["attn_norm"]),
                              "mlp_norm": norm(lw["mlp_norm"]),
                              "attn": attn, "mlp": mlp}
    return tree


# -- the reference's blocks ----------------------------------------------------------


def _experts(x, layer, spec, ops):
    """``x + MoE(norm(x))`` over the shared expert and the held experts."""
    eps, top_k, first = spec[0], spec[1], spec[2]
    y = rmsnorm(x, layer["mlp_norm"], eps)
    chosen, weights = route(y, layer["router"], top_k, 1.0, ops)
    out = _swiglu(y, layer["shared_gate"], layer["shared_up"],
                  layer["shared_down"], ops)
    held = layer["experts_gate"][0].shape[0]

    def add_expert(total, e):
        leaves = [(layer[p][0][e], layer[p][1][e]) for p in (
            "experts_gate", "experts_up", "experts_down")]
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        return total + w[..., None] * _swiglu(y, *leaves, ops), None

    out, _ = jax.lax.scan(add_expert, out, jnp.arange(held))
    return x + out


def _softmax_mixer(y, layer, spec, ops):
    h, h_kv, hd = spec[3:6]
    n, s, _ = y.shape
    q = ops.dense(y, layer["q_proj"]).reshape(n, s, h_kv, h // h_kv, hd)
    k = ops.dense(y, layer["k_proj"]).reshape(n, s, h_kv, hd)
    v = ops.dense(y, layer["v_proj"]).reshape(n, s, h_kv, hd)
    scores = jnp.einsum("nqgrd,nkgd->ngrqk", q, k) * hd ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("ngrqk,nkgd->nqgrd", jax.nn.softmax(scores, axis=-1), v)
    gate = jax.nn.sigmoid(ops.dense(y, layer["gate_proj"]))
    return ops.dense(attn.reshape(n, s, h * hd) * gate, layer["o_proj"])


def delta_rule(q, k, v, a, b):
    """The recurrence as a plain scan over the sequence. ``q, k, a [N, S, H,
    dk]``, ``v [N, S, H, dv]``, ``b [N, S, H]`` -> ``o [N, S, H, dv]``, from
    ``S_0 = 0``."""

    def token(state, t):
        q_t, k_t, v_t, a_t, b_t = t
        state = a_t[..., None] * state
        u = jnp.sum(state * k_t[..., None], axis=-2)
        state = state + (b_t[..., None] * k_t)[..., None] \
            * (v_t - u)[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    n, _, h, dk = q.shape
    state0 = jnp.zeros((n, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(token, state0,
                        tuple(t.swapaxes(0, 1) for t in (q, k, v, a, b)))
    return o.swapaxes(0, 1)


def _short_conv(pre, taps):
    """Causal depthwise convolution over time: tap ``W - 1`` on the current
    token, zeros before the sequence. pre ``[N, S, C]``, taps ``[W, C]``."""
    w, s = taps.shape[0], pre.shape[1]
    padded = jnp.pad(pre, ((0, 0), (w - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * taps[i].astype(jnp.float32)
               for i in range(w))


def _l2norm(z):
    return z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + _L2_EPS)


def _output_gate(y, layer, ops):
    return jax.nn.sigmoid(
        ops.dense(ops.dense(y, layer["g_a_proj"]), layer["g_b_proj"]))


def _linear_mixer(y, layer, spec, ops):
    eps, (h, dk) = spec[0], spec[3:5]
    n, s, _ = y.shape

    def conv(name):
        mixed = _short_conv(ops.dense(y, layer[name + "_proj"]),
                            layer["conv_" + name])
        return jax.nn.silu(mixed).reshape(n, s, h, dk)

    q, k, v = _l2norm(conv("q")) * dk ** -0.5, _l2norm(conv("k")), conv("v")
    f = ops.dense(ops.dense(y, layer["f_a_proj"]), layer["f_b_proj"])
    a = jnp.exp(-jnp.exp(layer["A_log"])[:, None] * jax.nn.softplus(
        f + layer["dt_bias"]).reshape(n, s, h, dk))
    b = 2.0 * jax.nn.sigmoid(
        ops.lower(y) @ layer["beta_proj"].astype(jnp.float32))
    o = rmsnorm(delta_rule(q, k, v, a, b), layer["o_norm"], eps)
    return ops.dense(o.reshape(n, s, h * dk) * _output_gate(y, layer, ops),
                     layer["o_proj"])


def _softmax_layer(x, layer, spec, ops):
    y = rmsnorm(x, layer["attn_norm"], spec[0])
    return _experts(x + _softmax_mixer(y, layer, spec, ops), layer, spec, ops)


def _linear_layer(x, layer, spec, ops):
    y = rmsnorm(x, layer["attn_norm"], spec[0])
    return _experts(x + _linear_mixer(y, layer, spec, ops), layer, spec, ops)


def block(cfg: dict, kind: str):
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements SwiGLU only")
    head = (float(cfg["rms_norm_eps"]), cfg["num_experts_per_tok"],
            cfg["expert_rank"] * cfg["n_routed_experts"])
    if kind == "softmax_layer":
        return _softmax_layer, head + (
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])
    return _linear_layer, head + (cfg["linear_heads"], cfg["linear_head_dim"])


# -- the counts ------------------------------------------------------------------------


def mixer_params(cfg: dict, kind: str) -> int:
    """Matmul parameters of one layer's mixer as held here."""
    d = cfg["hidden_size"]
    if kind == "softmax_layer":
        hd = cfg["head_dim"]
        return d * hd * (3 * cfg["num_attention_heads"]
                         + 2 * cfg["num_key_value_heads"])
    h = cfg["linear_heads"]
    c = h * cfg["linear_head_dim"]
    return 4 * d * c + 2 * (d + c) * cfg["gate_rank"] + d * h


def model_flops(cfg: dict, *, prompt_tokens: int, rows: int, decoded: int,
                prefill_context_sum: float, decode_context_sum: float,
                moe_local_pairs: int, **_) -> float:
    """Model FLOPs of a stretch of serving, real tokens only. Per token: 2 x
    each layer's mixer parameters, the router's and the shared expert's; in a
    linear layer the recurrence at 7 x dk x dv a held head (the decay, ``u``,
    the rank-one update, the read-out) and the convolution's taps; per LOCAL
    (token, expert) pair, from the program's counter, one routed expert; in a
    softmax layer ``4 x heads x head_dim`` per (query, key) pair; the head
    once per prefilled row and decoded token."""
    d = cfg["hidden_size"]
    kinds = layer_kinds(cfg)
    n_soft = kinds.count("softmax_layer")
    n_lin = len(kinds) - n_soft
    h, dk = cfg["linear_heads"], cfg["linear_head_dim"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    per_token = (
        2 * (n_soft * mixer_params(cfg, "softmax_layer")
             + n_lin * mixer_params(cfg, "linear_layer")
             + len(kinds) * (d * cfg["router_experts"]
                             + cfg["n_shared_experts"] * expert))
        + n_lin * h * (7 * dk * dk + 2 * 3 * dk * cfg["conv_width"]))
    pair = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * n_soft
    return ((prompt_tokens + decoded) * per_token
            + moe_local_pairs * 2 * expert
            + (rows + decoded) * 2 * d * cfg["vocab_size"]
            + (prefill_context_sum + decode_context_sum) * pair)
