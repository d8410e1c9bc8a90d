"""Latent attention (MLA) over a leading dense layer and then routed experts
with a shared expert, as ONE CHIP'S SHARE of a stated deployment: the DeepSeek-V3
shape of block that ``A.X-K1`` publishes (``configs/ax-k1-int8.json``). int8
weight-only kernels with float32 per-channel scales, the routed experts'
kernels stacked ``[held, K, N]``; bfloat16 router, norms and embedding.

The reference's layers, as equations (``x`` is ``[T, d]``, float32 throughout,
``highest`` matmul precision, no cache, no dispatch):

*MLA.* ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, per head ``[q_nope |
q_rope]``; ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv)``; ``k_r`` and
``q_rope`` rotated (``k_r`` once, shared by all heads); ``[k_nope | v]`` per
head ``= c_kv W_kvb``. Scores ``(q_nope . k_nope + q_rope . k_r) * s``, ``s =
(nope + rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
causal softmax; the heads' ``v`` sums concatenated through ``W_o``. The rotary
frequencies are YaRN's (``_yarn_inv_freq``); the cos/sin multiplier is
``mscale / mscale_all_dim``'s ratio of attention factors, 1 for this
configuration and asserted so.

*Experts.* ``p = sigmoid(x W_g)`` over ALL published experts; the
``num_experts_per_tok`` largest (ties to the lower index); ``w =
routed_scaling_factor * p_sel / sum(p_sel)``; ``y = Shared(x) + sum_i w_i
E_i(x)``, ``E(x) = W_d (silu(W_g' x) * W_u x)``. Layer 0 is a dense SwiGLU.

*The chip's share.* The layer holds experts ``[rank * n, rank * n + n)`` of the
published count. It routes over all of them, normalises over all the chosen,
and adds only the chosen experts it holds to the shared expert; that partial
sum goes on to the next layer. Nothing stands in for the absent chips.

Departures from the published code, each deliberate:

- rotary pairs are neighbours ``(x[2i], x[2i+1])`` and are rotated in place;
  the published code de-interleaves first and rotates halves, which permutes
  q_rope and k_r alike and leaves every product as it is (``assumed``:
  ``rope_pairing``);
- ``topk_method`` "none": the plain top k over all experts; ``n_group`` and
  ``topk_group`` stay in the file unused and there is no score-correction bias
  (``assumed``: ``selection``, ``score_correction_bias``);
- every expert is computed for every token and weighted by zero where it was
  not chosen: the same sum, no dispatch.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib.reference import rmsnorm

STRETCH_COUNTERS = ("moe_local_pairs",)

# -- the configuration ----------------------------------------------------------


def load(cfg: dict) -> dict:
    assumed, share = cfg.get("assumed", {}), cfg["deployment"]
    cfg["pad_token_id"] = assumed.get("pad_token_id", 0)
    cfg["eos_token_id"] = assumed["eos_token_id"]
    cfg["router_experts"] = cfg["published"]["n_routed_experts"]
    cfg["expert_shards"] = share["chips_sharing_a_layer"]
    cfg["expert_rank"] = share["expert_rank"]
    if cfg["router_experts"] != cfg["n_routed_experts"] * cfg["expert_shards"]:
        raise ValueError("experts held x chips sharing a layer is not the "
                         "published count")
    if not 0 < cfg["eos_token_id"] < cfg["vocab_size"]:
        raise ValueError("the end-of-sequence id lies outside the slice")
    rope = cfg["rope_scaling"]
    if rope["type"] != "yarn" or rope["mscale"] != rope["mscale_all_dim"]:
        raise ValueError("the reference takes YaRN with a cos/sin multiplier "
                         "of 1 (mscale == mscale_all_dim)")
    if cfg.get("topk_method", "none") != "none" \
            or cfg["scoring_func"] != "sigmoid" or cfg["moe_layer_freq"] != 1:
        raise ValueError("the reference selects the plain top k of sigmoid "
                         "scores in every layer after the dense ones")
    return cfg


def rehearsal_cut(cfg: dict) -> dict:
    """The CPU rehearsal's toy: 1 dense + 2 expert layers, 4 of 16 experts
    held, every mechanism present. Never measured."""
    cut = dict(cfg)
    cut.update(num_hidden_layers=3, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=32,
               qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
               intermediate_size=512, moe_intermediate_size=128,
               n_routed_experts=4, router_experts=16, expert_shards=4,
               expert_rank=min(cfg["expert_rank"], 3), num_experts_per_tok=4,
               vocab_size=1024, eos_token_id=min(cfg["eos_token_id"], 2))
    return cut


# -- the weights ------------------------------------------------------------------


def layer_kinds(cfg: dict) -> List[str]:
    dense = cfg["first_k_dense_replace"]
    return ["dense_layer" if i < dense else "expert_layer"
            for i in range(cfg["num_hidden_layers"])]


def _int8_stack(key, shape, sigma):
    """``[E, K, N]`` int8 kernels with a float32 scale per expert and output
    channel: ``weights.make_leaf``'s ``int8`` rule, stacked."""
    from perfbench.lib.weights import _UNIFORM_INT8_STD

    kq, ks = jax.random.split(key)
    wq = jax.lax.bitcast_convert_type(
        jax.random.bits(kq, shape, jnp.uint8), jnp.int8)
    scale = (sigma / _UNIFORM_INT8_STD) * (
        1.0 + 0.25 * jax.random.uniform(ks, shape[:-2] + shape[-1:],
                                        jnp.float32, -1.0, 1.0))
    return wq, scale


LEAF_MAKERS = {"int8_stack": _int8_stack}


def layer_leaves(cfg: dict, kind: str) -> List[Tuple[str, str, tuple]]:
    """(name, kind, shape) of one layer's leaves, in key order."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    leaves = [("attn_norm", "norm", (d,)),
              ("q_a_proj", "int8", (d, ql)), ("q_a_norm", "norm", (ql,)),
              ("q_b_proj", "int8", (ql, h * (dn + dr))),
              ("kv_a_proj", "int8", (d, kl + dr)),
              ("kv_a_norm", "norm", (kl,)),
              ("kv_b_proj", "int8", (kl, h * (dn + dv))),
              ("o_proj", "int8", (h * dv, d)),
              ("mlp_norm", "norm", (d,))]
    if kind == "dense_layer":
        ff = cfg["intermediate_size"]
        return leaves + [("gate_proj", "int8", (d, ff)),
                         ("up_proj", "int8", (d, ff)),
                         ("down_proj", "int8", (ff, d))]
    ff, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    sf = ff * cfg["n_shared_experts"]
    return leaves + [("router", "embed", (d, cfg["router_experts"])),
                     ("shared_gate", "int8", (d, sf)),
                     ("shared_up", "int8", (d, sf)),
                     ("shared_down", "int8", (sf, d)),
                     ("experts_gate", "int8_stack", (held, d, ff)),
                     ("experts_up", "int8_stack", (held, d, ff)),
                     ("experts_down", "int8_stack", (held, ff, d))]


def global_leaves(cfg: dict) -> List[Tuple[str, str, tuple]]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return [("embedding", "embed", (v, d)), ("final_norm", "norm", (d,)),
            ("lm_head", "int8", (d, v))]


# -- the program's names ------------------------------------------------------------


def model_config(cfg: dict, name: str):
    from fairness_llm_tpu.models.configs import SparseLatentConfig

    served, rope = cfg.get("served", {}), cfg["rope_scaling"]
    return SparseLatentConfig(
        name=name, vocab_size=cfg["vocab_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"],
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        activation=cfg.get("hidden_act", "silu"),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        eos_token_id=cfg["eos_token_id"], pad_token_id=cfg["pad_token_id"],
        dtype=served.get("activation_dtype", "bfloat16"),
        weight_quant=served.get("weight_quant", "int8"),
        use_flash_attention=False,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_factor=float(rope["factor"]),
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_original_max=int(rope["original_max_position_embeddings"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        first_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_d_ff=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        expert_shards=cfg["expert_shards"], expert_rank=cfg["expert_rank"],
    )


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
        attn = {p: dense(lw[p]) for p in ("q_a_proj", "q_b_proj", "kv_a_proj",
                                          "kv_b_proj", "o_proj")}
        attn.update(q_a_norm=norm(lw["q_a_norm"]),
                    kv_a_norm=norm(lw["kv_a_norm"]))
        if "router" in lw:
            mlp = {"router": {"kernel": lw["router"]},
                   "shared": {p + "_proj": dense(lw["shared_" + p])
                              for p in ("gate", "up", "down")}}
            mlp.update({p: dense(lw[p]) for p in (
                "experts_gate", "experts_up", "experts_down")})
        else:
            mlp = {p: dense(lw[p])
                   for p in ("gate_proj", "up_proj", "down_proj")}
        tree[f"layer_{i}"] = {"attn_norm": norm(lw["attn_norm"]),
                              "mlp_norm": norm(lw["mlp_norm"]),
                              "attn": attn, "mlp": mlp}
    return tree


# -- the reference's blocks ----------------------------------------------------------


def _yarn_inv_freq(dim, theta, factor, beta_fast, beta_slow, original_max):
    """The rotary frequency of each pair ``i`` of ``dim / 2``: plain
    ``theta^(-2i/dim)`` where the pair turns more than ``beta_fast`` times in
    the original context, that over ``factor`` where it turns fewer than
    ``beta_slow`` times, a linear ramp in ``i`` between."""
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)

    def pair_that_turns(times):
        return dim * np.log(original_max / (times * 2 * np.pi)) \
            / (2 * np.log(theta))

    low = max(np.floor(pair_that_turns(beta_fast)), 0.0)
    high = min(np.ceil(pair_that_turns(beta_slow)), dim - 1.0)
    if low == high:
        high += 0.001
    scaled_share = np.clip((i - low) / (high - low), 0.0, 1.0)
    return plain * (1 - scaled_share) + plain / factor * scaled_share


def _rotate(x, inv_freq):
    """x: [S, ..., D], position = row index; neighbours are pairs, rotated
    in place."""
    s, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _attention(x, layer, spec, ops):
    (h, dn, dr, dv, rank, eps, scale, inv_freq) = spec[:8]
    n, s, _ = x.shape
    y = rmsnorm(x, layer["attn_norm"], eps)
    c_q = rmsnorm(ops.dense(y, layer["q_a_proj"]), layer["q_a_norm"], eps)
    q = ops.dense(c_q, layer["q_b_proj"]).reshape(n, s, h, dn + dr)
    kv_a = ops.dense(y, layer["kv_a_proj"])
    c_kv = rmsnorm(kv_a[..., :rank], layer["kv_a_norm"], eps)
    k_r = kv_a[..., rank:]
    kv = ops.dense(c_kv, layer["kv_b_proj"]).reshape(n, s, h, dn + dv)

    def row(args):
        qr, kvr, krr = args  # [S, H, dn+dr], [S, H, dn+dv], [S, dr]
        q_rot = _rotate(qr[..., dn:], inv_freq)
        k_rot = _rotate(krr, inv_freq)
        scores = (jnp.einsum("qhd,khd->hqk", qr[..., :dn], kvr[..., :dn])
                  + jnp.einsum("qhd,kd->hqk", q_rot, k_rot)) * scale
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          kvr[..., dn:])

    a = jax.lax.map(row, (q, kv, k_r)).reshape(n, s, h * dv)
    return x + ops.dense(a, layer["o_proj"])


def _swiglu(y, gate, up, down, ops):
    return ops.dense(jax.nn.silu(ops.dense(y, gate)) * ops.dense(y, up), down)


def _dense_layer(x, layer, spec, ops):
    x = _attention(x, layer, spec, ops)
    y = rmsnorm(x, layer["mlp_norm"], spec[5])
    return x + _swiglu(y, layer["gate_proj"], layer["up_proj"],
                       layer["down_proj"], ops)


def route(y, router, top_k: int, scaling: float, ops):
    """(chosen [.., k] expert ids over all experts, their weights)."""
    p = jax.nn.sigmoid(ops.lower(y) @ router.astype(jnp.float32))
    top, chosen = jax.lax.top_k(p, top_k)
    return chosen, scaling * top / jnp.sum(top, axis=-1, keepdims=True)


def routing(x, layer, spec, ops):
    """An expert layer up to its router: (the stream after attention, the
    normed input of the experts, chosen, weights)."""
    (eps, top_k, scaling) = (spec[5],) + spec[8:10]
    x = _attention(x, layer, spec, ops)
    y = rmsnorm(x, layer["mlp_norm"], eps)
    return (x, y) + route(y, layer["router"], top_k, scaling, ops)


def _expert_layer(x, layer, spec, ops):
    first = spec[10]
    x, y, chosen, weights = routing(x, layer, spec, ops)
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


def block(cfg: dict, kind: str):
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements SwiGLU only")
    rope = cfg["rope_scaling"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    m = 0.1 * rope["mscale_all_dim"] * np.log(rope["factor"]) + 1.0
    inv_freq = tuple(float(f) for f in _yarn_inv_freq(
        dr, float(cfg["rope_theta"]), rope["factor"], rope["beta_fast"],
        rope["beta_slow"], rope["original_max_position_embeddings"]))
    spec = (cfg["num_attention_heads"], dn, dr, cfg["v_head_dim"],
            cfg["kv_lora_rank"], float(cfg["rms_norm_eps"]),
            float((dn + dr) ** -0.5 * m * m), inv_freq)
    if kind == "dense_layer":
        return _dense_layer, spec
    first = cfg["expert_rank"] * cfg["n_routed_experts"]
    return _expert_layer, spec + (
        cfg["num_experts_per_tok"], float(cfg["routed_scaling_factor"]),
        first)


# -- the counts ------------------------------------------------------------------------


def attention_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * ql + ql * h * (dn + dr) + d * (kl + dr)
            + kl * h * (dn + dv) + h * dv * d)


def model_flops(cfg: dict, *, prompt_tokens: int, rows: int, decoded: int,
                prefill_context_sum: float, decode_context_sum: float,
                moe_local_pairs: int, **_) -> float:
    """Model FLOPs of a stretch of serving, real tokens only. Per token and
    layer: 2 x the attention projections' parameters (the same count whether
    ``W_kvb`` is applied to the latent or absorbed into the query and the
    output), and the dense layer's, or the router's and the shared expert's,
    MLP; per LOCAL (token, expert) pair, from the program's counter, one
    routed expert; attention per (query, key) pair at the cheaper form of each
    phase: expanded while prefilling (``2 H (nope + rope + v)``), absorbed
    while decoding (``2 H (2 kv_lora_rank + rope)``: the cache is never
    expanded); the head once per prefilled row and decoded token."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kinds = layer_kinds(cfg)
    n_expert = kinds.count("expert_layer")
    n_dense = len(kinds) - n_expert
    expert = 3 * d * cfg["moe_intermediate_size"]
    per_token = 2 * (
        len(kinds) * attention_params(cfg)
        + n_dense * 3 * d * cfg["intermediate_size"]
        + n_expert * (d * cfg["router_experts"]
                      + cfg["n_shared_experts"] * expert))
    head = 2 * d * cfg["vocab_size"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    pair_prefill = 2 * h * (qk + cfg["v_head_dim"]) * len(kinds)
    pair_decode = 2 * h * (2 * cfg["kv_lora_rank"]
                           + cfg["qk_rope_head_dim"]) * len(kinds)
    return ((prompt_tokens + decoded) * per_token
            + moe_local_pairs * 2 * expert
            + (rows + decoded) * head
            + prefill_context_sum * pair_prefill
            + decode_context_sum * pair_decode)
