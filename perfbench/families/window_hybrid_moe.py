"""GQA layers that see a window beside GQA layers that see everything, each
with QK-norm, an output gate and four norms, over two leading dense layers
and then routed experts with a shared expert, as ONE CHIP'S SHARE of a stated
deployment: the ``afmoe`` block that ``Trinity-Mini`` publishes
(``configs/trinity-mini-int8.json``). int8 weight-only kernels with float32
per-channel scales, the routed experts' kernels stacked ``[held, K, N]``;
bfloat16 router, norms and embedding; a float32 selection bias.

The reference's layers, as equations (float32 throughout, ``highest`` matmul
precision, no cache, no ring, no kernel, no dispatch; ``d`` the hidden size,
heads ``h`` and KV heads ``g`` of the share held, ``D = head_dim``).

*Stream.* ``x0 = sqrt(d) E[token]`` (``mup_enabled``: the embedding alone is
scaled). Per layer ``h = x + N2(Attn(N1(x)))``, ``y = h + N4(FFN(N3(h)))``:
four RMSNorms, before and after the mixer and the MLP.

*Attention.* ``q, k, v = W u``; per head ``q = RMSNorm_q(q)``, ``k =
RMSNorm_k(k)`` over the ``D`` values of a head, learned scale, before any
rotation. A ``sliding_attention`` layer rotates ``q`` and ``k`` (theta
``rope_theta``, the whole head, halves as pairs: ``(x[i], x[i + D/2])``) and
query ``i`` sees key ``j`` iff ``j <= i`` and ``i - j < sliding_window``; a
``full_attention`` layer rotates nothing and sees every ``j <= i``. ``o =
softmax(q k^T / sqrt(D)) v``, ``h / g`` queries per KV head, computed in
blocks of queries (three rows of 8.2k positions fit); out ``= W_o (o *
sigmoid(W_g u))``, the gate elementwise over heads x ``D``.

*FFN.* The first ``num_dense_layers`` layers: SwiGLU of ``intermediate_size``.
The rest: ``s = sigmoid(x W_r)`` over ALL published experts; the
``num_experts_per_tok`` largest of ``s + b`` (ties to the lower index; ``b``
the selection bias, used to choose and never to weigh); ``w = route_scale *
s_sel / (sum s_sel + 1e-20)`` (``route_norm``); ``Shared(x) + sum_i w_i
E_i(x)``, ``E(x) = W_d (silu(W_g x) * W_u x)``, the weight on the expert's
OUTPUT. One group (``n_group`` 1): no group limit.

*The chip's share.* The layer holds heads ``[rank * h, rank * h + h)``, their
KV heads, and experts ``[rank * n, rank * n + n)`` of the published counts.
It computes its own heads' part of ``W_o``'s sum, routes over all experts,
normalises over all the chosen, and adds only the chosen experts it holds to
the shared expert; the dense MLPs, the shared expert, the router and the
norms are whole. That partial result goes on to the next layer. Nothing
stands in for the absent chips or their exchange.

Departures from the published code, each deliberate:

- the config has no key for the order of weight and expert, for what else
  ``mup_enabled`` scales, or for the selection bias's values: each is set as
  ``assumed`` in the configuration file says (the outputs; nothing; drawn
  small from the seed);
- every expert is computed for every token and weighted by zero where it was
  not chosen: the same sum, no dispatch;
- the embedding's scale is applied by layer 0's block (a kind of its own,
  ``*_first``): the harness's loop embeds without one.
"""

from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp

from perfbench.families.latent_moe import _int8_stack, _swiglu
from perfbench.lib.reference import rmsnorm

STRETCH_COUNTERS = ("moe_local_pairs", "prefill_tokens_reused")
_QUERY_BLOCK = 512

# -- the configuration ----------------------------------------------------------


def load(cfg: dict) -> dict:
    assumed, share = cfg.get("assumed", {}), cfg["deployment"]
    cfg["pad_token_id"] = assumed.get("pad_token_id", 0)
    cfg["eos_token_id"] = assumed["eos_token_id"]
    cfg["router_experts"] = cfg["published"]["num_experts"]
    # the held count under the name the experts' layer metrics scale by
    cfg["n_routed_experts"] = cfg["num_experts"]
    cfg["expert_shards"] = cfg["head_shards"] = share["chips_sharing_a_layer"]
    cfg["expert_rank"] = cfg["head_rank"] = share["rank"]
    if cfg["router_experts"] != cfg["num_experts"] * cfg["expert_shards"]:
        raise ValueError("experts held x chips sharing a layer is not the "
                         "published count")
    for key in ("num_attention_heads", "num_key_value_heads"):
        if cfg["published"][key] != cfg[key] * cfg["head_shards"]:
            raise ValueError(f"{key}: heads held x chips sharing a layer is "
                             "not the published count")
    if not 0 < cfg["eos_token_id"] < cfg["vocab_size"]:
        raise ValueError("the end-of-sequence id lies outside the slice")
    if cfg["score_func"] != "sigmoid" or not cfg["route_norm"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["rope_scaling"] is not None or not cfg["mup_enabled"] \
            or cfg["num_shared_experts"] != 1:
        raise ValueError(
            "the reference takes sigmoid scores normalised over the chosen, "
            "one group, plain rotary positions, a scaled embedding and one "
            "shared expert")
    if len(cfg["layer_types"]) < cfg["num_hidden_layers"]:
        raise ValueError("layer_types is shorter than the layers held")
    return cfg


def rehearsal_cut(cfg: dict) -> dict:
    """The CPU rehearsal's toy: two periods (three window layers of 16 and a
    full layer, twice; layers 0 and 1 dense), 4 of 16 experts and half the
    heads held, every mechanism present. Never measured."""
    cut = dict(cfg)
    cut.update(num_hidden_layers=8, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, head_shards=2,
               head_rank=min(cfg["head_rank"], 1), intermediate_size=512,
               moe_intermediate_size=128, num_experts=4, n_routed_experts=4,
               router_experts=16,
               expert_shards=4, expert_rank=min(cfg["expert_rank"], 3),
               num_experts_per_tok=4, sliding_window=16, vocab_size=1024,
               eos_token_id=min(cfg["eos_token_id"], 2))
    return cut


# -- the weights ------------------------------------------------------------------


def layer_kinds(cfg: dict) -> List[str]:
    """``dense|expert`` x ``window|full``; layer 0 is a kind of its own
    (``..._first``): its block scales the embedding."""
    return [("dense" if i < cfg["num_dense_layers"] else "expert")
            + ("_window" if cfg["layer_types"][i] == "sliding_attention"
               else "_full") + ("" if i else "_first")
            for i in range(cfg["num_hidden_layers"])]


def _router_bias(key, shape, sigma):
    """The selection bias: a trained buffer in the checkpoint, here drawn at
    the initializer's range, which is the spacing of the scores round the
    cut, so that it changes some choices and not all."""
    return sigma * jax.random.normal(key, shape, jnp.float32)


LEAF_MAKERS = {"int8_stack": _int8_stack, "router_bias": _router_bias}


def layer_leaves(cfg: dict, kind: str) -> List[Tuple[str, str, tuple]]:
    """(name, kind, shape) of one layer's leaves, in key order."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    leaves = [("attn_norm", "norm", (d,)), ("q_proj", "int8", (d, q)),
              ("k_proj", "int8", (d, kv)), ("v_proj", "int8", (d, kv)),
              ("q_norm", "norm", (hd,)), ("k_norm", "norm", (hd,)),
              ("attn_gate", "int8", (d, q)), ("o_proj", "int8", (q, d)),
              ("attn_post_norm", "norm", (d,)), ("mlp_norm", "norm", (d,)),
              ("mlp_post_norm", "norm", (d,))]
    if kind.startswith("dense"):
        ff = cfg["intermediate_size"]
        return leaves + [("mlp_gate", "int8", (d, ff)),
                         ("mlp_up", "int8", (d, ff)),
                         ("mlp_down", "int8", (ff, d))]
    ff, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    sf = ff * cfg["num_shared_experts"]
    return leaves + [("router", "embed", (d, cfg["router_experts"])),
                     ("router_bias", "router_bias", (cfg["router_experts"],)),
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
    from fairness_llm_tpu.models.configs import WindowHybridConfig

    served, shards = cfg.get("served", {}), cfg["head_shards"]
    layers = cfg["num_hidden_layers"]
    return WindowHybridConfig(
        name=name, vocab_size=cfg["vocab_size"], num_layers=layers,
        num_heads=cfg["num_attention_heads"] * shards,
        num_kv_heads=cfg["num_key_value_heads"] * shards,
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        activation=cfg.get("hidden_act", "silu"),
        embed_scale=bool(cfg["mup_enabled"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        sliding_window=cfg["sliding_window"],
        eos_token_id=cfg["eos_token_id"], pad_token_id=cfg["pad_token_id"],
        dtype=served.get("activation_dtype", "bfloat16"),
        weight_quant=served.get("weight_quant", "int8"),
        use_flash_attention=False,
        layer_types=tuple(cfg["layer_types"][:layers]),
        first_dense_layers=cfg["num_dense_layers"],
        head_shards=shards, head_rank=cfg["head_rank"],
        num_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_d_ff=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["num_shared_experts"],
        routed_scaling=float(cfg["route_scale"]),
        norm_topk_prob=bool(cfg["route_norm"]),
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
        attn = {p: dense(lw[p]) for p in ("q_proj", "k_proj", "v_proj",
                                          "o_proj")}
        attn.update(gate_proj=dense(lw["attn_gate"]),
                    q_norm=norm(lw["q_norm"]), k_norm=norm(lw["k_norm"]))
        if "router" in lw:
            mlp = {"router": {"kernel": lw["router"]},
                   "router_bias": lw["router_bias"],
                   "shared": {p + "_proj": dense(lw["shared_" + p])
                              for p in ("gate", "up", "down")}}
            mlp.update({p: dense(lw[p]) for p in (
                "experts_gate", "experts_up", "experts_down")})
        else:
            mlp = {p + "_proj": dense(lw["mlp_" + p])
                   for p in ("gate", "up", "down")}
        tree[f"layer_{i}"] = {
            "attn": attn, "mlp": mlp,
            **{p: norm(lw[p]) for p in ("attn_norm", "attn_post_norm",
                                        "mlp_norm", "mlp_post_norm")}}
    return tree


# -- the reference's blocks ----------------------------------------------------------
# spec: (eps, window or 0, rotary, heads, kv heads, head_dim, theta, top_k,
# first held expert, route_scale, scale on the block's input)


def _rotate(x, theta):
    """Rotary positions over the whole head, position = index along axis 1,
    the two halves of a head as pairs. x ``[N, S, ..., D]``."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (d // 2,))
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attend(q, k, v, window: int):
    """Causal softmax attention in blocks of queries. q ``[N, S, G, R, D]``,
    k and v ``[N, S, G, D]``; ``window`` 0: every earlier key."""
    n, s = q.shape[:2]
    blk = math.gcd(s, _QUERY_BLOCK)
    at_k = jnp.arange(s)[None, :]

    def block_of_queries(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        at_q = (i * blk + jnp.arange(blk))[:, None]
        see = at_k <= at_q
        if window:
            see = see & (at_q - at_k < window)
        scores = jnp.einsum("nqgrd,nkgd->ngrqk", qb, k) * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(see, scores, -jnp.inf), axis=-1)
        return jnp.einsum("ngrqk,nkgd->nqgrd", probs, v)

    out = jax.lax.map(block_of_queries, jnp.arange(s // blk))
    return out.swapaxes(0, 1).reshape(q.shape)


def _qk_norm(z, scale, eps):
    """Per head, over its ``D`` values."""
    return rmsnorm(z, scale, eps)


def _post_norm(z, layer, name, eps):
    """N2 and N4: what the mixer and the MLP give, normed before it is
    added."""
    return rmsnorm(z, layer[name], eps)


def _attention(u, layer, spec, ops):
    eps, window, rotary, h, g, hd, theta = spec[:7]
    n, s, _ = u.shape
    q = ops.dense(u, layer["q_proj"]).reshape(n, s, g, h // g, hd)
    k = ops.dense(u, layer["k_proj"]).reshape(n, s, g, hd)
    v = ops.dense(u, layer["v_proj"]).reshape(n, s, g, hd)
    q = _qk_norm(q, layer["q_norm"], eps)
    k = _qk_norm(k, layer["k_norm"], eps)
    if rotary:  # a sliding_attention layer rotates; a full one does not
        q, k = _rotate(q, theta), _rotate(k, theta)
    o = attend(q, k, v, window).reshape(n, s, h * hd)
    gate = jax.nn.sigmoid(ops.dense(u, layer["attn_gate"]))
    return ops.dense(o * gate, layer["o_proj"])


def route(y, layer, top_k: int, scale: float, ops):
    """(chosen [.., k] expert ids over all experts, their weights): chosen by
    ``s + b``, weighed by ``s``."""
    s = jax.nn.sigmoid(ops.lower(y) @ layer["router"].astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + layer["router_bias"], top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def _experts(y, layer, spec, ops):
    top_k, first, scale = spec[7:10]
    chosen, weights = route(y, layer, top_k, scale, ops)
    out = _swiglu(y, layer["shared_gate"], layer["shared_up"],
                  layer["shared_down"], ops)
    held = layer["experts_gate"][0].shape[0]

    def add_expert(total, e):
        leaves = [(layer[p][0][e], layer[p][1][e]) for p in (
            "experts_gate", "experts_up", "experts_down")]
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        return total + w[..., None] * _swiglu(y, *leaves, ops), None

    out, _ = jax.lax.scan(add_expert, out, jnp.arange(held))
    return out


def _layer(x, layer, spec, ops):
    eps = spec[0]
    x = x * spec[10]
    mixed = _attention(rmsnorm(x, layer["attn_norm"], eps), layer, spec, ops)
    x = x + _post_norm(mixed, layer, "attn_post_norm", eps)
    y = rmsnorm(x, layer["mlp_norm"], eps)
    if "router" in layer:
        out = _experts(y, layer, spec, ops)
    else:
        out = _swiglu(y, layer["mlp_gate"], layer["mlp_up"],
                      layer["mlp_down"], ops)
    return x + _post_norm(out, layer, "mlp_post_norm", eps)


def block(cfg: dict, kind: str):
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the reference implements SwiGLU only")
    return _layer, (
        float(cfg["rms_norm_eps"]),
        int(cfg["sliding_window"]) if "_window" in kind else 0,
        "_window" in kind, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], float(cfg["rope_theta"]),
        cfg["num_experts_per_tok"], cfg["expert_rank"] * cfg["num_experts"],
        float(cfg["route_scale"]),
        float(cfg["hidden_size"]) ** 0.5 if kind.endswith("_first") else 1.0)


# -- the counts ------------------------------------------------------------------------


def attention_params(cfg: dict) -> int:
    """q, gate and o over the held query heads, k and v over the held KV
    heads."""
    return cfg["hidden_size"] * cfg["head_dim"] * (
        3 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def model_flops(cfg: dict, *, prompt_tokens: int, rows: int, decoded: int,
                prefill_context_sum: float, decode_context_sum: float,
                moe_local_pairs: int, prefill_tokens_reused: int = 0,
                **_) -> float:
    """Model FLOPs of a stretch of serving, work DONE: a prompt position that
    was copied from a resident row is not counted (``prefill_tokens_reused``,
    the program's counter). Per computed position and layer: 2 x the
    attention projections' parameters and the dense layer's, or the router's
    and the shared expert's, MLP; per LOCAL (token, expert) pair, from the
    program's counter, one routed expert; the head once per prefilled row and
    decoded token; ``4 x heads x head_dim`` per (query, key) pair. The pairs
    of the prompts: a row that computes its last ``n - c`` positions makes
    ``(n^2 - c^2) / 2``, at least ``(1 - c / n)`` of its ``n^2 / 2``, so the
    whole sum is scaled by the share of positions computed (never over the
    true count); in a window layer a position sees ``sliding_window`` keys at
    most, so its pairs are the lesser of that count and the window a computed
    position, and likewise a decoded token's."""
    d = cfg["hidden_size"]
    kinds = layer_kinds(cfg)
    n_expert = sum(k.startswith("expert") for k in kinds)
    n_window = sum("_window" in k for k in kinds)
    expert = 3 * d * cfg["moe_intermediate_size"]
    per_token = 2 * (
        len(kinds) * attention_params(cfg)
        + (len(kinds) - n_expert) * 3 * d * cfg["intermediate_size"]
        + n_expert * (d * cfg["router_experts"]
                      + cfg["num_shared_experts"] * expert))
    computed = prompt_tokens - prefill_tokens_reused
    seen = prefill_context_sum * computed / max(prompt_tokens, 1)
    window = cfg["sliding_window"]
    pairs = ((len(kinds) - n_window) * (seen + decode_context_sum)
             + n_window * (min(seen, computed * window)
                           + min(decode_context_sum, decoded * window)))
    return ((computed + decoded) * per_token
            + moe_local_pairs * 2 * expert
            + (rows + decoded) * 2 * d * cfg["vocab_size"]
            + pairs * 4 * cfg["num_attention_heads"] * cfg["head_dim"])
