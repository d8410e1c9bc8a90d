"""A second family, for ``tests/test_families.py`` alone: the test copies it
into a scratch benchmark tree's ``families/`` to show that a new kind of model
is added and not edited in. Two kinds of layer the program's one dense block
can still serve: ``plain`` is ``dense_gqa``'s block; ``turned`` has the same
shapes under other names, in another key order, with its norms made by a leaf
kind of this file's own."""

import jax
import jax.numpy as jnp

from perfbench.families import dense_gqa
from perfbench.lib.reference import rmsnorm

load = dense_gqa.load
rehearsal_cut = dense_gqa.rehearsal_cut
global_leaves = dense_gqa.global_leaves
model_config = dense_gqa.model_config
model_flops = dense_gqa.model_flops

_TURNED = {"wo": "o_proj", "down": "down_proj", "up": "up_proj",
           "gate": "gate_proj", "wv": "v_proj", "wk": "k_proj",
           "wq": "q_proj", "norm_2": "mlp_norm", "norm_1": "attn_norm",
           "bq": "q_bias", "bk": "k_bias", "bv": "v_bias"}


def _wide_norm(key, shape, sigma):
    return (1.0 + 0.3 * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


LEAF_MAKERS = {"wide_norm": _wide_norm}


def layer_kinds(cfg):
    return ["plain" if i % 2 == 0 else "turned"
            for i in range(cfg["num_hidden_layers"])]


def layer_leaves(cfg, kind):
    plain = dense_gqa.layer_leaves(cfg, "block")
    if kind == "plain":
        return plain
    by_name = {name: (leaf, shape) for name, leaf, shape in plain}
    return [(mine, "wide_norm" if mine.startswith("norm") else
             by_name[theirs][0], by_name[theirs][1])
            for mine, theirs in _TURNED.items() if theirs in by_name]


def program_tree(cfg, glob, layers):
    kinds = layer_kinds(cfg)
    return dense_gqa.program_tree(cfg, glob, [
        lw if kind == "plain" else
        {theirs: lw[mine] for mine, theirs in _TURNED.items() if mine in lw}
        for kind, lw in zip(kinds, layers)])


def _turned(x, layer, spec, ops):
    """The block over the turned names: its own copy, not ``dense_gqa``'s."""
    (h, hkv, hd, eps, theta, window) = spec
    n, s, _ = x.shape
    y = rmsnorm(x, layer["norm_1"], eps)
    q, k, v = (ops.dense(y, layer[w]) for w in ("wq", "wk", "wv"))
    if "bq" in layer:
        q, k, v = (t + layer[b].astype(jnp.float32)
                   for t, b in ((q, "bq"), (k, "bk"), (v, "bv")))
    q = q.reshape(n, s, h, hd)
    k = k.reshape(n, s, hkv, hd)
    v = v.reshape(n, s, hkv, hd)
    a = jax.vmap(lambda qr, kr, vr: dense_gqa._attend_row(
        dense_gqa._rope(qr, theta), dense_gqa._rope(kr, theta), vr,
        window))(q, k, v).reshape(n, s, h * hd)
    x = x + ops.dense(a, layer["wo"])
    y = rmsnorm(x, layer["norm_2"], eps)
    return x + ops.dense(
        jax.nn.silu(ops.dense(y, layer["gate"])) * ops.dense(y, layer["up"]),
        layer["down"])


def block(cfg, kind):
    fn, spec = dense_gqa.block(cfg, "block")
    return (fn if kind == "plain" else _turned), spec
