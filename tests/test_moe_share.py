"""Routed experts as one chip's share: the router, the grouped product, no
dropped token, and the shares adding up to the uncut layer (the test that
ties the chip's share to the model: ``model-configs`` guide, section 4)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.models.configs import (  # noqa: E402
    SparseLatentConfig,
    get_model_config,
)
from fairness_llm_tpu.models.transformer import (  # noqa: E402
    SparseMLP,
    route_tokens,
)
from fairness_llm_tpu.ops.expert_dispatch import (  # noqa: E402
    round_sizes,
    routed_experts,
)
from fairness_llm_tpu.ops.quant_matmul import (  # noqa: E402
    grouped_quant_matmul,
    quantize_weight,
)
from fairness_llm_tpu.parallel.sharding import held_experts  # noqa: E402
from perfbench.families import latent_moe  # noqa: E402
from perfbench.lib import modelcfg, reference  # noqa: E402

TINY = get_model_config("tiny-mla-moe")


def test_router_scores_selection_and_weights_match_the_reference():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 16)) * 0.3, jnp.float32)
    chosen, weights = jax.jit(lambda a, b: route_tokens(a, b, TINY))(x, router)
    with jax.default_matmul_precision("highest"):
        want_c, want_w = latent_moe.route(
            x, router, TINY.experts_per_token, TINY.routed_scaling,
            reference.Ops())
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want_c))
    assert np.allclose(weights, want_w, atol=1e-6)
    # by hand: weights are 2.5 x sigmoid scores over their own sum
    p = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                          @ np.asarray(router, np.float64))))
    top = np.sort(p, axis=-1)[:, ::-1][:, :4]
    assert np.allclose(weights, 2.5 * top / top.sum(-1, keepdims=True),
                       atol=1e-5)
    assert np.allclose(np.asarray(weights).sum(-1), 2.5, atol=1e-5)


def _plan(sizes, bm, n_tiles):
    """Row tiles for groups of ``sizes`` rows: what ``routed_experts``
    builds, by plain Python."""
    tile_expert, row_expert = [], []
    for e, n in enumerate(sizes):
        tiles = -(-n // bm)
        tile_expert += [e] * tiles
        row_expert += [e] * n + [-1] * (tiles * bm - n)
    used = len(tile_expert)
    tile_block = [min(t, used - 1) for t in range(n_tiles)]
    tile_expert = [tile_expert[b] for b in tile_block]
    row_expert += [-1] * (n_tiles * bm - len(row_expert))
    return (jnp.asarray(tile_expert, jnp.int32),
            jnp.asarray(tile_block, jnp.int32),
            jnp.asarray([used], jnp.int32), np.asarray(row_expert))


@pytest.mark.parametrize("sizes", [(5, 0, 37, 16), (0, 0, 0, 1), (16, 16, 16, 16)])
def test_grouped_int8_product_equals_a_loop_over_experts(sizes):
    """The Pallas kernel in interpret mode, ragged groups (an empty one, one
    spilling into a third tile), unused tiles behind the used ones."""
    rng = np.random.default_rng(1)
    E, K, N, bm, n_tiles = 4, 256, 384, 16, 8
    w = rng.normal(size=(E, K, N)).astype(np.float32) * 0.05
    wq, scale = jax.vmap(quantize_weight)(jnp.asarray(w))
    te, tb, used, row_expert = _plan(sizes, bm, n_tiles)
    x = jnp.asarray(rng.normal(size=(n_tiles * bm, K)), jnp.float32)
    x = jnp.where(jnp.asarray(row_expert >= 0)[:, None], x, 0.0)
    got = grouped_quant_matmul(x, wq, scale, te, tb, used, bm=bm,
                               interpret=True)
    xla = grouped_quant_matmul(x, wq, scale, te, tb, used, bm=bm)
    for e in range(E):
        rows = np.flatnonzero(row_expert == e)
        want = np.asarray(x)[rows] @ (np.asarray(wq[e], np.float32)
                                      * np.asarray(scale[e])[None, :])
        assert np.allclose(np.asarray(got)[rows], want, atol=2e-4), e
        assert np.allclose(np.asarray(xla)[rows], want, atol=2e-4), e


def _float_experts(rng, held, d, ff):
    make = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)  # noqa: E731
    return ((make(held, d, ff), None), (make(held, d, ff), None),
            (make(held, ff, d), None))


def _expert(x, leaves, e):
    g, u, dn = (np.asarray(leaf[0][e], np.float64) for leaf in leaves)
    h = x @ g
    return (h / (1 + np.exp(-h)) * (x @ u)) @ dn


def test_every_token_to_the_same_experts_drops_none():
    """All 64 tokens choose the same experts, three of them held here: 192
    local pairs against a round of 128, so the rounds' loop runs twice, every
    token gets all three experts' terms, and the counters say 64 tokens, 192
    pairs, 64 on the fullest expert."""
    rng = np.random.default_rng(2)
    T, d, ff, held, E, k = 64, 32, 16, 4, 16, 4
    bm, pairs, n_tiles = round_sizes(T, k, E, held)
    assert pairs < 3 * T
    leaves = _float_experts(rng, held, d, ff)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    chosen = jnp.tile(jnp.asarray([[0, 9, 1, 3]], jnp.int32), (T, 1))
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    valid = jnp.ones((T,), bool)
    run = jax.jit(lambda *a: routed_experts(
        *a, first=0, num_experts=E, activation=jax.nn.silu))
    y, counters = run(x, chosen, weights, valid, *leaves)
    x64, w64 = np.asarray(x, np.float64), np.asarray(weights, np.float64)
    want = sum(w64[:, s:s + 1] * _expert(x64, leaves, e)
               for s, e in ((0, 0), (2, 1), (3, 3)))
    assert np.allclose(np.asarray(y), want, atol=1e-4)
    assert list(np.asarray(counters)) == [T, 3 * T, T]
    # padding rows route nowhere and are not counted
    half = jnp.arange(T) < T // 2
    y2, c2 = run(x, chosen, weights, half, *leaves)
    assert np.allclose(np.asarray(y2)[: T // 2], want[: T // 2], atol=1e-4)
    assert not np.asarray(y2)[T // 2:].any()
    assert list(np.asarray(c2)) == [T // 2, 3 * T // 2, T // 2]


def test_held_experts_are_the_ranks_equal_range():
    assert held_experts(192, 16, 0) == (0, 12)
    assert held_experts(192, 16, 15) == (180, 12)
    assert held_experts(16, 1, 0) == (0, 16)
    for bad in ((192, 16, 16), (192, 7, 0), (192, 0, 0)):
        with pytest.raises(ValueError):
            held_experts(*bad)
    assert dataclasses.replace(TINY, expert_shards=4,
                               expert_rank=2).experts_held == (8, 4)


def test_the_four_shares_of_the_layer_add_up_to_the_uncut_layer():
    """The program's expert layer at 16 experts, top 4: each of 4 ranks holds
    4 experts' kernels (slices of the uncut layer's), routes over all 16, and
    adds its own experts to the shared expert. The ranks' routed parts, with
    the shared expert counted once, are the uncut layer; and the local pairs
    of the ranks add up to every pair."""
    rng = np.random.default_rng(3)
    layer = SparseMLP(TINY)
    x = jnp.asarray(rng.normal(size=(2, 24, TINY.d_model)), jnp.float32)
    valid = jnp.ones((2, 24), bool)
    import flax.linen as nn

    params = nn.meta.unbox(jax.jit(layer.init)(jax.random.key(1), x, valid))
    params = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape) * 0.2, p.dtype), params)
    whole, counted = jax.jit(layer.apply)(params, x, valid)
    assert list(np.asarray(counted)[:2]) == [48, 48 * 4]
    no_experts = dataclasses.replace(TINY, routed_scaling=0.0)
    shared, _ = jax.jit(SparseMLP(no_experts).apply)(params, x, valid)
    total, pairs = np.asarray(shared, np.float64), 0
    for rank in range(4):
        cfg = dataclasses.replace(TINY, expert_shards=4, expert_rank=rank)
        first, held = cfg.experts_held
        mine = jax.tree.map(lambda p: p, params)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine["params"][name] = {
                "kernel": params["params"][name]["kernel"][first:first + held]}
        part, c = jax.jit(SparseMLP(cfg).apply)(mine, x, valid)
        total += np.asarray(part, np.float64) - np.asarray(shared, np.float64)
        pairs += int(c[1])
    assert pairs == 48 * 4
    assert np.allclose(total, np.asarray(whole), atol=2e-5)
    assert np.abs(np.asarray(whole) - np.asarray(shared)).max() > 0.1


def test_the_references_shares_add_up_to_the_uncut_reference():
    """The same for the plain reference's expert layer (the benchmark
    family's toy widths, 16 experts): rank r of 4 given experts [4r, 4r+4),
    against the layer holding all 16. ``A`` is the layer with the routed sum
    weighted by zero: attention, residual and shared expert alone."""
    full = modelcfg.load_config(
        os.path.join(ROOT, "perfbench", "configs", "ax-k1-int8.json"))
    cut = modelcfg.rehearsal_cut(full)
    uncut = dict(cut, n_routed_experts=16, expert_shards=1, expert_rank=0)
    from perfbench.lib import weights as W

    leaves = latent_moe.layer_leaves(uncut, "expert_layer")
    layer = jax.jit(lambda w: W._make(uncut, W.base_key(w), 1, leaves,
                                      latent_moe.LEAF_MAKERS))(W.seed_words(7))
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, 16, cut["hidden_size"])), jnp.float32)

    def run(cfg, lw):
        fn, spec = latent_moe.block(cfg, "expert_layer")
        with jax.default_matmul_precision("highest"):
            return np.asarray(reference._block(x, lw, fn, spec, None),
                              np.float64)

    whole = run(uncut, layer)
    alone = run(dict(uncut, routed_scaling_factor=0.0), layer)
    total = alone.copy()
    for rank in range(4):
        mine = dict(layer)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = tuple(part[4 * rank: 4 * rank + 4]
                               for part in layer[name])
        total += run(dict(cut, expert_rank=rank), mine) - alone
    assert np.allclose(total, whole, atol=1e-4)
    assert np.abs(whole - alone).max() > 0.05


def test_reckonings_read_the_layers_own_sizes():
    """approx_param_count, the cache bytes and the roofline's step bytes for
    the published shape as this chip holds it; GQA-only code refuses it."""
    from jax.sharding import Mesh

    from fairness_llm_tpu.parallel import sharding as shd
    from fairness_llm_tpu.runtime.stepbuilder import build_prefix
    from fairness_llm_tpu.telemetry.roofline import decode_step_bytes

    full = modelcfg.load_config(
        os.path.join(ROOT, "perfbench", "configs", "ax-k1-int8.json"))
    cfg = latent_moe.model_config(full, "ax-k1-int8")
    assert isinstance(cfg, SparseLatentConfig)
    mla = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
           + 8192 * 7168)
    expert = 3 * 7168 * 2048
    assert cfg.attention_param_count == mla
    assert cfg.mlp_param_count("moe") == 13 * expert + 7168 * 192
    want = (2 * 20480 * 7168 + 15 * mla + 3 * 7168 * 18432
            + 14 * (13 * expert + 7168 * 192))
    assert cfg.approx_param_count == want
    assert 10.2e9 < want < 10.3e9  # 10.39e9 bytes: the embedding is bf16
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "tp", "sp"))
    assert shd.per_device_kv_cache_bytes(cfg, mesh, 192, 384) \
        == 192 * 384 * 15 * 576 * 2
    embed = 20480 * 7168
    stats = {"batch": 192, "cache_slots": 384, "prefix_len": 0}
    assert decode_step_bytes(cfg, stats) \
        == (want - embed) + 2 * embed + 192 * 384 * 15 * 1152
    # the dense reckonings are what they were
    mistral = get_model_config("mistral-7b-int8")
    assert mistral.approx_param_count == 2 * 32000 * 4096 + 32 * (
        4096 * (2 * 4096 + 2 * 1024) + 3 * 4096 * 14336)
    assert mistral.cache_values_per_token == 2 * 8 * 128
    assert shd.per_device_kv_cache_bytes(mistral, mesh, 48, 384) \
        == 2 * 32 * 48 * 384 * 8 * 128 * 2
    quant = dataclasses.replace(mistral, kv_cache_quant=True)
    assert decode_step_bytes(quant, stats) - decode_step_bytes(
        quant, dict(stats, batch=0)) == 192 * 384 * 32 * 2 * 8 * (128 + 4)
    # and code that knows GQA only says so instead of answering
    tp2 = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
               ("dp", "tp", "sp"))
    from fairness_llm_tpu.runtime.weights import hf_name_map

    for refuse in (lambda: shd.make_axis_rules(cfg, tp2),
                   lambda: build_prefix(cfg, None, prefix_len=8),
                   lambda: hf_name_map(cfg)):
        with pytest.raises(NotImplementedError):
            refuse()
    assert dict(shd.make_axis_rules(mistral, tp2))["expert"] is None


def test_cli_refuses_tp_for_a_latent_cache(monkeypatch):
    from fairness_llm_tpu.cli import main as cli

    with pytest.raises(SystemExit, match="no head axis"):
        cli.main(["--phase", "1", "--quick", "--model", "tiny-mla-moe",
                  "--continuous", "--tp", "2", "--no-save"])
