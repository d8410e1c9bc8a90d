"""Heads and experts as one chip's share of eight (``LinearHybridConfig``:
``heads_held`` beside ``experts_held``), in ``test_moe_share.py``'s manner:
the eight shares' partial results, with what every chip computes alike (the
shared expert) counted once, add up to the uncut layer; for the program's
three modules and for the plain reference's; and the reckonings (parameters,
cache bytes, step bytes) read each layer's own sizes.

Float32 throughout. A share's ``o_proj`` sums 1/8 of the uncut product's
terms, so shares and whole differ in summation order alone: readings 1e-7 to
6e-7 on outputs of size 0.1 to 1; tolerance 2e-5."""

import dataclasses
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from fairness_llm_tpu.models.configs import (  # noqa: E402
    LinearHybridConfig,
    get_model_config,
)
from fairness_llm_tpu.models.transformer import (  # noqa: E402
    Attention,
    DeltaRuleAttention,
    SparseMLP,
)
from fairness_llm_tpu.parallel.sharding import held_heads  # noqa: E402
from perfbench.families import linear_hybrid_moe  # noqa: E402
from perfbench.lib import modelcfg, reference  # noqa: E402
from perfbench.lib import weights as W  # noqa: E402

TOL = 2e-5
SHARES = 8
#: 16 query / 8 KV / 8 linear heads of 16 and 16 experts: an eighth is 2
#: query heads on 1 KV head, 1 linear head, 2 experts.
UNCUT = dataclasses.replace(
    get_model_config("tiny-kda-moe"), num_heads=16, num_kv_heads=8,
    linear_heads=8)


def _share(rank):
    return dataclasses.replace(UNCUT, head_shards=SHARES, head_rank=rank,
                               expert_shards=SHARES, expert_rank=rank)


def _cols(p, rank, axis=-1):
    n = p.shape[axis] // SHARES
    return jax.lax.slice_in_dim(p, rank * n, (rank + 1) * n, axis=axis)


def _init(module, rng, *args):
    params = nn.meta.unbox(jax.jit(module.init)(jax.random.key(1), *args))
    return jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape) * 0.3, p.dtype), params)


def _cut(params, rank, whole=(), leading=("o_proj",)):
    """Rank ``rank``'s slice of a module's parameters: output columns of
    every leaf, but the leading axis of ``leading`` (``o_proj``'s input
    rows, the stacked experts), and ``whole`` as they are."""
    def cut(path, p):
        name = "/".join(str(k.key) for k in path)
        if any(w in name for w in whole):
            return p
        if any(r in name for r in leading):
            return _cols(p, rank, axis=0)
        return _cols(p, rank)

    return jax.tree_util.tree_map_with_path(cut, params)


def test_held_heads_are_the_ranks_equal_range():
    assert held_heads(64, 8, 0) == (0, 8) and held_heads(8, 8, 7) == (7, 1)
    with pytest.raises(ValueError):
        held_heads(64, 7, 0)
    assert UNCUT.heads_held == (16, 8, 8)
    assert _share(3).heads_held == (2, 1, 1)
    assert _share(3).experts_held == (6, 2)
    with pytest.raises(ValueError):
        dataclasses.replace(UNCUT, head_shards=3)


@pytest.mark.parametrize("module", ["linear", "softmax", "experts"])
def test_the_eight_shares_of_a_module_add_up_to_the_uncut_module(module):
    """Each rank holds slices of the uncut module's parameters: its heads'
    columns of the projections, taps, gates, ``A_log``, ``dt_bias`` and beta,
    their rows of ``o_proj``; or its experts. What is replicated (the
    low-rank gates' first halves, the output norm, the router, the shared
    expert) it holds whole."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 12, UNCUT.d_model)), jnp.float32)
    valid = jnp.ones((2, 12), bool)
    pos = jnp.tile(jnp.arange(12)[None], (2, 1))
    if module == "linear":
        build = lambda cfg: DeltaRuleAttention(cfg)  # noqa: E731
        args = (x, None, valid)
        kw = dict(whole=("f_a_proj", "g_a_proj", "o_norm"))
    elif module == "softmax":
        build = lambda cfg: Attention(cfg)  # noqa: E731
        args = (x, pos, None, None, valid, pos)
        kw = {}
    else:
        build = lambda cfg: SparseMLP(cfg)  # noqa: E731
        args = (x, valid)
        kw = dict(whole=("router", "shared"), leading=("experts_",))
    params = _init(build(UNCUT), rng, *args)
    whole = np.asarray(jax.jit(build(UNCUT).apply)(params, *args)[0],
                       np.float64)
    # the shared expert: every rank computes it alike, so it counts once
    once = _shared_alone(params, x) if module == "experts" else 0.0
    total = np.zeros_like(whole) + once
    for rank in range(SHARES):
        part = jax.jit(build(_share(rank)).apply)(
            _cut(params, rank, **kw), *args)[0]
        total += np.asarray(part, np.float64) - once
    assert np.abs(total - whole).max() < TOL
    assert np.abs(whole - once).max() > 0.05


def _shared_alone(params, x):
    from fairness_llm_tpu.models.transformer import MLP

    ff = UNCUT.num_shared_experts * UNCUT.expert_d_ff
    return np.asarray(jax.jit(MLP(UNCUT, width=ff).apply)(
        {"params": params["params"]["shared"]}, x), np.float64)


def test_the_references_shares_add_up_to_the_uncut_reference():
    """The same for the plain reference's two layers (the benchmark family's
    toy widths): rank r of 2 head ranks and of 4 expert ranks given its
    heads' and experts' slices, against the layer holding all of them. A
    whole layer is not linear in its mixer (the experts read the mixer's
    sum), so the mixers and the experts are added up apart."""
    full = modelcfg.load_config(os.path.join(
        ROOT, "perfbench", "configs", "solar-open2-int8.json"))
    cut = modelcfg.rehearsal_cut(full)
    uncut = dict(cut, num_attention_heads=8, num_key_value_heads=4,
                 linear_heads=8, head_shards=1, head_rank=0,
                 n_routed_experts=16, expert_shards=1, expert_rank=0)
    x = jnp.asarray(np.random.default_rng(6).normal(
        size=(2, 16, cut["hidden_size"])), jnp.float32)
    ops = reference.Ops()
    F = linear_hybrid_moe

    def leaf_cut(leaf, rank, shares, axis):
        if isinstance(leaf, tuple):  # int8 (kernel, scale): scale per column
            k = _slice(leaf[0], rank, shares, axis)
            s = leaf[1] if axis == 0 else _slice(leaf[1], rank, shares, -1)
            return (k, s)
        return _slice(leaf, rank, shares, axis)

    def _slice(p, rank, shares, axis):
        n = p.shape[axis] // shares
        return jax.lax.slice_in_dim(p, rank * n, (rank + 1) * n, axis=axis)

    for kind, mixer in (("linear_layer", F._linear_mixer),
                        ("softmax_layer", F._softmax_mixer)):
        leaves = F.layer_leaves(uncut, kind)
        layer = jax.jit(lambda w, leaves=leaves: W._make(
            uncut, W.base_key(w), 1, leaves, F.LEAF_MAKERS))(W.seed_words(9))
        with jax.default_matmul_precision("highest"):
            whole = np.asarray(mixer(x, layer, F.block(uncut, kind)[1], ops),
                               np.float64)
            total = np.zeros_like(whole)
            for rank in range(2):
                mine = dict(layer)
                for name in layer:
                    if name in ("o_proj",):
                        mine[name] = leaf_cut(layer[name], rank, 2, 0)
                    elif name in ("q_proj", "k_proj", "v_proj", "gate_proj",
                                  "conv_q", "conv_k", "conv_v", "f_b_proj",
                                  "g_b_proj", "A_log", "dt_bias",
                                  "beta_proj"):
                        mine[name] = leaf_cut(layer[name], rank, 2, -1)
                share = dict(cut, head_rank=rank)
                total += np.asarray(
                    mixer(x, mine, F.block(share, kind)[1], ops), np.float64)
            assert np.abs(total - whole).max() < TOL, kind
            assert np.abs(whole).max() > 0.05
            # the experts: rank r of 4 holds experts [4r, 4r + 4)
            if kind == "softmax_layer":
                continue
            spec = F.block(uncut, kind)[1]
            whole = np.asarray(F._experts(x, layer, spec, ops), np.float64)
            none = dict(layer, experts_down=(
                layer["experts_down"][0], 0.0 * layer["experts_down"][1]))
            alone = np.asarray(F._experts(x, none, spec, ops), np.float64)
            total = alone.copy()
            for rank in range(4):
                mine = dict(layer, **{n: tuple(
                    p[4 * rank: 4 * rank + 4] for p in layer[n]) for n in (
                    "experts_gate", "experts_up", "experts_down")})
                spec_r = F.block(dict(cut, expert_rank=rank), kind)[1]
                total += np.asarray(F._experts(x, mine, spec_r, ops),
                                    np.float64) - alone
            # the toy's routed sum is 0.03 of a stream of size 1
            assert np.abs(total - whole).max() < TOL
            assert np.abs(whole - alone).max() > 0.01


def test_reckonings_read_each_layers_own_sizes():
    """approx_param_count, the cache bytes and the roofline's step bytes for
    the published shape as this chip holds it; GQA-only code refuses it
    although three of its layers are GQA."""
    from jax.sharding import Mesh

    from fairness_llm_tpu.parallel import sharding as shd
    from fairness_llm_tpu.runtime.weights import hf_name_map
    from fairness_llm_tpu.telemetry.roofline import decode_step_bytes

    full = modelcfg.load_config(os.path.join(
        ROOT, "perfbench", "configs", "solar-open2-int8.json"))
    cfg = linear_hybrid_moe.model_config(full, "solar-open2-int8")
    assert isinstance(cfg, LinearHybridConfig)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.linear_heads) == (64, 8, 64)
    assert cfg.heads_held == (8, 1, 8) and cfg.experts_held == (0, 40)
    assert cfg.mixer_kinds == ("gqa", "kda", "kda", "kda") * 3
    assert cfg.attention == "hybrid" and set(cfg.layer_kinds) == {"moe"}
    linear = (4 * 4096 * 1024 + 2 * (4096 + 1024) * 128 + 4096 * 8
              + 3 * 1024 * 4 + 1024 + 8 + 128)
    softmax = 4096 * 128 * (3 * 8 + 2 * 1)
    experts = 41 * 3 * 4096 * 1280 + 4096 * 320
    assert cfg.mixer_param_count("kda") == linear
    assert cfg.mixer_param_count("gqa") == softmax
    assert cfg.mlp_param_count("moe") == experts
    want = 2 * 24576 * 4096 + 9 * linear + 3 * softmax + 12 * experts
    assert cfg.approx_param_count == want
    assert 8.15e9 < want < 8.17e9  # 8.27e9 bytes: the embedding is bf16
    state = 9 * (8 * 128 * 128 * 4 + 3 * 3072 * 2)
    assert cfg.row_state_bytes == state == 4884480
    assert cfg.layer_cache_values == (256, 0, 0, 0) * 3
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "tp", "sp"))
    assert shd.per_device_kv_cache_bytes(cfg, mesh, 192, 384) \
        == 192 * (384 * 3 * 256 * 2 + state)
    embed = 24576 * 4096
    stats = {"batch": 192, "cache_slots": 384, "prefix_len": 0}
    assert decode_step_bytes(cfg, stats) == (
        cfg.approx_param_count - embed) + 2 * embed \
        + 192 * (384 * 3 * 512 + 2 * state)
    # the latent and dense reckonings are what they were
    latent = get_model_config("tiny-mla-moe")
    assert latent.attention == "mla" and latent.row_state_bytes == 0
    assert latent.layer_cache_values == (24,) * 3
    mistral = get_model_config("mistral-7b-int8")
    assert mistral.attention == "gqa" and mistral.heads_held == (32, 8, 0)
    tp2 = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
               ("dp", "tp", "sp"))
    for refuse in (lambda: shd.make_axis_rules(cfg, tp2),
                   lambda: hf_name_map(cfg)):
        with pytest.raises(NotImplementedError):
            refuse()


def test_cli_refuses_tp_for_a_per_row_state():
    from fairness_llm_tpu.cli import main as cli

    with pytest.raises(SystemExit, match="no head axis"):
        cli.main(["--phase", "1", "--quick", "--model", "tiny-kda-moe",
                  "--continuous", "--tp", "2", "--no-save"])
