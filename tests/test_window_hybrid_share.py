"""Heads and experts as one chip's share of four (``WindowHybridConfig`` on
``HeadShareConfig``: ``heads_held`` beside ``experts_held``), in
``test_linear_hybrid_share.py``'s manner: the four shares' partial results,
with what every chip computes alike (the shared expert; the dense MLPs and
the norms are whole on each) counted once, add up to the uncut layer's, for a
window layer and for a full layer; for the program's modules and for the plain
reference's; and the reckonings read each layer's own sizes. The sums are
taken where the deployment's all-reduce would stand, after ``o_proj`` and
after the experts: the post-norms that follow are not linear.

Float32 throughout. A share's ``o_proj`` sums a quarter of the uncut
product's terms, so shares and whole differ in summation order alone:
readings 1e-7 to 5e-7 on outputs of size 0.1 to 3; tolerance 2e-5."""

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
    HeadShareConfig,
    LinearHybridConfig,
    WindowHybridConfig,
    get_model_config,
)
from fairness_llm_tpu.models.transformer import (  # noqa: E402
    MLP,
    Attention,
    SparseMLP,
)
from perfbench.families import window_hybrid_moe as F  # noqa: E402
from perfbench.lib import modelcfg, reference  # noqa: E402
from perfbench.lib import weights as W  # noqa: E402

TOL = 2e-5
SHARES = 4
#: 8 query / 4 KV heads of 16 and 16 experts: a quarter is 2 query heads on
#: 1 KV head and 4 experts. Layer 0 sees a window of 16, layer 3 everything.
UNCUT = dataclasses.replace(get_model_config("tiny-window-moe"), num_heads=8,
                            num_kv_heads=4)
CONFIG = os.path.join(ROOT, "perfbench", "configs", "trinity-mini-int8.json")


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


def test_the_share_is_lifted_not_copied():
    """One class holds the heads' share for both hybrids."""
    assert issubclass(WindowHybridConfig, HeadShareConfig)
    assert issubclass(LinearHybridConfig, HeadShareConfig)
    assert "head_shards" not in vars(WindowHybridConfig).get(
        "__annotations__", {})
    assert UNCUT.heads_held == (8, 4, 0) and UNCUT.experts_held == (0, 16)
    assert _share(3).heads_held == (2, 1, 0)
    assert _share(3).experts_held == (12, 4)
    with pytest.raises(ValueError):
        dataclasses.replace(UNCUT, head_shards=3)
    with pytest.raises(ValueError):
        dataclasses.replace(UNCUT, layer_types=("full_attention",))
    with pytest.raises(ValueError):
        dataclasses.replace(UNCUT, sliding_window=None)


@pytest.mark.parametrize("module", ["window", "full", "experts"])
def test_the_four_shares_of_a_module_add_up_to_the_uncut_module(module):
    """Each rank holds its heads' columns of q, k, v and the gate and their
    rows of ``o_proj``, or its experts; the QK-norms' scales (one per value
    of a head), the router, its selection bias and the shared expert it
    holds whole. 40 positions: the window of 16 binds."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 40, UNCUT.d_model)), jnp.float32)
    valid = jnp.ones((2, 40), bool)
    pos = jnp.tile(jnp.arange(40)[None], (2, 1))
    if module == "experts":
        build = lambda cfg: SparseMLP(cfg)  # noqa: E731
        args = (x, valid)
        kw = dict(whole=("router", "shared"), leading=("experts_",))
    else:
        layer = 0 if module == "window" else 3
        assert (UNCUT.layer_windows[layer], UNCUT.layer_rotary[layer]) == (
            (16, True) if module == "window" else (None, False))
        build = lambda cfg: Attention(cfg, layer=layer)  # noqa: E731
        args = (x, pos, None, None, valid, pos)
        kw = dict(whole=("q_norm", "k_norm"))
    params = _init(build(UNCUT), rng, *args)
    whole = np.asarray(jax.jit(build(UNCUT).apply)(params, *args)[0],
                       np.float64)
    # the shared expert: every rank computes it alike, so it counts once
    once = 0.0
    if module == "experts":
        ff = UNCUT.num_shared_experts * UNCUT.expert_d_ff
        once = np.asarray(jax.jit(MLP(UNCUT, width=ff).apply)(
            {"params": params["params"]["shared"]}, x), np.float64)
        assert np.abs(np.asarray(params["params"]["router_bias"])).max() > 0.1
    total = np.zeros_like(whole) + once
    for rank in range(SHARES):
        part = jax.jit(build(_share(rank)).apply)(
            _cut(params, rank, **kw), *args)[0]
        total += np.asarray(part, np.float64) - once
    assert np.abs(total - whole).max() < TOL
    assert np.abs(whole - once).max() > 0.05


def test_a_window_layer_and_a_full_layer_differ_and_the_window_binds():
    """The same parameters as layer 0 (window, rotary) and as layer 3 (full,
    no rotation) give another result, and a window layer's last position does
    not move when a key 16 or more back does."""
    rng = np.random.default_rng(6)
    x = np.asarray(rng.normal(size=(1, 40, UNCUT.d_model)), np.float32)
    valid = jnp.ones((1, 40), bool)
    pos = jnp.arange(40)[None]
    params = _init(Attention(UNCUT, layer=0), rng, jnp.asarray(x), pos, None,
                   None, valid, pos)

    def run(layer, x):
        return np.asarray(jax.jit(Attention(UNCUT, layer=layer).apply)(
            params, jnp.asarray(x), pos, None, None, valid, pos)[0])

    assert np.abs(run(0, x) - run(3, x)).max() > 0.05
    moved = x.copy()
    moved[0, :24] += 1.0  # positions 0-23: 16 and more behind position 39
    assert np.array_equal(run(0, moved)[0, -1], run(0, x)[0, -1])
    assert np.abs(run(3, moved)[0, -1] - run(3, x)[0, -1]).max() > 1e-3
    moved = x.copy()
    moved[0, 24] += 1.0  # 15 behind: inside the window
    assert np.abs(run(0, moved)[0, -1] - run(0, x)[0, -1]).max() > 1e-4


def test_the_references_shares_add_up_to_the_uncut_reference():
    """The same for the plain reference's layers (the benchmark family's toy
    widths): rank r of 2 head ranks and of 4 expert ranks given its heads'
    and experts' slices, against the layer holding all of them, for a window
    layer and a full layer. A whole layer is not linear in its mixer (the
    post-norm and the experts read the mixer's sum), so the mixers and the
    experts are added up apart, where the all-reduces would stand."""
    cut = modelcfg.rehearsal_cut(modelcfg.load_config(CONFIG))
    uncut = dict(cut, num_attention_heads=8, num_key_value_heads=4,
                 head_shards=1, head_rank=0, num_experts=16, expert_shards=1,
                 expert_rank=0)
    x = jnp.asarray(np.random.default_rng(6).normal(
        size=(2, 64, cut["hidden_size"])), jnp.float32)
    ops = reference.Ops()

    def _slice(p, rank, shares, axis):
        n = p.shape[axis] // shares
        return jax.lax.slice_in_dim(p, rank * n, (rank + 1) * n, axis=axis)

    def leaf_cut(leaf, rank, shares, axis):
        k = _slice(leaf[0], rank, shares, axis)
        return (k, leaf[1] if axis == 0 else _slice(leaf[1], rank, shares, -1))

    for kind in ("expert_window", "expert_full"):
        leaves = F.layer_leaves(uncut, kind)
        layer = jax.jit(lambda w, leaves=leaves: W._make(
            uncut, W.base_key(w), 2, leaves, F.LEAF_MAKERS))(W.seed_words(9))
        with jax.default_matmul_precision("highest"):
            spec = F.block(uncut, kind)[1]
            assert bool(spec[1]) == spec[2] == (kind == "expert_window")
            whole = np.asarray(F._attention(x, layer, spec, ops), np.float64)
            total = np.zeros_like(whole)
            for rank in range(2):
                mine = dict(layer, o_proj=leaf_cut(layer["o_proj"], rank, 2, 0),
                            **{n: leaf_cut(layer[n], rank, 2, -1) for n in (
                                "q_proj", "k_proj", "v_proj", "attn_gate")})
                total += np.asarray(F._attention(
                    x, mine, F.block(dict(cut, head_rank=rank), kind)[1],
                    ops), np.float64)
            assert np.abs(total - whole).max() < TOL, kind
            assert np.abs(whole).max() > 0.05
            # the experts: rank r of 4 holds experts [4r, 4r + 4)
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
            assert np.abs(total - whole).max() < TOL
            assert np.abs(whole - alone).max() > 0.01


def test_reckonings_read_each_layers_own_sizes():
    """approx_param_count and what each kind of layer retains for the
    published shape as this chip holds it; GQA-only code refuses it although
    every one of its mixers is GQA (its MLPs are not all dense)."""
    from jax.sharding import Mesh

    from fairness_llm_tpu.parallel import sharding as shd
    from fairness_llm_tpu.telemetry.roofline import decode_step_bytes

    cfg = F.model_config(modelcfg.load_config(CONFIG), "trinity-mini-int8")
    assert isinstance(cfg, WindowHybridConfig)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.num_experts) == (32, 4, 128)
    assert cfg.heads_held == (8, 1, 0) and cfg.experts_held == (0, 32)
    assert cfg.attention == "gqa" and cfg.num_layers == 32
    assert cfg.layer_kinds == ("dense",) * 2 + ("moe",) * 30
    assert cfg.layer_windows == (2048, 2048, 2048, None) * 8
    assert cfg.layer_rotary == (True, True, True, False) * 8
    assert cfg.retained_len(8192) == (3072, 3072, 3072, 8192) * 8
    assert cfg.retained_len(384) == (384,) * 32
    assert cfg.embed_scale and cfg.routed_scaling == 2.826
    mixer = 2048 * 128 * (3 * 8 + 2 * 1)
    dense = 3 * 2048 * 6144
    experts = 33 * 3 * 2048 * 1024 + 2048 * 128
    assert cfg.mixer_param_count("gqa") == mixer == 6815744
    assert cfg.mlp_param_count("dense") == dense
    assert cfg.mlp_param_count("moe") == experts
    want = 2 * 50048 * 2048 + 32 * mixer + 2 * dense + 30 * experts
    assert cfg.approx_param_count == want
    assert 6.73e9 < want < 6.74e9  # parameters held
    assert cfg.cache_values_per_token == 256 and cfg.row_state_bytes == 0
    # the step's bytes count the pool's rings (3072) where the cache is the
    # slot pool's, and every position in the static engine's
    embed = 50048 * 2048
    row = 512 * (24 * 3072 + 8 * 8192)
    stats = {"batch": 64, "cache_slots": 8192, "prefix_len": 0}
    params = (cfg.approx_param_count - embed) + 2 * embed
    assert 6.83e9 < params < 6.85e9  # bytes: the embedding is bfloat16
    assert decode_step_bytes(cfg, stats) == params + 64 * 8192 * 32 * 512
    assert decode_step_bytes(cfg, dict(stats, ring=True)) \
        == params + 64 * row
    assert 64 * row == 4563402752  # 4.56 GB against 8.59 without the ring
    tp2 = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
               ("dp", "tp", "sp"))
    with pytest.raises(NotImplementedError):
        shd.make_axis_rules(cfg, tp2)
    # the other hybrid's share is what it was
    solar = get_model_config("tiny-kda-moe")
    assert solar.heads_held == (4, 2, 4) and solar.layer_windows == (None,) * 4
    assert solar.layer_rotary == (False,) * 4
