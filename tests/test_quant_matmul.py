"""int8 weight-only quantization: kernel oracle, model parity, sharding, load.

The capability under test is the round-4 headline: serving weights stored
int8 in HBM with dequantization inside the Pallas matmul tile (the naive
dequant-at-use gets hoisted out of decode loops by XLA and materializes the
float tree — ops/quant_matmul.py). The reference has no local
weights at all (its models are remote APIs, SURVEY.md §0); parity here is
against our own float path, which is golden/HF-parity tested elsewhere.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fairness_llm_tpu.config import MeshConfig, ModelSettings
from fairness_llm_tpu.models.configs import get_model_config
from fairness_llm_tpu.models.transformer import Transformer, init_params, init_params_lowmem
from fairness_llm_tpu.ops import quant_matmul as qm
from fairness_llm_tpu.ops.quant_matmul import (
    dequantize_weight,
    matmul_blocks,
    quant_matmul,
    quant_tileable,
    quantize_weight,
)
from fairness_llm_tpu.parallel import sharding as shd
from fairness_llm_tpu.runtime.engine import DecodeEngine
from fairness_llm_tpu.runtime.weights import dequantize_params, quantize_params


def _ref_matmul(x, wq, scale):
    w = wq.astype(jnp.float32) * scale[None, :].astype(jnp.float32)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


# ---------------------------------------------------------------------------
# Quantize / dequantize
# ---------------------------------------------------------------------------


def test_quantize_round_trip_error_bound():
    w = jax.random.normal(jax.random.key(0), (256, 384), jnp.float32) * 0.05
    q, s = quantize_weight(w)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    back = dequantize_weight(q, s, jnp.float32)
    # symmetric 127-level quant: per-channel error <= scale/2 = amax/254
    bound = np.abs(np.asarray(w)).max(axis=0) / 254.0 + 1e-9
    assert (np.abs(np.asarray(back - w)) <= bound[None, :] * 1.001).all()


def test_quantize_zero_column_safe():
    w = jnp.zeros((128, 128), jnp.float32)
    q, s = quantize_weight(w)
    assert (np.asarray(q) == 0).all() and np.isfinite(np.asarray(s)).all()
    assert (np.asarray(dequantize_weight(q, s)) == 0).all()


# ---------------------------------------------------------------------------
# Kernel (interpret mode — the Mosaic pipeline itself is exercised on TPU by
# bench.py and the topology-AOT test below)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "m,k,n",
    [
        (48, 768, 3072),  # sweep decode shape
        (16, 256, 128),  # minimum tiles
        (1, 128, 256),  # single row -> sublane padding
        (45, 384, 640),  # M not a multiple of 8
    ],
)
def test_kernel_oracle_interpret(m, k, n):
    kx, kw = jax.random.split(jax.random.key(m * k + n))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32) * 0.02
    wq, scale = quantize_weight(w)
    got = quant_matmul(x, wq, scale, interpret=True)
    want = _ref_matmul(x, wq, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_kernel_oracle_bf16_interpret():
    x = jax.random.normal(jax.random.key(1), (16, 256), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(2), (256, 256), jnp.float32) * 0.02
    wq, scale = quantize_weight(w)
    got = quant_matmul(x, wq, scale, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _ref_matmul(x.astype(jnp.float32), wq, scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=2e-2, atol=2e-2
    )


def test_tileability_gate():
    assert quant_tileable(768, 3072)
    assert not quant_tileable(768, 16032)  # llama vocab / 8: not a lane multiple
    assert not quant_tileable(100, 256)
    # the non-tileable XLA fallback still computes correctly
    x = jax.random.normal(jax.random.key(3), (8, 100), jnp.float32)
    w = jax.random.normal(jax.random.key(4), (100, 96), jnp.float32) * 0.02
    wq, scale = quantize_weight(w)
    np.testing.assert_allclose(
        np.asarray(quant_matmul(x, wq, scale)),
        np.asarray(_ref_matmul(x, wq, scale)),
        rtol=2e-5, atol=2e-5,
    )


# ---------------------------------------------------------------------------
# The block rule: total and legal over every shape the configurations produce
# ---------------------------------------------------------------------------

# (d_model, q_dim, kv_dim, d_ff, vocab) of the dense configurations the cells
# and the readiness tests run; A.X-K1's projections are listed one by one.
_DENSE = {
    "mistral-7b": (4096, 4096, 1024, 14336, 32000),
    "qwen2-7b": (3584, 3584, 512, 18944, 152064),
    "llama3-70b": (8192, 8192, 1024, 28672, 128256),
}
_AX_K1 = [(7168, 1536), (1536, 12288), (8192, 7168), (512, 16384),
          (7168, 2048), (2048, 7168), (7168, 18432), (18432, 7168),
          (7168, 20480)]
# decode pools (48, 96, 192), the replay's part-filled chunks, one 64-token
# window of 8 rows, a single row, a row count that needs padding
_ROWS = (1, 45, 48, 64, 96, 128, 192, 512)


def _weight_shapes():
    """name -> (K, N) of every int8 product, whole and as the tp = 4 / tp = 8
    shards ``quant_matmul_sharded`` hands the kernel (column-parallel cuts N,
    row-parallel cuts K); what misses the tile gate takes the XLA path and
    is left out, as at the call."""
    out = {}
    for name, (d, q, kv, ff, vocab) in _DENSE.items():
        for tp in (1, 4, 8):
            col = [(d, q // tp), (d, kv // tp), (d, ff // tp), (d, vocab // tp)]
            row = [(q // tp, d), (ff // tp, d)]
            for k, n in col + row:
                if quant_tileable(k, n):
                    out[f"{name}-tp{tp}-{k}x{n}"] = (k, n)
    for k, n in _AX_K1:
        out[f"ax-k1-{k}x{n}"] = (k, n)
    return out


_SHAPES = _weight_shapes()


@pytest.mark.parametrize("name", sorted(_SHAPES))
def test_block_rule_total_and_legal(name):
    k, n = _SHAPES[name]
    for m in _ROWS:
        for x_item, out_item in ((2, 2), (2, 4), (4, 4)):
            b = matmul_blocks(m, k, n, x_item, out_item)
            tiles = -(-m // b.bm)
            # divides or pads as the kernel expects; lane and sublane multiples
            assert k % b.bk == 0 and b.bk % 128 == 0, (m, b)
            assert n % b.bn == 0 and b.bn % 128 == 0, (m, b)
            assert b.bm % 8 == 0 and tiles * b.bm - m < 8 * tiles, (m, b)
            # what a step holds fits the limit the call sets
            assert b.vmem_bytes(x_item, out_item) <= qm._VMEM_LIMIT, (m, b)
            # more than one row tile: K whole, so the weight block's index
            # holds across the row tiles and the weight is streamed once
            assert tiles == 1 or b.bk == k, (m, b)
            # never a smaller weight block than the 512-divisor blocks were
            assert b.bk * b.bn >= qm._pick_block(k) * qm._pick_block(n), (m, b)
            # few steps, few unrolled chunks: set-up must not grow
            assert (n // b.bn) * (k // b.bk) * tiles <= 512, (m, b)
            assert -(-b.bk // qm._K_CHUNK) <= 64, (m, b)


def test_block_rule_sizes_the_decode_blocks_by_bytes():
    """The cells' decode calls: one row tile, about a megabyte a block and
    more, whole rows where K has the divisor and K-deep where it has not."""
    for m, k, n in [(48, 4096, 14336), (48, 14336, 4096), (48, 3584, 18944),
                    (96, 18944, 3584), (48, 3584, 152064), (192, 7168, 2048)]:
        b = matmul_blocks(m, k, n)
        assert b.bm == m and b.bk * b.bn >= 768 << 10, (m, k, n, b)
    assert matmul_blocks(48, 4096, 14336) == (48, 128, 14336)  # whole rows
    assert matmul_blocks(96, 3584, 18944) == (96, 3584, 512)  # 128 < 2 x 96
    assert matmul_blocks(48, 4736, 3584) == (48, 4736, 256)  # no K divisor
    assert matmul_blocks(512, 4096, 14336) == (512, 4096, 256)  # FLOP-bound
    assert matmul_blocks(1030, 4096, 14336) == (344, 4096, 512)  # 3 row tiles


def test_block_rule_shrinks_under_a_small_budget():
    """Each arm of the fit loop: N block first, then the row tile."""
    wide = matmul_blocks(512, 896, 4736 * 2, weight_block_bytes=16 << 20)
    assert wide == (512, 896, 4736 * 2)
    cut_n = matmul_blocks(512, 896, 4736 * 2, weight_block_bytes=16 << 20,
                          vmem_limit=48 << 20)
    assert cut_n.bm == 512 and cut_n.bn == 4736
    cut_m = matmul_blocks(512, 896, 4736, 4, 4, weight_block_bytes=128 << 10,
                          vmem_limit=3 << 20)
    assert cut_m.bn == 128 and cut_m.bk == 896 and cut_m.bm < 512
    assert cut_m.vmem_bytes(4, 4) <= 3 << 20
    cut_k = matmul_blocks(48, 896, 4736, weight_block_bytes=64 << 10)
    assert cut_k == (48, 128, 128)


# Toy shapes with the cells' factor structure (Qwen2: 3584 = 7 x 512,
# 18944 = 37 x 512): K = 7 x 128 walks one full chunk and a ragged one,
# N = 37 x 128 has no divisor between one lane tile and the whole row.
_TOY_K, _TOY_N = 7 * 128, 37 * 128
_ARMS = {
    # K whole, one N block a lane tile wide, one row tile (the decode arm)
    "kdeep": dict(weight_block_bytes=128 << 10),
    # the whole weight one block
    "whole": dict(weight_block_bytes=8 << 20),
    # K cut across the grid: the float32 scratch carries the partial sums
    "kgrid": dict(weight_block_bytes=64 << 10),
    # several row tiles under one resident weight block
    "rowtiles": dict(weight_block_bytes=128 << 10, vmem_limit=1 << 20),
}


@pytest.mark.parametrize("arm", sorted(_ARMS))
@pytest.mark.parametrize("m", [48, 96, 192, 512])
@pytest.mark.parametrize("dtypes", ["f32-f32", "bf16-bf16", "bf16-f32"])
def test_kernel_oracle_each_arm_of_the_rule(arm, m, dtypes):
    xd, od = (dict(f32=jnp.float32, bf16=jnp.bfloat16)[t]
              for t in dtypes.split("-"))
    kx, kw = jax.random.split(jax.random.key(m))
    x = jax.random.normal(kx, (m, _TOY_K), xd)
    w = jax.random.normal(kw, (_TOY_K, _TOY_N), jnp.float32) * 0.02
    wq, scale = quantize_weight(w)
    blocks = matmul_blocks(m, _TOY_K, _TOY_N, x.dtype.itemsize,
                           jnp.dtype(od).itemsize, **_ARMS[arm])
    if arm == "kdeep":
        assert blocks.bk == _TOY_K and blocks.bn == 128 and blocks.bm == m
    elif arm == "whole":
        assert blocks == (m, _TOY_K, _TOY_N)
    elif arm == "kgrid":
        assert blocks.bk == 128 and _TOY_K // blocks.bk == 7
    elif m >= 192:  # the small limit cuts the rows into tiles
        assert blocks.bk == _TOY_K and blocks.bm < m
    got = qm._quant_matmul_pallas(x, wq, scale, True, od, blocks)
    assert got.shape == (m, _TOY_N) and got.dtype == od
    want = _ref_matmul(x.astype(jnp.float32), wq, scale)
    tol = 2e-5 if xd == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


def test_kernel_path_counter_names_the_blocks(caplog):
    """A run says which blocks each shape took: the counter's ``blocks``
    label and, once per label set, a log line."""
    import logging

    from fairness_llm_tpu.telemetry.registry import get_registry, use_registry

    x = jnp.ones((48, 256), jnp.float32)
    with use_registry(), caplog.at_level(logging.INFO):
        quant_matmul(x, jnp.ones((256, 384), jnp.int8), jnp.ones((384,)),
                     interpret=True)
        labels = [dict(c.labels) for c in get_registry().instruments()
                  if c.name == "kernel_path_total"]
    assert [(w["path"], w["shape"], w["blocks"]) for w in labels] == [
        ("pallas", "256x384", "48:48x256x384")]
    assert "int8_matmul 256x384 takes blocks 48:48x256x384" in caplog.text


# ---------------------------------------------------------------------------
# Model forward parity
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_quant():
    cfg = get_model_config("tiny-test")
    qcfg = dataclasses.replace(cfg, weight_quant="int8")
    params = init_params(cfg, jax.random.key(0))
    return cfg, qcfg, params, quantize_params(params)


def test_forward_matches_dequantized_float_model(tiny_quant):
    cfg, qcfg, params, qparams = tiny_quant
    dq = dequantize_params(qparams)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    pos = jnp.tile(jnp.arange(16, dtype=jnp.int32)[None, :], (2, 1))
    lf, _ = Transformer(cfg).apply({"params": dq}, tokens, pos)
    lq, _ = Transformer(qcfg).apply({"params": qparams}, tokens, pos)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lf), rtol=1e-4, atol=1e-5)


def test_quant_close_to_original_float_model(tiny_quant):
    """Quantization error on the LOGITS stays small for a normal-scale tree
    (the guarantee callers actually care about)."""
    cfg, qcfg, params, qparams = tiny_quant
    tokens = jax.random.randint(jax.random.key(2), (2, 16), 0, cfg.vocab_size)
    pos = jnp.tile(jnp.arange(16, dtype=jnp.int32)[None, :], (2, 1))
    lf, _ = Transformer(cfg).apply({"params": params}, tokens, pos)
    lq, _ = Transformer(qcfg).apply({"params": qparams}, tokens, pos)
    scale = float(jnp.max(jnp.abs(lf)))
    assert float(jnp.max(jnp.abs(lq - lf))) < 0.02 * scale + 0.02


def test_untied_lm_head_quantized():
    """tiny-test ties nothing: lm_head must appear as kernel_q + kernel_scale
    in the quant tree and the float leaf must be gone."""
    cfg = get_model_config("tiny-test")
    qcfg = dataclasses.replace(cfg, weight_quant="int8")
    qp = init_params(qcfg, jax.random.key(0))
    assert qp["lm_head"]["kernel_q"].dtype == jnp.int8
    assert qp["lm_head"]["kernel_scale"].dtype == jnp.float32
    assert qp["layer_0"]["attn"]["q_proj"]["kernel_q"].dtype == jnp.int8


def test_lowmem_init_matches_tree_structure():
    qcfg = dataclasses.replace(get_model_config("tiny-test"), weight_quant="int8")
    a = init_params(qcfg, jax.random.key(0))
    b = init_params_lowmem(qcfg, jax.random.key(0))
    sa = jax.tree.map(lambda x: (x.shape, str(x.dtype)), a)
    sb = jax.tree.map(lambda x: (x.shape, str(x.dtype)), b)
    assert sa == sb
    logits, _ = Transformer(qcfg).apply(
        {"params": b},
        jnp.zeros((1, 8), jnp.int32),
        jnp.tile(jnp.arange(8, dtype=jnp.int32)[None, :], (1, 1)),
    )
    assert bool(jnp.all(jnp.isfinite(logits)))


# ---------------------------------------------------------------------------
# Sharded parity (8-device CPU mesh)
# ---------------------------------------------------------------------------


def test_sharded_forward_matches_unsharded(tiny_quant):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg, qcfg, params, qparams = tiny_quant
    tokens = jax.random.randint(jax.random.key(3), (4, 16), 0, cfg.vocab_size)
    pos = jnp.tile(jnp.arange(16, dtype=jnp.int32)[None, :], (4, 1))
    l0, _ = Transformer(qcfg).apply({"params": qparams}, tokens, pos)

    mesh = shd.make_mesh(MeshConfig(dp=2, tp=2, sp=1))
    rules = shd.make_axis_rules(qcfg, mesh)
    qp_sharded = shd.shard_params(qparams, shd.param_shardings(qcfg, mesh, rules))
    model = Transformer(qcfg)
    with mesh, nn.logical_axis_rules(rules):
        ls = jax.jit(lambda p, t, po: model.apply({"params": p}, t, po)[0])(
            qp_sharded, tokens, pos
        )
    np.testing.assert_allclose(np.asarray(ls), np.asarray(l0), rtol=1e-5, atol=1e-5)


def test_engine_greedy_parity_and_mesh(tiny_quant):
    """Greedy decode: quant engine == engine over the dequantized float tree,
    single-device AND on a dp×tp mesh."""
    cfg, qcfg, params, qparams = tiny_quant
    settings = ModelSettings(temperature=0.0, top_k=0, top_p=1.0, max_tokens=8)
    prompts = ["hello world this is", "a quantization test of", "the tiny model decode"]
    e_f = DecodeEngine(cfg, params=dequantize_params(qparams), seed=0)
    e_q = DecodeEngine(qcfg, params=qparams, seed=0)
    of = e_f.generate(prompts, settings, seed=0)
    oq = e_q.generate(prompts, settings, seed=0)
    assert (of.tokens == oq.tokens).all()

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = shd.make_mesh(MeshConfig(dp=2, tp=2, sp=1))
    e_m = DecodeEngine(qcfg, params=qparams, mesh=mesh)
    om = e_m.generate(prompts, settings, seed=0)
    assert (om.tokens == oq.tokens).all()


# ---------------------------------------------------------------------------
# Checkpoint loading
# ---------------------------------------------------------------------------


def test_load_checkpoint_int8(tmp_path, tiny_quant):
    """HF-layout checkpoint -> int8 tree: quantize-at-load equals
    quantize(load) and the engine serves it."""
    from fairness_llm_tpu.runtime.weights import load_checkpoint, save_checkpoint_hf

    cfg, qcfg, params, qparams = tiny_quant
    save_checkpoint_hf(cfg, params, str(tmp_path))
    loaded = load_checkpoint(qcfg, str(tmp_path), dtype=jnp.float32)
    want = quantize_params(
        load_checkpoint(cfg, str(tmp_path), dtype=jnp.float32)
    )
    flat_a = jax.tree_util.tree_flatten_with_path(loaded)[0]
    flat_b = jax.tree_util.tree_flatten_with_path(want)[0]
    assert [p for p, _ in flat_a] == [p for p, _ in flat_b]
    for (pa, a), (_, b) in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(pa))


def test_save_checkpoint_dequantizes(tmp_path, tiny_quant):
    from fairness_llm_tpu.runtime.weights import load_checkpoint, save_checkpoint_hf

    cfg, qcfg, params, qparams = tiny_quant
    save_checkpoint_hf(qcfg, qparams, str(tmp_path))
    back = load_checkpoint(cfg, str(tmp_path), dtype=jnp.float32)
    want = dequantize_params(qparams)
    for pa, a in jax.tree_util.tree_flatten_with_path(back)[0]:
        b = want
        for part in pa:
            b = b[part.key]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)


def test_quant_round_trip_preserves_biases():
    """tiny-gpt2 carries biases on every projection: quantize->dequantize
    must keep them (regression: dequantize_params once dropped sibling
    leaves while rebuilding the module dict)."""
    cfg = get_model_config("tiny-gpt2")
    params = init_params(cfg, jax.random.key(0))
    back = dequantize_params(quantize_params(params))
    flat_a = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_b = jax.tree_util.tree_flatten_with_path(back)[0]
    assert [p for p, _ in flat_a] == [p for p, _ in flat_b]
    bias = back["layer_0"]["attn"]["q_proj"]["bias"]
    np.testing.assert_array_equal(
        np.asarray(bias), np.asarray(params["layer_0"]["attn"]["q_proj"]["bias"])
    )


def test_shared_prefix_on_mesh_batch1_forward(tiny_quant):
    """The engine's shared-prefix prefill runs batch=1 with an arbitrary
    prefix length; on a dp>1 mesh the QuantDense row sharding must fall back
    to replication when rows don't divide dp (regression: shard_map
    divisibility crash)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg, qcfg, params, qparams = tiny_quant
    settings = ModelSettings(temperature=0.0, top_k=0, top_p=1.0, max_tokens=6)
    mesh = shd.make_mesh(MeshConfig(dp=2, tp=2, sp=1))
    e_q = DecodeEngine(qcfg, params=qparams, seed=0)
    e_m = DecodeEngine(qcfg, params=qparams, mesh=mesh)
    # identical long-ish prompts -> auto prefix detection; explicit True
    # keeps the exact (odd) length, exercising the indivisible-rows path
    base = "the quick brown fox jumps over the lazy dog " * 4
    prompts = [base + tail for tail in ("alpha", "beta", "gamma")]
    o1 = e_q.generate(prompts, settings, seed=0, share_prefix=True)
    om = e_m.generate(prompts, settings, seed=0, share_prefix=True)
    assert o1.stats["prefix_len"] > 0
    assert (o1.tokens == om.tokens).all()


def test_train_step_rejects_quant_config():
    from fairness_llm_tpu.train import make_train_step

    qcfg = dataclasses.replace(get_model_config("tiny-test"), weight_quant="int8")
    with pytest.raises(ValueError, match="serving-only"):
        make_train_step(qcfg)


def test_backend_for_weight_quant_override(tmp_path, tiny_quant):
    """config.weight_quant='int8' (CLI --weight-quant) must route a real
    checkpoint through quantize-at-load and serve greedy-identically to an
    explicitly quantized engine."""
    import dataclasses as dc

    from fairness_llm_tpu.config import default_config
    from fairness_llm_tpu.pipeline.backends import EngineBackend, backend_for
    from fairness_llm_tpu.runtime.weights import save_checkpoint_hf

    cfg, qcfg, params, qparams = tiny_quant
    ckpt = tmp_path / "tiny-test"
    ckpt.mkdir()
    save_checkpoint_hf(cfg, params, str(ckpt))
    # tokenizer files: backend_for needs none for tiny-test (byte tokenizer)
    conf = dc.replace(
        default_config(), weights_dir=str(tmp_path), weight_quant="int8"
    )
    backend = backend_for("tiny-test", conf)
    assert isinstance(backend, EngineBackend)
    assert backend.engine.config.weight_quant == "int8"
    assert backend.engine.params["layer_0"]["attn"]["q_proj"]["kernel_q"].dtype == jnp.int8


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_int8_load_path(tmp_path, seed):
    """Fuzz the quantize-at-load path: random weight trees (including
    adversarial values — zeros, huge magnitudes, denormals, +-inf-free
    extremes) must round-trip load->quantize->serve without NaN/Inf logits
    or crashes, and scales must stay finite/positive."""
    import dataclasses as dc

    from fairness_llm_tpu.runtime.weights import load_checkpoint, save_checkpoint_hf

    rng = np.random.default_rng(seed)
    cfg = get_model_config("tiny-test")
    qcfg = dc.replace(cfg, weight_quant="int8")
    params = init_params(cfg, jax.random.key(seed))

    def mutate(x):
        x = np.asarray(x, np.float32).copy()
        mode = rng.integers(0, 4)
        if mode == 0:
            x[:] = 0.0  # all-zero kernel -> zero scale guard
        elif mode == 1:
            x *= 1e30  # huge magnitudes -> scale overflow guard
        elif mode == 2:
            x *= 1e-38  # denormal-range -> scale underflow guard
        return jnp.asarray(x)

    params = jax.tree.map(mutate, params)
    d = tmp_path / "fuzz"
    d.mkdir()
    save_checkpoint_hf(cfg, params, str(d))
    loaded = load_checkpoint(qcfg, str(d), dtype=jnp.float32)
    for path, leaf in jax.tree_util.tree_flatten_with_path(loaded)[0]:
        name = getattr(path[-1], "key", "")
        arr = np.asarray(leaf)
        if name == "kernel_scale":
            assert np.isfinite(arr).all() and (arr > 0).all(), path
        if name == "kernel_q":
            assert arr.dtype == np.int8
            assert (np.abs(arr.astype(np.int32)) <= 127).all()


# ---------------------------------------------------------------------------
# 70B capacity accounting (cheap, analytic — the compiled-program proof runs
# on the TPU topology in tools/prove_70b_int8_fit.py / bench.py)
# ---------------------------------------------------------------------------


def test_70b_int8_analytic_bytes_fit_v5e():
    import types

    cfg = get_model_config("llama3-70b-int8")
    mesh = types.SimpleNamespace(shape={"dp": 1, "tp": 8, "sp": 1})
    rules = shd.make_axis_rules(cfg, mesh)

    class _M:
        shape = {"dp": 1, "tp": 8, "sp": 1}

    per = shd.per_device_param_bytes(cfg, _M, rules)
    # int8 kernels + f32 scales + bf16 embeddings/norms: ~9.1 GB/chip —
    # under 15.75 with ~6 GB left for KV cache + activations. The bf16
    # config at the same tp=8 is ~17.6 GB (test_70b_readiness.py).
    assert per < 10.0e9
    bf16 = shd.per_device_param_bytes(get_model_config("llama3-70b"), _M, rules)
    assert bf16 > 15.75e9 > per
