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
from fairness_llm_tpu.ops.quant_matmul import (
    dequantize_weight,
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
