"""Oracle tests for the fused decode-attention kernel (interpret mode).

The kernel (``ops/decode_attention.py``) is OFF by default — measured ~8%
slower than XLA's fusions on the sweep (ROADMAP.md D4) — but
stays in the tree as oracle-verified groundwork for a head-major cache
layout. These tests pin its semantics against a dense reference: GQA head
mapping, partial validity masks, the shared-prefix joint softmax (including
the 128-padding mask), and the engine-facing gate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fairness_llm_tpu.ops.decode_attention import (
    decode_attention,
    decode_attn_supported,
)


def _oracle(q, k, v, valid, sk=None, sv=None):
    B, H, D = q.shape
    rep = H // k.shape[2]
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    s_own = jnp.einsum("bhd,blhd->bhl", q, kk) * D ** -0.5
    s_own = jnp.where(valid[:, None, :], s_own, -1e30)
    if sk is not None:
        P = sk.shape[0]
        sk2 = jnp.repeat(sk, rep, axis=1)
        sv2 = jnp.repeat(sv, rep, axis=1)
        s_sh = jnp.einsum("bhd,phd->bhp", q, sk2) * D ** -0.5
        s = jnp.concatenate([s_sh, s_own], axis=-1)
        vj = jnp.concatenate(
            [jnp.broadcast_to(sv2[None], (B, P, H, D)), vv], axis=1
        )
    else:
        s, vj = s_own, vv
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", p, vj)


@pytest.mark.parametrize("shared_p", [None, 96, 128])
@pytest.mark.parametrize("hkv", [2, 4])
def test_kernel_matches_dense_oracle(shared_p, hkv):
    rng = np.random.default_rng(0)
    B, H, D, L = 8, 4, 64, 256
    q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, hkv, D)).astype(np.float32))
    valid = jnp.asarray(rng.random((B, L)) < 0.5).at[:, 0].set(True)
    shared = None
    if shared_p:
        sk = jnp.asarray(rng.normal(size=(shared_p, hkv, D)).astype(np.float32))
        sv = jnp.asarray(rng.normal(size=(shared_p, hkv, D)).astype(np.float32))
        shared = (sk, sv)
    got = decode_attention(q, k, v, valid, shared, interpret=True)
    want = _oracle(q, k, v, valid, *(shared or (None, None)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_supported_gate():
    assert decode_attn_supported(48, 256, 64)
    assert not decode_attn_supported(45, 256, 64)  # batch not 8-multiple
    assert not decode_attn_supported(48, 224, 64)  # cache not 128-multiple
    assert not decode_attn_supported(48, 256, 48)  # head_dim not 64-multiple
    # batch-blocking keeps big batches eligible (rows are independent);
    # the per-block VMEM budget still bounds cache length x head_dim
    assert decode_attn_supported(192, 384, 64)
    assert decode_attn_supported(360, 256, 64, kv_itemsize=1)
    assert not decode_attn_supported(48, 4096, 64)  # 8-row block over budget
    assert decode_attn_supported(48, 4096, 64, kv_itemsize=1)  # int8: half bytes
    assert decode_attn_supported(48, 256, 64, shared_len=704)  # the sweep shape
    # a multi-thousand-token shared prefix joins the VMEM accounting
    assert not decode_attn_supported(48, 256, 64, shared_len=30000)


def test_batch_block_choice():
    from fairness_llm_tpu.ops.decode_attention import _pick_batch_block

    # whole batch when it fits; largest dividing 8-multiple otherwise
    assert _pick_batch_block(48, 256, 64, 0, 4) == 48
    bb = _pick_batch_block(360, 256, 64, 0, 1)
    assert bb > 0 and 360 % bb == 0 and bb % 8 == 0 and bb < 360


@pytest.mark.parametrize("shared_p", [None, 96])
def test_kernel_int8_cache_matches_dequant_oracle(shared_p):
    """int8-cache mode: the kernel must equal dense attention over the
    DEQUANTIZED cache (scale-folding into scores/probs is exact math, so
    tolerance is float rounding, not quantization error)."""
    rng = np.random.default_rng(2)
    B, H, hkv, D, L = 8, 4, 2, 64, 256
    q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, hkv, D)).astype(np.float32))
    valid = jnp.asarray(rng.random((B, L)) < 0.5).at[:, 0].set(True)

    from fairness_llm_tpu.models.transformer import _dequantize_kv, _quantize_kv

    qk, ks = _quantize_kv(k)
    qv, vs = _quantize_kv(v)
    shared = None
    if shared_p:
        sk = jnp.asarray(rng.normal(size=(shared_p, hkv, D)).astype(np.float32))
        sv = jnp.asarray(rng.normal(size=(shared_p, hkv, D)).astype(np.float32))
        shared = (sk, sv)
    got = decode_attention(
        q, qk, qv, valid, shared, k_scale=ks, v_scale=vs, interpret=True
    )
    want = _oracle(
        q, _dequantize_kv(qk, ks, jnp.float32), _dequantize_kv(qv, vs, jnp.float32),
        valid, *(shared or (None, None)),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_kernel_int8_requires_both_scales():
    q = jnp.zeros((8, 4, 64), jnp.float32)
    k = jnp.zeros((8, 128, 2, 64), jnp.int8)
    valid = jnp.ones((8, 128), bool)
    with pytest.raises(ValueError, match="k_scale and v_scale"):
        decode_attention(q, k, k, valid, k_scale=jnp.ones((8, 128, 2)), interpret=True)


def test_zero_length_prefix_is_no_prefix():
    rng = np.random.default_rng(1)
    B, H, Hkv, D, L = 8, 4, 2, 64, 128
    q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, Hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, Hkv, D)).astype(np.float32))
    valid = jnp.asarray(np.ones((B, L), bool))
    empty = (jnp.zeros((0, Hkv, D)), jnp.zeros((0, Hkv, D)))
    got = decode_attention(q, k, v, valid, empty, interpret=True)
    want = decode_attention(q, k, v, valid, None, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def _oracle_multi(q, k, v, valid, offs, sk=None, sv=None):
    """Dense reference for the multi-query (speculative verify) kernel mode:
    query i of row b sees own-cache slot j iff valid AND j <= offs[b] + i;
    shared-prefix slots are always visible."""
    B, Q, H, D = q.shape
    L = k.shape[1]
    rep = H // k.shape[2]
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    s_own = jnp.einsum("bqhd,blhd->bhql", q, kk) * D ** -0.5
    j = jnp.arange(L)
    causal = j[None, None, :] <= offs[:, None, None] + jnp.arange(Q)[None, :, None]
    allowed = valid[:, None, :] & causal  # [B, Q, L]
    s_own = jnp.where(allowed[:, None, :, :], s_own, -1e30)
    if sk is not None:
        P = sk.shape[0]
        sk2 = jnp.repeat(sk, rep, axis=1)
        sv2 = jnp.repeat(sv, rep, axis=1)
        s_sh = jnp.einsum("bqhd,phd->bhqp", q, sk2) * D ** -0.5
        s = jnp.concatenate([s_sh, s_own], axis=-1)
        vj = jnp.concatenate(
            [jnp.broadcast_to(sv2[None], (B, P, H, D)), vv], axis=1
        )
    else:
        s, vj = s_own, vv
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhql,blhd->bqhd", p, vj)


@pytest.mark.parametrize("shared_p", [None, 96])
@pytest.mark.parametrize("hkv", [2, 4])
def test_multi_query_kernel_matches_dense_oracle(shared_p, hkv):
    """q_len > 1 (speculative verify window) with per-row causal offsets."""
    rng = np.random.default_rng(3)
    B, Q, H, D, L = 8, 4, 4, 64, 256
    q = jnp.asarray(rng.normal(size=(B, Q, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, hkv, D)).astype(np.float32))
    offs = jnp.asarray(rng.integers(1, L - Q, size=B).astype(np.int32))
    # valid: everything at/below the verify window (the engine invariant),
    # with a few earlier holes to exercise the mask AND.
    j = np.arange(L)[None, :]
    valid_np = j <= (np.asarray(offs)[:, None] + Q - 1)
    valid_np &= rng.random((B, L)) < 0.9
    valid_np[:, 0] = True
    valid = jnp.asarray(valid_np)
    shared = None
    if shared_p:
        sk = jnp.asarray(rng.normal(size=(shared_p, hkv, D)).astype(np.float32))
        sv = jnp.asarray(rng.normal(size=(shared_p, hkv, D)).astype(np.float32))
        shared = (sk, sv)
    got = decode_attention(q, k, v, valid, shared, q_offsets=offs, interpret=True)
    want = _oracle_multi(q, k, v, valid, offs, *(shared or (None, None)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_multi_query_kernel_int8_cache_matches_dequant_oracle():
    rng = np.random.default_rng(4)
    B, Q, H, hkv, D, L = 8, 3, 4, 2, 64, 256
    q = jnp.asarray(rng.normal(size=(B, Q, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, L, hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, L, hkv, D)).astype(np.float32))
    offs = jnp.asarray(rng.integers(0, L - Q, size=B).astype(np.int32))
    valid = jnp.asarray(rng.random((B, L)) < 0.8).at[:, 0].set(True)

    from fairness_llm_tpu.models.transformer import _dequantize_kv, _quantize_kv

    qk, ks = _quantize_kv(k)
    qv, vs = _quantize_kv(v)
    got = decode_attention(
        q, qk, qv, valid, None, k_scale=ks, v_scale=vs, q_offsets=offs,
        interpret=True,
    )
    want = _oracle_multi(
        q, _dequantize_kv(qk, ks, jnp.float32),
        _dequantize_kv(qv, vs, jnp.float32), valid, offs,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_multi_query_requires_offsets_and_gate_accounts_q():
    q4 = jnp.zeros((8, 4, 4, 64), jnp.float32)
    k = jnp.zeros((8, 128, 2, 64), jnp.float32)
    valid = jnp.ones((8, 128), bool)
    with pytest.raises(ValueError, match="q_offsets"):
        decode_attention(q4, k, k, valid, interpret=True)
    # the VMEM model must charge q_len (a huge window fails where q=1 passes)
    assert decode_attn_supported(48, 4096, 64, kv_itemsize=1, q_len=1)
    assert decode_attn_supported(48, 256, 64, q_len=9)


def test_model_gate_off_by_default_and_off_paths():
    """The model only takes the kernel on TPU + flag + compatible config;
    in this CPU suite the gate must always be False so decode behavior (and
    every parity/golden test) is byte-stable."""
    import dataclasses

    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.models.transformer import Attention

    cfg = get_model_config("gpt2-small")
    assert not cfg.use_decode_attention_kernel  # measured slower: default off
    on = dataclasses.replace(cfg, use_decode_attention_kernel=True)
    attn = Attention(on)
    # CPU backend -> gated off even when the flag is set
    assert not attn._decode_kernel_ok(1, object(), 48, 256)


def test_engine_vmem_compile_fallback(monkeypatch):
    """A Mosaic scoped-VMEM compile failure (the gate's calibrated byte
    model under-predicting) must degrade the engine to the XLA attention
    path, not fail generate(): the engine catches the error, disables the
    kernel flag, recompiles once, and serves."""
    import dataclasses

    from fairness_llm_tpu.config import ModelSettings
    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    cfg = dataclasses.replace(
        get_model_config("tiny-test"), use_decode_attention_kernel=True
    )
    eng = DecodeEngine(cfg, seed=0)
    real = DecodeEngine._decode_fn
    state = {"raised": False}

    def fake_decode_fn(self, *args, **kwargs):
        if not state["raised"]:
            state["raised"] = True

            def boom(*a, **k):
                # The realistic shape: Mosaic rejections surface through
                # the XLA runtime layer (jax.errors.JaxRuntimeError is the
                # XlaRuntimeError alias), which is what the engine's
                # narrowed compile-error check matches on.
                import jax

                raise jax.errors.JaxRuntimeError(
                    "Ran out of scoped vmem while compiling the kernel"
                )

            return boom
        return real(self, *args, **kwargs)

    monkeypatch.setattr(DecodeEngine, "_decode_fn", fake_decode_fn)
    out = eng.generate(
        ["hello there", "general kenobi"],
        ModelSettings(temperature=0.0, max_tokens=4),
        seed=0,
    )
    assert state["raised"]
    assert not eng.config.use_decode_attention_kernel  # fell back
    # The downgrade is RECORDED: stats carry the effective attention path,
    # so a record produced past a gate miss can't claim kernel provenance.
    assert out.stats["decode_kernel"] is False
    assert len(out.texts) == 2

    # A non-VMEM error (or one with the kernel already off) still raises.
    state["raised"] = False

    def fake_other(self, *args, **kwargs):
        def boom(*a, **k):
            raise RuntimeError("unrelated failure")

        return boom

    monkeypatch.setattr(DecodeEngine, "_decode_fn", fake_other)
    eng2 = DecodeEngine(cfg, seed=0)
    with pytest.raises(RuntimeError, match="unrelated"):
        eng2.generate(["x"], ModelSettings(temperature=0.0, max_tokens=2))

    # Narrowed catch: an arbitrary PYTHON exception that merely mentions
    # 'vmem' is NOT a kernel compile failure and must propagate instead of
    # silently downgrading the engine (the old substring-only match
    # absorbed it).
    def fake_lookalike(self, *args, **kwargs):
        def boom(*a, **k):
            raise RuntimeError("user callback touched vmem stats")

        return boom

    monkeypatch.setattr(DecodeEngine, "_decode_fn", fake_lookalike)
    eng3 = DecodeEngine(cfg, seed=0)
    with pytest.raises(RuntimeError, match="vmem stats"):
        eng3.generate(["x"], ModelSettings(temperature=0.0, max_tokens=2))
    assert eng3.config.use_decode_attention_kernel  # NOT downgraded
