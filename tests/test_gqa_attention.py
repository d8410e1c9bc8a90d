"""The attention module's XLA branch contracts queries against the KV cache
with the kv-head axis kept (no ``Hkv x rep`` copy of keys or values), and the
per-row cache write is one scatter.

Three guards: every shape of the branch against an independent
repeat-then-einsum reference written here in numpy float32; the per-row write
against ``dynamic_update_slice``'s clamped window, row by row; and the jaxpr of
one ``serve_step`` iteration, which must hold nothing of the repeated pool's
size (the copy that cost 19-28% of device time before PR 26). What the TPU's
compiler makes of it is checked in ``test_70b_readiness.py``."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fairness_llm_tpu.models.configs import ModelConfig
from fairness_llm_tpu.models.transformer import (
    Attention,
    LayerCache,
    Transformer,
    _cache_write,
    _dequantize_kv,
    _quantize_kv,
    init_cache,
    init_params,
)

B, L, D, D_MODEL, PC, WINDOW = 3, 12, 8, 32, 5, 4
HKV = 2


def _config(rep: int, **over) -> ModelConfig:
    # learned positions: the module applies no rope, so q/k/v are the plain
    # projections and the reference needs nothing of the module but weights.
    return ModelConfig(
        name=f"gqa-rep{rep}", vocab_size=64, num_layers=1, num_heads=HKV * rep,
        num_kv_heads=HKV, d_model=D_MODEL, d_ff=64, head_dim=D, max_seq_len=64,
        pos_emb="learned", dtype="float32", use_flash_attention=False, **over,
    )


def _init(module, key, *args):
    params = nn.meta.unbox(jax.jit(lambda k: module.init(k, *args))(key)["params"])
    return jax.tree.map(lambda p: p * 15.0, params)  # init is 0.02: sharpen the softmax


def _reference(q, keys, values, allowed, rep, shared=None):
    """Repeat every kv head ``rep`` times, then plain per-head attention."""
    keys, values = np.repeat(keys, rep, axis=2), np.repeat(values, rep, axis=2)
    scale = np.float32(D ** -0.5)
    scores = np.einsum("bqhd,bkhd->bhqk", q, keys) * scale
    scores = np.where(allowed[:, None], scores, np.float32(-1e30))
    if shared is not None:
        sk, sv = (np.repeat(t, rep, axis=1) for t in shared)
        s_sh = np.einsum("bqhd,khd->bhqk", q, sk) * scale  # before every query
        scores = np.concatenate([s_sh, scores], axis=-1)
    scores = scores - scores.max(axis=-1, keepdims=True)
    probs = np.exp(scores)
    probs = probs / probs.sum(axis=-1, keepdims=True)
    if shared is None:
        return np.einsum("bhqk,bkhd->bqhd", probs, values)
    n = sk.shape[0]
    return (np.einsum("bhqk,khd->bqhd", probs[..., :n], sv)
            + np.einsum("bhqk,bkhd->bqhd", probs[..., n:], values))


def _case(rep: int, mode: str, variant: str, kv_quant: bool = False):
    """Run the module and the reference on one shape; returns (got, want,
    new cache layer, expected keys)."""
    window = WINDOW if variant == "window" else None
    cfg = _config(rep, sliding_window=window, kv_cache_quant=kv_quant)
    rng = np.random.default_rng(rep * 100 + len(mode) * 10 + len(variant))
    S = 1 if mode == "step" else 4
    x = rng.standard_normal((B, S, D_MODEL)).astype(np.float32)
    shift = PC if variant == "shared" else 0  # shared keys sit at 0..Pc-1

    module = Attention(cfg)
    if mode == "nocache":
        cache_layer = cache_index = write_offsets = None
        K = S
        starts = np.zeros(B, np.int64)
        key_valid = np.ones((B, K), bool)
        key_valid[1, 1] = False  # one padded key
    else:
        K = L
        own = tuple(rng.standard_normal((B, L, HKV, D)).astype(np.float32)
                    for _ in range(2))
        if mode == "step":  # rows at their own lengths
            starts = np.array([3, 7, 5])
            cache_index, write_offsets = None, jnp.asarray(starts, jnp.int32)
        else:  # one uniform write index
            starts = np.full(B, 6)
            cache_index, write_offsets = jnp.int32(6), None
        key_valid = np.arange(L)[None, :] < (starts + S)[:, None]
        key_valid[2, 0] = False  # a hole the mask must honour
        if kv_quant:
            qk, ks = _quantize_kv(jnp.asarray(own[0]))
            qv, vs = _quantize_kv(jnp.asarray(own[1]))
            cache_layer = LayerCache(k=qk, v=qv, k_scale=ks, v_scale=vs)
            own = tuple(np.asarray(_dequantize_kv(t, s, jnp.float32))
                        for t, s in ((qk, ks), (qv, vs)))
        else:
            cache_layer = LayerCache(k=jnp.asarray(own[0]), v=jnp.asarray(own[1]))
    positions = (starts[:, None] + np.arange(S)[None, :] + shift).astype(np.int32)
    key_positions = np.tile(np.arange(K, dtype=np.int32)[None] + shift, (B, 1))
    shared = None
    if variant == "shared":
        shared = tuple(rng.standard_normal((PC, HKV, D)).astype(np.float32)
                       for _ in range(2))

    args = (jnp.asarray(x), jnp.asarray(positions), cache_layer, cache_index,
            jnp.asarray(key_valid), jnp.asarray(key_positions), False,
            None if shared is None else tuple(map(jnp.asarray, shared)),
            write_offsets)
    params = _init(module, jax.random.key(rep), *args)
    got, new_layer = jax.jit(lambda p: module.apply({"params": p}, *args))(params)

    w = {n: np.asarray(params[n]["kernel"], np.float32)
         for n in ("q_proj", "k_proj", "v_proj", "o_proj")}
    q = (x @ w["q_proj"]).reshape(B, S, HKV * rep, D)
    k_new = (x @ w["k_proj"]).reshape(B, S, HKV, D)
    v_new = (x @ w["v_proj"]).reshape(B, S, HKV, D)
    if kv_quant:
        k_new, v_new = (np.asarray(_dequantize_kv(*_quantize_kv(jnp.asarray(t)), jnp.float32))
                        for t in (k_new, v_new))
    if mode == "nocache":
        keys, values = k_new, v_new
    else:
        keys, values = own[0].copy(), own[1].copy()
        for b in range(B):
            keys[b, starts[b]:starts[b] + S] = k_new[b]
            values[b, starts[b]:starts[b] + S] = v_new[b]
    q_slot = starts[:, None, None] + np.arange(S)[None, :, None]  # [B, S, 1]
    allowed = (np.arange(K)[None, None, :] <= q_slot) & key_valid[:, None, :]
    if window is not None:
        allowed &= (positions[:, :, None] - key_positions[:, None, :]) < window
    attn = _reference(q, keys, values, allowed, rep, shared)
    want = attn.reshape(B, S, -1) @ w["o_proj"]
    return np.asarray(got), want, new_layer, keys


@pytest.mark.parametrize("variant", ["plain", "window", "shared"])
@pytest.mark.parametrize("mode", ["step", "chunk", "nocache"])
@pytest.mark.parametrize("rep", [1, 2, 4, 7])
def test_grouped_attention_matches_repeat_reference(rep, mode, variant):
    got, want, new_layer, keys = _case(rep, mode, variant)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    if new_layer is not None:  # the cache keeps its layout: [B, L, Hkv, D]
        assert new_layer.k.shape == (B, L, HKV, D)
        np.testing.assert_allclose(np.asarray(new_layer.k), keys, atol=1e-6)


def test_grouped_attention_shared_prefix_honours_the_window():
    """Shared keys further back than the window are masked per query."""
    cfg = _config(4, sliding_window=WINDOW)
    module = Attention(cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, 1, D_MODEL)), jnp.float32)
    cache = LayerCache(k=jnp.zeros((B, L, HKV, D)), v=jnp.zeros((B, L, HKV, D)))
    starts = jnp.asarray([0, 1, 2], jnp.int32)
    kv = jnp.arange(L)[None, :] <= starts[:, None]
    kpos = jnp.tile(jnp.arange(L, dtype=jnp.int32)[None] + PC, (B, 1))
    pos = (starts + PC)[:, None]
    shared = [jnp.asarray(rng.standard_normal((PC, HKV, D)), jnp.float32)
              for _ in range(2)]
    far = [s.at[: PC - WINDOW + 1].set(99.0) for s in shared]  # out of every window
    params = _init(module, jax.random.key(0), x, pos, cache, None, kv, kpos,
                   False, tuple(shared), starts)
    run = jax.jit(lambda sh: module.apply(
        {"params": params}, x, pos, cache, None, kv, kpos, False, sh, starts)[0])
    np.testing.assert_allclose(
        np.asarray(run(tuple(shared))), np.asarray(run(tuple(far))), atol=1e-6)


def test_grouped_attention_int8_kv_cache():
    got, want, new_layer, _ = _case(4, "step", "plain", kv_quant=True)
    assert new_layer.k.dtype == jnp.int8
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("trailing", [(HKV, D), (HKV,)])  # k/v; int8-KV scales
@pytest.mark.parametrize("S", [1, 4])
def test_per_row_cache_write_is_a_clamped_window_write(S, trailing):
    """One scatter per write, with ``dynamic_update_slice``'s semantics row by
    row: the window starts at the row's offset, clamped to fit the buffer."""
    rng = np.random.default_rng(S)
    buf = rng.standard_normal((B, L) + trailing).astype(np.float32)
    upd = rng.standard_normal((B, S) + trailing).astype(np.float32)
    offsets = np.array([0, L - S, L + 3])  # the last one runs off the end
    got = jax.jit(_cache_write)(jnp.asarray(buf), jnp.asarray(upd), None,
                                jnp.asarray(offsets, jnp.int32))
    want = buf.copy()
    for b, off in enumerate(np.clip(offsets, 0, L - S)):
        want[b, off:off + S] = upd[b]
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# structural witness: nothing of the repeated pool's size in serve_step
# ---------------------------------------------------------------------------

SLOTS, CACHE_LEN = 3, 40


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _serve_step_jaxpr(cfg):
    from fairness_llm_tpu.runtime.sampling import SamplerSettings
    from fairness_llm_tpu.runtime.stepbuilder import build_serve_step

    run = build_serve_step(
        cfg, Transformer(cfg), SamplerSettings(temperature=0.0, top_k=0, top_p=1.0),
        cfg.pad_token_id, cfg.eos_token_id, num_slots=SLOTS, chunk=2,
        guard=False)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: init_cache(cfg, SLOTS, CACHE_LEN))
    row = lambda dt: jax.ShapeDtypeStruct((SLOTS,), dt)  # noqa: E731
    logits = jax.ShapeDtypeStruct((SLOTS, cfg.vocab_size), jnp.float32)
    return jax.make_jaxpr(run)(
        params, cache, logits, row(jnp.uint32), row(jnp.int32), row(jnp.int32),
        row(jnp.int32), row(jnp.bool_), row(jnp.bool_)).jaxpr


@pytest.mark.parametrize("rep", [2, 7])
def test_serve_step_never_holds_the_repeated_pool(rep):
    cfg = dataclasses.replace(
        _config(rep), name=f"gqa-witness-rep{rep}", vocab_size=512, head_dim=16,
        d_model=64, num_layers=2, pos_emb="rope")
    pool = SLOTS * CACHE_LEN * cfg.num_kv_heads * cfg.head_dim
    seen_pool = False
    for eqn in _eqns(_serve_step_jaxpr(cfg)):
        for out in eqn.outvars:
            shape = tuple(getattr(out.aval, "shape", ()))
            size = int(np.prod(shape)) if shape else 1
            assert not (size > pool and size % pool == 0), (
                f"{eqn.primitive.name} makes {shape}: {size // pool} pools")
            if eqn.primitive.name == "broadcast_in_dim":
                assert not (size >= pool and shape[:2] == (SLOTS, CACHE_LEN)), (
                    f"broadcast_in_dim onto the pool's shape: {shape}")
            if eqn.primitive.name == "scatter" and size == pool:
                # what lets the TPU compiler emit one scatter, not a row loop
                seen_pool = True
                assert eqn.params["unique_indices"] and eqn.params["indices_are_sorted"]
    assert seen_pool  # the walk reached the cache write inside the loop
