"""70B readiness: compile-time proof of the BASELINE tp=8 config.

No environment this suite runs in holds 70B of weights, so readiness is
proven the way XLA allows it to be: AOT-lower and backend-compile the REAL
llama3-70b prefill+decode program at tp=8 over the virtual 8-device mesh with
abstract (``ShapeDtypeStruct``) parameters — every sharding rule, layout, and
collective is decided at compile time, so a rule change that would break the
70B path on hardware fails here first. Memory is asserted from the compiled
program's own analysis plus the analytic estimator, including the honest
negative result: bf16 70B params at tp=8 are ~17.6 GB/chip — OVER a v5e's
16 GB HBM — so the framework must flag it (fit paths: tp=16 or int8 weights).

Replaces nothing in the reference (it has no local models, SURVEY.md §0);
this guards the `BASELINE.json` llama3-70b TP=8 target config.
"""

import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from fairness_llm_tpu.config import MeshConfig
from fairness_llm_tpu.models.configs import get_model_config
from fairness_llm_tpu.models.transformer import Transformer, init_cache
from fairness_llm_tpu.parallel import sharding as shd

V5E_HBM_BYTES = 16 * 1024**3

def _rules_for_shape(cfg, shape):
    """make_axis_rules only reads mesh.shape — a shim lets us probe mesh
    geometries (tp=16) larger than the 8 virtual devices can realize."""
    return shd.make_axis_rules(cfg, types.SimpleNamespace(shape=shape))


def test_70b_rules_tp8_shard_everything():
    cfg = get_model_config("llama3-70b")
    rules = dict(_rules_for_shape(cfg, {"dp": 1, "tp": 8, "sp": 1}))
    # 64 q heads -> 8/chip; 8 kv heads -> exactly 1/chip; ff + vocab divide.
    assert rules["q_heads"] == "tp"
    assert rules["kv_heads"] == "tp"
    assert rules["ff"] == "tp"
    assert rules["vocab"] == "tp"


def test_70b_rules_tp16_gqa_fallback():
    """kv_heads=8 cannot split across tp=16: KV falls back to replicated
    (the production GQA fallback) while q/ff/vocab still shard."""
    cfg = get_model_config("llama3-70b")
    rules = dict(_rules_for_shape(cfg, {"dp": 1, "tp": 16, "sp": 1}))
    assert rules["kv_heads"] is None
    assert rules["q_heads"] == "tp"
    assert rules["ff"] == "tp"
    assert rules["vocab"] == "tp"


@pytest.fixture(scope="module")
def compiled_70b():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = get_model_config("llama3-70b")
    mesh = shd.make_mesh(MeshConfig(dp=1, tp=8, sp=1))
    rules = shd.make_axis_rules(cfg, mesh)
    shardings = shd.param_shardings(cfg, mesh, rules)

    model = Transformer(cfg)
    abstract = jax.eval_shape(
        model.init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.int32),
    )
    abstract = nn.meta.unbox(abstract["params"])
    aparams = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=s),
        abstract, shardings,
    )

    B, S, NEW = 8, 128, 4

    def prefill_and_decode(params, tokens, positions, valid):
        # The engine's program shape: batch prefill writes the cache, then
        # cached single-token steps extend it — all inside ONE program so the
        # cache sharding is decided entirely by GSPMD propagation.
        cache = init_cache(cfg, B, S + NEW)
        logits, cache = model.apply(
            {"params": params}, tokens, positions, valid, cache,
            left_padded=True, last_only=True,
        )

        def step(_, carry):
            logits, cache = carry
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            pos = cache.lengths[:, None]
            logits, cache = model.apply(
                {"params": params}, tok[:, None], pos,
                jnp.ones((B, 1), jnp.bool_), cache,
            )
            return logits, cache

        logits, cache = jax.lax.fori_loop(0, NEW, step, (logits, cache))
        return logits

    bs = shd.batch_sharding(mesh)
    atoks = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bs)
    apos = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bs)
    avalid = jax.ShapeDtypeStruct((B, S), jnp.bool_, sharding=bs)
    with mesh, nn.logical_axis_rules(rules):
        compiled = jax.jit(prefill_and_decode).lower(
            aparams, atoks, apos, avalid
        ).compile()
    return cfg, mesh, rules, compiled


@pytest.mark.slow  # ~80 s: the 80-layer AOT backend-compile dominates tier-1
def test_70b_aot_compiles_tp8(compiled_70b):
    # Existence of `compiled` IS the proof — GSPMD accepted every rule and
    # laid out all 80 layers' collectives at tp=8.
    cfg, mesh, rules, compiled = compiled_70b
    assert compiled.memory_analysis() is not None


@pytest.mark.slow  # shares compiled_70b — must move with the test above
def test_70b_param_bytes_match_compiled_analysis(compiled_70b):
    cfg, mesh, rules, compiled = compiled_70b
    analytic = shd.per_device_param_bytes(cfg, mesh, rules)
    measured = compiled.memory_analysis().argument_size_in_bytes
    # measured includes the token/position inputs (KB-scale vs 17.6 GB params)
    assert abs(measured - analytic) / analytic < 0.02


def test_70b_bf16_tp8_exceeds_v5e_hbm_and_is_flagged():
    """The honest capacity statement the CLI warning is built on: bf16 70B
    params at tp=8 do NOT fit one v5e chip; tp=16 (two v5e-8 slices) does."""
    cfg = get_model_config("llama3-70b")
    mesh8 = types.SimpleNamespace(shape={"dp": 1, "tp": 8, "sp": 1})
    rules8 = _rules_for_shape(cfg, mesh8.shape)
    per8 = shd.per_device_param_bytes(cfg, mesh8, rules8)
    assert per8 > V5E_HBM_BYTES  # ~17.6 GB

    mesh16 = types.SimpleNamespace(shape={"dp": 1, "tp": 16, "sp": 1})
    rules16 = _rules_for_shape(cfg, mesh16.shape)
    per16 = shd.per_device_param_bytes(cfg, mesh16, rules16)
    assert per16 < V5E_HBM_BYTES  # ~8.9 GB (kv replicated but tiny vs ff/vocab)

    # 8B at tp=8 fits comfortably — the primary BASELINE serving config.
    cfg8b = get_model_config("llama3-8b")
    per_8b = shd.per_device_param_bytes(cfg8b, mesh8, _rules_for_shape(cfg8b, mesh8.shape))
    assert per_8b < 4e9


def test_70b_decode_kv_cache_estimate():
    cfg = get_model_config("llama3-70b")
    mesh = types.SimpleNamespace(shape={"dp": 1, "tp": 8, "sp": 1})
    rules = _rules_for_shape(cfg, mesh.shape)
    # sweep shape: batch 48, 1k cache slots; kv sharded 1 head/chip ->
    # 2 * 80 layers * 48 * 1024 * 1 head * 128 dim * 2 B = ~2.0 GB/chip
    got = shd.per_device_kv_cache_bytes(cfg, mesh, batch=48, max_len=1024, rules=rules)
    assert got == 2 * 80 * 48 * 1024 * 1 * 128 * 2


def test_8b_flash_prefill_compiles_sharded_on_v5e_topology():
    """tp=8 serving prefill with the FLASH kernel engaged, through the real
    v5e compiler: the round-4 shard_map dispatch is what makes a Pallas
    flash call legal inside a multi-chip program at all (Mosaic refuses
    GSPMD-partitioned contexts — previously multi-chip prefill silently
    required dense attention). 2 layers of llama3-8b's exact dims at
    S=1024 (flash-eligible length, batch 8 over dp=2 x tp=4 so BOTH batch
    and head sharding run through the wrap)."""
    import dataclasses

    import numpy as np

    try:
        from jax.experimental import topologies

        td = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x4")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"TPU topology unavailable: {type(e).__name__}")
    from fairness_llm_tpu.ops.quant_matmul import force_pallas

    cfg = dataclasses.replace(
        get_model_config("llama3-8b"), name="llama3-8b-2l", num_layers=2,
    )
    assert cfg.use_flash_attention
    mesh = jax.sharding.Mesh(
        np.array(td.devices).reshape(2, 4, 1), ("dp", "tp", "sp")
    )
    rules = shd.make_axis_rules(cfg, mesh)
    shardings = shd.param_shardings(cfg, mesh, rules)
    model = Transformer(cfg)
    abstract = nn.meta.unbox(
        jax.eval_shape(
            model.init, jax.random.key(0),
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.int32),
        )["params"]
    )
    aparams = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=s),
        abstract, shardings,
    )

    B, S = 8, 1024

    def prefill(params, tokens, positions, valid):
        cache = init_cache(cfg, B, S + 1)
        logits, _ = model.apply(
            {"params": params}, tokens, positions, valid, cache,
            left_padded=True, last_only=True,
        )
        return logits

    bs = shd.batch_sharding(mesh)
    atoks = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bs)
    avalid = jax.ShapeDtypeStruct((B, S), jnp.bool_, sharding=bs)
    with mesh, nn.logical_axis_rules(rules), force_pallas():
        # force_pallas makes _flash_ok treat the lowering target as TPU in
        # this CPU-pinned test process; the wrap then must produce a program
        # the actual TPU compiler accepts.
        lowered = jax.jit(prefill).lower(aparams, atoks, atoks, avalid)
        # the kernel must actually be IN the program (a silent dense
        # fallback would also compile, proving nothing)
        assert "tpu_custom_call" in lowered.as_text()
        compiled = lowered.compile()
    assert compiled.memory_analysis() is not None


def test_70b_int8_layer_compiles_on_v5e_topology():
    """The int8 fit proof's LOWERING, at suite speed: a 2-layer model with
    llama3-70b's exact per-layer dimensions, int8 weights, tp=8, compiled by
    the REAL v5e TPU compiler against a topology descriptor — every Pallas
    quant matmul, shard_map wrap, and collective the 80-layer program uses,
    in ~1/40th the compile time. The full-model compile (memory analysis:
    9.29 GB/chip vs 15.75 — fits) is tools/prove_70b_int8_fit.py, recorded
    in the bench as ``int8_70b_fit``. Temps must stay activation-scale: the
    round-3 negative was 35 GB of hoisted bf16 dequants, which two layers
    would already betray (~0.9 GB of kernels -> bf16 temps would dwarf the
    0.1 GB activation budget this asserts)."""
    import dataclasses

    import jax.tree_util as jtu

    try:
        from jax.experimental import topologies

        td = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x4")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"TPU topology unavailable: {type(e).__name__}")
    from fairness_llm_tpu.models.transformer import init_cache
    from fairness_llm_tpu.ops.quant_matmul import force_pallas

    cfg = dataclasses.replace(
        get_model_config("llama3-70b-int8"), name="llama3-70b-int8-2l", num_layers=2
    )
    import numpy as np

    mesh = jax.sharding.Mesh(
        np.array(td.devices).reshape(1, 8, 1), ("dp", "tp", "sp")
    )
    rules = shd.make_axis_rules(cfg, mesh)
    shardings = shd.param_shardings(cfg, mesh, rules)
    model = Transformer(cfg)
    abstract = nn.meta.unbox(
        jax.eval_shape(
            model.init, jax.random.key(0),
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.int32),
        )["params"]
    )
    flat, treedef = jtu.tree_flatten_with_path(abstract)
    aleaves = []
    for (path, leaf), s in zip(flat, jtu.tree_leaves(shardings)):
        name = getattr(path[-1], "key", "")
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            dt = leaf.dtype
        else:
            dt = jnp.float32 if name == "kernel_scale" else jnp.bfloat16
        aleaves.append(jax.ShapeDtypeStruct(leaf.shape, dt, sharding=s))
    aparams = jtu.tree_unflatten(treedef, aleaves)

    B, S = 8, 128

    def prefill_and_step(params, tokens, positions, valid):
        cache = init_cache(cfg, B, S + 1)
        logits, cache = model.apply(
            {"params": params}, tokens, positions, valid, cache,
            left_padded=True, last_only=True,
        )
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        logits, _ = model.apply(
            {"params": params}, tok[:, None], cache.lengths[:, None],
            jnp.ones((B, 1), jnp.bool_), cache,
        )
        return logits

    bs = shd.batch_sharding(mesh)
    atoks = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bs)
    avalid = jax.ShapeDtypeStruct((B, S), jnp.bool_, sharding=bs)
    with mesh, nn.logical_axis_rules(rules), force_pallas():
        compiled = (
            jax.jit(prefill_and_step).lower(aparams, atoks, atoks, avalid).compile()
        )
    ma = compiled.memory_analysis()
    # int8 kernels dominate args; temps stay activation-scale (no hoisted
    # bf16 copy of the weights — the property the kernel exists to provide).
    assert ma.argument_size_in_bytes < 1.5e9
    assert ma.temp_size_in_bytes < 0.5e9


def test_serve_step_streams_the_kv_pool_once_on_v5e_topology():
    """The decode program of the benchmark's first cell (Mistral-7B int8,
    48 slots x 384, two layers) as the v5e's compiler leaves it: the only
    ``while`` is the decode loop (the per-row cache write is one native
    scatter, not a loop over the rows), nothing holds the pool written out
    once per query head, and the temporaries stay activation-sized (the
    repeated pool was 0.16 GB at this depth)."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    try:
        from jax.experimental import topologies

        td = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"TPU topology unavailable: {type(e).__name__}")
    from fairness_llm_tpu.models.transformer import init_params_lowmem
    from fairness_llm_tpu.ops.quant_matmul import force_pallas
    from fairness_llm_tpu.runtime.sampling import SamplerSettings
    from fairness_llm_tpu.runtime.stepbuilder import build_serve_step

    slots, cache_len = 48, 384
    cfg = dataclasses.replace(
        get_model_config("mistral-7b-int8"), name="mistral-7b-int8-2l", num_layers=2)
    mesh = Mesh(np.array(td.devices[:1]).reshape(1, 1, 1), ("dp", "tp", "sp"))
    rules = shd.make_axis_rules(cfg, mesh)

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    def host(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P()))

    params = placed(
        jax.eval_shape(lambda: init_params_lowmem(cfg, jax.random.key(0), jnp.bfloat16)),
        shd.param_shardings(cfg, mesh, rules))
    cache = jax.eval_shape(lambda: init_cache(cfg, slots, cache_len))
    cache = placed(cache, shd.kv_tree_shardings(cfg, mesh, cache))
    step = build_serve_step(
        cfg, Transformer(cfg), SamplerSettings(temperature=0.0, top_k=0, top_p=1.0),
        cfg.pad_token_id, cfg.eos_token_id, num_slots=slots, chunk=8, guard=True)
    row = lambda dt: host((slots,), dt)  # noqa: E731
    with force_pallas(), mesh, nn.logical_axis_rules(rules):
        compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
            params, cache, host((slots, cfg.vocab_size), jnp.float32),
            row(jnp.uint32), row(jnp.int32), row(jnp.int32), row(jnp.int32),
            row(jnp.bool_), row(jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count(" while(") == 1
    hkv, rep, d = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim
    assert f"[{slots},{cache_len},{hkv},{d}]" in text  # the cache is there to be found
    assert f"[{slots},{cache_len},{hkv * rep},{d}]" not in text
    assert f"[{slots},{cache_len},{hkv},{rep},{d}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 1024**2


@pytest.mark.parametrize("m,k,n,out", [
    (48, 4096, 14336, "bfloat16"),   # whole rows [128, N]
    (96, 3584, 18944, "bfloat16"),   # K-deep [K, 512]
    (48, 3584, 152064, "float32"),   # Qwen2's head: K-deep at the cap
    (512, 18944, 3584, "bfloat16"),  # a 64-token window of 8 rows
    (192, 18432, 7168, "bfloat16"),  # A.X-K1 layer 0: K-deep, one lane tile
])
def test_int8_matmul_blocks_compile_on_v5e_topology(m, k, n, out):
    """The block rule's choices at the benchmark cells' real widths go
    through the REAL v5e compiler (Mosaic refuses what interpret mode
    accepts: a misaligned slice, more VMEM than a kernel may take)."""
    try:
        from jax.experimental import topologies

        td = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"TPU topology unavailable: {type(e).__name__}")
    from fairness_llm_tpu.ops.quant_matmul import force_pallas, quant_matmul

    one = jax.sharding.SingleDeviceSharding(td.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
        ((m, k), jnp.bfloat16), ((k, n), jnp.int8), ((n,), jnp.float32))]
    with force_pallas():
        compiled = jax.jit(
            lambda x, w, s: quant_matmul(x, w, s, out_dtype=jnp.dtype(out))
        ).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    # no float copy of the weight outside the kernel
    assert compiled.memory_analysis().temp_size_in_bytes < k * n


def test_kda_step_compiles_in_place_on_v5e_topology():
    """The delta rule's decode kernel at the routed-linear cell's real size
    (192 slots x 8 heads of 128 x 128 float32 state, 100 MB a layer) goes
    through the REAL v5e compiler: one Mosaic call, the state aliased in
    place (no second copy of it among the temporaries)."""
    try:
        from jax.experimental import topologies

        td = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"TPU topology unavailable: {type(e).__name__}")
    from fairness_llm_tpu.ops.delta_rule import kda_step
    from fairness_llm_tpu.ops.quant_matmul import force_pallas

    one = jax.sharding.SingleDeviceSharding(td.devices[0])
    rows, heads, d = 192, 8, 128
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one) for s in (
        (rows, heads, d, d), (rows, heads, d), (rows, heads, d),
        (rows, heads, d), (rows, heads, d), (rows, heads))]
    with force_pallas():
        compiled = jax.jit(kda_step, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "kda_step" in text
    state_bytes = rows * heads * d * d * 4
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes // 8


def test_the_ring_cells_two_programs_compile_in_place_on_v5e_topology():
    """Both ``serve_prefill`` programs (8 rows that copy; one row alone in
    its wide window) and ``serve_step`` of ``trinity-mini-int8.catalog8k``
    at the cell's real pool (64 slots x 8192 positions, a bucket of 8064)
    and every published width, one period deep (two dense and
    two expert layers; three window layers that keep a ring of 3072 and one
    full layer), through the REAL v5e compiler as ``tools/aot_preflight.py``
    builds them: the Mosaic kernels are there, every leaf of the pool, ring
    or row, is written in place (no whole-leaf copy of either length), and
    all three programs fit the chip."""
    try:
        from jax.experimental import topologies

        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"TPU topology unavailable: {type(e).__name__}")
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)  # it imports its neighbours as it runs
    try:
        from aot_preflight import preflight

        recs = preflight("unused", slots=64, prompt_budget=8064, max_new=128,
                         tp=1, layers=4, nb=8,
                         bench_config="trinity-mini-int8")
    finally:
        sys.path.remove(tools)
    assert [r["program"] for r in recs] == ["serve_prefill"] * 2 \
        + ["serve_step"]
    # a batch that copies: 8 rows x 64 positions; the row sent ahead alone:
    # 384 positions a call (21 calls; 8 to a ring; 2048 + 383 <= 3072)
    assert [(r["nb"], r["window"], r["copies"]) for r in recs[:2]] \
        == [(8, 64, True), (1, 384, False)]
    for r in recs:
        assert r["leaf_lengths"] == [3072, 8192] and r["cache_len"] == 8192
        assert r["pool_copies"] == 0, r
        assert r["int8_matmul"] > 0 and r["grouped_int8_matmul"] > 0
        assert r["fits"] and r["all-reduce"] == 0
        # the pool is aliased: what goes in comes out in place
        assert r["alias_gb"] > 0.5


@pytest.mark.parametrize("slots,L,hkv,heads,max_len,window", [
    (64, 8192, 1, 8, 8192, None),   # Trinity's full layer
    (64, 3072, 1, 8, 8192, 2048),   # Trinity's ring under its window
    (48, 384, 8, 32, 384, 4096),    # Mistral's sweep
    (96, 384, 4, 28, 384, None),    # Qwen2's replay
])
def test_the_pool_kernels_compile_in_place_on_v5e_topology(
        slots, L, hkv, heads, max_len, window):
    """The serving step's attention over the slot pool at the cells' real
    leaves goes through the REAL v5e compiler: both named kernels, the leaf
    written and read in place (no copy of it, and its ``[B, L * Hkv, D]``
    view a bitcast, not a relayout)."""
    import re

    try:
        from jax.experimental import topologies

        td = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"TPU topology unavailable: {type(e).__name__}")
    from fairness_llm_tpu.models.transformer import _cache_write
    from fairness_llm_tpu.ops import pool_attention as pa
    from fairness_llm_tpu.ops.quant_matmul import force_pallas

    one = jax.sharding.SingleDeviceSharding(td.devices[0])

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one)

    def step(k, v, q, new, lead, shared, live, pos, last):
        at = last % L if L < max_len else last
        k = _cache_write(k, new[:, None], None, at)
        v = _cache_write(v, new[:, None], None, at)
        plan = pa.PoolPlan(lead, shared, live, pos, last, max_len)
        return k, v, pa.pool_attention(q, k, v, plan, window=window)

    leaf = shape((slots, L, hkv, 128), jnp.bfloat16)
    row = shape((slots,), jnp.int32)
    with force_pallas():
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
            leaf, leaf, shape((slots, heads, 128), jnp.bfloat16),
            shape((slots, hkv, 128), jnp.bfloat16), row, row,
            shape((slots,), jnp.bool_), row, row).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "pool_attention_own" in text and "pool_attention_lead" in text
    assert not re.search(rf"\[{slots},{L},[^=]*\bcopy\(", text)
    assert not re.search(rf"= \S+\[{slots},{L * hkv},128\]\S* "
                         r"(?:copy|reshape|transpose)\(", text)


def test_gdn_step_compiles_in_place_on_v5e_topology():
    """``olmo-hybrid-7b-int8``'s decode update of the slot pool's state at
    its real leaf (48 rows, 30 heads of 96 x 192, two side by side) goes
    through the REAL v5e compiler: one named kernel, the leaf aliased in
    place and never copied (a 192-lane leaf would be padded to 256)."""
    import re

    try:
        from jax.experimental import topologies

        td = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"TPU topology unavailable: {type(e).__name__}")
    from fairness_llm_tpu.ops.delta_rule import gdn_step
    from fairness_llm_tpu.ops.quant_matmul import force_pallas

    one = jax.sharding.SingleDeviceSharding(td.devices[0])

    def shape(s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)

    with force_pallas():
        compiled = jax.jit(gdn_step, donate_argnums=(0,)).lower(
            shape((48, 15, 96, 384)), shape((48, 30, 96)),
            shape((48, 30, 96)), shape((48, 30, 192)), shape((48, 30)),
            shape((48, 30))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "gdn_step" in text
    assert not re.search(r"\[48,15,96,384\][^=]*\bcopy\(", text)
    assert not re.search(r"= f32\[720,96,384\]\S* copy\(", text)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 48 * 15 * 96 * 384 * 4
