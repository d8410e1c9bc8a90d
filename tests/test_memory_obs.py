"""HBM memory ledger tests (ISSUE 18, telemetry/memory.py).

The accounting contract: every pool gauge equals the hand-computed nbytes
of the live device tree it claims to describe (the slot cache of k and v
or of latents, carried logits, params, prefix-KV LRU entries — and the
per-shard split on a tp mesh), registration/release/rebuild conserve the
total, the headroom arithmetic is exact against an injected analytic
limit, attribution-off records nothing, and the ``--require-memory`` validator gate accepts a
real run and rejects a stripped one.
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fairness_llm_tpu.config import MeshConfig, ModelSettings, ServingConfig
from fairness_llm_tpu.models.configs import get_model_config
from fairness_llm_tpu.parallel import make_mesh
from fairness_llm_tpu.runtime.engine import DecodeEngine
from fairness_llm_tpu.serving import ContinuousScheduler, Request
from fairness_llm_tpu.telemetry import (
    set_aot_memory_capture,
    set_attribution,
    snapshot,
    use_registry,
    use_timeline,
)
from fairness_llm_tpu.telemetry.memory import (
    MemoryLedger,
    count_pool_copies,
    use_memory_ledger,
)


def _tool(name):
    sys.path.insert(0, "/root/repo/tools")
    try:
        import importlib

        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


def greedy(m: int) -> ModelSettings:
    return ModelSettings(temperature=0.0, max_tokens=m)


@pytest.fixture(scope="module")
def engine():
    return DecodeEngine(get_model_config("tiny-test"), seed=0)


# -- pool accounting oracles ---------------------------------------------------


def test_register_release_conservation():
    """Alloc/release/rebuild conservation: the ledger total is exactly the
    sum of live entries, re-registering a handle REPLACES (rebuild
    semantics), and a drained pool's gauge reads 0 rather than going
    stale."""
    with use_registry() as reg, use_memory_ledger() as mem:
        a = jnp.zeros((8, 16), jnp.float32)   # 512 B
        b = jnp.zeros((4,), jnp.int32)        # 16 B
        assert mem.register("kv_contiguous", "t:a", a) == 8 * 16 * 4
        assert mem.register("logits_carry", "t:b", b) == 16
        assert mem.total_bytes() == 512 + 16
        assert reg.read_value("hbm_bytes", component="memory",
                              pool="kv_contiguous") == 512
        # Rebuild: same handle, twice the array — replaced, not added.
        assert mem.register("kv_contiguous", "t:a",
                            jnp.zeros((16, 16), jnp.float32)) == 1024
        assert mem.pool_bytes("kv_contiguous") == 1024
        assert mem.total_bytes() == 1024 + 16
        # Release drains to zero and the published gauge follows.
        assert mem.release("kv_contiguous", "t:a") == 1024
        assert mem.release("logits_carry", "t:b") == 16
        assert mem.total_bytes() == 0
        assert reg.read_value("hbm_bytes", default=-1.0, component="memory",
                              pool="kv_contiguous") == 0.0
        # Double release is a no-op, not an error.
        assert mem.release("kv_contiguous", "t:a") == 0
        # Unknown pools fail loudly — closed set, like incident classes.
        with pytest.raises(ValueError):
            mem.register("vram", "t:x", a)


def test_contiguous_cache_oracle(engine):
    """hbm_bytes{pool=kv_contiguous} equals the hand-computed bytes of the
    slot cache: layers x (k, v) x [num_slots, cache_len, n_kv, head_dim]
    f32 — and logits_carry equals num_slots x vocab x 4."""
    cfg = engine.config
    with use_registry() as reg, use_memory_ledger() as mem:
        sched = ContinuousScheduler(engine, ServingConfig(
            enabled=True, num_slots=2, max_prompt_len=64, max_new_tokens=16,
        ), settings=greedy(8))
        L = sched.cache_len
        expect_kv = (cfg.num_layers * 2 * 2 * L
                     * cfg.num_kv_heads * cfg.head_dim * 4  # k/v planes, f32
                     + 2 * L * 1    # key_valid, bool
                     + 2 * L * 4    # key_positions, int32
                     + 4            # index, scalar int32
                     + 2 * 4)       # lengths, int32 per slot
        assert mem.pool_bytes("kv_contiguous") == expect_kv
        assert mem.pool_bytes("logits_carry") == 2 * cfg.vocab_size * 4
        assert reg.read_value("hbm_bytes", component="memory",
                              pool="kv_contiguous") == expect_kv


def test_latent_cache_oracle():
    """hbm_bytes{pool=kv_contiguous} of a pool of latents equals the
    hand-computed bytes of its leaves: layers x [num_slots, cache_len] x
    one latent and its rotated key a token, f32, and the same bookkeeping
    planes as the dense pool."""
    from toys import KINDS, toy_engine

    from fairness_llm_tpu.models.transformer import MOE_COUNTERS

    engine, _ = toy_engine(KINDS["latent"], seed=18)
    cfg = engine.config
    with use_registry() as reg, use_memory_ledger() as mem:
        sched = ContinuousScheduler(engine, ServingConfig(
            enabled=True, num_slots=2, max_prompt_len=64, max_new_tokens=16,
        ), settings=greedy(8))
        L = sched.cache_len
        per_token = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        assert per_token == cfg.cache_values_per_token
        expect = (cfg.num_layers * 2 * L * per_token * 4  # latents, f32
                  + 2 * L * 1    # key_valid, bool
                  + 2 * L * 4    # key_positions, int32
                  + 4            # index, scalar int32
                  + 2 * 4        # lengths, int32 per slot
                  + len(MOE_COUNTERS) * 4)  # the experts' counters, int32
        assert sum(x.nbytes for x in jax.tree.leaves(sched._cache)) == expect
        assert mem.pool_bytes("kv_contiguous") == expect
        assert reg.read_value("hbm_bytes", component="memory",
                              pool="kv_contiguous") == expect


def test_params_pool_and_rebuild():
    """Engine construction registers the param tree; the handle is stable,
    so re-running the preflight (the rebuild path) replaces rather than
    double-counts."""
    with use_registry() as reg, use_memory_ledger() as mem:
        eng = DecodeEngine(get_model_config("tiny-test"), seed=0)
        expect = sum(int(x.nbytes) for x in
                     jax.tree_util.tree_leaves(eng.params))
        assert mem.pool_bytes("params") == expect
        eng._account_params_memory()  # what the VMEM-fallback rebuild runs
        assert mem.pool_bytes("params") == expect
        assert reg.read_value("hbm_bytes_total", component="memory",
                              reconciliation="indicative") == \
            mem.total_bytes()


def test_tp2_shard_split():
    """On a tp=2 mesh a sharded tree publishes per-device hbm_bytes rows
    under shard=tp<id> labels, and the split sums to the per-shard
    bytes."""
    mesh = make_mesh(MeshConfig(tp=2))
    spec = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("tp", None))
    x = jax.device_put(jnp.zeros((8, 16), jnp.float32), spec)
    with use_registry() as reg, use_memory_ledger() as mem:
        assert mem.register("kv_contiguous", "t:x", x) == 512
        shards = {d.id: 0 for sh in [x.addressable_shards] for s in sh
                  for d in [s.device]}
        assert len(shards) == 2
        total = 0
        for did in shards:
            v = reg.read_value("hbm_bytes", default=-1.0,
                               component="memory", pool="kv_contiguous",
                               shard=f"tp{did}")
            assert v == 256  # half of the 512 B array per device
            total += v
        assert total == 512


def test_prefix_kv_lru_instrumented(engine):
    """The engine's prefix-KV LRU registers each cached entry under
    pool=prefix_cache, counts evictions, and keeps the entry gauge at the
    working-set cap."""
    g = greedy(4)
    with use_registry() as reg, use_memory_ledger() as mem:
        for i in range(6):  # 6 distinct prefixes > the LRU's 4-entry cap
            common = f"shared instruction block {i} " * 8
            engine.generate([common + "user a", common + "user b"], g,
                            share_prefix=True)
        assert mem.pool_bytes("prefix_cache") > 0
        assert reg.read_value("prefix_kv_entries",
                              component="engine") <= 4
        assert reg.read_value("prefix_kv_evictions_total",
                              component="engine") >= 1


# -- reconciliation / headroom -------------------------------------------------


def test_headroom_math():
    with use_registry() as reg, use_memory_ledger() as mem:
        # No limit known: the ledger abstains, it never guesses.
        assert mem.headroom_bytes() is None
        assert mem.headroom_frac() is None
        mem.register("other", "t:a", jnp.zeros((256,), jnp.float32))  # 1 KiB
        mem.set_analytic_limit(10_240)
        assert mem.headroom_bytes() == 10_240 - 1_024
        assert mem.headroom_frac() == pytest.approx(9_216 / 10_240)
        assert reg.read_value("hbm_bytes_limit", component="memory",
                              reconciliation="indicative") == 10_240
        assert reg.read_value("hbm_headroom_bytes", component="memory",
                              reconciliation="indicative") == 9_216
        # CPU reports no memory_stats, so no measured delta and no alerts.
        assert reg.read_value("hbm_reconciliation_alerts_total",
                              component="memory") == 0


_HLO = """\
HloModule jit_serve_prefill_8x256, input_output_alias={ {0}: (2, {}, may-alias) }

%fused_computation.7 (param_0.1: bf16[48,384,8,128]) -> bf16[48,384,8,128] {
  %param_0.1 = bf16[48,384,8,128]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %copy.9 = bf16[48,384,8,128]{3,1,2,0:T(8,128)(2,1)} copy(%param_0.1)
}

ENTRY %main.3 (k.1: bf16[48,384,8,128], pos.1: s32[48,384]) -> bf16[48,384,8,128] {
  %k.1 = bf16[48,384,8,128]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %pos.1 = s32[48,384]{1,0:T(8,128)} parameter(1)
  %copy.1 = bf16[48,384,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} copy(%k.1), metadata={op_name="a=b"}
  %copy.2 = s32[48,384]{1,0:T(8,128)} copy(%pos.1)
  %copy.3 = bf16[8,384,8,128]{3,2,1,0:T(8,128)(2,1)} copy(%rows)
  ROOT %fusion.7 = bf16[48,384,8,128]{3,2,1,0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.7
}
"""


@pytest.mark.parametrize("slots, cache_len, want", [
    (48, 384, 2),  # a leaf and the positions; not the fusion's own read
    (8, 384, 1),   # the gathered rows are a pool of 8 slots' leaf
    (96, 384, 0),
    (48, (128, 384), 2),  # every length a leaf has: a ring beside the rows
    (48, (128, 38), 0),   # a length is matched whole, not as a prefix
])
def test_pool_copies_are_whole_leaves_copied_outside_fusions(
        slots, cache_len, want):
    assert count_pool_copies(_HLO, slots, cache_len) == want


def test_attribution_off_records_nothing():
    prev = set_attribution(True)
    try:
        with use_registry() as reg, use_memory_ledger() as mem:
            set_attribution(False)
            assert mem.register("other", "t:a",
                                jnp.zeros((64,), jnp.float32)) == 0
            assert mem.total_bytes() == 0
            assert not any(
                getattr(m, "name", "").startswith(("hbm_", "memory_"))
                for m in reg.instruments()
            )
    finally:
        set_attribution(prev)


# -- validator gate / CLI ------------------------------------------------------


def _serve_with_memory_obs(engine, mem):
    """A small serving run with the AOT capture armed — what a
    --telemetry-dir run records (telemetry.configure arms the flag)."""
    engine._account_params_memory()  # fixture engine predates this ledger
    prev = set_aot_memory_capture(True)
    try:
        sched = ContinuousScheduler(engine, ServingConfig(
            enabled=True, num_slots=2, max_prompt_len=64, max_new_tokens=8,
        ), settings=greedy(8))
        res = sched.serve([Request(prompt=p, settings=greedy(8))
                           for p in ("hello there", "quick brown fox",
                                     "one two three")])
        assert all(r.ok for r in res)
    finally:
        set_aot_memory_capture(prev)


def test_validate_require_memory_accept_reject(engine, tmp_path):
    from fairness_llm_tpu.telemetry import write_snapshot

    check = _tool("validate_telemetry").check
    with use_registry() as reg, use_timeline(), use_memory_ledger() as mem:
        _serve_with_memory_obs(engine, mem)
        write_snapshot(reg, str(tmp_path))
        assert check(str(tmp_path), require_memory=True) == 0
    # Same snapshot with the AOT program gauges stripped must fail: every
    # program in compiles_total owes a program_memory_bytes row.
    snap = json.load(open(tmp_path / "telemetry_snapshot.json"))
    bad1 = dict(snap)
    bad1["gauges"] = [g for g in snap["gauges"]
                      if g["name"] != "program_memory_bytes"]
    p1 = tmp_path / "bad_programs.json"
    p1.write_text(json.dumps(bad1))
    assert check(str(p1), require_memory=True) == 1
    # And with the pool gauges stripped too (no ledger at all).
    bad2 = dict(snap)
    bad2["gauges"] = [g for g in snap["gauges"]
                      if not g["name"].startswith("hbm_")]
    p2 = tmp_path / "bad_pools.json"
    p2.write_text(json.dumps(bad2))
    assert check(str(p2), require_memory=True) == 1


def test_cli_memory_report(engine, tmp_path, capsys):
    from fairness_llm_tpu.cli.main import main as cli_main
    from fairness_llm_tpu.telemetry import write_snapshot

    with use_registry() as reg, use_timeline(), use_memory_ledger() as mem:
        _serve_with_memory_obs(engine, mem)
        mem.set_analytic_limit(mem.total_bytes() + (32 << 20))
        write_snapshot(reg, str(tmp_path))
    assert cli_main(["memory-report", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "HBM memory ledger" in out
    assert "indicative" in out            # CPU: analytic-only labeling
    assert "kv_contiguous" in out
    assert "per-program AOT memory" in out
    # telemetry-report appends the same section when memory data exists.
    assert cli_main(["telemetry-report", str(tmp_path)]) == 0
    assert "HBM memory ledger" in capsys.readouterr().out
    # --require-ledger on an empty snapshot fails.
    empty = tmp_path / "empty"
    with use_registry() as reg2:
        from fairness_llm_tpu.telemetry import write_snapshot as ws

        ws(reg2, str(empty))
    assert cli_main(["memory-report", str(empty),
                     "--require-ledger"]) == 1
