"""Decode cost ledger + perf sentinel tests (ISSUE 12; collectives
component added by ISSUE 16).

Five contracts:

- the SHARED component taxonomy: ``tools/account_decode_step.py`` imports
  the first-match-wins ``COMPONENTS`` table from ``telemetry/costmodel.py``
  (no private copy), and every historical op-name fixture classifies the
  way the round-3..11 private table classified it;
- the jaxpr cost walk is hand-verifiable: tiny toy programs (one dot, one
  attention-shaped dot, one cache DUS, one while loop) produce exactly the
  bytes/FLOPs first principles predict, split per-call vs per-step;
- EVERY compiled decode variant (plain/spec engine decode, serving
  prefill/step) publishes a nonzero ledger after a continuous +
  speculative smoke, and the gap decomposition's components sum to the
  measured wall exactly;
- the perf sentinel accepts a clean same-fingerprint re-run, rejects an
  injected 3x slowdown and token-parity drift, and REFUSES a baseline
  recorded under a different harness fingerprint;
- the ``collectives`` component is pinned at all three layers — xplane
  regex (first-match, ahead of gather/attention), jaxpr primitives
  (shard_map psum oracle), and the analytic ``tp_collective_costs``
  injection for GSPMD-auto tp programs, with its double-count guard.
"""

import copy
import json
import sys

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from fairness_llm_tpu.config import ModelSettings, ServingConfig, SpeculationConfig
from fairness_llm_tpu.models.configs import get_model_config
from fairness_llm_tpu.runtime.engine import DecodeEngine
from fairness_llm_tpu.telemetry import (
    gap_decomposition,
    has_cost_data,
    jaxpr_ledger,
    render_cost_report,
    snapshot,
    use_registry,
    use_timeline,
)
from fairness_llm_tpu.telemetry.costmodel import COMPONENTS, classify


def _tool(name):
    sys.path.insert(0, "/root/repo/tools")
    try:
        import importlib

        return importlib.import_module(name)
    finally:
        sys.path.pop(0)


# -- shared taxonomy -----------------------------------------------------------


def test_account_decode_step_imports_shared_components():
    tool = _tool("account_decode_step")
    assert tool.COMPONENTS is COMPONENTS
    assert tool.classify is classify


# Historical xplane op names from the round-3/4 device captures, with the
# classification the private table in tools/account_decode_step.py produced
# through round 11 (labels renamed to the shared taxonomy, grouping
# IDENTICAL). First-match-wins ordering is part of the contract: e.g.
# "multiply_reduce_fusion" must stay attention (not elementwise), and
# "dynamic-update-slice_fusion" must stay KV (not a fusion catch-all).
HISTORICAL_OP_FIXTURES = [
    ("multiply_reduce_fusion.3", "attention"),
    ("reduce_fusion", "attention"),
    ("softmax_exp", "attention"),
    ("exponential.12", "attention"),
    ("divide_fusion.2", "attention"),
    ("dynamic-update-slice.7", "kv_rw"),
    ("dynamic-update-slice_fusion", "kv_rw"),
    ("fused_dynamic_update_slice", "kv_rw"),
    ("slice.42", "weights_dma"),
    ("bitcast-convert.1", "weights_dma"),
    ("copy.3", "weights_dma"),
    ("dynamic-slice-start", "weights_dma"),
    ("copy-start.2", "weights_dma"),
    ("copy-done.2", "weights_dma"),
    ("slice_fusion", "weights_dma"),
    ("dot.17", "matmuls"),
    ("dot_general_fusion", "matmuls"),
    ("convolution.1", "matmuls"),
    ("rsqrt.4", "norms_elementwise"),
    ("layer_norm_fusion", "norms_elementwise"),
    ("add_fusion.9", "norms_elementwise"),
    ("multiply_fusion", "norms_elementwise"),
    ("subtract.2", "norms_elementwise"),
    ("tanh.1", "norms_elementwise"),
    ("gelu_fusion", "norms_elementwise"),
    ("sort.1", "sampling"),
    ("argmax.3", "sampling"),
    ("rng-bit-generator", "sampling"),
    ("random_fold_in", "sampling"),
    ("iota.2", "sampling"),
    ("cumsum_fusion", "sampling"),
    ("select_n.5", "sampling"),
    ("compare.8", "sampling"),
    ("gather.11", "gather_scatter"),
    ("scatter.4", "gather_scatter"),
    ("while.1", "control"),
    ("condition.2", "control"),
    ("tuple.1", "control"),
    ("parameter.0", "control"),
    ("constant.5", "control"),
    ("some-unknown-op", "other"),
]


@pytest.mark.parametrize("name,expected", HISTORICAL_OP_FIXTURES)
def test_historical_op_names_classify_identically(name, expected):
    assert classify(name) == expected


# -- jaxpr walk vs hand-computed oracles ---------------------------------------


def _ledger_of(fn, *args):
    return jaxpr_ledger(jax.make_jaxpr(fn)(*args), "toy")


def test_jaxpr_ledger_2d_dot_is_matmul():
    w = jnp.ones((8, 32), jnp.float32)
    x = jnp.ones((16, 8), jnp.float32)
    led = _ledger_of(
        lambda w, x: lax.dot_general(x, w, (((1,), (0,)), ((), ()))), w, x
    )
    assert set(led.per_call) == {"matmuls"} and not led.per_step
    c = led.per_call["matmuls"]
    # bytes: x[16,8] + w[8,32] + out[16,32], f32
    assert c.bytes == (16 * 8 + 8 * 32 + 16 * 32) * 4
    # flops: 2 * M * N * K
    assert c.flops == 2 * 16 * 32 * 8


def test_jaxpr_ledger_rank4_dot_is_attention():
    q = jnp.ones((2, 2, 4, 8), jnp.float32)
    led = _ledger_of(
        lambda q: lax.dot_general(q, q, (((3,), (3,)), ((0, 1), (0, 1)))), q
    )
    assert set(led.per_call) == {"attention"}
    c = led.per_call["attention"]
    # bytes: two [2,2,4,8] operands + the [2,2,4,4] scores, f32
    assert c.bytes == (2 * (2 * 2 * 4 * 8) + 2 * 2 * 4 * 4) * 4
    # flops: 2 * out-elements * contracted dim
    assert c.flops == 2 * (2 * 2 * 4 * 4) * 8


def test_jaxpr_ledger_dus_is_kv_rw():
    cache = jnp.zeros((4, 8), jnp.float32)
    row = jnp.ones((1, 8), jnp.float32)
    led = _ledger_of(
        lambda c, r: lax.dynamic_update_slice(c, r, (0, 0)), cache, row
    )
    assert set(led.per_call) == {"kv_rw"}
    c = led.per_call["kv_rw"]
    # bytes: cache in + row + two scalar int32 start indices + cache out
    assert c.bytes == 4 * 8 * 4 + 1 * 8 * 4 + 2 * 4 + 4 * 8 * 4
    assert c.flops == 4 * 8  # one per output element


def test_jaxpr_ledger_while_body_lands_per_step():
    def loop(x):
        def body(c):
            i, acc = c
            return i + jnp.int32(1), acc + acc

        return lax.while_loop(lambda c: c[0] < jnp.int32(4), body,
                              (jnp.int32(0), x))

    led = _ledger_of(loop, jnp.ones((8,), jnp.float32))
    assert not led.per_call and led.has_loop
    # cond: lt over two int32 scalars -> bool scalar = 9 bytes, 1 flop
    assert (led.per_step["control"].bytes,
            led.per_step["control"].flops) == (9, 1)
    # body: scalar add (12 B, 1 flop) + [8] f32 add (96 B, 8 flops), both
    # elementwise.
    assert (led.per_step["norms_elementwise"].bytes,
            led.per_step["norms_elementwise"].flops) == (108, 9)
    # min-times: per-step cost x steps against the given rooflines.
    mt = led.min_times_s(4, 1e9, 1e9)
    assert mt["norms_elementwise"] == pytest.approx(4 * 108 / 1e9)


def test_jaxpr_ledger_scan_multiplies_by_length():
    def scanned(x):
        def step(carry, _):
            return carry + x, None

        out, _ = lax.scan(step, x, None, length=5)
        return out

    led = _ledger_of(scanned, jnp.ones((8,), jnp.float32))
    # 5 iterations of one [8]+[8] add, all per_call (scan has a static trip
    # count — only while bodies are per_step).
    assert not led.per_step
    assert led.per_call["norms_elementwise"].bytes == 5 * (3 * 8 * 4)
    assert led.per_call["norms_elementwise"].flops == 5 * 8


# -- collectives component (ISSUE 16 satellite) --------------------------------
#
# Three layers, each pinned: the xplane regex (measured captures), the jaxpr
# primitive set (shard_map-manual programs), and the analytic injection path
# (GSPMD-auto tp programs whose jaxpr cannot show the collectives XLA adds
# after partitioning).


# Collective op names as they appear in real xplane captures; all must land
# in "collectives". Ordering is load-bearing: "all-gather"/"reduce-scatter"
# must NOT fall through to gather_scatter or attention's reduce pattern
# (the HISTORICAL_OP_FIXTURES above re-running unchanged pins the converse —
# "reduce_fusion" stays attention, "gather.11" stays gather_scatter).
COLLECTIVE_OP_FIXTURES = [
    "all-reduce.1",
    "all-reduce-start",
    "all-gather.3",
    "reduce-scatter_fusion",
    "collective-permute.2",
    "all-to-all",
    "psum",
]


@pytest.mark.parametrize("name", COLLECTIVE_OP_FIXTURES)
def test_collective_op_names_classify_as_collectives(name):
    assert classify(name) == "collectives"


def test_collectives_is_first_match_in_components():
    # First-match-wins: collectives must outrank gather_scatter/attention so
    # "all-gather"/"reduce-scatter" never misfile as memory ops.
    assert COMPONENTS[0][0] == "collectives"


def test_jaxpr_ledger_shard_map_psum_is_collectives():
    # The jaxpr-visible path: shard_map-manual code traces its psum
    # explicitly (unlike GSPMD-auto programs), and the walk descends into
    # the shard_map sub-jaxpr and books it under "collectives".
    import numpy as np

    from fairness_llm_tpu.parallel.sharding import compat_shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    fn = compat_shard_map(lambda x: lax.psum(x, "tp"), mesh,
                          in_specs=P("tp"), out_specs=P())
    led = _ledger_of(fn, jnp.ones((8,), jnp.float32))
    assert "collectives" in led.per_call
    c = led.per_call["collectives"]
    # psum over the per-device [4] f32 shard: in + out avals.
    assert c.bytes == 2 * 4 * 4
    assert c.flops == 4


def test_tp_collective_costs_oracle():
    from fairness_llm_tpu.telemetry.costmodel import tp_collective_costs

    cfg = get_model_config("tiny-test")  # f32, 2 layers, 4 heads, d_ff 128,
    #                                      d_model 64, vocab 512
    # tp=2, 2 rows x 1 token: both the head and ff axes shard -> one ring
    # all-reduce of the [2, 1, 64] f32 activation per projection per layer
    # at 2(tp-1)/tp, plus the (tp-1)/tp logits all-gather.
    act = 2 * 1 * 64 * 4
    expect = int(2 * 2 * act * 2 * (1 / 2)) + int(2 * 1 * 512 * 4 * (1 / 2))
    assert tp_collective_costs(cfg, 2, rows=2, tokens=1) == \
        [("step", expect, 0)]
    assert expect == 4096  # the exact serve_step@tp2 row serve_tp asserts
    # tp=8: heads (4) fall back to replicated -> only the ff all-reduce and
    # the vocab all-gather charge.
    expect8 = (int(2 * 1 * act * 2 * (7 / 8))
               + int(2 * 1 * 512 * 4 * (7 / 8)))
    assert tp_collective_costs(cfg, 8, rows=2, tokens=1) == \
        [("step", expect8, 0)]
    # Identity / nothing-shards cases charge nothing.
    assert tp_collective_costs(cfg, 1, rows=2) == []
    assert tp_collective_costs(cfg, 3, rows=2) == []  # no axis divides by 3
    # scope passes through (prefill books per-call, not per-step).
    assert tp_collective_costs(cfg, 2, rows=2, tokens=1,
                               scope="call")[0][0] == "call"


def test_instrument_jit_injects_analytic_collectives():
    from fairness_llm_tpu.telemetry.costmodel import instrument_jit

    with use_registry() as reg, use_timeline():
        run = instrument_jit(lambda x: x * 2.0, "toy_tp@tp2",
                             collectives=[("step", 4096, 0)])
        run(jnp.ones((8,), jnp.float32))
        snap = snapshot(reg)
    assert run.ledger is not None
    assert run.ledger.per_step["collectives"].bytes == 4096
    rows = [g for g in snap["gauges"]
            if g["name"] == "cost_ledger_bytes"
            and g["labels"].get("component") == "collectives"]
    assert rows and all(g["labels"]["program"] == "toy_tp@tp2"
                        for g in rows)
    assert sum(g["value"] for g in rows) == 4096


def test_instrument_jit_never_double_counts_explicit_collectives():
    # A shard_map-manual program already traces its psum; the analytic rows
    # must be DROPPED for it, or collectives would be charged twice.
    import numpy as np

    from fairness_llm_tpu.parallel.sharding import compat_shard_map
    from fairness_llm_tpu.telemetry.costmodel import instrument_jit
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    fn = compat_shard_map(lambda x: lax.psum(x, "tp"), mesh,
                          in_specs=P("tp"), out_specs=P())
    with use_registry(), use_timeline():
        run = instrument_jit(fn, "toy_manual",
                             collectives=[("call", 999_999, 0)])
        run(jnp.ones((8,), jnp.float32))
    assert run.ledger is not None
    # Only the walked psum traffic — the analytic 999_999 row was skipped.
    assert run.ledger.per_call["collectives"].bytes == 2 * 4 * 4


# -- every decode variant publishes a ledger + decomposition sums ------------------


@pytest.fixture(scope="module")
def engine():
    return DecodeEngine(get_model_config("tiny-test"), seed=0)


def _greedy(m):
    return ModelSettings(temperature=0.0, max_tokens=m)


def _smoke_all_variants(engine):
    from fairness_llm_tpu.serving import ContinuousScheduler, Request

    scfg = ServingConfig(enabled=True, num_slots=2, max_prompt_len=192,
                         max_new_tokens=16, decode_chunk=4)
    engine.generate(["one two three", "four five six"], _greedy(6))
    engine.generate(["a b c d e f g h i j k l"], _greedy(6),
                    speculation=SpeculationConfig(enabled=True))
    s1 = ContinuousScheduler(engine, scfg, settings=_greedy(8))
    r = s1.serve([Request(prompt="a b c", id="c1", settings=_greedy(8)),
                  Request(prompt="d e f", id="c2", settings=_greedy(8))])
    assert all(x.ok for x in r)


VARIANTS = ("decode", "spec_decode", "serve_prefill", "serve_step")


def test_all_decode_variants_publish_ledgers(engine):
    with use_registry() as reg, use_timeline():
        _smoke_all_variants(engine)
        snap = snapshot(reg)
    assert has_cost_data(snap)
    by_prog = {}
    for g in snap["gauges"]:
        if g["name"] == "cost_ledger_bytes":
            p = g["labels"]["program"]
            by_prog[p] = by_prog.get(p, 0.0) + g["value"]
    for prog in VARIANTS:
        assert by_prog.get(prog, 0.0) > 0, f"no ledger for {prog}"
    # The loop programs split per-step work out of the per-call remainder.
    step_scopes = {g["labels"]["program"] for g in snap["gauges"]
                   if g["name"] == "cost_ledger_bytes"
                   and g["labels"].get("scope") == "step"}
    assert {"decode", "spec_decode", "serve_step"} <= step_scopes
    # Gap decomposition: every program's components sum EXACTLY to the
    # measured wall (+ measured host gap) — the acceptance tolerance check.
    decomp = gap_decomposition(snap)
    for prog in VARIANTS:
        d = decomp[prog]
        assert d["wall_s"] > 0
        assert d["sum_check_s"] == pytest.approx(d["total_s"], rel=1e-9)
        assert d["top_gap_contributor"] in (
            "host_gap", "dispatch", "compile", "unattributed_in_step")
        # Every program compiled at least once in this fresh-registry
        # smoke, so its first-call wall is tagged as compile time.
        assert d["compile_s"] > 0
    # Serving step programs ran >= 2 chunks, so their host gap is a
    # MEASURED nonzero quantity, not an estimate.
    assert decomp["serve_step"]["host_gap_s"] > 0
    # The report renders and names a contributor per program.
    report = render_cost_report(snap)
    for prog in VARIANTS:
        assert f"[{prog}]" in report
    assert "top gap contributor:" in report
    assert "sum check: OK" in report


def test_attribution_off_records_no_cost_data(engine):
    from fairness_llm_tpu.telemetry import set_attribution

    prev = set_attribution(True)
    try:
        with use_registry() as reg, use_timeline():
            set_attribution(False)
            engine.generate(["cost off one", "cost off two"], _greedy(4))
            snap = snapshot(reg)
    finally:
        set_attribution(prev)
    assert not has_cost_data(snap)
    assert not any(g["name"].startswith("cost_ledger")
                   for g in snap["gauges"])


def test_validate_telemetry_require_costmodel(engine, tmp_path):
    from fairness_llm_tpu.telemetry import write_snapshot

    check = _tool("validate_telemetry").check
    with use_registry() as reg, use_timeline():
        _smoke_all_variants(engine)
        write_snapshot(reg, str(tmp_path))
        assert check(str(tmp_path), require_costmodel=True) == 0
    # A snapshot whose compiled programs have no ledgers must fail: keep
    # compiles_total, drop the cost gauges.
    snap = json.load(open(tmp_path / "telemetry_snapshot.json"))
    snap["gauges"] = [g for g in snap["gauges"]
                     if not g["name"].startswith("cost_")]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(snap))
    assert check(str(bad), require_costmodel=True) == 1


def test_cli_perf_report(engine, tmp_path, capsys):
    from fairness_llm_tpu.cli.main import main as cli_main
    from fairness_llm_tpu.telemetry import write_snapshot

    with use_registry() as reg, use_timeline():
        _smoke_all_variants(engine)
        write_snapshot(reg, str(tmp_path))
    assert cli_main(["perf-report", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "DECODE COST LEDGER / GAP ATTRIBUTION" in out
    assert "[serve_step]" in out and "top gap contributor:" in out
    # telemetry-report appends the same section when cost data exists.
    assert cli_main(["telemetry-report", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "DECODE COST LEDGER / GAP ATTRIBUTION" in out
    # --require-ledger on an empty snapshot fails.
    empty = tmp_path / "empty"
    with use_registry() as reg2:
        write_snapshot(reg2, str(empty))
    assert cli_main(["perf-report", str(empty), "--require-ledger"]) == 1


# -- perf sentinel -------------------------------------------------------------


def _fake_baseline():
    return {
        "schema_version": 1,
        "created_at_unix": 0.0,
        "fingerprint": {"jax": "0.4.37", "platform": "cpu",
                        "device_kind": "cpu", "cpu_count": 8,
                        "model": "tiny-test"},
        "entries": {
            "headline.profiles_per_sec": {"kind": "wall", "value": 10.0},
            "headline.token_checksum": {"kind": "exact", "value": "abc123"},
            "continuous.speedup": {"kind": "wall", "value": 1.4},
            "continuous.useful_tokens": {"kind": "exact", "value": 1234},
            "prefix_cache.hit_ratio": {"kind": "exact", "value": 0.965},
        },
    }


def test_sentinel_accepts_clean_rerun():
    ps = _tool("perf_sentinel")
    base = _fake_baseline()
    fresh = copy.deepcopy(base)
    # Same-fingerprint re-run with in-band wall jitter (±40%) and
    # identical counters must pass.
    fresh["entries"]["headline.profiles_per_sec"]["value"] = 14.0
    fresh["entries"]["continuous.speedup"]["value"] = 1.0
    problems, walls, report = ps.compare(base, fresh)
    assert problems == [] and walls == []
    assert all(r["status"] == "ok" for r in report["entries"].values())


def test_sentinel_rejects_injected_3x_slowdown():
    ps = _tool("perf_sentinel")
    base = _fake_baseline()
    slow = copy.deepcopy(base)
    for spec in slow["entries"].values():
        if spec["kind"] == "wall":
            spec["value"] = spec["value"] / 3.0
    problems, walls, _ = ps.compare(base, slow)
    assert problems == []
    assert len(walls) == 2  # both wall entries out of the 2.0x band


def test_sentinel_rejects_token_parity_drift():
    ps = _tool("perf_sentinel")
    base = _fake_baseline()
    drift = copy.deepcopy(base)
    drift["entries"]["headline.token_checksum"]["value"] = "deadbeef"
    drift["entries"]["prefix_cache.hit_ratio"]["value"] = 0.5
    problems, walls, _ = ps.compare(base, drift)
    assert len(problems) == 2 and walls == []
    assert any("token_checksum" in p for p in problems)


def test_sentinel_missing_entry_is_hard():
    ps = _tool("perf_sentinel")
    base = _fake_baseline()
    fresh = copy.deepcopy(base)
    del fresh["entries"]["continuous.useful_tokens"]
    problems, _, _ = ps.compare(base, fresh)
    assert len(problems) == 1 and "missing" in problems[0]


def test_sentinel_refuses_cross_fingerprint(tmp_path):
    ps = _tool("perf_sentinel")
    base = _fake_baseline()
    other = copy.deepcopy(base)
    other["fingerprint"]["device_kind"] = "TPU v5e"
    other["fingerprint"]["cpu_count"] = 4
    mism = ps.fingerprint_mismatches(base["fingerprint"],
                                     other["fingerprint"])
    assert len(mism) == 2
    # End to end through the CLI: refusal exits 2 (never compares), and
    # --allow-refusal downgrades it to a reported skip (exit 0).
    bpath, fpath = tmp_path / "base.json", tmp_path / "fresh.json"
    bpath.write_text(json.dumps(base))
    fpath.write_text(json.dumps(other))
    argv = sys.argv
    try:
        sys.argv = ["perf_sentinel.py", "--baseline", str(bpath),
                    "--fresh", str(fpath)]
        assert ps.main() == ps.EXIT_REFUSED
        sys.argv = sys.argv + ["--allow-refusal"]
        assert ps.main() == ps.EXIT_OK
    finally:
        sys.argv = argv


def test_sentinel_best_of_n_merge_and_rep_parity():
    ps = _tool("perf_sentinel")
    a = _fake_baseline()
    b = copy.deepcopy(a)
    b["entries"]["headline.profiles_per_sec"]["value"] = 12.0  # better rep
    merged, problems = ps.merge_best([a, b])
    assert problems == []
    assert merged["entries"]["headline.profiles_per_sec"]["value"] == 12.0
    # Lower-is-better wall entries (on/off overhead ratios) keep the
    # SMALLEST rep — max-merging them would keep the noisiest run.
    a["entries"]["overload.overhead_ratio"] = {
        "kind": "wall", "value": 1.5, "better": "lower"}
    b["entries"]["overload.overhead_ratio"] = {
        "kind": "wall", "value": 1.02, "better": "lower"}
    merged, problems = ps.merge_best([a, b])
    assert problems == []
    assert merged["entries"]["overload.overhead_ratio"]["value"] == 1.02
    # Exact entries disagreeing BETWEEN reps is itself parity drift.
    b["entries"]["headline.token_checksum"]["value"] = "zzz"
    _, problems = ps.merge_best([a, b])
    assert len(problems) == 1 and "BETWEEN reps" in problems[0]


def test_sentinel_malformed_wall_value_is_reported_not_crash():
    ps = _tool("perf_sentinel")
    base = _fake_baseline()
    bad = copy.deepcopy(base)
    bad["entries"]["headline.profiles_per_sec"]["value"] = "12.5x"
    problems, walls, _ = ps.compare(base, bad)
    assert problems == [] and len(walls) == 1  # reported, no traceback


def test_host_gap_excludes_prefill_busy_time():
    """The cost ledger's measured host gap counts device-IDLE time between
    chunks; a prefill in the gap is attributed to its own program, so the
    busy cursor must exclude it (step_gap_s keeps the PR-7 all-host-time
    semantics)."""
    from fairness_llm_tpu.telemetry import get_registry, use_registry
    from fairness_llm_tpu.telemetry.timeline import use_timeline

    with use_registry() as reg, use_timeline() as tl:
        tl.decode_chunk("serving", 1.0, 0.3, steps=8, program="serve_step")
        tl.note_busy("serving", 1.5, 0.3)  # a prefill at [1.5, 1.8)
        tl.decode_chunk("serving", 2.0, 0.3, steps=8, program="serve_step")
        # step_gap_s: full between-chunk host time 2.0 - 1.3 = 0.7.
        gap_hist = reg.histogram("step_gap_s", component="serving")
        assert gap_hist.sum == pytest.approx(0.7)
        # cost host gap: only the idle 2.0 - 1.8 = 0.2.
        assert reg.read_value("cost_host_gap_s_total",
                              component="costmodel",
                              program="serve_step") == pytest.approx(0.2)


def test_sentinel_self_check_passes_on_real_format():
    ps = _tool("perf_sentinel")
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump(_fake_baseline(), f)
        path = f.name
    assert ps.self_check(path) == ps.EXIT_OK


def test_bench_baseline_shape():
    """bench.write_bench_baseline flattens a result into sentinel-comparable
    entries with the right kinds and a 4-field-plus-model fingerprint."""
    sys.path.insert(0, "/root/repo")
    try:
        import bench
    finally:
        sys.path.pop(0)
    result = {
        "value": 12.5,
        "detail": {
            "decode_tokens_per_sec": 1600.0,
            "token_checksum": "cafe0123",
            "continuous": {
                "continuous": {"tokens_per_sec": 50.0, "useful_tokens": 999},
                "speedup_tokens_per_sec": 1.37,
            },
            "speculative": {
                "speedup": 1.14, "acceptance_rate": 0.62,
                "verify_steps": 45,
            },
        },
    }
    entries = bench.baseline_entries(result)
    assert entries["headline.profiles_per_sec"] == {
        "kind": "wall", "value": 12.5, "better": "higher"}
    assert entries["headline.token_checksum"]["kind"] == "exact"
    assert entries["continuous.useful_tokens"] == {"kind": "exact",
                                                   "value": 999}
    assert entries["speculative.acceptance_rate"]["kind"] == "exact"
    assert entries["speculative.speedup"]["kind"] == "wall"
    fp = bench.harness_fingerprint("tiny-test")
    assert set(fp) == {"jax", "platform", "device_kind", "cpu",
                       "cpu_count", "model"}
    assert fp["jax"] == jax.__version__ and fp["model"] == "tiny-test"
    assert fp["cpu"]  # host CPU identity present (ISA family at minimum)
    # Overhead ratios are lower-is-better: the sentinel's best-of-N merge
    # must keep the SMALLEST rep for them.
    result["detail"]["overload_overhead"] = {"overhead_ratio": 1.02}
    entries = bench.baseline_entries(result)
    assert entries["overload.overhead_ratio"]["better"] == "lower"
