"""Performance-attribution layer tests (ISSUE 7): the device-step timeline
(span recording, step gaps, Chrome-trace export + schema), compile
observability, live roofline gauges, the SLO burn-rate evaluator against
hand-computed fixtures, heartbeat gap detection under a fake clock, and the
scheduler/fleet integration invariants — spans land on the correct replica
track through eviction+requeue and fleet migration.
"""

from __future__ import annotations

import json

import pytest

from fairness_llm_tpu.config import ModelSettings, ResilienceConfig, ServingConfig
from fairness_llm_tpu.models.configs import get_model_config
from fairness_llm_tpu.telemetry import (
    Heartbeat,
    SLOEvaluator,
    SLOTargets,
    Timeline,
    set_attribution,
    snapshot,
    summarize_chrome_trace,
    use_registry,
    use_timeline,
    validate_chrome_trace,
)
from fairness_llm_tpu.telemetry.compilestats import record_compile
from fairness_llm_tpu.telemetry.roofline import (
    decode_step_bytes,
    observe_decode,
    set_achievable_gbps,
)
from fairness_llm_tpu.telemetry.slo import render_slo_report


# -- timeline core ------------------------------------------------------------


def test_timeline_spans_export_and_schema():
    tl = Timeline()
    tl.record_span("sched.prefill.dispatch", "prefill", "serving", 10.0, 0.5,
                   rows=3)
    tl.record_instant("fence", "r1", t=10.2, reason="crash")
    tl.record_request("req-1", "serving", 9.8, 11.0, "completed", tokens=4)
    trace = tl.to_chrome_trace()
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    # The request span backdates before the prefill span: ts must still be
    # relative to the EARLIEST event (no negative timestamps).
    assert all(e.get("ts", 0) >= 0 for e in evs if e["ph"] != "M")
    x = [e for e in evs if e["ph"] == "X"]
    assert len(x) == 1 and x[0]["name"] == "sched.prefill.dispatch"
    assert x[0]["dur"] == pytest.approx(0.5e6)
    # Request lanes: one async b/e pair with the request id.
    b = [e for e in evs if e["ph"] == "b"]
    e_ = [e for e in evs if e["ph"] == "e"]
    assert len(b) == len(e_) == 1 and b[0]["id"] == "req-1"
    assert b[0]["args"]["outcome"] == "completed"
    # Thread metadata names every lane (requests lane + device lane).
    names = {m["args"]["name"] for m in evs
             if m["ph"] == "M" and m["name"] == "thread_name"}
    assert "serving · device steps" in names
    assert "serving · requests" in names
    assert "r1 · device steps" in names


def test_timeline_step_gap_accounting():
    with use_registry() as reg:
        tl = Timeline()
        tl.decode_chunk("serving", 1.0, 0.3, steps=8)  # first: no gap yet
        tl.decode_chunk("serving", 1.5, 0.3, steps=8)  # gap = 1.5 - 1.3
        tl.decode_chunk("other", 5.0, 0.1, steps=4)    # separate track
        h = reg.histogram("step_gap_s", component="serving")
        assert h.count == 1
        assert h.max == pytest.approx(0.2, abs=1e-9)
        assert tl.top_gaps[0][0] == pytest.approx(0.2, abs=1e-9)
        # Cursor cleared -> the idle stretch to the next chunk is NOT a gap.
        tl.clear_track_cursor("serving")
        tl.decode_chunk("serving", 100.0, 0.3, steps=8)
        assert h.count == 1
        # The gap rides on the span args for the trace summary.
        spans = [e for e in tl.to_chrome_trace()["traceEvents"]
                 if e["ph"] == "X" and "gap_s" in e.get("args", {})]
        assert len(spans) == 1


def test_timeline_ring_bound_counts_drops():
    tl = Timeline(capacity=4)
    for i in range(7):
        tl.record_instant(f"e{i}", "t")
    assert len(tl.events()) == 4
    assert tl.dropped == 3
    assert tl.to_chrome_trace()["otherData"]["dropped_events"] == 3


def test_validate_chrome_trace_catches_corruption():
    assert validate_chrome_trace([]) == ["trace is not an object"]
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "ts": 1.0},          # no dur
        {"ph": "e", "name": "r", "pid": 1, "ts": 2.0, "id": "r",
         "cat": "request"},                                       # e before b
        {"ph": "??", "name": "x", "pid": 1, "ts": 0.0},           # unknown ph
    ]}
    problems = validate_chrome_trace(bad)
    assert any("bad dur" in p for p in problems)
    assert any("e before its b" in p for p in problems)
    assert any("unknown ph" in p for p in problems)


def test_summarize_chrome_trace_groups_programs_and_gaps():
    tl = Timeline()
    tl.record_span("sched.prefill.dispatch", "prefill", "serving", 0.0, 0.5)
    tl.decode_chunk("serving", 1.0, 0.2, steps=8)
    tl.decode_chunk("serving", 1.3, 0.2, steps=8)
    tl.record_request("r1", "serving", 0.0, 1.5, "completed")
    with use_registry():
        pass
    text = summarize_chrome_trace(tl.to_chrome_trace())
    assert "sched.prefill.dispatch" in text
    assert "decode_chunk[8]" in text
    assert "largest step gaps" in text
    assert "completed=1" in text


def test_attribution_switch_gates_everything():
    with use_registry() as reg, use_timeline() as tl:
        prev = set_attribution(False)
        try:
            tl.record_span("x", "decode", "serving", 0.0, 1.0)
            tl.decode_chunk("serving", 0.0, 1.0, steps=4)
            tl.decode_chunk("serving", 2.0, 1.0, steps=4)
            record_compile("serve_step", "shape", 1.0)
            observe_decode(get_model_config("tiny-test"),
                           {"batch": 2, "cache_slots": 8, "prefix_len": 0},
                           4, 1.0, program="serve_step")
            ev = SLOEvaluator()
            assert ev.observe("completed", ttft_s=0.1, e2e_s=0.2) is None
        finally:
            set_attribution(prev)
        assert tl.events() == []
        assert reg.instruments() == []


# -- compile stats ------------------------------------------------------------


def test_compilestats_counters_and_span():
    with use_registry() as reg, use_timeline() as tl:
        record_compile("serve_step", "shape", 1.25,
                       key=("serve_step", 8, False))
        record_compile("serve_step", "decode_chunk", 0.5)
        assert reg.counter("compiles_total", component="compile",
                           program="serve_step", reason="shape").value == 1
        assert reg.counter("compiles_total", component="compile",
                           program="serve_step",
                           reason="decode_chunk").value == 1
        # the stages' seconds and the compile.<program> spans come from
        # JAX's own stage reports (tests/test_sched_spans.py), not from here.
        assert [m.name for m in reg.instruments()
                if m.name != "compiles_total"] == []
        assert tl.events() == []


# -- roofline -----------------------------------------------------------------


def test_decode_step_bytes_model():
    cfg = get_model_config("tiny-test")
    stats = {"batch": 4, "cache_slots": 96, "prefix_len": 0}
    model_item = 2 if cfg.dtype == "bfloat16" else 4
    per_slot = cfg.num_kv_heads * cfg.head_dim * model_item * 2 * cfg.num_layers
    expected = cfg.approx_param_count * model_item + 4 * 96 * per_slot
    assert decode_step_bytes(cfg, stats) == expected
    # The shared prefix adds one batch-wide read per step.
    with_prefix = decode_step_bytes(cfg, {**stats, "prefix_len": 64})
    assert with_prefix == expected + 64 * per_slot


def test_roofline_gauges_math():
    cfg = get_model_config("tiny-test")
    stats = {"batch": 4, "cache_slots": 96, "prefix_len": 0}
    with use_registry() as reg:
        set_achievable_gbps(100.0)
        try:
            out = observe_decode(cfg, stats, steps=10, wall_s=0.5,
                                 program="serve_step")
        finally:
            set_achievable_gbps(None)
        sb = decode_step_bytes(cfg, stats)
        assert out["step_bytes"] == sb
        assert out["gbps"] == pytest.approx(sb * 10 / 0.5 / 1e9)
        assert out["fraction"] == pytest.approx(out["gbps"] / 100.0)
        assert reg.read_value("achieved_over_achievable",
                              component="roofline",
                              program="serve_step") == pytest.approx(
            out["fraction"])
        assert reg.read_value("decode_step_bytes", component="roofline",
                              program="serve_step") == sb
        # No steps / no wall -> nothing observed (never a div-by-zero).
        assert observe_decode(cfg, stats, 0, 0.5, program="p") is None
        assert observe_decode(cfg, stats, 5, 0.0, program="p") is None


# -- SLO burn rates -----------------------------------------------------------


def test_slo_burn_rates_hand_computed():
    t = SLOTargets(ttft_p95_s=1.0, e2e_p99_s=10.0, error_rate=0.1,
                   fast_window_s=60.0, slow_window_s=600.0)
    clock = [1000.0]
    with use_registry() as reg:
        ev = SLOEvaluator(targets=t, clock=lambda: clock[0])
        # 8 good, 1 failed (ttft also over target), 1 expired (no ttft).
        for i in range(8):
            ev.observe("completed", ttft_s=0.5, e2e_s=1.0, t=1000.0 + i)
        ev.observe("failed", ttft_s=2.0, e2e_s=1.0, t=1009.0)
        ev.observe("expired", t=1010.0)
        out = ev.evaluate(now=1010.0)
        # errors: 2/10 observed vs 0.1 budget -> burn 2.0
        assert out["run"]["error_rate"] == pytest.approx(2.0)
        # ttft: 1 over of 9 with a ttft, vs 5% budget -> (1/9)/0.05
        assert out["run"]["ttft_p95"] == pytest.approx((1 / 9) / 0.05)
        assert out["run"]["e2e_p99"] == 0.0
        assert reg.read_value("slo_burn_rate", component="serving",
                              slo="error_rate",
                              window="run") == pytest.approx(2.0)
        # Crossing 1.0 counted exactly once per (slo, window).
        assert reg.counter("slo_alerts_total", component="serving",
                           slo="error_rate", window="run").value == 1
        # preempted is excluded entirely (infra scheduling, not failure).
        n = reg.read_value("slo_window_requests", component="serving",
                           window="run")
        ev.observe("preempted", t=1011.0)
        assert reg.read_value("slo_window_requests", component="serving",
                              window="run") == n


def test_slo_windows_age_out_and_alerts_resolve():
    t = SLOTargets(error_rate=0.5, fast_window_s=10.0, slow_window_s=1000.0)
    with use_registry() as reg:
        ev = SLOEvaluator(targets=t, clock=lambda: 0.0)
        ev.observe("failed", t=100.0)  # burn fast = (1/1)/0.5 = 2.0
        assert reg.read_value("slo_burn_rate", component="serving",
                              slo="error_rate",
                              window="fast") == pytest.approx(2.0)
        assert reg.counter("slo_alerts_total", component="serving",
                           slo="error_rate", window="fast").value == 1
        # 50s later the failure left the 10s fast window; two successes keep
        # the window populated -> burn 0, alert resolves, no double count.
        ev.observe("completed", t=150.0)
        ev.observe("completed", t=151.0)
        assert reg.read_value("slo_burn_rate", component="serving",
                              slo="error_rate", window="fast") == 0.0
        # slow window still sees 1 error of 3 -> (1/3)/0.5 < 1: no new alert
        assert reg.counter("slo_alerts_total", component="serving",
                           slo="error_rate", window="fast").value == 1
        # A second burst re-alerts (crossing again): three failures put the
        # fast window at 3 bad of 5 -> (3/5)/0.5 = 1.2 > 1.
        ev.observe("failed", t=152.0)
        ev.observe("failed", t=153.0)
        ev.observe("failed", t=154.0)
        assert reg.read_value("slo_burn_rate", component="serving",
                              slo="error_rate",
                              window="fast") == pytest.approx(1.2)
        assert reg.counter("slo_alerts_total", component="serving",
                           slo="error_rate", window="fast").value == 2


def test_slo_run_window_exact_past_deque_capacity():
    # An early error burst must NOT age out of the run window when the
    # bounded deque wraps — the --fail-on-burn gate reads run-window burns.
    t = SLOTargets(error_rate=0.1, fast_window_s=1.0, slow_window_s=2.0)
    with use_registry():
        ev = SLOEvaluator(targets=t, capacity=8, clock=lambda: 0.0)
        ev.observe("failed", t=0.0)
        for i in range(20):  # pushes the failure out of the deque
            ev.observe("completed", t=100.0 + i)
        out = ev.evaluate(now=200.0)
        assert out["run"]["error_rate"] == pytest.approx((1 / 21) / 0.1)
        assert out["fast"]["error_rate"] == 0.0


def test_slo_maybe_evaluate_decays_idle_windows():
    clock = [0.0]
    t = SLOTargets(error_rate=0.5, fast_window_s=10.0, slow_window_s=1000.0)
    with use_registry() as reg:
        ev = SLOEvaluator(targets=t, clock=lambda: clock[0])
        ev.observe("failed", t=5.0)
        assert reg.read_value("slo_burn_rate", component="serving",
                              slo="error_rate",
                              window="fast") == pytest.approx(2.0)
        # No further traffic: a loop calling maybe_evaluate decays the
        # fast window (and resolves the alert) once the failure ages out.
        clock[0] = 100.0
        ev.maybe_evaluate()
        assert reg.read_value("slo_burn_rate", component="serving",
                              slo="error_rate", window="fast") == 0.0
        # Run window keeps the whole-run truth.
        assert reg.read_value("slo_burn_rate", component="serving",
                              slo="error_rate",
                              window="run") == pytest.approx(2.0)


def test_slo_report_renders_from_snapshot():
    with use_registry() as reg:
        ev = SLOEvaluator(targets=SLOTargets(error_rate=0.1),
                          clock=lambda: 0.0)
        ev.observe("failed", t=1.0)
        text = render_slo_report(snapshot(reg))
        assert "error_rate" in text and "BURNING" in text
        assert "ttft_p95" in text  # gauges exist even with no ttft samples
    empty = render_slo_report({"gauges": [], "counters": []})
    assert "no slo_burn_rate gauges" in empty


# -- heartbeat gaps -----------------------------------------------------------


def test_heartbeat_gap_fake_clock():
    clock = [0.0]
    with use_registry() as reg:
        hb = Heartbeat(interval_s=10.0, name="sweep", clock=lambda: clock[0])
        assert hb.poke()            # first beat, no gap
        clock[0] = 11.0
        assert hb.poke()            # normal cadence: 11s < 1.5x interval
        assert reg.peek("heartbeat_gap_s", component="sweep") is None
        clock[0] = 14.0
        assert not hb.poke()        # within interval: no beat
        clock[0] = 61.0             # the loop went dark for 50s
        assert hb.poke()
        h = reg.histogram("heartbeat_gap_s", component="sweep")
        assert h.count == 1 and h.max == pytest.approx(50.0)
        assert reg.read_value("heartbeat_gap_max_s",
                              component="sweep") == pytest.approx(50.0)
        assert hb.max_gap_s == pytest.approx(50.0)


# -- scheduler / fleet integration --------------------------------------------


@pytest.fixture(scope="module")
def engine():
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    return DecodeEngine(get_model_config("tiny-test"), seed=0)


def _greedy(m):
    return ModelSettings(temperature=0.0, top_k=0, top_p=1.0, max_tokens=m)


def _serve(engine, reqs, fault_injector=None):
    from fairness_llm_tpu.serving import ContinuousScheduler

    sched = ContinuousScheduler(
        engine,
        ServingConfig(enabled=True, num_slots=2, max_prompt_len=128,
                      max_new_tokens=8, decode_chunk=2),
        settings=_greedy(8),
        fault_injector=fault_injector,
    )
    return sched, sched.serve(reqs)


def test_scheduler_emits_spans_compiles_and_roofline(engine):
    from fairness_llm_tpu.serving import Request

    reqs = [Request(prompt=p, id=f"tl{i}", settings=_greedy(6))
            for i, p in enumerate(["one two three", "four five six",
                                   "seven eight nine"])]
    with use_registry() as reg, use_timeline() as tl:
        sched, results = _serve(engine, reqs)
        assert all(r.ok for r in results)
        spans = [e for e in tl.events() if e["type"] == "span"]
        cats = {s["cat"] for s in spans}
        assert {"prefill", "decode", "compile"} <= cats
        # Single-engine path: every span on the one "serving" track (a
        # compile outside every program's call, and the scheduler's own
        # construction, have no scheduler to name: "host").
        assert {s["track"] for s in spans if s["name"] not in (
            "compile.unscoped", "startup.scheduler")} == {"serving"}
        reqspans = [e for e in tl.events() if e["type"] == "request"]
        assert {e["name"] for e in reqspans} == {"tl0", "tl1", "tl2"}
        assert all(e["args"]["outcome"] == "completed" for e in reqspans)
        # Compile observability: this scheduler's first prefill bucket and
        # step program each compiled once; later chunks were cache hits.
        assert reg.counter("compiles_total", component="compile",
                           program="serve_step", reason="shape").value == 1
        # ... and JAX's own stage reports put its seconds under its label.
        assert reg.read_value("compile_stage_seconds_total",
                              component="compile", program="serve_step",
                              stage="backend_compile") > 0
        # Live roofline gauges populated from real chunk walls.
        assert reg.read_value("achieved_hbm_gbps", component="roofline",
                              program="serve_step") > 0
        # The CPU has no row in the peaks table: no device-rate fraction.
        assert reg.peek("achieved_over_achievable", component="roofline",
                        program="serve_step") is None
        assert reg.read_value("decode_step_bytes", component="roofline",
                              program="serve_step") == decode_step_bytes(
            engine.config,
            {"batch": 2, "cache_slots": sched.cache_len, "prefix_len": 0})
        # Step gaps: >= 2 chunks ran, so at least one gap was observed.
        assert reg.histogram("step_gap_s", component="serving").count >= 1
        # The export is schema-valid and carries the acceptance span kinds.
        trace = tl.to_chrome_trace()
        assert validate_chrome_trace(trace) == []


def test_eviction_requeue_events_on_track_and_ordered(engine):
    from fairness_llm_tpu.serving import Request
    from fairness_llm_tpu.telemetry import assert_span_order
    from fairness_llm_tpu.utils.failures import ScriptedFaultInjector

    reqs = [Request(prompt="the quick brown fox", id="flaky",
                    settings=_greedy(6)),
            Request(prompt="jumped over", id="calm", settings=_greedy(6))]
    inj = ScriptedFaultInjector({("flaky", "decode"): 1})
    with use_registry(), use_timeline() as tl:
        sched, results = _serve(engine, reqs, fault_injector=inj)
        assert all(r.ok for r in results)
        # The requeue instant landed on the scheduler's track, and the
        # request's lifecycle stayed ordered through eviction+readmission.
        instants = [e for e in tl.events() if e["type"] == "instant"]
        req_evs = [e for e in instants if e["args"].get("request_id")
                   == "flaky"]
        assert any(e["name"] == "requeued" for e in req_evs)
        assert {e["track"] for e in req_evs} == {"serving"}
        assert [e["name"] for e in req_evs].count("admitted") == 2
        for rid in ("flaky", "calm"):
            _, evs = next(f for f in sched.tracer.finished
                          if f[0].request_id == rid)
            assert_span_order(evs)
        # One balanced request span per request, despite the requeue.
        reqspans = [e for e in tl.events() if e["type"] == "request"]
        assert sorted(e["name"] for e in reqspans) == ["calm", "flaky"]
        assert validate_chrome_trace(tl.to_chrome_trace()) == []


def test_fleet_events_land_on_replica_tracks(engine):
    from fairness_llm_tpu.config import FleetConfig, IntegrityConfig
    from fairness_llm_tpu.serving import ReplicaSet, Request
    from fairness_llm_tpu.utils.failures import ScriptedFaultInjector

    reqs = [Request(prompt=f"prompt number {i} with words", id=f"fl{i}",
                    settings=_greedy(6)) for i in range(6)]
    inj = ScriptedFaultInjector(replica_crashes={"r1": 3})
    with use_registry(), use_timeline() as tl:
        fleet = ReplicaSet(
            engine,
            ServingConfig(enabled=True, num_slots=2, max_prompt_len=128,
                          max_new_tokens=8, decode_chunk=2),
            settings=_greedy(8),
            fleet=FleetConfig(replicas=2, fence_cooldown_s=0.01),
            resilience=ResilienceConfig(enabled=True, breaker_threshold=2,
                                        breaker_cooldown_s=0.01),
            fault_injector=inj,
            integrity=IntegrityConfig(canary_max_tokens=4),
        )
        results = fleet.serve(reqs)
        assert all(r.ok for r in results)
        assert inj.replica_faults_fired == [("r1", "replica_crash")]
        # The fence instant is pinned to the SICK replica's track.
        fences = [e for e in tl.events()
                  if e["type"] == "instant" and e["name"] == "fence"]
        assert fences and {e["track"] for e in fences} == {"r1"}
        assert fences[0]["args"]["reason"] == "replica_crash"
        # Both replicas decoded on their own tracks before/after the fence.
        decode_tracks = {e["track"] for e in tl.events()
                         if e["type"] == "span" and e["cat"] == "decode"}
        assert {"r0", "r1"} <= decode_tracks
        # Every request's terminal span sits on the replica that finished
        # it — never a mixed/unknown lane.
        reqspans = [e for e in tl.events() if e["type"] == "request"
                    and not e["name"].startswith("__")]
        assert {e["name"] for e in reqspans} == {f"fl{i}" for i in range(6)}
        assert {e["track"] for e in reqspans} <= {"r0", "r1"}
        assert validate_chrome_trace(tl.to_chrome_trace()) == []


def test_router_discounts_slo_burn(engine):
    from fairness_llm_tpu.serving.router import HealthRouter

    class _Q:
        closed = False
        full = False

        def __len__(self):
            return 0

    class _Pool:
        occupancy = 0

    class _Sched:
        breakers = None
        watchdog = None
        num_slots = 2
        queue = _Q()
        pool = _Pool()
        _pending = ()

    class _Rep:
        def __init__(self, name):
            self.name = name
            self.fenced = False
            self.sched = _Sched()

    with use_registry() as reg:
        router = HealthRouter()
        healthy, burning = _Rep("a"), _Rep("b")
        reg.gauge("slo_burn_rate", component="serving", replica="b",
                  slo="error_rate", window="fast").set(4.0)
        assert router.health_score(healthy) == 1.0
        assert router.health_score(burning) == pytest.approx(0.25)
        assert router.pick([healthy, burning]) is healthy
        # Burn below 1.0 is budget consumption WITHIN the SLO: no discount.
        reg.gauge("slo_burn_rate", component="serving", replica="b",
                  slo="error_rate", window="fast").set(0.9)
        assert router.health_score(burning) == 1.0


# -- CLI / validator surface --------------------------------------------------


def test_validate_telemetry_require_profile(engine, tmp_path):
    import sys

    sys.path.insert(0, "/root/repo/tools")
    try:
        from validate_telemetry import check
    finally:
        sys.path.pop(0)
    from fairness_llm_tpu.serving import Request
    from fairness_llm_tpu.telemetry import get_timeline, write_snapshot

    reqs = [Request(prompt=f"words here {i}", id=f"vp{i}",
                    settings=_greedy(6)) for i in range(3)]
    with use_registry() as reg, use_timeline():
        _serve(engine, reqs)
        write_snapshot(reg, str(tmp_path))
        # trace.json missing -> --require-profile fails naming it.
        assert check(str(tmp_path), require_profile=True) == 1
        get_timeline().export(str(tmp_path / "trace.json"))
        assert check(str(tmp_path), require_profile=True) == 0


def test_cli_slo_report_and_timeline_section(engine, tmp_path, capsys):
    from fairness_llm_tpu.cli.main import main as cli_main
    from fairness_llm_tpu.serving import Request
    from fairness_llm_tpu.telemetry import get_timeline, write_snapshot

    reqs = [Request(prompt=f"more words {i}", id=f"cli{i}",
                    settings=_greedy(6)) for i in range(3)]
    with use_registry() as reg, use_timeline():
        _serve(engine, reqs)
        write_snapshot(reg, str(tmp_path))
        get_timeline().export(str(tmp_path / "trace.json"))
    assert cli_main(["slo-report", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "SLO BURN RATES" in out and "error_rate" in out
    assert cli_main(["telemetry-report", str(tmp_path), "--validate",
                     "--timeline"]) == 0
    out = capsys.readouterr().out
    assert "TIMELINE SUMMARY" in out and "decode_chunk" in out
    assert "snapshot schema: OK" in out


def test_engine_generate_records_attribution(engine):
    with use_registry() as reg, use_timeline() as tl:
        out = engine.generate(["alpha beta gamma"], _greedy(5), seed=0)
        assert out.texts
        spans = [e for e in tl.events() if e["type"] == "span"]
        gen = [s for s in spans if s["name"].startswith("generate[")]
        assert gen and gen[0]["track"] == "engine"
        # A fresh (batch, prompt, max_new) key compiled under this registry.
        assert reg.counter("compiles_total", component="compile",
                           program="decode", reason="shape").value >= 1
        assert reg.read_value("achieved_hbm_gbps",
                              component="roofline", program="decode") > 0


def test_chrome_trace_json_roundtrip(tmp_path):
    tl = Timeline()
    tl.record_span("s", "decode", "serving", 0.0, 1.0)
    path = tl.export(str(tmp_path / "sub" / "trace.json"))
    with open(path) as f:
        loaded = json.load(f)
    assert validate_chrome_trace(loaded) == []
    assert loaded["displayTimeUnit"] == "ms"
