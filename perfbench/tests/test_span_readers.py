"""The readers of the program's own spans, lifecycle instants and registry
values, on hand-made inputs: what each computes, what reads as nothing, and
the two faults that raise."""

import importlib.util
import json
import os
import types

import pytest

from fairness_llm_tpu.telemetry import use_registry
from fairness_llm_tpu.telemetry.timeline import Timeline, use_timeline

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MS = 1_000_000  # ns


def _reader(name):
    path = os.path.join(BENCH, "readers", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _args(metric):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        return json.load(f)["args"]


def _trace(events):
    return types.SimpleNamespace(trace=types.SimpleNamespace(
        host_events=events))


# two iterations of 100 ms: the first admits one batch, both decode
ITERATIONS = [
    ("sched.iterate", 0, 100 * MS),
    ("sched.expire", 0, 1 * MS),
    ("sched.admit.plan", 1 * MS, 2 * MS),
    ("sched.prefill.dispatch", 3 * MS, 4 * MS),
    ("sched.decode.prepare", 7 * MS, 1 * MS),
    ("sched.decode.dispatch", 8 * MS, 2 * MS),
    ("sched.decode.sync", 10 * MS, 89 * MS),
    ("sched.decode.emit", 99 * MS, 1 * MS),
    ("np.asarray(jax.Array)", 10 * MS, 89 * MS),  # the runtime's own event
    ("sched.iterate", 100 * MS, 100 * MS),
    ("sched.expire", 100 * MS, 1 * MS),
    ("sched.decode.prepare", 101 * MS, 3 * MS),
    ("sched.decode.dispatch", 104 * MS, 6 * MS),
    ("sched.decode.sync", 110 * MS, 88 * MS),
    ("sched.decode.emit", 198 * MS, 2 * MS),
]


@pytest.mark.parametrize("metric, expected", [
    # (1 + 2 + 1 + 1) + (1 + 3 + 2) ms over two iterations
    ("sched_host_ms_per_iter.req", 5.5),
    ("sched_host_ms_per_iter.tpot", 5.5),
    # 4 + 2 + 6 ms over three calls
    ("dispatch_ms_per_call.req", 4.0),
    ("dispatch_ms_per_call.tpot", 4.0),
])
def test_span_time_quotients(metric, expected):
    read = _reader("span_time")
    assert read(_trace(ITERATIONS), **_args(metric)) == pytest.approx(expected)


def test_span_time_reads_nothing_without_trace_or_spans():
    read = _reader("span_time")
    args = _args("sched_host_ms_per_iter.req")
    assert read(types.SimpleNamespace(trace=None), **args) is None
    # the commit before the spans existed: only the runtime's own events
    assert read(_trace([("np.asarray(jax.Array)", 0, 5 * MS)]),
                **args) is None


def test_span_time_raises_on_a_child_outside_every_iteration():
    read = _reader("span_time")
    stray = ITERATIONS + [("sched.decode.emit", 250 * MS, 1 * MS)]
    with pytest.raises(ValueError, match="outside every sched.iterate"):
        read(_trace(stray), **_args("sched_host_ms_per_iter.req"))
    straddling = ITERATIONS + [("sched.decode.sync", 95 * MS, 10 * MS)]
    with pytest.raises(ValueError, match="outside every sched.iterate"):
        read(_trace(straddling), **_args("dispatch_ms_per_call.req"))


def _served(*ids, ok=True):
    return types.SimpleNamespace(window=types.SimpleNamespace(served=[
        types.SimpleNamespace(spec=types.SimpleNamespace(id=i), ok=ok)
        for i in ids]))


def _lifecycle(tl, rid, **at):
    for name, t in sorted(at.items(), key=lambda kv: kv[1]):
        tl.record_instant(name, "serving", t=t, cat="lifecycle",
                          request_id=rid)


def test_lifecycle_percentile_over_the_windows_requests():
    read = _reader("lifecycle")
    args = _args("first_token_after_admit_p95_ms")
    with use_timeline() as tl:
        for i in range(20):  # 100, 110, ... 290 ms from admitted
            _lifecycle(tl, f"w{i}", submitted=i, admitted=i + 0.5,
                       prefill_start=i + 0.51,
                       first_token=i + 0.6 + 0.01 * i)
        _lifecycle(tl, "lead-in", admitted=0.0, first_token=50.0)
        tl.record_instant("fence", "serving", t=3.0)  # not a lifecycle edge
        ctx = _served(*[f"w{i}" for i in range(20)])
        assert read(ctx, **args) == pytest.approx(280.5)
        assert read(ctx, start="submitted", end="admitted",
                    percentile=50) == pytest.approx(500.0)
        assert read(_served("nobody"), **args) is None
        assert read(_served("w3", ok=False), **args) is None


def test_lifecycle_counts_a_requeued_request_from_its_last_admission():
    read = _reader("lifecycle")
    with use_timeline() as tl:
        _lifecycle(tl, "again", submitted=0.0, admitted=1.0)
        _lifecycle(tl, "again", requeued=2.0, admitted=5.0, first_token=5.25)
        assert read(_served("again"), start="admitted", end="first_token",
                    percentile=95) == pytest.approx(250.0)


def test_lifecycle_raises_once_the_ring_has_dropped_events():
    read = _reader("lifecycle")
    with use_timeline(Timeline(capacity=4)) as tl:
        for i in range(3):
            _lifecycle(tl, f"r{i}", admitted=float(i), first_token=i + 0.5)
        assert tl.dropped == 2
        with pytest.raises(ValueError, match="dropped 2 events"):
            read(_served("r2"), **_args("first_token_after_admit_p95_ms"))


def test_registry_sums_and_shares():
    read = _reader("registry_value")
    with use_registry() as reg:
        assert read(None, **_args("setup_trace_lower_s")) is None
        assert read(None, **_args("hbm_peak_pct.req")) is None
        seconds = {("serve_step", "trace"): 2.0, ("serve_step", "lower"): 1.5,
                   ("serve_step", "ledger_trace"): 0.25,
                   ("serve_step", "cache_load"): 4.0,
                   ("serve_prefill", "trace"): 3.0,
                   ("serve_prefill", "backend_compile"): 20.0,
                   ("unscoped", "trace"): 100.0,
                   ("unscoped", "backend_compile"): 100.0}
        for (program, stage), s in seconds.items():
            reg.gauge("compile_stage_seconds_total", component="compile",
                      program=program, stage=stage).add(s)
        assert read(None, **_args("setup_trace_lower_s")) == \
            pytest.approx(6.75)
        assert read(None, **_args("setup_compile_load_s")) == \
            pytest.approx(24.0)
        for dev, peak in (("0", 12e9), ("1", 15e9)):
            reg.gauge("hbm_peak_bytes", component="memory",
                      device=dev).set(peak)
            reg.gauge("hbm_limit_bytes", component="memory",
                      device=dev).set(16e9)
        for metric in ("hbm_peak_pct.req", "hbm_peak_pct.tpot"):
            assert read(None, **_args(metric)) == pytest.approx(93.75)
        # a histogram of the same name has no single value to sum
        reg.histogram("compile_seconds", component="compile").observe(1.0)
        assert read(None, metric="compile_seconds") is None


def test_every_new_metric_has_its_file_and_entry():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    moved = {m["name"]: set(m.get("workloads", cells))
             for m in bench["end_to_end"]}
    for name in ("sched_host_ms_per_iter.req", "sched_host_ms_per_iter.tpot",
                 "dispatch_ms_per_call.req", "dispatch_ms_per_call.tpot",
                 "first_token_after_admit_p95_ms", "setup_trace_lower_s",
                 "setup_compile_load_s", "hbm_peak_pct.req",
                 "hbm_peak_pct.tpot"):
        entry = entries[name]
        assert set(entry["workloads"]) <= moved[entry["moves"]]
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["name"] == name
        assert os.path.exists(os.path.join(BENCH, spec["reader"]))
