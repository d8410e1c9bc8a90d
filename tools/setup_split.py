#!/usr/bin/env python3
"""Where a benchmark cell's set-up goes, stage by stage: one
``perfbench/run.py`` run in this process, its ``setup_s`` split by what the
program booked (``telemetry/compilestats.py``) and by the benchmark's own
marks.

    python3 tools/setup_split.py --workload qwen2-7b-int8.sweep --seed 7 \\
        --seconds 3 [--trace 1] [--attribution off]

Runs ``run.main`` unchanged, its last line printed as it is, then prints one
``SETUP_SPLIT`` JSON line: ``setup_s`` (process start to the window, plus
the lead-in, as ``run.py`` counts it); the marks ``imports_s``,
``weights_s``, ``build_s``, ``warm_s``; in each of the phases weights,
build and warm-up the compile seconds booked ``unscoped`` and to the step
programs; ``setup_build_s``, ``setup_trace_body_s``, the inner jits'
tracing and ``lower``, ``setup_compile_load_s``, ``setup_first_call_s``
(the per-layer metrics' sums, read from the registry whatever
``--trace``); the warm-up's own running (``warm_s`` less what it booked);
the lead-in; and ``remainder_s``, what no column holds. ``fn_identity``
gives per program the sum of ``compile_trace_fn_seconds_total`` over its
``trace`` + ``ledger_trace`` (1.0 when the tracing is booked by function
in full); ``stages`` each program's stages, ``top_fns`` the ten functions
whose tracing took longest, ``long_traces`` every trace event of half a
second and more as JAX reported it (whatever the switch).
``--attribution off`` switches the attribution layer off first: ``setup_s``
then is what the program costs without any of it.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import run as bench  # noqa: E402  (its clock starts here: run.py's _T_PROCESS)


def _booked() -> dict:
    """The registry's set-up counters, summed as the per-layer metrics and
    the table's columns sum them."""
    from fairness_llm_tpu.telemetry import get_registry

    out = {"unscoped": 0.0, "trace_lower": 0.0, "compile_load": 0.0,
           "build": 0.0, "first_call": 0.0, "trace_body": 0.0}
    traced, by_fn = {}, {}
    for m in get_registry().instruments():
        lbl, v = m.labels, getattr(m, "value", 0.0)
        if m.name == "compile_stage_seconds_total":
            if lbl["program"] == "unscoped":
                out["unscoped"] += v
            elif lbl["stage"] in ("trace", "lower", "ledger_trace"):
                out["trace_lower"] += v
            else:
                out["compile_load"] += v
            if lbl["stage"] in ("trace", "ledger_trace"):
                traced[lbl["program"]] = traced.get(lbl["program"], 0.0) + v
        elif m.name == "startup_seconds_total":
            out["first_call" if lbl["stage"] == "first_call"
                else "build"] += v
        elif m.name == "compile_trace_fn_seconds_total":
            by_fn[lbl["program"]] = by_fn.get(lbl["program"], 0.0) + v
            if lbl["outer"] == "1" and lbl["program"] != "unscoped":
                out["trace_body"] += v
    out["fn_identity"] = {p: by_fn.get(p, 0.0) / s
                          for p, s in sorted(traced.items()) if s > 0}
    return out


def _detail(top: int = 10) -> dict:
    """Each program's stages, and the functions whose tracing took longest."""
    from fairness_llm_tpu.telemetry import get_registry

    stages, fns = {}, []
    for m in get_registry().instruments():
        if m.name == "compile_stage_seconds_total":
            stages.setdefault(m.labels["program"], {})[m.labels["stage"]] = \
                m.value
        elif m.name == "compile_trace_fn_seconds_total":
            fns.append((m.value, m.labels["program"], m.labels["fn"],
                        m.labels["outer"]))
    return {"stages": stages, "top_fns": sorted(fns, reverse=True)[:top]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--attribution", choices=("on", "off"), default="on")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    import jax.monitoring

    from fairness_llm_tpu.telemetry.timeline import set_attribution
    from perfbench.lib import drivers, sut

    set_attribution(a.attribution == "on")
    at = {}  # phase boundary -> (time.monotonic, booked counters)
    long_traces = []  # this tool's own listener, on whatever the switch says

    def on_duration(event, duration, **kw):
        if event.endswith("jaxpr_trace_duration") and duration >= 0.5:
            long_traces.append((str(kw.get("fun_name", "")), duration))

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    def mark(name):
        at[name] = (time.monotonic(), _booked())

    built, make = sut.ServedModel.__init__, sut.ServedModel._make

    def init(self, *args, **kw):
        mark("weights")
        built(self, *args, **kw)
        mark("warm")

    def made(self, seed):
        tree = make(self, seed)
        if "build" not in at:
            mark("build")
        return tree

    sut.ServedModel.__init__, sut.ServedModel._make = init, made
    for kind, (warm, drive) in list(drivers.DRIVERS.items()):
        def warmed(*args, _warm=warm):
            _warm(*args)
            mark("after_warm")

        def driven(*args, _drive=drive):
            mark("drive")
            return _drive(*args)

        drivers.DRIVERS[kind] = (warmed, driven)
    line = io.StringIO()
    argv = ["--workload", a.workload, "--seed", str(a.seed), "--seconds",
            str(a.seconds), "--trace", str(a.trace)]
    with contextlib.redirect_stdout(line):
        rc = bench.main(argv + (["--rehearse"] if a.rehearse else []))
    sys.stdout.write(line.getvalue())
    last = json.loads(line.getvalue().strip().splitlines()[-1])
    marks = last["marks"]
    c = bench.load_cell(a.workload)
    lead = float(c["mix"].get("lead_in_s", 0.0)) \
        if c["mix"]["kind"] == "open" else 0.0
    setup_s = at["drive"][0] + lead - bench._T_PROCESS
    phases = ("weights", "build", "warm", "after_warm", "drive")
    delta = {p: {k: at[q][1][k] - at[p][1][k]
                 for k in at[p][1] if k != "fn_identity"}
             for p, q in zip(phases, phases[1:])}
    end = at["drive"][1]
    warm, build = delta["warm"], delta["build"]
    programs = {p: d["trace_lower"] + d["compile_load"] + d["first_call"]
                for p, d in delta.items()}
    from fairness_llm_tpu.telemetry import get_registry

    row = {
        "workload": a.workload, "seed": a.seed,
        "attribution": a.attribution,
        "cache": {m.labels["outcome"]: m.value
                  for m in get_registry().instruments()
                  if m.name == "compile_cache_persistent_total"
                  and m.labels["program"] != "unscoped"},
        "setup_s": setup_s, "imports_s": marks["imports_s"],
        "weights_s": marks["weights_s"], "build_s": marks["build_s"],
        "warm_s": marks["warm_s"], "lead_in_s": lead,
        "unscoped_s": dict({"imports": at["weights"][1]["unscoped"]},
                           **{p: d["unscoped"] for p, d in delta.items()}),
        "setup_build_s": end["build"],
        "setup_trace_body_s": end["trace_body"],
        "inner_trace_and_lower_s": end["trace_lower"] - end["trace_body"],
        "setup_trace_lower_s": end["trace_lower"],
        "setup_compile_load_s": end["compile_load"],
        "setup_first_call_s": end["first_call"],
        "step_programs_by_phase_s": programs,
        # ServedModel.__init__ before its weights' clock starts: the
        # engine's and the serving package's imports
        "init_imports_s": at["build"][0] - at["weights"][0]
        - marks["weights_s"],
        # the build's own rest: the backend round the scheduler, the tokenizer
        "build_rest_s": marks["build_s"] - (
            build["unscoped"] + programs["build"] + build["build"]),
        # what the warm-up ran beyond what it booked: the passes themselves
        "warm_running_s": marks["warm_s"] - (
            warm["unscoped"] + programs["warm"] + warm["build"]),
        # the memory peak's read and the tracer's construction
        "after_warm_s": at["drive"][0] - at["after_warm"][0],
        "fn_identity": end["fn_identity"],
        "long_traces": long_traces,
        **_detail(),
    }
    columns = (row["imports_s"] + row["init_imports_s"] + row["weights_s"]
               + row["unscoped_s"]["build"] + row["unscoped_s"]["warm"]
               + end["build"] + row["build_rest_s"] + end["trace_lower"]
               + end["compile_load"] + end["first_call"]
               + row["warm_running_s"] + row["after_warm_s"] + lead)
    row["remainder_s"] = setup_s - columns
    print("SETUP_SPLIT " + json.dumps(row))
    return rc


if __name__ == "__main__":
    sys.exit(main())
