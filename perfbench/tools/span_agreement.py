#!/usr/bin/env python3
"""Do the program's own spans and the benchmark's spans round it agree?

    python3 perfbench/tools/span_agreement.py --workload <cell> --seed <n> \
        --seconds <s>

Makes one ``--trace 1`` run of the cell through ``run.py`` (its result line
is printed as always), keeps a copy of the trace as the profiler stops, and
checks on the profiler's clock, in the traced stretch:

- the children of every ``sched.iterate`` cover it (the least covered one
  and the whole);
- the iterations together fill the benchmark's phase spans (``generate`` of
  a closed cell, ``step`` of an open one);
- every ``sched.decode.dispatch`` and ``sched.prefill.dispatch`` contains
  exactly one of the benchmark's own spans round the compiled call;
- the host time the scheduler's spans count (expire, plan, prepare, emit)
  against the device's idle time (``window_s - busy_s``), per iteration;
- what the executables are called on the device (``XLA Modules``).

A tool for the builder and the reviewer: it decides nothing. Its run's set-up
is not the yardstick: called from here, ``run.main`` has read its tracing and
lowering half as long again as a plain ``run.py`` (PERF.md, section 5).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

HERE = os.path.dirname(os.path.abspath(__file__))


def _spans_of(metric: str):
    """The span names a layer metric sums, from its own data file."""
    with open(os.path.join(os.path.dirname(HERE), "layer_metrics",
                           metric + ".json")) as f:
        return tuple(json.load(f)["args"]["spans"])


HOST = _spans_of("sched_host_ms_per_iter.req")
DISPATCH = _spans_of("dispatch_ms_per_call.req")
PHASES = ("generate", "step")


def _inside(inner, outer, slack=1000):
    return outer[0] - slack <= inner[0] and inner[1] <= outer[1] + slack


def agreement(path: str, labels, device: dict) -> dict:
    from jax.profiler import ProfileData

    host, modules = {}, {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == "/host:CPU":
                for ev in line.events:
                    if ev.name.startswith(("sched.", "backend.",
                                           "compile.")) \
                            or ev.name in PHASES or ev.name in labels:
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
            elif plane.name.startswith("/device:TPU:0") \
                    and line.name == "XLA Modules":
                for ev in line.events:
                    name = ev.name.split("(")[0]
                    modules[name] = modules.get(name, 0) + 1
    iterations = sorted(host.get("sched.iterate", []))
    out = {"xla_modules": modules,
           "host_spans": {k: len(v) for k, v in sorted(host.items())}}
    if not iterations:
        return out
    children = [(n, s) for n in HOST + DISPATCH + ("sched.decode.sync",)
                for s in host.get(n, [])]
    cover = []
    for it in iterations:
        mine = sum(s[1] - s[0] for _, s in children if _inside(s, it))
        cover.append(mine / max(it[1] - it[0], 1))
    total = sum(b - a for a, b in iterations)
    out["iterations"] = len(iterations)
    out["children_cover_least"] = min(cover)
    out["children_cover_whole"] = sum(
        s[1] - s[0] for _, s in children) / total
    phase = sum(b - a for p in PHASES for a, b in host.get(p, []))
    out["iterate_s"], out["phase_s"] = total / 1e9, phase / 1e9
    out["iterate_over_phase"] = total / phase if phase else None
    calls = [s for label in labels for s in host.get(label, [])]
    holds = [sum(1 for c in calls if _inside(c, d))
             for n in DISPATCH for d in host.get(n, [])]
    out["dispatch_spans"] = len(holds)
    out["dispatch_spans_holding_one_call"] = sum(1 for h in holds if h == 1)
    out["calls_outside_dispatch_spans"] = len(calls) - sum(holds)
    host_s = sum(b - a for n in HOST for a, b in host.get(n, [])) / 1e9
    out["sched_host_s"] = host_s
    out["sched_host_ms_per_iter"] = 1e3 * host_s / len(iterations)
    if "busy_s" in device:
        idle = device["window_s"] - device["busy_s"]
        out["device_idle_s"] = idle
        out["idle_less_host_ms_per_iter"] = \
            1e3 * (idle - host_s) / len(iterations)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    import run as bench

    kept_dir = os.path.join(ROOT, "perfbench_out", a.workload, "trace_kept")
    shutil.rmtree(kept_dir, ignore_errors=True)
    window = {}
    stop = bench.Tracer.stop

    def stop_and_keep(self):
        """run.py drops its trace once it has read it: keep a copy."""
        stop(self)
        shutil.copytree(self.dir, kept_dir)
        window["window_s"] = self.t1 - self.t0

    bench.Tracer.stop = stop_and_keep
    try:
        rc = bench.main(["--workload", a.workload, "--seed", str(a.seed),
                         "--seconds", str(a.seconds), "--trace", "1"])
    finally:
        bench.Tracer.stop = stop
    from perfbench.lib.trace import Trace, find_xplane

    labels = {"serve_step", "serve_prefill", "paged_step", "paged_prefill"}
    path = find_xplane(kept_dir)
    window["busy_s"] = Trace(path, labels).busy_seconds()
    print("AGREEMENT " + json.dumps(agreement(path, labels, window)))
    shutil.rmtree(kept_dir, ignore_errors=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
