#!/usr/bin/env python3
"""Device time of a benchmark cell's traced run, executable by executable:
one ``perfbench/run.py`` run with ``--trace 1`` in this process, and from the
same trace each XLA module's runs, its device time a run, and its ops by
device time a run (the op events that start inside the module's runs).

    python3 tools/module_ops.py --workload trinity-mini-int8.catalog8k \\
        --seed 7 --seconds 12 [--top 25]

Runs ``run.main`` unchanged, its last line printed as it is, then prints one
``MODULE_OPS`` JSON line per executable, the longest first: ``module``,
``runs``, ``ms_per_run``, and ``top``, ``[op, ms a run, events a run]`` for
the ``--top`` ops (ops that only hold others are left out, as in the
benchmark's ``breakdown``). The benchmark's own ``prefill_ms_per_ktok``
sums every ``serve_prefill`` executable; this tells them apart.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

import run as bench  # noqa: E402  (its clock starts here: run.py's _T_PROCESS)


def by_module(trace, top: int) -> list:
    """Per executable on device 0: runs, milliseconds a run, top ops."""
    import numpy as np
    from perfbench.lib.trace import _CONTAINERS, short_name

    dev = trace.devices[0]
    order = np.argsort(dev["op_start"], kind="stable")
    start, ids = dev["op_start"][order], dev["op_id"][order]
    dur = dev["op_dur"][order]
    n = len(dev["texts"])
    runs = defaultdict(list)
    for name, t0, d, _ in dev["modules"]:
        runs[name].append((t0, d))
    out = []
    for name, spans in runs.items():
        seconds, count = np.zeros(n), np.zeros(n)
        for t0, d in spans:
            lo, hi = np.searchsorted(start, [t0, t0 + d])
            seconds += np.bincount(ids[lo:hi], weights=dur[lo:hi],
                                   minlength=n) / 1e9
            count += np.bincount(ids[lo:hi], minlength=n)
        ops, events = defaultdict(float), defaultdict(float)
        for i in np.flatnonzero(count):
            if dev["parsed"][i][1] not in _CONTAINERS:
                key = short_name(dev["texts"][i])
                ops[key] += seconds[i]
                events[key] += count[i]
        k = len(spans)
        out.append(dict(
            module=name, runs=k,
            ms_per_run=round(sum(d for _, d in spans) / k / 1e6, 4),
            top=[[op, round(float(s) / k * 1e3, 4),
                  round(float(events[op]) / k, 1)]
                 for op, s in sorted(ops.items(), key=lambda kv: -kv[1])
                 [:top]]))
    return sorted(out, key=lambda r: -r["ms_per_run"] * r["runs"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--top", type=int, default=25)
    a = ap.parse_args(argv)
    from perfbench.lib import trace as trace_mod

    found = []

    class Kept(trace_mod.Trace):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            found.append(by_module(self, a.top))

    trace_mod.Trace = Kept
    rc = bench.main(["--workload", a.workload, "--seed", str(a.seed),
                     "--seconds", str(a.seconds), "--trace", "1"])
    for rec in (found[0] if found else []):
        print("MODULE_OPS " + json.dumps(rec))
    return rc


if __name__ == "__main__":
    sys.exit(main())
