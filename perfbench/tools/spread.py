#!/usr/bin/env python3
"""Spread of each end-to-end metric over sets of runs, as the bound's rule
reads it: (Q3 - Q1) / median with ``statistics.quantiles(values, n=4)``.

    python3 perfbench/tools/spread.py chiprun_out/s2/A_*.out -- chiprun_out/s2/B_*.out
"""

from __future__ import annotations

import json
import statistics
import sys


def last_line(path: str) -> dict:
    with open(path) as f:
        lines = [ln for ln in f.read().strip().splitlines() if ln.startswith("{")]
    return json.loads(lines[-1])


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv) -> int:
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    rows = [[last_line(p) for p in s] for s in sets if s]
    names = list(rows[0][0]["metrics"])
    for name in names:
        out = []
        for runs in rows:
            vals = [r["metrics"][name]["value"] for r in runs]
            out.append((statistics.median(vals), spread(vals), min(vals),
                        max(vals)))
        print(name, " | ".join(
            f"median {m:.6g} spread {100 * s:.3f}% range {lo:.6g}..{hi:.6g}"
            for m, s, lo, hi in out))
    bad = [r for runs in rows for r in runs if not r["correct"]]
    print(f"runs {sum(len(r) for r in rows)}, not correct {len(bad)}, "
          f"peak bytes {sorted({r['device']['memory_peak_bytes'] for runs in rows for r in runs})}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
