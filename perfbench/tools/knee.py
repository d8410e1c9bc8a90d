#!/usr/bin/env python3
"""The sweep of offered rates that finds an open-loop cell's knee: in ONE
process, the cell's own mix at each rate for a short window, and for each
rate whether a backlog grew (time to first token of the window's second half
against its first, the queue's depth). Run once, on the chip, when the cell
is defined; the cell then runs at 0.8 of the highest rate that held.

    python3 perfbench/tools/knee.py --workload <cell> --rates 3,4,5,6 \
        --seconds 24 --seed 5
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=24.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    import run as bench

    c, _ = bench.prepare(a.workload, a.rehearse)
    cfg, mix, pool = c["cfg"], c["mix"], c["pool"]
    from perfbench.lib import drivers
    from perfbench.lib.sut import ServedModel

    sut = ServedModel(cfg, c["cell"]["config"], mix, pool, a.seed)
    drivers.warm_open(sut, mix, a.seed)
    out = open(a.out, "a") if a.out else None
    for k, rate in enumerate(float(r) for r in a.rates.split(",")):
        m = dict(mix, rate_per_s=rate)
        w = drivers.run_open(sut, m, a.seed + k, a.seconds, drivers.NoTrace(),
                             0.0)
        # drain what the post-roll left in the pool before the next rate
        stats = sut.new_stats()
        while sut.scheduler.has_work:
            sut.scheduler.step(stats)
        half = a.seconds / 2
        t1 = [t for d, t in w.ttft_by_due if d < half]
        t2 = [t for d, t in w.ttft_by_due if d >= half]
        c_ = w.counters
        row = {"rate_per_s": rate, "attempted": w.attempted,
               "failed": w.failed,
               "ttft_p50_first_half_ms": 1e3 * float(np.median(t1)),
               "ttft_p50_second_half_ms": 1e3 * float(np.median(t2)),
               "ttft_p95_ms": w.end_to_end["ttft_p95_ms"],
               "tpot_ms": w.end_to_end["tpot_ms"],
               "queue_depth_max": c_["queue_depth_max"],
               "queue_wait_p95_ms": 1e3 * float(
                   np.percentile(w.queue_wait_s, 95)),
               "occupancy_pct": 100.0 * c_["occupancy_sum"] / max(
                   c_["decode_steps"] * pool["num_slots"], 1),
               "gen_late_p95_ms": 1e3 * float(
                   np.percentile(w.gen_late_s, 95)),
               "drained_s": w.marks["drained_s"]}
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
