#!/usr/bin/env python3
"""Readings for a cell's limits: in ONE process (one set-up), for each seed
remake the weights, drive a short window of the cell's own traffic at the
cell's own sizes, and read the served sample's widest gap and the control's
(the reference with lower-precision activations, at the same positions).
Prints one JSON line per seed; run it on the chip.

    python3 perfbench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 1 --control int8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", default="int8,fp8")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    import run as bench  # perfbench/run.py

    c, _ = bench.prepare(a.workload, a.rehearse)
    cfg, mix, pool = c["cfg"], c["mix"], c["pool"]
    from perfbench.lib import correct, drivers
    from perfbench.lib.sut import ServedModel

    seeds = [int(s) for s in a.seeds.split(",")]
    sut = ServedModel(cfg, c["cell"]["config"], mix, pool, seeds[0])
    warm, drive = drivers.DRIVERS[mix["kind"]]
    warm(sut, mix, seeds[0])
    per_request = max(int(mix["output_tokens"]), 1)
    count = max(3, -(-bench.SAMPLE_TOKENS // per_request))
    out = open(a.out, "a") if a.out else None
    for seed in seeds:
        t0 = time.monotonic()
        sut.set_seed_weights(seed)
        window = drive(sut, mix, seed, a.seconds, drivers.NoTrace(), 0.0)
        t1 = time.monotonic()
        sample = correct.draw_sample(window.served, seed, count)
        row = {"workload": a.workload, "seed": seed,
               "attempted": window.attempted, "failed": window.failed,
               "length_faults": correct.length_faults(
                   sample, cfg["eos_token_id"]),
               "end_to_end": window.end_to_end}
        row.update(correct.gaps(
            cfg, seed, sample,
            control_formats=[f for f in a.control.split(",") if f]))
        row["drive_s"] = round(t1 - t0, 2)
        row["check_s"] = round(time.monotonic() - t1, 2)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
