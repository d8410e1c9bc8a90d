#!/usr/bin/env python3
"""Where a benchmark cell's time goes inside the scheduler's iteration, from
the program's own spans, in an UNTRACED run: the recorder is always on, so a
stall that happens once is in it.

    python3 tools/stall_report.py --workload qwen2-7b-int8.sweep --seed 7 \
        --seconds 51 --runs 4

Builds the cell as ``perfbench/run.py`` does (``prepare``, ``ServedModel``,
the cell's warm-up and driver), then drives ``--runs`` windows in the one
process, each on a fresh timeline and its own traffic seed. Per window it
prints the end-to-end numbers; per pass of a closed cell (per second of an
open one) the iterations and each span's summed time; the passes that took
longer than the median pass, with the span that holds the difference; the
slowest iteration beside the median one; every ``compile.*`` span (after
the warm-up there should be none); and writes the timeline as a Chrome
trace and the iterations as JSON under ``--out``.

``--mix-file`` / ``--pool-file`` lay a JSON object over the cell's traffic
mix and pool (a traffic that has no cell, such as the listwise passes of
PERF.md). ``--attribution alternate`` switches the attribution layer off in
every second window (``set_attribution``): the windows' end-to-end numbers,
by side, are what the instrumentation costs. ``--heartbeat`` runs a thread
that wakes every 5 ms and notes every wake-up that came over 50 ms late: a
stall during which the heartbeat kept time was the device's or the
runtime's (the host thread waits in ``device_get`` with the interpreter
lock released); one the heartbeat missed too froze the whole process.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

STALL_S = 0.05  # a pass this much over the median pass is reported


def iterations_of(events):
    """The ring's ``sched.iterate`` spans, each with its children's summed
    seconds by name and what no child covers (``uncovered``)."""
    rows = {}
    for e in events:
        if e["type"] == "span" and e["name"] == "sched.iterate":
            rows[e["args"]["seq"]] = {
                "seq": e["args"]["seq"], "t0": e["t0"], "dur_s": e["dur_s"],
                "spans": {}}
    for e in events:
        if e["type"] != "span" or e["args"].get("parent") != "sched.iterate":
            continue
        row = rows.get(e["args"].get("seq"))
        if row is not None:
            row["spans"][e["name"]] = row["spans"].get(e["name"], 0.0) \
                + e["dur_s"]
    for row in rows.values():
        row["uncovered"] = row["dur_s"] - sum(row["spans"].values())
    return sorted(rows.values(), key=lambda r: r["t0"])


def passes_of(events):
    """(start, end) of each ``ServingBackend.generate`` call: from its
    ``backend.submit`` (request construction) to its ``backend.collect``."""
    starts = [e["t0"] for e in events if e["type"] == "span"
              and e["name"] == "backend.submit"
              and e["args"].get("stage") == "build"]
    ends = [e["t0"] + e["dur_s"] for e in events if e["type"] == "span"
            and e["name"] == "backend.collect"]
    return list(zip(starts, ends))


def _sum_spans(rows):
    total = {}
    for r in rows:
        for name, s in r["spans"].items():
            total[name] = total.get(name, 0.0) + s
    total["(uncovered)"] = sum(r["uncovered"] for r in rows)
    return total


def _fmt(spans):
    return " ".join(f"{k.replace('sched.', '')}={1e3 * v:.1f}"
                    for k, v in sorted(spans.items()))


def report(events, window, kind: str, out=sys.stdout) -> dict:
    """Print one window's report; return what the caller tabulates."""
    iters = iterations_of(events)
    found = {"iterations": len(iters), "stalled": []}
    if not iters:
        print("  no sched.iterate span recorded", file=out)
        return found
    if kind == "closed":
        groups = [(f"pass {k}", a, b)
                  for k, (a, b) in enumerate(passes_of(events))]
    else:
        first = iters[0]["t0"]
        last = iters[-1]["t0"] + iters[-1]["dur_s"]
        groups = [(f"second {k}", first + k, first + k + 1)
                  for k in range(int(last - first) + 1)]
    table = []
    for label, a, b in groups:
        rows = [r for r in iters if a <= r["t0"] < b]
        spans = _sum_spans(rows)
        table.append((label, b - a, rows, spans))
        print(f"  {label}: {b - a:.3f} s, {len(rows)} iterations, "
              f"outside them {1e3 * (b - a - sum(r['dur_s'] for r in rows)):.1f}"
              f" ms; ms by span: {_fmt(spans)}", file=out)
    if kind == "closed" and len(table) >= 3:
        med = statistics.median(t[1] for t in table)
        names = sorted({n for t in table for n in t[3]})
        typical = {n: statistics.median(t[3].get(n, 0.0) for t in table)
                   for n in names}
        for label, secs, rows, spans in table:
            if secs - med < STALL_S:
                continue
            excess = {n: spans.get(n, 0.0) - typical[n] for n in names}
            worst = max(excess, key=excess.get)
            slow = max(rows, key=lambda r: r["dur_s"])
            found["stalled"].append({
                "pass": label, "seconds": secs, "over_median_s": secs - med,
                "span": worst, "span_excess_s": excess[worst],
                "slowest_iteration": slow})
            print(f"  STALL {label}: {secs:.3f} s against a median of "
                  f"{med:.3f}; {worst} holds {1e3 * excess[worst]:.1f} ms of "
                  f"the {1e3 * (secs - med):.1f}; its slowest iteration "
                  f"(seq {slow['seq']}, {1e3 * slow['dur_s']:.1f} ms): "
                  f"{_fmt(dict(slow['spans'], **{'(uncovered)': slow['uncovered']}))}",
                  file=out)
    slowest = max(iters, key=lambda r: r["dur_s"])
    by_dur = sorted(iters, key=lambda r: r["dur_s"])
    median = by_dur[len(by_dur) // 2]
    for tag, r in (("slowest", slowest), ("median", median)):
        print(f"  {tag} iteration seq {r['seq']}: {1e3 * r['dur_s']:.1f} ms; "
              f"{_fmt(dict(r['spans'], **{'(uncovered)': r['uncovered']}))}",
              file=out)
    covered = sum(sum(r["spans"].values()) for r in iters)
    total = sum(r["dur_s"] for r in iters)
    print(f"  children cover {100 * covered / total:.2f}% of "
          f"{total:.3f} s of iterations; least covered iteration "
          f"{100 * min(1 - r['uncovered'] / r['dur_s'] for r in iters):.2f}%",
          file=out)
    compiles = [e for e in events if e["type"] == "span"
                and e["name"].startswith("compile.")]
    for e in compiles:
        print(f"  COMPILE {e['name']} {1e3 * e['dur_s']:.1f} ms at "
              f"+{e['t0'] - iters[0]['t0']:.3f} s {e['args']}", file=out)
    found["compiles"] = len(compiles)
    return found


def print_setup_split(warm_s: float, out=sys.stdout) -> None:
    """Set-up by stage, from the registry, beside the warm-up's wall: the
    constructors' and the first calls' own seconds
    (``startup_seconds_total``), each program's compile stages
    (``compile_stage_seconds_total``), and the five functions whose
    tracing took longest (``compile_trace_fn_seconds_total``)."""
    from fairness_llm_tpu.telemetry import get_registry

    instruments = get_registry().instruments()
    rows = [(m.labels["program"], m.labels["stage"], m.value)
            for m in instruments if m.name == "compile_stage_seconds_total"]
    hits = {(m.labels["program"], m.labels["outcome"]): m.value
            for m in instruments
            if m.name == "compile_cache_persistent_total"}
    booked = sum(v for p, _, v in rows if p != "unscoped")
    print(f"SETUP warm-up {warm_s:.2f} s, of it booked to the step programs' "
          f"compile stages {booked:.2f} s; persistent cache {hits}", file=out)
    startup = {}
    for m in instruments:
        if m.name == "startup_seconds_total":
            startup[m.labels["stage"]] = startup.get(m.labels["stage"],
                                                     0.0) + m.value
    for stage, value in sorted(startup.items()):
        print(f"  SETUP startup {stage} {value:.3f} s", file=out)
    for program, stage, value in sorted(rows):
        print(f"  SETUP {program} {stage} {value:.3f} s", file=out)
    fns = sorted(((m.value, m.labels["program"], m.labels["fn"],
                   m.labels["outer"]) for m in instruments
                  if m.name == "compile_trace_fn_seconds_total"),
                 reverse=True)
    for value, program, fn, outer in fns[:5]:
        print(f"  SETUP traced {program} {fn} (outer {outer}) {value:.3f} s",
              file=out)


class Heartbeat:
    """A thread that sleeps ``period_s`` at a time and keeps every interval
    between two wake-ups that ran over ``late_s``."""

    def __init__(self, period_s: float = 0.005, late_s: float = 0.05):
        self.period_s, self.late_s = period_s, late_s
        self.gaps = []  # (start, end) on time.monotonic
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(self.period_s):
            now = time.monotonic()
            if now - last > self.late_s:
                self.gaps.append((last, now))
            last = now

    def __enter__(self) -> "Heartbeat":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


def report_heartbeat(beat: Heartbeat, events, out=sys.stdout) -> None:
    """The late wake-ups, and for each iteration whose sync ran over the
    median by 50 ms whether the heartbeat was late inside it."""
    iters = iterations_of(events)
    if not iters:
        return
    t0 = iters[0]["t0"]
    print(f"  heartbeat: {len(beat.gaps)} wake-ups over "
          f"{1e3 * beat.late_s:.0f} ms late: "
          f"{[(round(a - t0, 3), round(b - a, 3)) for a, b in beat.gaps]}",
          file=out)
    plain = [r["spans"].get("sched.decode.sync", 0.0) for r in iters
             if "sched.prefill.dispatch" not in r["spans"]]
    if not plain:
        return
    typical = statistics.median(plain)
    for r in iters:
        sync = r["spans"].get("sched.decode.sync", 0.0)
        if "sched.prefill.dispatch" in r["spans"] or sync - typical < 0.05:
            continue
        a, b = r["t0"], r["t0"] + r["dur_s"]
        missed = sum(min(b, y) - max(a, x) for x, y in beat.gaps
                     if x < b and y > a)
        print(f"  SLOW SYNC seq {r['seq']} at +{a - t0:.3f} s: sync "
              f"{1e3 * sync:.1f} ms against {1e3 * typical:.1f}; the "
              f"heartbeat was late for {1e3 * missed:.1f} ms of it",
              file=out)


def _quartiles(values):
    if len(values) < 2:
        return [values[0]] * 3 if values else []
    return statistics.quantiles(values, n=4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--runs", type=int, default=1)
    ap.add_argument("--mix-file")
    ap.add_argument("--pool-file")
    ap.add_argument("--attribution", choices=("on", "off", "alternate"),
                    default="on")
    ap.add_argument("--heartbeat", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    import run as bench

    c, _ = bench.prepare(a.workload, a.rehearse)
    cfg, mix, pool = c["cfg"], c["mix"], c["pool"]
    for path, target in ((a.mix_file, mix), (a.pool_file, pool)):
        if path:
            with open(path) as f:
                target.update(json.load(f))
    from fairness_llm_tpu.telemetry.timeline import Timeline, set_timeline
    from perfbench.lib import drivers
    from perfbench.lib.sut import ServedModel

    out_dir = a.out or os.path.join(ROOT, "chiprun_out", "stall_report",
                                    a.workload)
    os.makedirs(out_dir, exist_ok=True)
    sut = ServedModel(cfg, c["cell"]["config"], mix, pool, a.seed)
    warm, drive = drivers.DRIVERS[mix["kind"]]
    t0 = time.monotonic()
    warm(sut, mix, a.seed)
    print_setup_split(time.monotonic() - t0)
    sides = {"on": [], "off": []}
    for k in range(a.runs):
        on = a.attribution == "on" or (a.attribution == "alternate"
                                       and k % 2 == 0)
        tl = Timeline()
        tl.enabled = on
        prev = set_timeline(tl)
        beat = Heartbeat() if a.heartbeat else contextlib.nullcontext()
        try:
            with beat:
                w = drive(sut, mix, a.seed + k, a.seconds, drivers.NoTrace(),
                          0.0)
                stats = sut.new_stats()
                while sut.scheduler.has_work:  # the open loop's post-roll
                    sut.scheduler.step(stats)
        finally:
            set_timeline(prev)
        side = "on" if on else "off"
        sides[side].append(w.end_to_end)
        print(f"WINDOW {k} seed {a.seed + k} attribution {side}: "
              f"{json.dumps(w.end_to_end)} attempted {w.attempted} failed "
              f"{w.failed} pass_s {[round(x, 3) for x in w.pass_s]} "
              f"dropped {tl.dropped}")
        if not on:
            continue
        events = tl.events()
        found = report(events, w, mix["kind"])
        if a.heartbeat:
            report_heartbeat(beat, events)
        tl.export(os.path.join(out_dir, f"trace_{a.seed + k}.json"))
        with open(os.path.join(out_dir, f"iterations_{a.seed + k}.json"),
                  "w") as f:
            json.dump({"end_to_end": w.end_to_end, "pass_s": w.pass_s,
                       "found": found, "iterations": iterations_of(events)},
                      f)
    for side, rows in sides.items():
        for metric in sorted({m for r in rows for m in r}):
            values = [r[metric] for r in rows]
            print(f"SUMMARY attribution {side} {metric}: n={len(values)} "
                  f"quartiles {_quartiles(values)} values {values}")
    sut.free()
    return 0


if __name__ == "__main__":
    sys.exit(main())
