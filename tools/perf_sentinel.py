"""Noise-aware perf regression sentinel: compare a fresh bench run against
the committed ``bench_baseline.json``.

The CPU harness's single-run wall jitter is ±30-60% (docs/PERFORMANCE.md
methodology), so naive "wall got slower" gates would flap. This sentinel is
noise-aware by construction:

- **Fingerprint refusal** — a baseline records its harness fingerprint
  (jax version, platform, device kind, cpu count, model —
  ``bench.harness_fingerprint``). Comparing numbers across fingerprints is
  meaningless, so the sentinel REFUSES (exit 2) instead of passing or
  failing; ``--allow-refusal`` downgrades the refusal to a reported skip
  (exit 0) for CI runners whose hardware can never match the committed
  baseline's.
- **Best-of-N measurement** — ``--run`` executes the cheap bench entries N
  times (``--reps``) in subprocesses and keeps each wall entry's BEST
  value (rates: max), the same min-of-reps idiom every bench entry uses
  internally. ``exact`` entries must agree across reps — disagreement IS
  the regression (nondeterminism), reported as parity drift.
- **Ratio bands for walls** — a ``wall`` entry regresses only when
  fresh/baseline leaves ``[1/band, band]`` (default 2.0x: wide enough for
  the harness's known jitter after best-of-N, tight enough that an
  injected 3x slowdown always fails). ``--wall-warn-only`` downgrades wall
  violations to warnings (the CI mode) — parity stays hard.
- **Exact comparison for counters** — hit ratios, token checksums/counts,
  shed rates (``kind: exact``) are deterministic on one fingerprint and
  compared exactly; drift there is a correctness regression, never noise.

Usage:
    python tools/perf_sentinel.py --baseline bench_baseline.json --fresh fresh.json
    python tools/perf_sentinel.py --baseline bench_baseline.json \
        --run --reps 2 --entries continuous [--wall-warn-only]
    python tools/perf_sentinel.py --self-check bench_baseline.json

``--self-check`` proves the gates bite on THIS harness without needing a
matching committed fingerprint: a clean self-comparison must pass, an
injected 3x slowdown must fail, an injected parity drift must fail, and a
perturbed fingerprint must refuse — the CI step hard-fails if any gate
fails to bite. Exit codes: 0 ok / warn-only, 1 regression, 2 refused.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_WALL_BAND = 2.0
# Entries cheap enough for a CI runner (the headline sweep always rides).
CHEAP_ENTRIES = "continuous"

EXIT_OK = 0
EXIT_REGRESSION = 1
EXIT_REFUSED = 2


def load_baseline(path: str) -> Dict:
    with open(path, encoding="utf-8") as f:
        base = json.load(f)
    for key in ("fingerprint", "entries"):
        if key not in base:
            raise SystemExit(f"{path}: not a bench baseline (missing {key!r})")
    return base


def fingerprint_mismatches(a: Dict, b: Dict) -> List[str]:
    """Human-readable field-by-field fingerprint differences (empty =
    comparable)."""
    out = []
    for key in sorted(set(a) | set(b)):
        if a.get(key) != b.get(key):
            out.append(f"{key}: baseline={a.get(key)!r} fresh={b.get(key)!r}")
    return out


def compare(baseline: Dict, fresh: Dict,
            wall_band: float = DEFAULT_WALL_BAND) -> Tuple[List[str], List[str], Dict]:
    """Compare two baseline-format records (same fingerprint asserted by
    the caller). Returns (problems, wall_violations, report): wall
    violations are split out so the caller can decide whether they are
    hard failures or warnings; ``problems`` (missing entries, exact-entry
    drift) are ALWAYS hard."""
    problems: List[str] = []
    wall_violations: List[str] = []
    rows = {}
    for name, spec in baseline["entries"].items():
        kind = spec.get("kind", "exact")
        base_v = spec.get("value")
        fresh_spec = fresh["entries"].get(name)
        row = {"kind": kind, "baseline": base_v}
        if fresh_spec is None:
            problems.append(f"{name}: present in baseline, missing from the "
                            "fresh run (entry skipped or renamed?)")
            row["status"] = "missing"
            rows[name] = row
            continue
        fresh_v = fresh_spec.get("value")
        row["fresh"] = fresh_v
        if kind == "wall":
            try:
                ratio = float(fresh_v) / float(base_v)
            except (TypeError, ValueError, ZeroDivisionError):
                ratio = None
            row["ratio"] = round(ratio, 4) if ratio is not None else None
            if ratio is None or not (1.0 / wall_band <= ratio <= wall_band):
                wall_violations.append(
                    f"{name}: {base_v!r} -> {fresh_v!r} "
                    f"(ratio {ratio if ratio is None else round(ratio, 3)}; "
                    f"band [{1 / wall_band:.3f}, {wall_band:.3f}])"
                )
                row["status"] = "wall_violation"
            else:
                row["status"] = "ok"
        else:
            if fresh_v != base_v:
                problems.append(
                    f"{name}: exact-compared counter drifted "
                    f"({base_v!r} -> {fresh_v!r}) — correctness regression, "
                    "not noise"
                )
                row["status"] = "drift"
            else:
                row["status"] = "ok"
        rows[name] = row
    report = {
        "wall_band": wall_band,
        "entries": rows,
        "problems": problems,
        "wall_violations": wall_violations,
    }
    return problems, wall_violations, report


def merge_best(runs: List[Dict]) -> Tuple[Dict, List[str]]:
    """Best-of-N merge of baseline-format records: per wall entry keep the
    BEST rep in the entry's improvement direction (``better``: "higher"
    for rates/speedups — the default — "lower" for on/off overhead
    ratios); exact entries must agree across runs (disagreement = parity
    drift)."""
    problems: List[str] = []
    merged = copy.deepcopy(runs[0])
    for run in runs[1:]:
        for name, spec in run["entries"].items():
            have = merged["entries"].get(name)
            if have is None:
                merged["entries"][name] = spec
                continue
            if spec.get("kind") == "wall":
                lower = spec.get("better", "higher") == "lower"
                try:
                    v, cur = float(spec["value"]), float(have["value"])
                    if (v < cur) if lower else (v > cur):
                        have["value"] = spec["value"]
                except (TypeError, ValueError):
                    pass
            elif spec.get("value") != have.get("value"):
                problems.append(
                    f"{name}: exact entry disagrees BETWEEN reps of the "
                    f"fresh run ({have.get('value')!r} vs "
                    f"{spec.get('value')!r}) — nondeterministic harness"
                )
    return merged, problems


def run_bench(entries: str, reps: int) -> Tuple[Dict, List[str]]:
    """Run the cheap bench entries ``reps`` times in subprocesses; each run
    writes a baseline-format record via ``--baseline-out``."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    runs = []
    with tempfile.TemporaryDirectory(prefix="perf_sentinel_") as td:
        for rep in range(reps):
            out = os.path.join(td, f"run{rep}.json")
            cmd = [sys.executable, os.path.join(root, "bench.py"),
                   "--entries", entries, "--baseline-out", out]
            proc = subprocess.run(cmd, cwd=root, capture_output=True,
                                  text=True)
            if proc.returncode != 0:
                raise SystemExit(
                    f"bench rep {rep} failed (exit {proc.returncode}):\n"
                    f"{proc.stderr[-2000:]}"
                )
            with open(out, encoding="utf-8") as f:
                runs.append(json.load(f))
    return merge_best(runs)


def self_check(baseline_path: str) -> int:
    """Prove the gates bite on this harness: clean pass, 3x-slowdown fail,
    parity-drift fail, cross-fingerprint refusal."""
    base = load_baseline(baseline_path)
    failures = []

    # 1. A clean self-comparison must pass.
    problems, walls, _ = compare(base, base)
    if problems or walls:
        failures.append(f"clean self-comparison not clean: {problems + walls}")

    # 2. An injected 3x slowdown on every wall entry must violate the band.
    slow = copy.deepcopy(base)
    n_wall = 0
    for spec in slow["entries"].values():
        if spec.get("kind") == "wall":
            spec["value"] = float(spec["value"]) / 3.0
            n_wall += 1
    if n_wall:
        problems, walls, _ = compare(base, slow)
        if len(walls) != n_wall:
            failures.append(
                f"3x slowdown flagged {len(walls)}/{n_wall} wall entries"
            )
    else:
        failures.append("baseline has no wall entries to slow down")

    # 3. An injected token-parity drift must hard-fail.
    drift = copy.deepcopy(base)
    n_exact = 0
    for spec in drift["entries"].values():
        if spec.get("kind") == "exact":
            spec["value"] = "DRIFTED" if isinstance(spec["value"], str) \
                else (spec["value"] or 0) + 1
            n_exact += 1
    if n_exact:
        problems, _, _ = compare(base, drift)
        if len(problems) != n_exact:
            failures.append(
                f"parity drift flagged {len(problems)}/{n_exact} entries"
            )
    else:
        failures.append("baseline has no exact entries to drift")

    # 4. A perturbed fingerprint must refuse.
    other = dict(base["fingerprint"], cpu_count=-1)
    if not fingerprint_mismatches(base["fingerprint"], other):
        failures.append("perturbed fingerprint compared as identical")

    if failures:
        print("SELF-CHECK FAILED:")
        for f in failures:
            print(f"  - {f}")
        return EXIT_REGRESSION
    print(f"SELF-CHECK OK: clean pass / 3x-slowdown fail ({n_wall} wall "
          f"entries) / parity-drift fail ({n_exact} exact entries) / "
          "cross-fingerprint refusal all behave")
    return EXIT_OK


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", help="committed bench_baseline.json")
    ap.add_argument("--fresh", help="a fresh baseline-format record to "
                                    "compare (from bench --baseline-out)")
    ap.add_argument("--run", action="store_true",
                    help="measure fresh entries now: run bench.py "
                         "--entries ... N times, best-of-N merge")
    ap.add_argument("--reps", type=int, default=2,
                    help="with --run: best-of-N repetitions (default 2)")
    ap.add_argument("--entries", default=CHEAP_ENTRIES,
                    help="with --run: bench entries to measure "
                         f"(default: {CHEAP_ENTRIES})")
    ap.add_argument("--wall-band", type=float, default=DEFAULT_WALL_BAND,
                    help="wall-entry ratio band (default 2.0)")
    ap.add_argument("--wall-warn-only", action="store_true",
                    help="wall-band violations warn instead of failing "
                         "(parity/counter drift still hard-fails) — the "
                         "CI mode")
    ap.add_argument("--allow-refusal", action="store_true",
                    help="a fingerprint refusal exits 0 (reported, no "
                         "numbers compared) instead of 2 — for CI runners "
                         "whose hardware cannot match the committed "
                         "baseline's fingerprint")
    ap.add_argument("--report", help="write the comparison report JSON here")
    ap.add_argument("--self-check", metavar="BASELINE",
                    help="prove the gates bite on this harness, then exit")
    a = ap.parse_args()

    if a.self_check:
        return self_check(a.self_check)
    if not a.baseline or not (a.fresh or a.run):
        ap.error("need --baseline plus one of --fresh/--run "
                 "(or --self-check)")
    if a.wall_band <= 1.0:
        ap.error("--wall-band must be > 1")

    base = load_baseline(a.baseline)
    rep_problems: List[str] = []
    if a.fresh:
        fresh = load_baseline(a.fresh)
    else:
        fresh, rep_problems = run_bench(a.entries, max(a.reps, 1))

    report: Dict = {
        "baseline": a.baseline,
        "baseline_fingerprint": base["fingerprint"],
        "fresh_fingerprint": fresh["fingerprint"],
    }

    def write_report():
        if a.report:
            with open(a.report, "w", encoding="utf-8") as f:
                json.dump(report, f, indent=1, sort_keys=True)
            print(f"report: {a.report}")

    mism = fingerprint_mismatches(base["fingerprint"], fresh["fingerprint"])
    if mism:
        report["status"] = "refused"
        report["fingerprint_mismatches"] = mism
        print(f"REFUSED: baseline {a.baseline} was recorded under a "
              "different harness fingerprint — cross-fingerprint numbers "
              "are not comparable:")
        for m in mism:
            print(f"  - {m}")
        write_report()
        if a.allow_refusal:
            print("(--allow-refusal: exiting 0 without comparing)")
            return EXIT_OK
        return EXIT_REFUSED

    # Only the entries the fresh run actually measured are comparable when
    # it ran a subset (--entries): drop baseline entries outside it, BUT
    # only entry-name prefixes the subset explains — a wholesale drop would
    # let a silently-skipped headline pass.
    if a.run:
        measured = {e.strip() for e in a.entries.split(",") if e.strip()}
        measured.add("headline")
        base = copy.deepcopy(base)
        base["entries"] = {
            k: v for k, v in base["entries"].items()
            if k.split(".", 1)[0] in measured
        }

    problems, wall_violations, cmp_report = compare(
        base, fresh, wall_band=a.wall_band
    )
    problems = rep_problems + problems
    report.update(cmp_report)

    for w in wall_violations:
        tag = "WARN (wall band)" if a.wall_warn_only else "FAIL (wall band)"
        print(f"{tag}: {w}")
    for p in problems:
        print(f"FAIL: {p}")
    hard = list(problems) + ([] if a.wall_warn_only else wall_violations)
    report["status"] = "fail" if hard else (
        "warn" if wall_violations else "ok")
    write_report()
    if hard:
        print(f"PERF SENTINEL: {len(hard)} failure(s)")
        return EXIT_REGRESSION
    ok_n = sum(1 for r in report["entries"].values()
               if r.get("status") == "ok")
    print(f"PERF SENTINEL: OK ({ok_n} entries within bounds"
          + (f", {len(wall_violations)} wall warning(s)"
             if wall_violations else "") + ")")
    return EXIT_OK


if __name__ == "__main__":
    sys.exit(main())
