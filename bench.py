"""Benchmark: phase-1 recommendation-sweep decode throughput per chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

What it measures: the end-to-end hot path of the study — the 45-profile
counterfactual prompt sweep (SURVEY.md §3.2 hot loop) — as batched
autoregressive decode on the local accelerator: tokenize -> left-pad ->
prefill -> 128 scan decode steps -> detokenize. Model is gpt2-small
(BASELINE.json configs[0]) with randomly initialized bf16 weights — weight
values don't change FLOPs or memory traffic, so throughput is representative
while requiring no checkpoint download.

``vs_baseline`` is the HONEST headline: achieved decode bandwidth as a
fraction of this chip's MEASURED achievable streaming bandwidth (1.0 =
decode at the hardware wall; falls back to the fraction of the device's
published HBM peak, ``telemetry/roofline.py`` DEVICE_PEAKS, when there is no
in-run probe — ``baseline`` says which). The
reference-API comparison (its README estimates ~15 min for the 45-profile
sweep of sequential OpenAI calls, SURVEY.md §6 — a strawman next to
hardware-limit accounting) lives in ``detail.vs_reference_api_sweep``.

Run: python bench.py          (uses the default backend — TPU when present)
     BENCH_MODEL=tiny-test python bench.py   (smoke on CPU)
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax


REFERENCE_PROFILES_PER_SEC = 45 / (15 * 60)  # README estimate: 45 profiles / ~15 min
MAX_NEW_TOKENS = 128

# -- entry selection (ISSUE 12) ------------------------------------------------
# ``--entries a,b,c`` (or BENCH_ENTRIES) runs a subset of the auxiliary
# measurements — the perf-sentinel CI step runs only the CHEAP entries
# against the committed bench_baseline.json instead of the whole ~hour-long
# record. The headline sweep always runs (it IS the metric).

_ALL_ENTRIES = (
    "speculative", "continuous", "resilience", "integrity", "profiling",
    "fused_decode", "serve_tp", "incidents", "memory", "rollout", "fleet",
    "overload",
    "fairness", "capacity", "large_sweep", "phase2_listwise",
    "flash_proof", "int8_70b", "shard70b", "live8b",
)

_entries: "set | None" = None  # None = everything


class _SkippedEntry(Exception):
    """Raised inside an entry's try block when --entries excludes it."""


def _enabled(name: str) -> bool:
    return _entries is None or name in _entries


def _require_entry(name: str) -> None:
    if not _enabled(name):
        raise _SkippedEntry(name)


def set_entries(names) -> None:
    global _entries
    if names is None:
        _entries = None
        return
    bad = set(names) - set(_ALL_ENTRIES)
    if bad:
        raise SystemExit(f"unknown bench entries: {sorted(bad)} "
                         f"(choose from {', '.join(_ALL_ENTRIES)})")
    _entries = set(names)


# -- harness fingerprint + machine-readable baseline (ISSUE 12) ----------------


def _cpu_model() -> str:
    """Best-effort host CPU identity: ISA family plus the model name when
    readable. XLA-CPU codegen is host-target dependent (AVX2 vs AVX-512
    changes reduction order, which can flip near-tie argmax tokens), so
    exact-compared token checksums are only meaningful on one CPU model —
    the fingerprint must refuse across them."""
    import platform

    model = ""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        model = platform.processor() or ""
    return f"{platform.machine()} {model}".strip()


def harness_fingerprint(model_name: str) -> dict:
    """What makes two bench runs comparable: same jax, same backend, same
    chip kind, same host CPU, same host parallelism, same model.
    tools/perf_sentinel.py REFUSES to compare runs whose fingerprints
    differ — a number recorded on a v5e means nothing next to one from a
    4-core CI runner."""
    devices = jax.devices()
    return {
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "none",
        "cpu": _cpu_model(),
        "cpu_count": os.cpu_count(),
        "model": model_name,
    }


def baseline_entries(result: dict) -> dict:
    """Flatten a bench result into sentinel-comparable entries, each tagged
    ``kind``: ``wall`` metrics carry harness jitter (compared within a
    noise-aware ratio band) while ``exact`` counters (hit ratios, token
    counts/checksums, shed counts) are deterministic on one fingerprint and
    compared exactly — drift there is a correctness regression, not noise."""
    d = result.get("detail", {})
    entries: dict = {}

    def wall(name, value, better="higher"):
        # ``better`` is the improvement direction ("higher" for rates and
        # speedups, "lower" for on/off overhead ratios) — the sentinel's
        # best-of-N merge keeps the best rep PER THIS DIRECTION.
        if value is not None:
            entries[name] = {"kind": "wall", "value": float(value),
                             "better": better}

    def exact(name, value):
        if value is not None:
            entries[name] = {"kind": "exact", "value": value}

    wall("headline.profiles_per_sec", result.get("value"))
    wall("headline.decode_tokens_per_sec", d.get("decode_tokens_per_sec"))
    exact("headline.token_checksum", d.get("token_checksum"))
    c = d.get("continuous")
    if c:
        wall("continuous.tokens_per_sec",
             c.get("continuous", {}).get("tokens_per_sec"))
        wall("continuous.speedup", c.get("speedup_tokens_per_sec"))
        exact("continuous.useful_tokens",
              c.get("continuous", {}).get("useful_tokens"))
    s = d.get("speculative")
    if s:
        wall("speculative.speedup", s.get("speedup"))
        exact("speculative.acceptance_rate", s.get("acceptance_rate"))
        exact("speculative.verify_steps", s.get("verify_steps"))
    ov = d.get("overload_overhead")
    if ov:
        wall("overload.overhead_ratio", ov.get("overhead_ratio"),
             better="lower")
    pr = d.get("profiling_overhead")
    if pr:
        wall("profiling.overhead_ratio", pr.get("overhead_ratio"),
             better="lower")
    ic = d.get("incident_overhead")
    if ic:
        wall("incidents.overhead_ratio", ic.get("overhead_ratio"),
             better="lower")
    mo = d.get("memory_overhead")
    if mo:
        wall("memory.overhead_ratio", mo.get("overhead_ratio"),
             better="lower")
    ro = d.get("rollout_overhead")
    if ro:
        wall("rollout.overhead_ratio", ro.get("overhead_ratio"),
             better="lower")
    fd = d.get("fused_decode")
    if fd:
        # gap_per_token_reduction_k4 stays OUT of the sentinel baseline on
        # purpose: its run-to-run spread (measured 2.5-6.8x on this
        # harness — tiny absolute gaps divided by tiny absolute gaps)
        # exceeds the two-sided wall band. tokens/sec and the exact token
        # count are the stable regression proxies.
        wall("fused_decode.tokens_per_sec_k4",
             fd.get("k4", {}).get("tokens_per_sec"))
        exact("fused_decode.useful_tokens", fd.get("useful_tokens"))
    stp = d.get("serve_tp")
    if stp:
        # Real-mesh tp serving: walls per variant compare within the noise
        # band; the token checksum and the all-reduce count in the
        # compiled step HLO are exact — a zero all-reduce count means the
        # mesh silently degenerated to replication.
        wall("serve_tp.tokens_per_sec_contig_k4",
             stp.get("contig_k4", {}).get("tokens_per_sec"))
        exact("serve_tp.token_checksum", stp.get("token_checksum"))
        exact("serve_tp.useful_tokens", stp.get("useful_tokens"))
    cap = d.get("capacity")
    if cap:
        for n, row in (cap.get("capacity") or {}).items():
            wall(f"capacity.{n}.profiles_per_sec_per_chip",
                 row.get("profiles_per_sec_per_chip"))
            exact(f"capacity.{n}.shed_rate", row.get("shed_rate"))
    return entries


def write_bench_baseline(result: dict, path: str, model_name: str) -> str:
    """Write the machine-readable baseline tools/perf_sentinel.py compares
    against: per-entry metric + kind + the harness fingerprint."""
    baseline = {
        "schema_version": 1,
        "created_at_unix": time.time(),
        "fingerprint": harness_fingerprint(model_name),
        "entries": baseline_entries(result),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(baseline, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


# The bytes-per-step roofline model moved into the telemetry layer (ISSUE 7)
# so serving evaluates it LIVE per decode chunk; bench (and the tools that
# import it from here) share the single definition.
from fairness_llm_tpu.telemetry.roofline import decode_step_bytes  # noqa: E402


def measure_speculative(engine, prompts, settings_cls) -> dict | None:
    """Phase-1 sweep decoded GREEDILY with prompt-lookup speculation off vs on.

    Speculation is exact only for greedy decode, so this entry runs the same
    45-profile sweep at temperature 0 (the sweep's own 0.7-sampled headline
    can't use it). The sweep decodes in the STUDY's own chunking
    (``config.decode_batch_size``, the shape ``pipeline.phase1.decode_sweep``
    actually runs) — which on the CPU harness is also the decode-bound
    operating point where a verify step costs about a plain step (at
    whole-sweep batch a CPU is compute-bound and the k+1-wide forward
    multiplies FLOPs; on TPU decode is HBM-bound at every batch). Reports
    tokens/sec both ways plus measured acceptance and verify-step
    compression — the numbers the ISSUE-1 target (>= 1.2x) is judged on.
    Measured on the repo's CPU harness: 2.0x (28.4 -> 58.0 tok/s) at 46%
    acceptance, 28 verify steps for 128-token rows. Reuses the headline
    engine (same params; greedy programs compile alongside the sampled ones).
    """
    import numpy as np

    from fairness_llm_tpu.config import SpeculationConfig, default_config
    from fairness_llm_tpu.utils.profiling import SpeculationStats

    settings = settings_cls(temperature=0.0, top_k=0, top_p=1.0,
                            max_tokens=MAX_NEW_TOKENS)
    spec = SpeculationConfig(enabled=True)
    pad_id = engine.tokenizer.pad_id
    chunk = max(default_config().decode_batch_size, 1)
    chunks = [prompts[i : i + chunk] for i in range(0, len(prompts), chunk)]
    out: dict = {
        "profiles": len(prompts),
        "decode_batch_size": chunk,
        "max_new_tokens": MAX_NEW_TOKENS,
        "draft_len": spec.draft_len,
        "ngram_max": spec.ngram_max,
    }
    for label, sp in (("off", None), ("on", spec)):
        # Compile outside the timed window: one warmup per DISTINCT chunk
        # size (same-size chunks pad to the same bucket and share a program).
        warmed = set()
        for c in chunks:
            if len(c) not in warmed:
                warmed.add(len(c))
                engine.generate(c, settings, seed=0, speculation=sp)
        totals = SpeculationStats()
        ntok = 0
        t0 = time.perf_counter()
        for c in chunks:
            o = engine.generate(c, settings, seed=1, speculation=sp)
            jax.block_until_ready(o.tokens)
            # Greedy real models can stop at EOS early; count tokens actually
            # decoded rather than assuming the cap.
            ntok += int(np.sum(o.tokens != pad_id))
            st = (o.stats or {}).get("speculation")
            if st:
                totals = totals.merge(SpeculationStats.from_dict(st))
        wall = time.perf_counter() - t0
        out[label] = {
            "wall_s": round(wall, 3),
            "decoded_tokens": ntok,
            "tokens_per_sec": round(ntok / wall, 1),
            "speculation": totals.as_dict() if totals.verify_steps else None,
        }
    out["speedup"] = round(out["off"]["wall_s"] / out["on"]["wall_s"], 3)
    on_spec = out["on"]["speculation"] or {}
    out["acceptance_rate"] = on_spec.get("acceptance_rate")
    out["verify_steps"] = on_spec.get("verify_steps")
    return out


def _mixed_workload(engine, prompts, n_requests, targets, budgets):
    """Interleaved mixed-length serving workload shared by the continuous
    and resilience-overhead entries: request i's prompt repeats its source
    up to ``targets[i % ...]`` tokens and decodes ``budgets[i % ...]``
    tokens — every static chunk then contains one near-max row, which is
    precisely the waste continuous batching removes."""
    tok = engine.tokenizer
    out = []
    for i in range(n_requests):
        ids = tok.encode(prompts[i % len(prompts)])
        tl = targets[i % len(targets)]
        ids = (ids * (tl // max(len(ids), 1) + 1))[:tl]
        out.append((tok.decode(ids), budgets[i % len(budgets)]))
    return out


def _greedy(settings_cls, m):
    return settings_cls(temperature=0.0, top_k=0, top_p=1.0, max_tokens=m)


def measure_continuous(engine, prompts, settings_cls) -> dict | None:
    """Continuous batching vs static chunking on a mixed-length workload.

    The workload is what the static engine is worst at: prompts spanning
    32-448 tokens and per-request decode budgets spanning 16-128 tokens,
    interleaved so every static chunk pads to its longest prompt and decodes
    to its largest budget (finished rows burn steps until the chunk drains).
    The continuous server (serving/) evicts each row the step its own budget
    completes and backfills the freed KV slot from the queue, so total decode
    steps track sum(budgets)/num_slots instead of sum of per-chunk maxima.

    Greedy both ways (the serving parity contract), same number of rows in
    flight both ways (num_slots == the static chunk size), compile excluded
    by an identical warmup pass. Reports tokens/sec and p50/p95 request
    latency for both modes — the ISSUE-2 target is >= 1.3x tokens/sec.
    """
    import numpy as np

    from fairness_llm_tpu.config import ServingConfig, default_config
    from fairness_llm_tpu.serving import ContinuousScheduler, Request

    num_slots = max(default_config().decode_batch_size, 1)
    # 4x the pool: enough churn that the warm middle of the run (where
    # eviction+backfill keep the pool near-full) dominates the drain tail
    # (the tail is a fixed ~cap-length cost, so it amortizes with workload).
    n_requests = 4 * num_slots
    targets = [32, 64, 128, 256, 448]  # prompt token lengths, interleaved
    # Per-request max_tokens: a 10x spread (short lookups to long
    # generations) — see _mixed_workload.
    budgets = [16, 32, 48, 64, 96, 160]
    tok = engine.tokenizer
    workload = _mixed_workload(engine, prompts, n_requests, targets, budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    pad_id = tok.pad_id

    def run_static():
        lat, useful, t0 = [], 0, time.perf_counter()
        for s in range(0, n_requests, num_slots):
            chunk = workload[s : s + num_slots]
            cap = max(b for _, b in chunk)
            out = engine.generate([p for p, _ in chunk], greedy(cap), seed=1)
            jax.block_until_ready(out.tokens)
            done_at = time.perf_counter() - t0
            for row, (_, b) in zip(np.asarray(out.tokens), chunk):
                useful += int(np.sum(row[:b] != pad_id))
                lat.append(done_at)
        return time.perf_counter() - t0, useful, lat

    sched = ContinuousScheduler(
        engine,
        ServingConfig(
            enabled=True, num_slots=num_slots, max_prompt_len=512,
            max_new_tokens=max(budgets), decode_chunk=8,
        ),
        settings=greedy(max(budgets)),
    )

    def run_continuous():
        # Fresh Request objects each run (retry counters are per-object);
        # the SCHEDULER persists, so the warmup run leaves every prefill
        # bucket + the step program compiled.
        reqs = [
            Request(prompt=p, id=f"bench_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        # Same counting rule as the static side (non-pad tokens): the
        # result array holds emitted tokens incl. any stopping EOS, which
        # for a pad==eos tokenizer the static count excludes — apply the
        # identical filter so neither side gets a free token per request.
        useful = sum(
            int(np.sum(np.asarray(r.tokens) != pad_id))
            for r in results if r.ok
        )
        # TTFT per request from the scheduler's lifecycle spans
        # (telemetry/tracing.py): first-token materialization relative to
        # submission, chunk-granular — the client-visible number.
        ttfts = [r.ttft_s for r in results if r.ttft_s is not None]
        return wall, useful, [r.latency_s for r in results], ttfts, \
            sched.last_stats

    run_static()  # warmup: compile every static chunk shape
    run_continuous()  # warmup: compile prefill buckets + the step program
    # Best-of-2 per mode (the headline's min-of-reps idiom): single-run
    # walls on a co-tenanted CPU harness swing enough to flip the ratio.
    st_wall, st_tok, st_lat = min(
        (run_static() for _ in range(2)), key=lambda r: r[0]
    )
    ct_wall, ct_tok, ct_lat, ct_ttft, ct_stats = min(
        (run_continuous() for _ in range(2)), key=lambda r: r[0]
    )

    def pcts(lat, prefix=""):
        if not lat:
            return {}
        return {
            f"{prefix}p50_s": round(float(np.percentile(lat, 50)), 3),
            f"{prefix}p95_s": round(float(np.percentile(lat, 95)), 3),
        }

    st_rate, ct_rate = st_tok / st_wall, ct_tok / ct_wall
    return {
        "num_requests": n_requests,
        "num_slots": num_slots,
        "prompt_token_lengths": targets,
        "budgets_max_tokens": budgets,
        "static": {
            "wall_s": round(st_wall, 3), "useful_tokens": st_tok,
            "tokens_per_sec": round(st_rate, 1), **pcts(st_lat),
        },
        "continuous": {
            "wall_s": round(ct_wall, 3), "useful_tokens": ct_tok,
            "tokens_per_sec": round(ct_rate, 1), **pcts(ct_lat),
            # per-request TTFT (lifecycle spans) next to the e2e latency the
            # static side can't decompose — chunk-granular, see
            # telemetry/tracing.py
            **pcts(ct_ttft, prefix="ttft_"),
            "serving_stats": ct_stats.as_dict() if ct_stats else None,
        },
        "speedup_tokens_per_sec": round(ct_rate / st_rate, 3),
    }


def measure_resilience_overhead(engine, prompts, settings_cls) -> dict | None:
    """Fault-free continuous serving with the resilience layer off vs on.

    The watchdog arms/observes around every compiled prefill/decode chunk
    and the breakers record a success per chunk — pure host-side integer
    arithmetic plus a couple of ``time.monotonic`` calls, so the ISSUE-4
    target is overhead WITHIN the CPU harness's run-to-run noise (±30-60%
    single-run wall jitter; best-of-N per mode in one process is the
    comparison that holds still, per docs/PERFORMANCE.md methodology).

    Same mixed-length workload shape as ``measure_continuous`` (the
    realistic regime: constant admission churn = maximum watchdog/breaker
    call frequency per decoded token)."""
    from fairness_llm_tpu.config import (
        ResilienceConfig,
        ServingConfig,
        default_config,
    )
    from fairness_llm_tpu.serving import ContinuousScheduler, Request

    num_slots = max(default_config().decode_batch_size, 1)
    n_requests = 2 * num_slots
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    scfg = ServingConfig(
        enabled=True, num_slots=num_slots, max_prompt_len=512,
        max_new_tokens=max(budgets), decode_chunk=8,
    )
    # Generous watchdog budget: the guard measures the fault-free
    # bookkeeping cost, not hang classification (a CPU-harness chunk can
    # legitimately take seconds under co-tenancy).
    res = ResilienceConfig(enabled=True, max_step_seconds=300.0,
                           breaker_threshold=3)

    def run(sched, tag):
        reqs = [
            Request(prompt=p, id=f"res_{tag}_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results)
        toks = sum(len(r.tokens) for r in results)
        return wall, toks

    out = {}
    for tag, resilience in (("off", None), ("on", res)):
        sched = ContinuousScheduler(
            engine, scfg, settings=greedy(max(budgets)), resilience=resilience
        )
        run(sched, tag)  # warmup: compile prefill buckets + step program
        wall, toks = min((run(sched, tag) for _ in range(3)),
                         key=lambda r: r[0])
        out[tag] = {
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(toks / wall, 1),
        }
    out["overhead_ratio"] = round(
        out["on"]["wall_s"] / out["off"]["wall_s"], 3
    )
    return out


def measure_integrity_overhead(engine, prompts, settings_cls) -> dict | None:
    """Fault-free continuous serving with the numerics guards off vs on.

    The guard is one ``isfinite`` + AND-reduction over the step's logits
    folded INTO the compiled program (integrity/numerics.py) — device-side
    work this time, unlike the resilience guard's host-side bookkeeping, so
    the A/B compiles two distinct step programs and measures whether the
    reduction is visible over the decode loop's weight/KV streaming. The
    ISSUE-5 target is the same as ISSUE-4's: within the CPU harness's
    run-to-run noise (best-of-N per mode in one process).

    Same mixed-length workload shape as ``measure_continuous`` (constant
    admission churn = maximum prefill+decode program launches per token,
    i.e. maximum guard evaluations per token)."""
    from fairness_llm_tpu.config import ServingConfig, default_config
    from fairness_llm_tpu.serving import ContinuousScheduler, Request

    num_slots = max(default_config().decode_batch_size, 1)
    n_requests = 2 * num_slots
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    scfg = ServingConfig(
        enabled=True, num_slots=num_slots, max_prompt_len=512,
        max_new_tokens=max(budgets), decode_chunk=8,
    )

    def run(sched, tag):
        reqs = [
            Request(prompt=p, id=f"integ_{tag}_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results)
        return wall, sum(len(r.tokens) for r in results), results

    prev_guard = engine.numerics_guards
    out = {}
    tokens = {}
    try:
        for tag, guard in (("off", False), ("on", True)):
            engine.numerics_guards = guard
            sched = ContinuousScheduler(engine, scfg,
                                        settings=greedy(max(budgets)))
            run(sched, tag)  # warmup: compile prefill buckets + step program
            (wall, toks, results) = min(
                (run(sched, tag) for _ in range(3)), key=lambda r: r[0]
            )
            tokens[tag] = [tuple(int(t) for t in r.tokens) for r in results]
            out[tag] = {
                "wall_s": round(wall, 3),
                "tokens_per_sec": round(toks / wall, 1),
            }
    finally:
        engine.numerics_guards = prev_guard
    # The guard must never change the tokens — parity is part of the guard's
    # contract, so the bench asserts it on the workload it just decoded.
    assert tokens["on"] == tokens["off"], "numerics guard changed output"
    out["overhead_ratio"] = round(
        out["on"]["wall_s"] / out["off"]["wall_s"], 3
    )
    return out


def measure_profiling_overhead(engine, prompts, settings_cls) -> dict | None:
    """Fault-free continuous serving with the performance-attribution layer
    off vs on (ISSUE 7).

    The attribution layer is host-side bookkeeping per compiled call: one
    timeline span + step-gap histogram observe per decode chunk, a compile
    cache-lookup counter per program fetch, three roofline gauge writes, and
    one SLO window evaluation per terminal request. ``set_attribution``
    flips ALL of it, so the A/B isolates exactly the layer's cost. Target:
    overhead within the CPU harness's run-to-run noise (±30-60% single-run
    jitter; best-of-N per mode in one process, per docs/PERFORMANCE.md
    methodology), with token parity asserted.

    The "on" mode also reports what the layer measured: ``step_gap_s``
    p50/p95 (the per-chunk host sync ROADMAP item 3 attacks) next to
    tokens/sec, and the live ``achieved_over_achievable`` fraction.
    """
    from fairness_llm_tpu.config import ServingConfig, default_config
    from fairness_llm_tpu.serving import ContinuousScheduler, Request
    from fairness_llm_tpu.telemetry import (
        set_attribution,
        use_registry,
        use_timeline,
    )

    num_slots = max(default_config().decode_batch_size, 1)
    n_requests = 2 * num_slots
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    scfg = ServingConfig(
        enabled=True, num_slots=num_slots, max_prompt_len=512,
        max_new_tokens=max(budgets), decode_chunk=8,
    )

    def run(sched, tag):
        reqs = [
            Request(prompt=p, id=f"prof_{tag}_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results)
        toks = [tuple(int(t) for t in r.tokens) for r in results]
        return wall, toks

    out = {}
    tokens = {}
    prev = set_attribution(True)
    try:
        for tag, on in (("off", False), ("on", True)):
            # Fresh registry + timeline per mode: the "on" step-gap/roofline
            # numbers come from exactly this workload, and the "off" mode
            # proves the layer records nothing.
            with use_registry() as reg, use_timeline() as tl:
                set_attribution(on)
                sched = ContinuousScheduler(engine, scfg,
                                            settings=greedy(max(budgets)))
                run(sched, tag)  # warmup: compile prefill buckets + step
                wall, toks = min((run(sched, tag) for _ in range(3)),
                                 key=lambda r: r[0])
                tokens[tag] = toks
                total = sum(len(t) for t in toks)
                out[tag] = {
                    "wall_s": round(wall, 3),
                    "tokens_per_sec": round(total / wall, 1),
                }
                if on:
                    gap = reg.histogram("step_gap_s", component="serving")
                    out[tag].update({
                        "step_gap_p50_s": gap.percentile(50),
                        "step_gap_p95_s": gap.percentile(95),
                        "step_gap_count": gap.count,
                        "achieved_over_achievable": _published_fraction(
                            reg, "serve_step"),
                        "timeline_events": len(tl.events()),
                    })
                else:
                    # The off mode must have recorded NOTHING.
                    assert not tl.events(), "attribution off still recorded"
                    assert reg.peek("step_gap_s", component="serving") is None
    finally:
        set_attribution(prev)
    assert tokens["on"] == tokens["off"], "attribution layer changed output"
    out["overhead_ratio"] = round(
        out["on"]["wall_s"] / out["off"]["wall_s"], 3
    )
    return out


def measure_fused_decode(engine, prompts, settings_cls) -> dict | None:
    """Fused multi-step decode dispatch sweep (ISSUE 14): ``fuse_steps``
    k in {1, 2, 4, 8} over the same mixed workload, one process.

    The fused dispatch folds k decode chunks into ONE compiled call
    (runtime/stepbuilder.py), so the host work between dispatches — the
    eviction sweep, queue polls, telemetry, and the blocking device_get —
    amortizes ~1/k per generated token. ``step_gap_s`` (ISSUE 7) measures
    exactly that gap, so this entry reports, per k: tokens/sec (best-of-3,
    the ±30-60% jitter discipline), the step-gap p50/p95, the HOST GAP PER
    TOKEN (step-gap seconds summed over the timed reps / tokens they
    generated — the acceptance metric: k=4 must cut it >= 2x vs k=1), and
    the live ``achieved_over_achievable`` fraction. Token parity across
    every k is asserted — fusion moves dispatch boundaries, never tokens.
    """
    from fairness_llm_tpu.config import ServingConfig, default_config
    from fairness_llm_tpu.serving import ContinuousScheduler, Request
    from fairness_llm_tpu.telemetry import (
        set_attribution,
        use_registry,
        use_timeline,
    )

    num_slots = max(default_config().decode_batch_size, 1)
    # ONE admission wave (n_requests == num_slots): a backfill prefill
    # between two chunks lands inside step_gap_s (PR 7 semantics: ALL host
    # time between dispatches), and that prefill work is the same absolute
    # seconds at every k — it would dilute the 1/k dispatch-sync signal
    # this entry exists to measure toward 1x. The churn/backfill surface
    # is covered by the parity tests and the continuous entry; PR 12's
    # decomposition attributes prefill to its own program either way.
    n_requests = num_slots
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    def run(sched, tag):
        reqs = [
            Request(prompt=p, id=f"fused_{tag}_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results)
        toks = [tuple(int(t) for t in r.tokens) for r in results]
        return wall, toks

    out: dict = {}
    tokens = {}
    prev = set_attribution(True)
    try:
        for k in (1, 2, 4, 8):
            with use_timeline():
                scfg = ServingConfig(
                    enabled=True, num_slots=num_slots,
                    max_prompt_len=512, max_new_tokens=max(budgets),
                    decode_chunk=8, fuse_steps=k,
                )
                sched = ContinuousScheduler(
                    engine, scfg, settings=greedy(max(budgets)))
                with use_registry():
                    # Warmup in a THROWAWAY registry: the compile-era step
                    # gaps (step_gap_s keeps PR-7 all-host-time semantics,
                    # so first-call XLA walls land as gap samples) must not
                    # pollute the percentiles/counts reported below. Every
                    # instrument writer resolves get_registry() at write
                    # time, so the swap is safe mid-scheduler-lifetime.
                    run(sched, f"w{k}")
                with use_registry() as reg:
                    gap = reg.histogram("step_gap_s", component="serving")
                    rep_tokens = 0
                    best = None
                    for rep in range(3):
                        wall, toks = run(sched, f"r{k}_{rep}")
                        rep_tokens += sum(len(t) for t in toks)
                        if best is None or wall < best[0]:
                            best = (wall, toks)
                    wall, toks = best
                    tokens[k] = toks
                    total = sum(len(t) for t in toks)
                    prog = "serve_step" if k == 1 else "serve_step_fused"
                    out[f"k{k}"] = {
                        "wall_s": round(wall, 3),
                        "tokens_per_sec": round(total / wall, 1),
                        # Accumulated over the 3 timed reps (dividing sums
                        # beats best-of-1 for a per-token average).
                        "host_gap_per_token_s": round(
                            gap.sum / max(rep_tokens, 1), 8),
                        "step_gap_p50_s": gap.percentile(50),
                        "step_gap_p95_s": gap.percentile(95),
                        "dispatch_gaps": gap.count,
                        "achieved_over_achievable": _published_fraction(
                            reg, prog),
                    }
    finally:
        set_attribution(prev)
    for k in (2, 4, 8):
        assert tokens[k] == tokens[1], \
            f"fused decode k={k} changed the token stream"
    out["useful_tokens"] = sum(len(t) for t in tokens[1])
    out["gap_per_token_reduction_k4"] = round(
        out["k1"]["host_gap_per_token_s"]
        / max(out["k4"]["host_gap_per_token_s"], 1e-12), 2
    )
    out["speedup_k4_tokens_per_sec"] = round(
        out["k4"]["tokens_per_sec"] / out["k1"]["tokens_per_sec"], 3
    )
    return out


def _published_fraction(reg, program: str) -> float | None:
    """The live ``achieved_over_achievable`` gauge of ``program``, or None
    where it was not published (a device without published peaks)."""
    v = reg.read_value("achieved_over_achievable", None,
                       component="roofline", program=program)
    return None if v is None else round(v, 4)


def measure_serve_tp() -> dict | None:
    """Real-mesh tensor-parallel serving (the stepbuilder's mesh axis):
    tp=2 continuous serving — fuse 1 AND 4 — with
    the collectives EXECUTED, not modeled, on a real 2-device mesh
    (``--xla_force_host_platform_device_count``; the worker pins itself
    to the CPU platform and its record says so). The worker
    (tools/serve_tp_bench.py) asserts token-for-token parity against the
    single-device engine and that the compiled step HLO contains
    all-reduce before reporting any number — a silent fall-back to
    replication fails the entry rather than flattering it.

    CPU-harness entry only: a parent that holds a chip neither shares it
    with a child nor files the child's CPU numbers under its own device's
    record, so off the CPU the entry is left out (the same path on real
    chips is ``chip_smoke.py --tp N``).

    Subprocess by necessity: the forced host device count binds at jax
    init, which already happened in this process."""
    if jax.default_backend() != "cpu":
        print("serve_tp left out: CPU-mesh harness entry, parent runs on "
              f"{jax.default_backend()}", file=sys.stderr)
        return None
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2 " + \
        env.get("XLA_FLAGS", "").replace(
            "--xla_force_host_platform_device_count", "--ignored")
    env["JAX_PLATFORMS"] = "cpu"
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "serve_tp_bench.py")
    proc = subprocess.run(
        [sys.executable, worker, "--tp", "2"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"serve_tp worker failed (rc={proc.returncode}): "
            f"{proc.stderr.strip()[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def measure_incident_overhead(engine, prompts, settings_cls) -> dict | None:
    """Fault-free continuous serving with the incident layer — flight
    recorder + decision audit trail — off vs on (ISSUE 13).

    The layer is host-side bookkeeping per decision point: one bounded
    deque append + one counter per decision, a ring append per lifecycle
    edge / decode chunk / roofline sample, and a value-deduped transition
    ring entry per gauge change. ``set_recording`` flips ALL of it (the
    attribution layer stays ON in both modes), so the A/B isolates
    exactly this layer's cost. No incident manager is armed — a fault-free
    workload must never dump a bundle, and triggers are free no-ops while
    disarmed. Target: overhead within the CPU harness's run-to-run noise
    (±30-60% single-run jitter; best-of-3 per mode in one process, per
    docs/PERFORMANCE.md methodology), with token parity asserted.
    """
    from fairness_llm_tpu.config import ServingConfig, default_config
    from fairness_llm_tpu.serving import ContinuousScheduler, Request
    from fairness_llm_tpu.telemetry import (
        set_recording,
        use_flight_recorder,
        use_incident_manager,
        use_registry,
        use_timeline,
    )

    num_slots = max(default_config().decode_batch_size, 1)
    n_requests = 2 * num_slots
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    scfg = ServingConfig(
        enabled=True, num_slots=num_slots, max_prompt_len=512,
        max_new_tokens=max(budgets), decode_chunk=8,
    )

    def run(sched, tag):
        reqs = [
            Request(prompt=p, id=f"inc_{tag}_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results)
        toks = [tuple(int(t) for t in r.tokens) for r in results]
        return wall, toks

    out = {}
    tokens = {}
    prev = set_recording(True)
    try:
        for tag, on in (("off", False), ("on", True)):
            # Fresh registry/timeline/recorder/manager per mode: the "on"
            # ring depths come from exactly this workload, and the "off"
            # mode proves the layer records nothing.
            with use_registry() as reg, use_timeline(), \
                    use_flight_recorder() as rec, use_incident_manager():
                set_recording(on)
                sched = ContinuousScheduler(engine, scfg,
                                            settings=greedy(max(budgets)))
                run(sched, tag)  # warmup: compile prefill buckets + step
                wall, toks = min((run(sched, tag) for _ in range(3)),
                                 key=lambda r: r[0])
                tokens[tag] = toks
                total = sum(len(t) for t in toks)
                out[tag] = {
                    "wall_s": round(wall, 3),
                    "tokens_per_sec": round(total / wall, 1),
                }
                if on:
                    out[tag].update({
                        "ring_depths": {k: len(v)
                                        for k, v in rec.rings.items()},
                        "decisions_total": int(sum(
                            m.value for m in reg.instruments()
                            if getattr(m, "name", "") == "decisions_total"
                        )),
                    })
                else:
                    # The off mode must have recorded NOTHING. Counter
                    # absence is checked over instruments() (peek needs
                    # the exact label set incl. decision=..., so a
                    # component-only peek would pass vacuously).
                    assert all(not v for v in rec.rings.values()), \
                        "recording off still filled a ring"
                    assert not any(
                        getattr(m, "name", "") == "decisions_total"
                        for m in reg.instruments()
                    ), "recording off still counted decisions"
    finally:
        set_recording(prev)
    assert tokens["on"] == tokens["off"], "incident layer changed output"
    out["overhead_ratio"] = round(
        out["on"]["wall_s"] / out["off"]["wall_s"], 3
    )
    return out


def measure_memory_overhead(engine, prompts, settings_cls) -> dict | None:
    """Fault-free continuous serving with the HBM memory ledger — per-pool
    accounting + the AOT program-memory capture — off vs on (ISSUE 18).

    The ledger's steady-state cost is host-side: a pytree-nbytes walk per
    allocation/rebuild site (a handful per scheduler LIFETIME, not per
    step) and a gauge write per register/release; the AOT capture pays its
    second XLA compile during warmup only (once per program, flagged
    done). ``set_memory_obs`` flips both, so the A/B isolates exactly this
    layer. Target: overhead within the CPU harness's run-to-run noise
    (best-of-3 per mode, per docs/PERFORMANCE.md methodology), token
    parity asserted, ZERO reconciliation alerts in the on mode — a clean
    workload whose ledger disagrees with the device is an accounting bug,
    not noise.
    """
    from fairness_llm_tpu.config import ServingConfig, default_config
    from fairness_llm_tpu.serving import ContinuousScheduler, Request
    from fairness_llm_tpu.telemetry import (
        set_aot_memory_capture,
        set_memory_obs,
        use_memory_ledger,
        use_registry,
        use_timeline,
    )

    num_slots = max(default_config().decode_batch_size, 1)
    n_requests = 2 * num_slots
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    scfg = ServingConfig(
        enabled=True, num_slots=num_slots, max_prompt_len=512,
        max_new_tokens=max(budgets), decode_chunk=8,
    )

    def run(sched, tag):
        reqs = [
            Request(prompt=p, id=f"mem_{tag}_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results)
        toks = [tuple(int(t) for t in r.tokens) for r in results]
        return wall, toks

    out = {}
    tokens = {}
    prev_aot = set_aot_memory_capture(False)
    try:
        for tag, on in (("off", False), ("on", True)):
            # Fresh registry/timeline/ledger per mode: the "on" pool bytes
            # come from exactly this scheduler, and the "off" mode proves
            # the layer publishes nothing.
            with use_registry() as reg, use_timeline(), \
                    use_memory_ledger() as mem:
                set_memory_obs(on)
                sched = ContinuousScheduler(engine, scfg,
                                            settings=greedy(max(budgets)))
                run(sched, tag)  # warmup: compiles + the AOT capture
                wall, toks = min((run(sched, tag) for _ in range(3)),
                                 key=lambda r: r[0])
                tokens[tag] = toks
                total = sum(len(t) for t in toks)
                out[tag] = {
                    "wall_s": round(wall, 3),
                    "tokens_per_sec": round(total / wall, 1),
                }
                if on:
                    alerts = sum(
                        m.value for m in reg.instruments()
                        if getattr(m, "name", "")
                        == "hbm_reconciliation_alerts_total"
                    )
                    assert alerts == 0, \
                        "memory ledger reconciliation alerted on a clean A/B"
                    assert any(
                        getattr(m, "name", "") == "program_memory_bytes"
                        for m in reg.instruments()
                    ), "AOT memory capture published nothing"
                    out[tag].update({
                        "ledger_bytes": int(mem.total_bytes()),
                        "kv_bytes": int(mem.pool_bytes("kv_contiguous")),
                        "reconciliation_alerts": int(alerts),
                    })
                else:
                    assert not any(
                        getattr(m, "name", "") in ("hbm_bytes",
                                                   "program_memory_bytes")
                        for m in reg.instruments()
                    ), "memory obs off still published gauges"
                    assert mem.total_bytes() == 0, \
                        "memory obs off still accounted bytes"
    finally:
        set_aot_memory_capture(prev_aot)
    assert tokens["on"] == tokens["off"], "memory ledger changed output"
    out["overhead_ratio"] = round(
        out["on"]["wall_s"] / out["off"]["wall_s"], 3
    )
    return out


def measure_rollout_overhead(engine, prompts, settings_cls) -> dict | None:
    """Armed-idle rollout controller vs none attached (PR 20).

    The version axis's steady-state cost when NO wave is in flight is
    pure hot-path bookkeeping: per-submit version stamping (the
    pinned-affinity map), the router's version filter short-circuit, and
    the fleet tick's ``rollout.active`` probe. A/B: the same mixed
    workload through identical 2-replica fleets, one bare, one with a
    :class:`RolloutController` constructed but never started. Target:
    within the CPU harness's run-to-run noise (best-of-3 per mode, per
    docs/PERFORMANCE.md methodology), token parity asserted, and the
    armed mode must record ZERO rollout transitions — armed means armed,
    not creeping.
    """
    from fairness_llm_tpu.config import (
        FleetConfig,
        ResilienceConfig,
        RolloutConfig,
        ServingConfig,
        default_config,
    )
    from fairness_llm_tpu.serving import ReplicaSet, Request, RolloutController
    from fairness_llm_tpu.telemetry import use_registry, use_timeline

    num_slots = max(default_config().decode_batch_size, 2)
    per_replica = max(num_slots // 2, 1)
    n_requests = 2 * num_slots
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    scfg = ServingConfig(
        enabled=True, num_slots=per_replica, max_prompt_len=512,
        max_new_tokens=max(budgets), decode_chunk=8,
    )
    res = ResilienceConfig(enabled=True, breaker_threshold=3,
                           breaker_cooldown_s=0.05)

    def run(fleet, tag):
        reqs = [
            Request(prompt=p, id=f"ro_{tag}_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = fleet.serve(reqs)
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results)
        toks = [tuple(int(t) for t in r.tokens) for r in results]
        return wall, toks

    out = {"num_requests": n_requests, "replicas": 2,
           "slots_per_replica": per_replica}
    tokens = {}
    for tag, armed in (("off", False), ("on", True)):
        # Fresh registry/timeline per mode so the zero-transition check
        # reads exactly this fleet's instruments.
        with use_registry() as reg, use_timeline():
            fleet = ReplicaSet(engine, scfg, settings=greedy(max(budgets)),
                               fleet=FleetConfig(replicas=2),
                               resilience=res)
            ro = None
            if armed:
                ro = RolloutController(
                    fleet, "v1", engine=engine,
                    config=RolloutConfig(enabled=True),
                )  # constructed, never started: armed-idle
            run(fleet, tag)  # warmup: compile prefill buckets + steps
            wall, toks = min((run(fleet, tag) for _ in range(3)),
                             key=lambda r: r[0])
            tokens[tag] = toks
            total = sum(len(t) for t in toks)
            out[tag] = {
                "wall_s": round(wall, 3),
                "tokens_per_sec": round(total / wall, 1),
            }
            if armed:
                assert ro.state == "idle", "armed-idle controller moved"
                transitions = sum(
                    m.value for m in reg.instruments()
                    if getattr(m, "name", "") == "rollout_transitions_total"
                )
                assert transitions == 0, \
                    "armed-idle rollout recorded transitions"
    assert tokens["on"] == tokens["off"], "armed rollout changed output"
    out["overhead_ratio"] = round(
        out["on"]["wall_s"] / out["off"]["wall_s"], 3
    )
    return out


def measure_fleet(engine, prompts, settings_cls) -> dict | None:
    """2-replica fleet router vs a single scheduler, plus failover timing.

    Two measurements (ISSUE 6):

    - **Fault-free overhead**: the same mixed-length workload through one
      ``ContinuousScheduler`` with N slots vs a 2-replica ``ReplicaSet``
      with N/2 slots each — same TOTAL concurrency, so the delta is the
      router itself (health scoring, per-replica bookkeeping, the
      interleaved step loop). Target: within the CPU harness's run-to-run
      noise (±30-60% single-run jitter; best-of-3 per mode in one
      process, per docs/PERFORMANCE.md methodology). Token parity between
      the two modes is asserted on the workload just decoded.
    - **Failover recovery**: re-run with a scripted ``replica_crash`` on
      r1 mid-sweep and report fence -> first migrated token
      (``ReplicaSet.last_failover_s``), migrated count, and that zero
      requests were lost.
    """
    import numpy as np

    from fairness_llm_tpu.config import (
        FleetConfig,
        IntegrityConfig,
        ResilienceConfig,
        ServingConfig,
        default_config,
    )
    from fairness_llm_tpu.serving import ContinuousScheduler, ReplicaSet, Request
    from fairness_llm_tpu.utils.failures import ScriptedFaultInjector

    num_slots = max(default_config().decode_batch_size, 2)
    per_replica = num_slots // 2
    n_requests = 2 * num_slots
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    def scfg(slots):
        return ServingConfig(
            enabled=True, num_slots=slots, max_prompt_len=512,
            max_new_tokens=max(budgets), decode_chunk=8,
        )

    res = ResilienceConfig(enabled=True, breaker_threshold=3,
                           breaker_cooldown_s=0.05)

    def run(server, tag):
        reqs = [
            Request(prompt=p, id=f"fleet_{tag}_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = server.serve(reqs)
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results)
        toks = [tuple(int(t) for t in r.tokens) for r in results]
        return wall, toks

    out = {"num_requests": n_requests, "total_slots": num_slots,
           "replicas": 2, "slots_per_replica": per_replica}
    tokens = {}
    single = ContinuousScheduler(engine, scfg(num_slots),
                                 settings=greedy(max(budgets)))
    fleet = ReplicaSet(engine, scfg(per_replica), settings=greedy(max(budgets)),
                       fleet=FleetConfig(replicas=2), resilience=res)
    for tag, server in (("single", single), ("fleet", fleet)):
        run(server, tag)  # warmup: compile prefill buckets + step programs
        wall, toks = min((run(server, tag) for _ in range(3)),
                         key=lambda r: r[0])
        tokens[tag] = toks
        total = sum(len(t) for t in toks)
        out[tag] = {
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(total / wall, 1),
        }
    # The router must never change the tokens — fleet greedy parity is the
    # zero-loss contract's other half, asserted on what was just decoded.
    assert tokens["fleet"] == tokens["single"], "fleet routing changed output"
    out["router_overhead_ratio"] = round(
        out["fleet"]["wall_s"] / out["single"]["wall_s"], 3
    )

    # Failover: crash r1 a few health polls in, measure recovery.
    inj = ScriptedFaultInjector(replica_crashes={"r1": 4})
    crash_fleet = ReplicaSet(
        engine, scfg(per_replica), settings=greedy(max(budgets)),
        fleet=FleetConfig(replicas=2, fence_cooldown_s=0.1),
        resilience=res, fault_injector=inj,
        integrity=IntegrityConfig(canary_max_tokens=8),
    )
    reqs = [Request(prompt=p, id=f"failover_{i:04d}", settings=greedy(b))
            for i, (p, b) in enumerate(workload)]
    t0 = time.perf_counter()
    results = crash_fleet.serve(reqs)
    wall = time.perf_counter() - t0
    rejoined = crash_fleet.await_recovery(timeout_s=60.0)
    from fairness_llm_tpu.telemetry import get_registry

    out["failover"] = {
        "wall_s": round(wall, 3),
        "crash_fired": inj.replica_faults_fired == [("r1", "replica_crash")],
        "zero_lost": all(r.ok for r in results),
        "migrated_requests": int(get_registry().read_value(
            "fleet_migrated_requests_total", component="fleet")),
        "recovery_s_fence_to_first_migrated_token": (
            round(crash_fleet.last_failover_s, 4)
            if crash_fleet.last_failover_s is not None else None
        ),
        "crashed_replica_rejoined": rejoined,
    }
    assert out["failover"]["zero_lost"], "failover lost requests"
    return out


def measure_overload_overhead(engine, prompts, settings_cls) -> dict | None:
    """Fault-free, under-capacity continuous serving with overload control
    off vs on (ISSUE 8).

    With the controller armed but nothing overloaded, the added cost is
    host-side only: a per-class dequeue decision per admission, one depth
    sample + a throttled ladder evaluation per loop iteration, and a
    feasibility estimate per deadline-carrying request (none here) — the
    target is overhead within the CPU harness's run-to-run noise
    (best-of-N per mode in one process, docs/PERFORMANCE.md methodology),
    with token parity asserted across MIXED QoS classes: under capacity,
    class scheduling must not reorder anything observably.

    SLO targets are set harness-appropriate for the entry (compile-time
    TTFT outliers on the first chunk would otherwise legitimately burn the
    fast window and trigger a brownout mid-measurement — the controller
    doing its job, but not what an overhead A/B should measure)."""
    from fairness_llm_tpu.config import (
        OverloadConfig,
        ServingConfig,
        default_config,
    )
    from fairness_llm_tpu.serving import ContinuousScheduler, Request
    from fairness_llm_tpu.telemetry.slo import SLOTargets, set_slo_targets

    num_slots = max(default_config().decode_batch_size, 1)
    n_requests = 2 * num_slots  # under capacity: no queue pressure signal
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    scfg = ServingConfig(
        enabled=True, num_slots=num_slots, max_prompt_len=512,
        max_new_tokens=max(budgets), decode_chunk=8,
    )
    ov = OverloadConfig(enabled=True)

    def run(sched, tag):
        reqs = [
            Request(prompt=p, id=f"ov_{tag}_{i:04d}", settings=greedy(b),
                    qos="interactive" if i % 2 == 0 else "batch")
            for i, (p, b) in enumerate(workload)
        ]
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results), [
            (r.id, r.finish_reason) for r in results if not r.ok
        ]
        toks = [tuple(int(t) for t in r.tokens) for r in results]
        return wall, toks

    out = {}
    tokens = {}
    prev = set_slo_targets(SLOTargets(ttft_p95_s=300.0, e2e_p99_s=600.0))
    try:
        for tag, overload in (("off", None), ("on", ov)):
            sched = ContinuousScheduler(
                engine, scfg, settings=greedy(max(budgets)),
                overload=overload,
            )
            run(sched, tag)  # warmup: compile prefill buckets + step
            wall, toks = min((run(sched, tag) for _ in range(3)),
                             key=lambda r: r[0])
            tokens[tag] = toks
            total = sum(len(t) for t in toks)
            out[tag] = {
                "wall_s": round(wall, 3),
                "tokens_per_sec": round(total / wall, 1),
            }
            if overload is not None:
                assert sched.shed_controller.level == 0, (
                    "controller escalated on fault-free under-capacity "
                    "traffic"
                )
                assert sched.last_stats.shed == 0, "shed under capacity"
    finally:
        set_slo_targets(prev)
    # Class scheduling must be output-invariant under capacity: every
    # request decodes the same tokens whichever sub-queue it rode.
    assert tokens["on"] == tokens["off"], "overload control changed output"
    out["overhead_ratio"] = round(
        out["on"]["wall_s"] / out["off"]["wall_s"], 3
    )
    return out


def measure_fairness_overhead(engine, prompts, settings_cls) -> dict | None:
    """Fault-free continuous serving with fairness observability off vs on
    (ISSUE 9).

    The on mode is the full armed-and-fed path: every request tagged
    (group/attribute/pair_id), the profile grid + pair set registered with
    the monitor, the content feed folding each result into the streaming
    group accumulators, the pair watch joining every pair, and the derived
    DP/IF/exposure gauges refreshed — all inside the timed window, exactly
    the per-chunk cost a tagged study pays. The added work is host-side
    (dict folds per result, one small jit DP kernel per refresh), so the
    target is overhead within the CPU harness's run-to-run noise
    (best-of-N per mode in one process, docs/PERFORMANCE.md methodology),
    token parity asserted: observation must not change what is served."""
    from fairness_llm_tpu.config import ServingConfig, default_config
    from fairness_llm_tpu.serving import ContinuousScheduler, Request
    from fairness_llm_tpu.telemetry.fairness import (
        FairnessMonitor,
        set_fairness_monitor,
    )

    num_slots = max(default_config().decode_batch_size, 1)
    n_requests = 2 * num_slots
    budgets = [16, 32, 48, 64]
    workload = _mixed_workload(engine, prompts, n_requests,
                               targets=[32, 64, 128, 256], budgets=budgets)

    def greedy(m):
        return _greedy(settings_cls, m)

    scfg = ServingConfig(
        enabled=True, num_slots=num_slots, max_prompt_len=512,
        max_new_tokens=max(budgets), decode_chunk=8,
    )
    groups = ("g0", "g1")

    def run(sched, tag, mon):
        tagged = mon is not None
        reqs = []
        for i, (p, b) in enumerate(workload):
            rid = f"fair_{tag}_{i:04d}"
            reqs.append(Request(
                prompt=p, id=rid, settings=greedy(b),
                group=groups[i % 2] if tagged else None,
                attribute="bench" if tagged else None,
                pair_id=f"fair_{tag}_pp{i // 2:04d}" if tagged else None,
            ))
        if tagged:
            mon.begin_study()
            for r in reqs:
                mon.register_request(r.id, {"bench": r.group})
            for i in range(0, len(reqs) - 1, 2):
                mon.register_pair(f"fair_{tag}_pp{i // 2:04d}",
                                  reqs[i].id, reqs[i + 1].id, "bench")
        t0 = time.perf_counter()
        results = sched.serve(reqs)
        if tagged:
            # The content feed + gauge refresh belong inside the window:
            # a tagged study pays them per chunk.
            for r in results:
                mon.observe_output(r.id, r.text.split())
            mon.refresh()
        wall = time.perf_counter() - t0
        assert all(r.ok for r in results), [
            (r.id, r.finish_reason) for r in results if not r.ok
        ]
        toks = [tuple(int(t) for t in r.tokens) for r in results]
        return wall, toks

    out = {}
    tokens = {}
    for tag, mon in (("off", None), ("on", FairnessMonitor())):
        prev = set_fairness_monitor(mon) if mon is not None else None
        try:
            sched = ContinuousScheduler(engine, scfg,
                                        settings=greedy(max(budgets)))
            run(sched, tag, mon)  # warmup: compile + first DP kernel
            wall, toks = min((run(sched, tag, mon) for _ in range(3)),
                             key=lambda r: r[0])
            tokens[tag] = toks
            total = sum(len(t) for t in toks)
            out[tag] = {
                "wall_s": round(wall, 3),
                "tokens_per_sec": round(total / wall, 1),
            }
            if mon is not None:
                assert mon.pairs_joined == len(workload) // 2, (
                    mon.pairs_joined, len(workload))
                assert mon.pairs_divergent == 0, "divergence on fault-free"
                out[tag]["pairs_joined"] = mon.pairs_joined
        finally:
            if prev is not None:
                set_fairness_monitor(prev)
    # Observation must be output-invariant: every request decodes the same
    # tokens whether or not the fairness layer watched it.
    assert tokens["on"] == tokens["off"], "fairness observation changed output"
    out["overhead_ratio"] = round(
        out["on"]["wall_s"] / out["off"]["wall_s"], 3
    )
    return out


def measure_achievable_gbps() -> float | None:
    """This chip's ACHIEVABLE streaming bandwidth, measured in-run.

    The spec roofline is an upper bound, not what a streaming loop sustains.
    This puts a probe IN the bench (VERDICT r2 item 5) so a record can say
    whether decode is at the wall without re-deriving the experiment: a
    fori_loop whose carry feeds each iteration's
    element-wise read (acc-dependent ``minimum`` — loop-invariant code motion
    cannot hoist the re-read), timed with a value-forcing sync.
    """
    if jax.default_backend() != "tpu":
        # ~174 GB of host-memory traffic for a number that means nothing off
        # the chip; the headline then falls back to the spec-roofline fraction.
        return None
    import jax.numpy as jnp
    from jax import lax

    n = 85_000_000  # f32 -> 340 MB, far over any cache tier
    reps = 512  # ~174 GB of traffic (~0.2 s at spec), so dispatch+sync
    # latency is a small CONSERVATIVE bias. No latency subtraction — an
    # over-corrected subtraction once reported above-spec bandwidth, and an
    # under-estimate can't overstate how close decode is to the wall.
    x = jax.device_put(jnp.ones((n,), jnp.float32))

    @jax.jit
    def probe(x, start):
        def body(_, acc):
            return acc + jnp.sum(jnp.minimum(x, acc))

        return lax.fori_loop(0, reps, body, start)

    try:
        float(probe(x, jnp.float32(1e30)))  # compile + warm
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            float(probe(x, jnp.float32(1e30)))  # value-forced sync
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        return reps * x.nbytes / best / 1e9
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"bandwidth probe skipped: {type(e).__name__}: {e}", file=sys.stderr)
        return None


def flash_memory_proof() -> dict | None:
    """Compile-time proof of flash attention's decisive claim: at ~150 ranked
    items (S≈7k) the DENSE prefill's [B, H, S, S] score tensors (~9.2 GB
    each) overflow one v5e chip's HBM — the TPU compiler itself REJECTS the
    program at compile time ("Ran out of memory in memory space hbm", ~18.4 G
    needed of 15.75 G) — while flash streams k/v blocks through VMEM and
    compiles comfortably (VERDICT r2 item 6).
    Nothing is executed, so the dense side can't actually OOM the bench.
    TPU-only (flash is a Pallas kernel)."""
    if jax.default_backend() != "tpu":
        return None
    import dataclasses

    import flax.linen as nn
    import jax.numpy as jnp

    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.models.transformer import Transformer

    import re

    B, S = 4, 7168  # ~150 byte-tokenized ML-1M items per listwise prompt
    cfg = get_model_config("gpt2-small")
    out = {"batch": B, "seq": S}
    try:
        for label, flash in (("dense", False), ("flash", True)):
            c = dataclasses.replace(cfg, max_seq_len=8192, use_flash_attention=flash)
            model = Transformer(c)
            abstract = jax.eval_shape(
                model.init, jax.random.key(0),
                jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.int32),
            )
            aparams = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                nn.meta.unbox(abstract["params"]),
            )

            def fwd(params, tokens, positions, valid):
                # left_padded=True: the engine's layout promise, and the
                # static gate for the Pallas flash path (models/transformer.py
                # _flash_ok) — without it both sides compile dense.
                logits, _ = model.apply(
                    {"params": params}, tokens, positions, valid,
                    last_only=True, left_padded=True,
                )
                return logits

            arg = lambda dt: jax.ShapeDtypeStruct((B, S), dt)  # noqa: E731
            try:
                compiled = (
                    jax.jit(fwd)
                    .lower(aparams, arg(jnp.int32), arg(jnp.int32), arg(jnp.bool_))
                    .compile()
                )
            except Exception as e:  # noqa: BLE001 — compile-OOM is the signal
                msg = str(e)
                if "Ran out of memory" not in msg or "hbm" not in msg:
                    raise
                m = re.search(r"Used ([0-9.]+)G of ([0-9.]+)G hbm", msg)
                out[label] = {
                    "compiles": False,
                    "compile_oom": True,
                    "hbm_needed_gb": float(m.group(1)) if m else None,
                    "hbm_capacity_gb": float(m.group(2)) if m else None,
                }
                continue
            ma = compiled.memory_analysis()
            out[label] = {
                "compiles": True,
                "temp_gb": round(ma.temp_size_in_bytes / 1e9, 2),
                "total_gb": round(
                    (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                     + ma.output_size_in_bytes) / 1e9, 2),
            }
            del compiled
        # The claim holds when dense is compiler-rejected (or needs more than
        # the chip) while flash compiles and fits.
        dense, flash_r = out.get("dense", {}), out.get("flash", {})
        out["proven"] = bool(
            (not dense.get("compiles", True)
             or dense.get("total_gb", 0) > 15.75)
            and flash_r.get("compiles")
            and flash_r.get("total_gb", 1e9) < 15.75
        )
        return out
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"flash memory proof skipped: {type(e).__name__}: {e}", file=sys.stderr)
        out["error"] = f"{type(e).__name__}: {e}"
        return out


def int8_70b_fit() -> dict | None:
    """The round-4 capability record: llama3-70b int8 (dequant-in-tile
    weights, ops/quant_matmul.py) fits tp=8 on one v5e-8 slice.

    Two parts: (a) the committed full-model AOT memory analysis — 9.29
    GB/chip vs 15.75 (compiling all 80 layers takes ~4.5 min, so it is not
    re-run per bench; regenerate via ``python tools/prove_70b_int8_fit.py``);
    (b) an IN-RUN lowering check: a 2-layer same-dimensions variant compiled
    by the real v5e TPU compiler against a ``v5e:2x4`` topology descriptor,
    proving every kernel/shard_map/collective the artifact relies on still
    lowers today. TPU-compiler environments only.
    """
    root = os.path.dirname(os.path.abspath(__file__))
    out: dict = {}
    try:
        with open(os.path.join(root, "results", "proofs", "int8_70b_fit.json")) as f:
            out["full_model_committed"] = json.load(f)
    except Exception:  # noqa: BLE001 — artifact optional
        out["full_model_committed"] = None
    try:
        live = _load_tool("prove_70b_int8_fit").prove(num_layers=2)
        out["live_2layer_check"] = {
            "lowering_ok": True,
            "compile_s": live["compile_s"],
            "args_gb_per_chip": live["args_gb_per_chip"],
            "temps_gb_per_chip": live["temps_gb_per_chip"],
        }
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"70B 2-layer lowering check skipped: {type(e).__name__}", file=sys.stderr)
        out["live_2layer_check"] = {
            "lowering_ok": False, "error": f"{type(e).__name__}: {e}"
        }
    return out


def _load_tool(name: str):
    """Import a measurement tool module from tools/ by file path."""
    import importlib.util

    root = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def llama70b_shard_live() -> dict | None:
    """Recurring per-round 70B decode rate (VERDICT r4 item 4): the tp=8
    per-chip shard of llama3-70b-int8 decoded LIVE on this chip —
    tools/measure_70b_shard.py's measurement folded into the bench so a
    regression in the dequant-in-tile path's in-model rate (the round-4
    number: 569 GB/s, at the chip's own bandwidth wall) surfaces in the
    BENCH_r* record automatically instead of going stale in a one-off
    proof. ~2-3 min: 8.9 GB engine init + two decode-length compiles.
    TPU-only.

    This is the collectives-OMITTED emulation: one chip decodes its tp=8
    shard with no neighbors, so the number is an upper bound on the
    per-chip rate. The ``serve_tp`` entry is its cross-check — the same
    serving path over a REAL tp mesh with the all-reduces executed
    (asserted in the compiled HLO) and token parity pinned; when a real
    TPU pod is available, extend serve_tp rather than widening this
    emulation."""
    if jax.default_backend() != "tpu":
        return None
    return _load_tool("measure_70b_shard").run(batch=8, new_tokens=32)


def llama3_8b_live(achievable_gbps) -> dict | None:
    """BASELINE configs[1] — Llama-3-8B — served WHOLE on this chip
    (VERDICT r4 item 1, the first end-to-end >=7B full-model number):
    tools/serve_8b_live.py's phase-1 sweep + phase-2 listwise, int8
    dequant-in-tile weights (~8.6 GB of 15.75). The tool's own probe is
    skipped; the ratio uses THIS run's achievable-bandwidth probe so every
    operating point in the record is measured against the same wall."""
    if jax.default_backend() != "tpu":
        return None
    res = _load_tool("serve_8b_live").run(include_probe=False)
    ph1 = res.get("phase1_sweep")
    if ph1 and achievable_gbps:
        ph1["achievable_hbm_gbps_probe"] = round(achievable_gbps, 1)
        ph1["achieved_over_achievable"] = round(
            ph1["achieved_hbm_gbps"] / achievable_gbps, 3
        )
    return res


def phase2_7b_committed() -> dict | None:
    """Per-model summary of the committed 7B cross-model phase-2 record
    (tools/run_7b_cross_model.py -> results/phase2/phase2_7b_results.json):
    the BASELINE configs[2] set — mistral/qwen2/gemma at 7B, int8 weights —
    evaluated live on the chip. Embedded here (the int8_70b_fit pattern) so
    every BENCH_r* record carries the per-model numbers; the full eval
    (~25 min of engine inits + compiles) is a tool run, not a per-bench
    cost — regenerate with the tool when the serving path changes."""
    root = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(root, "results", "phase2", "phase2_7b_results.json")
    try:
        with open(path) as f:
            rec = json.load(f)
        return {
            "committed": True,
            "metadata": {
                k: rec["metadata"].get(k)
                for k in ("models", "num_items", "num_queries",
                          "num_comparisons", "elapsed_seconds", "device")
            },
            "per_model_perf": rec.get("per_model_perf"),
            "model_fairness": rec.get("comparison", {}).get("model_fairness"),
        }
    except Exception as e:  # noqa: BLE001 — artifact optional, but say so
        print(
            f"phase2_7b committed record unavailable ({type(e).__name__}: {e}); "
            f"regenerate with tools/run_7b_cross_model.py -> {path}",
            file=sys.stderr,
        )
        return None


def measure_capacity(engine, prompts, settings_cls) -> dict | None:
    """Capacity planning: the SAME seeded trace replayed against fixed
    fleets of 1 -> 3 replicas (ISSUE 11).

    One deterministic synthetic trace (diurnal curve + one burst +
    heavy-tailed sessions + mixed QoS, ``serving/replay.py``) is replayed
    time-compressed at each fleet size, best-of-3 per size in one process
    (CPU-harness ±30-60% single-run jitter, docs/PERFORMANCE.md
    methodology). Reported per size: profiles/sec and profiles/sec/CHIP
    (each replica models one chip's slot pool), interactive TTFT
    attainment against a fixed target, and the shed rate — the table an
    operator reads to pick a fleet size for an offered load. Token parity
    across fleet sizes is asserted on the completed intersection (routing
    and fleet size must never change the tokens)."""
    import dataclasses

    from fairness_llm_tpu.config import (
        FleetConfig,
        IntegrityConfig,
        OverloadConfig,
        ResilienceConfig,
        ServingConfig,
    )
    from fairness_llm_tpu.serving import (
        ReplayDriver,
        ReplicaSet,
        TraceConfig,
        generate_trace,
    )
    from fairness_llm_tpu.telemetry.slo import SLOTargets, set_slo_targets

    compression = 4.0
    ttft_target_s = 2.0
    tcfg = TraceConfig(
        seed=17, duration_s=24.0, base_sessions_per_s=0.8,
        diurnal_amplitude=0.5, diurnal_period_s=24.0,
        bursts=((8.0, 6.0, 5.0),), session_tail_alpha=1.3,
        session_max_turns=3, think_time_s=2.0, interactive_frac=0.75,
        max_tokens_choices=(8, 12, 16),
    )
    catalog = tuple(prompts[:8])
    events = generate_trace(tcfg, catalog)
    budget = max(tcfg.max_tokens_choices)

    def greedy(m):
        return _greedy(settings_cls, m)

    scfg = ServingConfig(
        enabled=True, num_slots=4, queue_capacity=32, max_prompt_len=512,
        max_new_tokens=budget, decode_chunk=8,
    )
    prev_targets = set_slo_targets(SLOTargets(
        ttft_p95_s=ttft_target_s, e2e_p99_s=60.0, fast_window_s=2.0,
    ))
    out = {
        "trace_events": len(events),
        "interactive_events": sum(e.qos == "interactive" for e in events),
        "trace_span_s": round(events[-1].t, 2) if events else 0.0,
        "compression": compression,
        "ttft_target_s": ttft_target_s,
        "capacity": {},
    }
    tokens_by_n = {}
    try:
        for n in (1, 2, 3):
            fleet = ReplicaSet(
                engine, scfg, settings=greedy(budget),
                fleet=FleetConfig(replicas=n, fence_cooldown_s=0.1),
                resilience=ResilienceConfig(enabled=True,
                                            breaker_cooldown_s=0.05),
                integrity=IntegrityConfig(canary_max_tokens=8),
                overload=OverloadConfig(
                    enabled=True, deadline_admission=False,
                    aging_s=5.0 / compression, healthy_window_s=0.5,
                    queue_window_s=1.0, eval_interval_s=0.1,
                    burn_threshold=8.0, retry_after_s=0.2,
                ),
            )
            # Warmup compiles the per-replica programs, then best-of-3.
            # The zero-loss invariant must hold on EVERY run — a discarded
            # slower run (or the warmup) losing requests is still a bug.
            runs = [ReplayDriver(fleet, events, compression=compression,
                                 max_wall_s=300.0).run()
                    for _ in range(4)]
            for k, r in enumerate(runs):
                assert r.lost == 0, \
                    f"replay lost requests at n={n} (run {k})"
            report = min(runs[1:], key=lambda r: r.wall_s)
            completed = report.outcomes.get("completed", 0)
            attain = report.slo_attainment(ttft_target_s)
            out["capacity"][str(n)] = {
                "replicas": n,
                "wall_s": round(report.wall_s, 3),
                "profiles_per_sec": round(completed / report.wall_s, 2),
                "profiles_per_sec_per_chip": round(
                    completed / report.wall_s / n, 2),
                "completed": completed,
                "shed_rate": round(report.shed_rate(), 4),
                "slo_attainment_ttft": (round(attain, 4)
                                        if attain is not None else None),
            }
            tokens_by_n[n] = dict(report.tokens)
    finally:
        set_slo_targets(prev_targets)
    # Fleet size must never change a completed request's tokens.
    common = set(tokens_by_n[1]) & set(tokens_by_n[2]) & set(tokens_by_n[3])
    assert common, "no common completed requests across fleet sizes"
    for rid in common:
        assert tokens_by_n[1][rid] == tokens_by_n[2][rid] == \
            tokens_by_n[3][rid], f"fleet size changed tokens for {rid}"
    out["parity_checked_requests"] = len(common)
    return out


def build_sweep_prompts():
    from fairness_llm_tpu.config import default_config
    from fairness_llm_tpu.data import (
        create_base_preferences,
        create_profile_grid,
        load_movielens,
    )
    from fairness_llm_tpu.pipeline.prompts import recommendation_prompt

    config = default_config()
    data = load_movielens(config.data_dir, seed=config.random_seed)
    prefs = create_base_preferences(data, seed=config.random_seed)
    profiles = create_profile_grid(prefs, config)
    return [recommendation_prompt(p) for p in profiles]


def build_listwise_prompts(num_items: int = 60, num_queries: int = 4):
    """Phase-2 at scale: long listwise ranking prompts (hundreds of items),
    several queries decoded as one batch — the prefill-heavy counterpart to
    the decode-heavy phase-1 sweep. Returns (prompts, items, queries) so the
    scored measurement reuses the SAME corpus and query set (the
    vs_listwise_decode ratio depends on that identity)."""
    from fairness_llm_tpu.config import default_config
    from fairness_llm_tpu.data import load_movielens, movielens_ranking_corpus
    from fairness_llm_tpu.pipeline.phase2 import make_queries
    from fairness_llm_tpu.pipeline.prompts import listwise_prompt

    config = default_config()
    data = load_movielens(config.data_dir, seed=config.random_seed)
    items = movielens_ranking_corpus(data, num_items, seed=config.random_seed, min_ratings=1)
    queries = make_queries(items, num_queries)
    return [listwise_prompt(items, query=q) for q in queries], items, queries


def measure_phase2_listwise(config, settings_cls) -> dict | None:
    """Queries/sec for the long-prompt listwise batch, flash vs dense prefill.

    The phase-1 sweep is decode-bound (prefill is an amortized sliver), so the
    flash prefill kernel doesn't move that number; THIS workload is where it
    runs in a headline path. gpt2-small's 1024 learned positions can't hold a
    60-item byte-tokenized prompt (~2.5k tokens); the bench widens the table
    (random weights — FLOPs and memory traffic are representative either way).
    Corpus size is capped so the DENSE comparison's [B, H, S, S] score tensor
    stays well under chip HBM; flash itself scales much further.
    """
    import dataclasses

    from fairness_llm_tpu.runtime.engine import DecodeEngine

    prompts, items, queries = build_listwise_prompts()
    num_items = len(items)
    long_cfg = dataclasses.replace(config, max_seq_len=4096, kv_cache_quant=False)
    settings = settings_cls(temperature=0.7, top_k=0, top_p=1.0, max_tokens=32)

    out = {}
    # Dense first so the flash engine survives the loop: the scored
    # measurement below reuses it rather than compiling a third engine
    # (which pushed the whole bench past its time budget).
    eng = None
    for label, flash in (("dense", False), ("flash", True)):
        if eng is not None:
            del eng
        eng = DecodeEngine(
            dataclasses.replace(long_cfg, use_flash_attention=flash), seed=0
        )
        # share_prefix=False: the listwise prompts share an auto-detectable
        # ~64-token prefix, and the shared-prefix prefill takes the joint
        # dense path — WITH sharing enabled the "flash" engine never runs
        # the flash kernel at all (discovered round 4: both columns were
        # measuring the same dense program).
        res = eng.generate(prompts, settings, seed=0, share_prefix=False)  # warmup
        t0 = time.perf_counter()
        res = eng.generate(prompts, settings, seed=1, share_prefix=False)
        jax.block_until_ready(res.tokens)
        wall = time.perf_counter() - t0
        out[label] = {
            "wall_s": round(wall, 3),
            "queries_per_sec": round(len(prompts) / wall, 3),
            "decode_shape": res.stats,
        }
    out["num_items"] = num_items
    out["num_queries"] = len(prompts)
    out["flash_speedup"] = round(out["dense"]["wall_s"] / out["flash"]["wall_s"], 3)

    # Likelihood-scored ranking over the SAME corpus and queries: all
    # (query, item) pairs score as chunked teacher-forced forwards (no
    # autoregressive decode, no parsing).
    from fairness_llm_tpu.pipeline.backends import EngineBackend
    from fairness_llm_tpu.pipeline.phase2 import scored_evaluation

    backend = EngineBackend(eng, name="bench")
    scored_evaluation(backend, items, queries)  # warmup/compile
    t0 = time.perf_counter()
    scored_evaluation(backend, items, queries)
    wall = time.perf_counter() - t0
    out["scored"] = {
        "wall_s": round(wall, 3),
        "queries_per_sec": round(len(queries) / wall, 3),
        # same query count as the listwise measurement -> direct wall ratio
        "vs_listwise_decode": round(out["flash"]["wall_s"] / max(wall, 1e-9), 2),
    }

    # Pairwise at scale: 200 comparisons over the same ML-1M corpus decoded
    # as ONE batch — the reference's pairwise hot loop
    # (phase2_cross_model_eval.py:165-210, 30 sequential API calls) at 6.7x
    # its comparison budget, the last reference hot loop without an at-scale
    # live number (VERDICT r4 weak item 3). Short decode cap: the answer is
    # one letter; 16 tokens is the reference-compatible envelope.
    try:
        from fairness_llm_tpu.pipeline.phase2 import pairwise_evaluation

        pw_settings = settings_cls(
            temperature=0.7, top_k=0, top_p=1.0, max_tokens=16
        )
        # Warm with the SAME seed as the timed run: the seed picks the item
        # pairs, so a different seed could sample longer prompts that cross
        # a bucket boundary and put a fresh compile inside the timed window.
        pairwise_evaluation(backend, items, 200, pw_settings, seed=1)  # compile
        t0 = time.perf_counter()
        _, comps = pairwise_evaluation(backend, items, 200, pw_settings, seed=1)
        wall = time.perf_counter() - t0
        unparsed = sum(1 for c in comps if not c["parsed"])
        out["pairwise_200"] = {
            "num_comparisons": len(comps),
            "wall_s": round(wall, 3),
            "comparisons_per_sec": round(len(comps) / wall, 2),
            # random weights parse poorly; the rate is the honest field the
            # study reports either way (parse_failures in phase2 results)
            "parse_failure_rate": round(unparsed / max(len(comps), 1), 3),
        }
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"pairwise-200 skipped: {type(e).__name__}: {e}", file=sys.stderr)
    del eng

    # 150-item listwise (S≈7k): the corpus size DENSE attention provably
    # cannot serve at all on this chip (flash_memory_proof: compile-OOM at
    # 18.4 GB of score temps) — so this runs flash-ONLY, live, turning the
    # compile-time capability claim into a measured number. TPU-only: the
    # Pallas path is the enabler being measured.
    if jax.default_backend() == "tpu" and config.head_dim % 64 == 0:
        try:
            big_prompts, big_items, big_queries = build_listwise_prompts(150, 4)
            cfg7k = dataclasses.replace(
                config, max_seq_len=8192, use_flash_attention=True,
                kv_cache_quant=False,
            )
            eng7k = DecodeEngine(cfg7k, seed=0)
            try:
                # share_prefix=False: flash-only by necessity — the shared-
                # prefix joint path is dense, which compile-OOMs at this S
                res = eng7k.generate(
                    big_prompts, settings, seed=0, share_prefix=False
                )  # compile
                t0 = time.perf_counter()
                res = eng7k.generate(big_prompts, settings, seed=1, share_prefix=False)
                jax.block_until_ready(res.tokens)
                wall = time.perf_counter() - t0
                out["listwise_150_flash_only"] = {
                    "num_items": len(big_items),
                    "num_queries": len(big_prompts),
                    "wall_s": round(wall, 3),
                    "queries_per_sec": round(len(big_prompts) / wall, 3),
                    "decode_shape": res.stats,
                    "dense_alternative": "compile-OOM (see flash_memory_proof)",
                }
            finally:
                del eng7k
        except Exception as e:  # noqa: BLE001 — auxiliary measurement only
            print(
                f"150-item listwise skipped: {type(e).__name__}: {e}",
                file=sys.stderr,
            )
    return out


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--entries", default=os.environ.get("BENCH_ENTRIES"),
                    help="comma-separated subset of auxiliary entries to "
                         f"run (default: all). Choices: {', '.join(_ALL_ENTRIES)}")
    ap.add_argument("--baseline-out",
                    default=os.environ.get("BENCH_BASELINE_OUT"),
                    help="also write the machine-readable perf-sentinel "
                         "baseline (entries + harness fingerprint) here")
    args = ap.parse_args()
    if args.entries:
        set_entries([e.strip() for e in args.entries.split(",") if e.strip()])
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    _run(baseline_out=args.baseline_out)


def _run(baseline_out: "str | None" = None) -> None:
    from fairness_llm_tpu.config import ModelSettings
    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    model_name = os.environ.get("BENCH_MODEL", "gpt2-small")
    config = get_model_config(model_name)
    if os.environ.get("BENCH_KV_QUANT") == "1":
        # int8 KV cache: the capacity lever that fits 3B-class models' caches
        # on one chip (see models/configs.py kv_cache_quant).
        import dataclasses

        config = dataclasses.replace(config, kv_cache_quant=True)
        model_name += "+int8kv"
    prompts = build_sweep_prompts()
    settings = ModelSettings(temperature=0.7, top_k=0, top_p=1.0, max_tokens=MAX_NEW_TOKENS)

    devices = jax.devices()
    engine = DecodeEngine(config, seed=0)

    # Warmup: compile prefill+decode for the sweep's bucketed shape.
    engine.generate(prompts, settings, seed=0)

    # Timed runs.
    times = []
    token_checksum = None
    for rep in range(3):
        t0 = time.perf_counter()
        out = engine.generate(prompts, settings, seed=rep + 1)
        jax.block_until_ready(out.tokens)
        times.append(time.perf_counter() - t0)
        if rep == 0:
            # Token-parity witness for the perf sentinel: the seed-1 sweep
            # is deterministic on one harness fingerprint, so a checksum
            # drift is a correctness regression (compared EXACTLY), unlike
            # the walls (compared within noise bands).
            import hashlib

            token_checksum = hashlib.sha256(
                out.tokens.tobytes()
            ).hexdigest()[:16]

    # Fused decode-attention kernel A/B on the same sweep (measured slower —
    # kept in the record so the regression/improvement trend is visible per
    # round; see ROADMAP.md D4 and ops/decode_attention.py).
    kernel_rate = None
    try:
        from fairness_llm_tpu.ops.decode_attention import decode_attn_supported

        # Only measure when the kernel would actually ENGAGE at this sweep's
        # shapes (same gate as the model) — otherwise the flag-on engine runs
        # the identical XLA path and the record would mislabel a baseline
        # rate as the kernel's.
        eligible = (
            not config.use_decode_attention_kernel
            and jax.default_backend() == "tpu"
            and jax.device_count() == 1
            and config.sliding_window is None
            and not config.kv_cache_quant
            and decode_attn_supported(
                out.stats["batch"], out.stats["cache_slots"],
                config.head_dim, out.stats["prefix_len"],
            )
        )
        if eligible:
            import dataclasses

            ek = DecodeEngine(
                dataclasses.replace(config, use_decode_attention_kernel=True),
                seed=0,
            )
            try:
                ek.generate(prompts, settings, seed=0)
                t0 = time.perf_counter()
                outk = ek.generate(prompts, settings, seed=1)
                jax.block_until_ready(outk.tokens)
                kernel_rate = len(prompts) / (time.perf_counter() - t0)
            finally:
                # release the duplicate weights even if generate() throws —
                # the large-sweep measurement below is already OOM-prone
                del ek
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"decode-kernel A/B skipped: {type(e).__name__}", file=sys.stderr)

    # Speculative decoding A/B on the same sweep, greedy (ISSUE 1): off vs on
    # tokens/sec plus measured acceptance. Runs while the headline engine is
    # alive (it reuses the params; only two more compiled programs).
    speculative = None
    try:
        if _enabled("speculative"):
            speculative = measure_speculative(engine, prompts, ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"speculative A/B skipped: {type(e).__name__}: {e}", file=sys.stderr)

    # Continuous-batching serving A/B (ISSUE 2): static chunking vs the
    # serving/ scheduler on a mixed-length workload, same engine/params.
    continuous = None
    try:
        if _enabled("continuous"):
            continuous = measure_continuous(engine, prompts, ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"continuous serving A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Resilience overhead guard (ISSUE 4): fault-free continuous serving
    # with the watchdog+breakers off vs on — the on/off wall ratio must
    # stay within harness noise (docs/PERFORMANCE.md).
    resilience = None
    try:
        if _enabled("resilience"):
            resilience = measure_resilience_overhead(engine, prompts,
                                                     ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"resilience overhead A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Integrity overhead guard (ISSUE 5): fault-free continuous serving
    # with the on-device numerics guards off vs on — the in-program finite
    # reduction must stay within harness noise, and the tokens identical.
    integrity = None
    try:
        if _enabled("integrity"):
            integrity = measure_integrity_overhead(engine, prompts,
                                                   ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"integrity overhead A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Attribution-layer overhead guard (ISSUE 7): fault-free continuous
    # serving with the timeline/compile-stats/roofline/SLO layer off vs on
    # — the on/off wall ratio must stay within harness noise, tokens
    # identical; the on mode reports step_gap_s p50/p95 next to tokens/sec.
    profiling = None
    try:
        if _enabled("profiling"):
            profiling = measure_profiling_overhead(engine, prompts,
                                                   ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"profiling overhead A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Fused multi-step dispatch sweep (ISSUE 14): fuse_steps k in
    # {1,2,4,8} on the same mixed workload — host gap per token must fall
    # ~1/k at exact token parity; reports step_gap_s p50/p95 and
    # achieved_over_achievable per k.
    fused_decode = None
    try:
        if _enabled("fused_decode"):
            fused_decode = measure_fused_decode(engine, prompts,
                                                ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"fused decode sweep skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Real-mesh tp=2 serving (subprocess; parity + executed collectives
    # asserted inside the worker). Cross-checks the llama70b_shard entry's
    # collectives-OMITTED per-chip emulation with a measurement where the
    # collectives are on the wire.
    serve_tp = None
    try:
        if _enabled("serve_tp"):
            serve_tp = measure_serve_tp()
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"serve_tp skipped: {type(e).__name__}: {e}", file=sys.stderr)

    # Incident-layer overhead guard (ISSUE 13): fault-free continuous
    # serving with the flight recorder + decision audit trail off vs on —
    # within harness noise, token parity asserted, zero bundles (no
    # manager armed).
    incidents = None
    try:
        if _enabled("incidents"):
            incidents = measure_incident_overhead(engine, prompts,
                                                  ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"incident overhead A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Memory-ledger overhead guard (ISSUE 18): fault-free continuous
    # serving with the HBM pool accounting + AOT program-memory capture
    # off vs on — within harness noise, token parity asserted, zero
    # reconciliation alerts.
    memory = None
    try:
        if _enabled("memory"):
            memory = measure_memory_overhead(engine, prompts,
                                             ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"memory overhead A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Armed-idle rollout overhead guard (PR 20): 2-replica fleet with a
    # constructed-but-idle RolloutController vs none — within harness
    # noise, token parity asserted, zero transitions recorded.
    rollout = None
    try:
        if _enabled("rollout"):
            rollout = measure_rollout_overhead(engine, prompts,
                                               ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"rollout overhead A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Replica-fleet A/B (ISSUE 6): 2-replica health-routed fleet vs a
    # single scheduler at the same total slot count (router overhead must
    # stay within harness noise), plus failover recovery time under an
    # injected replica crash (fence -> first migrated token).
    fleet = None
    try:
        if _enabled("fleet"):
            fleet = measure_fleet(engine, prompts, ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"fleet A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Overload-control overhead guard (ISSUE 8): fault-free, under-capacity
    # mixed-class serving with the QoS queue + shed controller off vs on —
    # within harness noise, token parity across classes, zero sheds, and
    # the controller pinned at level 0 throughout.
    overload = None
    try:
        if _enabled("overload"):
            overload = measure_overload_overhead(engine, prompts,
                                                 ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"overload overhead A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Fairness-observability overhead guard (ISSUE 9): fault-free
    # continuous serving with tagging + streaming accumulators + pair
    # watch off vs on — within harness noise, token parity asserted, every
    # pair joined with zero divergence.
    fairness = None
    try:
        if _enabled("fairness"):
            fairness = measure_fairness_overhead(engine, prompts,
                                                 ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"fairness overhead A/B skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Capacity planning (ISSUE 11): one seeded synthetic trace replayed
    # against 1/2/3-replica fleets — profiles/sec/chip vs interactive SLO
    # attainment vs shed rate, token parity across sizes asserted.
    capacity = None
    try:
        if _enabled("capacity"):
            capacity = measure_capacity(engine, prompts, ModelSettings)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"capacity sweep skipped: {type(e).__name__}: {e}",
              file=sys.stderr)

    # Large-sweep throughput: decode is weight-streaming-bound at small batch,
    # so a thousands-of-profiles ML-1M sweep runs at the batch-192 rate
    # instead. Big models can OOM at this batch on one chip — report null
    # rather than failing the whole benchmark. Each operating point carries
    # its own roofline fields (bytes/step, achieved GB/s) so the efficiency
    # fraction at the BEST point is a measured number, not just the worst
    # (45-profile) one.
    big_rate = None
    big_stats = None
    big_rate_int8 = None
    big8_stats = None
    big_rate_int8_kernel = None
    grouped_rate_int8 = None
    grouped_shapes = None
    big_rate_int8w = None
    big8w_stats = None
    try:
        _require_entry("large_sweep")
        big = list(prompts) * 4
        engine.generate(big, settings, seed=0)
        t0 = time.perf_counter()
        out_big = engine.generate(big, settings, seed=99)
        jax.block_until_ready(out_big.tokens)
        big_rate = len(big) / (time.perf_counter() - t0)
        big_stats = out_big.stats

        # int8 KV at 2x that scale: at large batch the decode is KV-bound,
        # so the quantized cache both fits more rows AND runs faster — the
        # sweet spot measured on v5e is ~360 rows (328 profiles/s, +50% over
        # the f32 batch-180 rate; 720 rows adds only ~5% more).
        import dataclasses

        if not config.kv_cache_quant:
            big8 = list(prompts) * 8
            cfg8 = dataclasses.replace(config, kv_cache_quant=True)
            eng8 = DecodeEngine(cfg8, seed=0)
            eng8.generate(big8, settings, seed=0)
            t0 = time.perf_counter()
            out8 = eng8.generate(big8, settings, seed=99)
            jax.block_until_ready(out8.tokens)
            big_rate_int8 = len(big8) / (time.perf_counter() - t0)
            big8_stats = out8.stats

            # LEVER A (VERDICT r4 weak item 1): remainder-length grouping.
            # The single-bucket batch pads every row's remainder to the
            # longest profile's bucket; decoding short-remainder and
            # long-remainder halves as two programs tightens each group's
            # prompt_len (32-multiple buckets) at the cost of streaming the
            # weight tree twice. Both halves pass the SAME sweep-wide
            # explicit prefix so attention layout matches the baseline.
            try:
                from fairness_llm_tpu.pipeline.backends import (
                    EngineBackend,
                    shared_prefix_ids,
                )

                pref = shared_prefix_ids(EngineBackend(eng8), big8)
                if pref is not None:
                    rows = [eng8.tokenizer.encode(p) for p in big8]
                    order = sorted(range(len(big8)), key=lambda i: len(rows[i]))
                    half = (len(big8) // 2) // 8 * 8
                    gs = [
                        [big8[i] for i in order[:half]],
                        [big8[i] for i in order[half:]],
                    ]
                    for g in gs:  # compile both shapes
                        eng8.generate(g, settings, seed=0, prefix_ids=pref)
                    t0 = time.perf_counter()
                    shapes, outs = [], []
                    for g in gs:
                        og = eng8.generate(g, settings, seed=99, prefix_ids=pref)
                        shapes.append(og.stats)
                        outs.append(og.tokens)
                    # Block on EVERY group's tokens: on a mesh/multi-device
                    # run the first group's work may still be in flight
                    # when the last call returns.
                    jax.block_until_ready(outs)
                    grouped_rate_int8 = len(big8) / (time.perf_counter() - t0)
                    grouped_shapes = shapes
            except Exception as e:  # noqa: BLE001 — auxiliary measurement only
                print(f"grouped-sweep skipped: {type(e).__name__}", file=sys.stderr)
            del eng8

            # LEVER B (same verdict item): int8 WEIGHTS x int8 KV at the
            # 360-row sweet spot — best_sustained has always streamed
            # f32/bf16 weights; the dequant-in-tile tree cuts the per-step
            # param stream (gpt2's tied embedding stays float, so the win
            # is bounded by the non-embed fraction).
            if config.weight_quant == "none":
                # Local try: a failure here (batch-360 is OOM-prone on big
                # models) must not abort the int8-KV kernel A/B below, whose
                # per-round trend predates this lever.
                try:
                    cfg8w = dataclasses.replace(cfg8, weight_quant="int8")
                    eng8w = DecodeEngine(cfg8w, seed=0)
                    try:
                        eng8w.generate(big8, settings, seed=0)
                        t0 = time.perf_counter()
                        out8w = eng8w.generate(big8, settings, seed=99)
                        jax.block_until_ready(out8w.tokens)
                        big_rate_int8w = len(big8) / (time.perf_counter() - t0)
                        big8w_stats = out8w.stats
                    finally:
                        del eng8w
                except Exception as e:  # noqa: BLE001 — auxiliary measurement
                    print(
                        f"int8w-sweep skipped: {type(e).__name__}", file=sys.stderr
                    )

            # Fused int8-KV decode-attention kernel (dequant-in-tile,
            # ops/decode_attention.py round 4) A/B at the KV-bound operating
            # point — the one kernel target with a byte-reduction story.
            from fairness_llm_tpu.ops.decode_attention import decode_attn_supported

            if (
                jax.default_backend() == "tpu"
                and jax.device_count() == 1
                and config.sliding_window is None
                and decode_attn_supported(
                    big8_stats["batch"], big8_stats["cache_slots"],
                    config.head_dim, big8_stats["prefix_len"], kv_itemsize=1,
                )
            ):
                eng8k = DecodeEngine(
                    dataclasses.replace(cfg8, use_decode_attention_kernel=True),
                    seed=0,
                )
                try:
                    eng8k.generate(big8, settings, seed=0)
                    t0 = time.perf_counter()
                    out8k = eng8k.generate(big8, settings, seed=99)
                    jax.block_until_ready(out8k.tokens)
                    big_rate_int8_kernel = len(big8) / (time.perf_counter() - t0)
                finally:
                    del eng8k
    except _SkippedEntry:
        pass
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"large-sweep measurement skipped: {type(e).__name__}", file=sys.stderr)

    # Roofline accounting: achieved bandwidth over the analytic bytes/step,
    # reported against the chip's measured achievable bandwidth. Random
    # weights never sample EOS, so the early-exit while_loop runs the full
    # MAX_NEW_TOKENS steps and steps-executed == the cap (real models exit
    # early and the bytes model would overcount). Params count at the
    # COMPUTE width (see decode_step_bytes — the loop streams bf16 slices
    # even for f32-stored trees).
    best = min(times)
    profiles_per_sec = len(prompts) / best  # single chip: total == per-chip
    tokens_per_sec = len(prompts) * MAX_NEW_TOKENS / best
    sweep_stats = out.stats
    step_bytes = decode_step_bytes(config, sweep_stats)
    achieved_gbps = step_bytes * MAX_NEW_TOKENS / best / 1e9

    # Free the phase-1 engine (params + compiled big-batch caches) before the
    # long-context engines spin up — at 1B/3B scale keeping it alive OOMs the
    # auxiliary measurement.
    del engine, out
    achievable_gbps = measure_achievable_gbps()
    phase2_listwise = None
    if _enabled("phase2_listwise"):
        try:
            phase2_listwise = measure_phase2_listwise(config, ModelSettings)
        except Exception as e:  # noqa: BLE001 — auxiliary measurement only
            print(f"phase2-listwise failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
    flash_proof = flash_memory_proof() if _enabled("flash_proof") else None
    int8_70b = int8_70b_fit() if _enabled("int8_70b") else None

    # Big-model live sections (each owns most of HBM; they run only after
    # every other engine is freed, serially). Fail-soft: a failed section
    # loses the section, not the round's record.
    shard70b = None
    try:
        if _enabled("shard70b"):
            shard70b = llama70b_shard_live()
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"70B shard live skipped: {type(e).__name__}: {e}", file=sys.stderr)
    live8b = None
    try:
        if _enabled("live8b"):
            live8b = llama3_8b_live(achievable_gbps)
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"8B live skipped: {type(e).__name__}: {e}", file=sys.stderr)

    # Roofline accounting per operating point: the headline (45 profiles,
    # the framework's WORST sustained number) plus each large-sweep point,
    # so "is decode efficient at scale" is answered where it's best.
    import dataclasses as _dc

    def roofline(cfg_for, stats_for, rate, n_profiles):
        if not (stats_for and rate):
            return None
        sb = decode_step_bytes(cfg_for, stats_for)
        gbps = sb * MAX_NEW_TOKENS * rate / n_profiles / 1e9
        return {
            "profiles_per_sec": round(rate, 2),
            "decode_shape": stats_for,
            "decode_bytes_per_step_mb": round(sb / 1e6, 1),
            "achieved_hbm_gbps": round(gbps, 1),
            "achieved_over_achievable": (
                round(gbps / achievable_gbps, 3) if achievable_gbps else None
            ),
        }

    large_sweep = roofline(config, big_stats, big_rate, len(prompts) * 4)
    cfg_int8 = _dc.replace(config, kv_cache_quant=True)
    large_sweep_int8 = roofline(cfg_int8, big8_stats, big_rate_int8, len(prompts) * 8)
    if large_sweep_int8 is not None:
        large_sweep_int8["kernel_profiles_per_sec"] = (
            round(big_rate_int8_kernel, 2) if big_rate_int8_kernel else None
        )
        if big_rate_int8_kernel:
            large_sweep_int8["kernel_speedup"] = round(
                big_rate_int8_kernel / big_rate_int8, 3
            )
        # Lever A record: remainder-length grouping A/B at the same rows.
        if grouped_rate_int8:
            large_sweep_int8["grouped_profiles_per_sec"] = round(
                grouped_rate_int8, 2
            )
            large_sweep_int8["grouped_speedup"] = round(
                grouped_rate_int8 / big_rate_int8, 3
            )
            large_sweep_int8["grouped_shapes"] = grouped_shapes
    # Lever B record: int8 weights UNDER the int8-KV operating point.
    cfg_int8w = _dc.replace(config, kv_cache_quant=True, weight_quant="int8")
    large_sweep_int8w = roofline(
        cfg_int8w, big8w_stats, big_rate_int8w, len(prompts) * 8
    )
    if large_sweep_int8w is not None and big_rate_int8:
        large_sweep_int8w["vs_float_weights"] = round(
            big_rate_int8w / big_rate_int8, 3
        )
    candidates = [
        ("base", roofline(config, sweep_stats, profiles_per_sec, len(prompts))),
        ("large_sweep", large_sweep),
        ("large_sweep_int8kv", large_sweep_int8),
        ("large_sweep_int8w_int8kv", large_sweep_int8w),
    ]
    if big_rate_int8_kernel and big8_stats:
        candidates.append(
            ("large_sweep_int8kv_kernel",
             roofline(cfg_int8, big8_stats, big_rate_int8_kernel, len(prompts) * 8))
        )
    if grouped_rate_int8 and grouped_shapes:
        # The grouped point streams DIFFERENT bytes than the single-program
        # batch (weight tree twice, tighter per-half KV), so its bandwidth
        # fields are computed from the halves' own shapes — best_sustained
        # must never carry roofline numbers for bytes it didn't stream.
        g_bytes = sum(
            decode_step_bytes(cfg_int8, s) * MAX_NEW_TOKENS for s in grouped_shapes
        )
        g_wall = len(prompts) * 8 / grouped_rate_int8
        g_gbps = g_bytes / g_wall / 1e9
        candidates.append(
            ("large_sweep_int8kv_grouped", {
                "profiles_per_sec": round(grouped_rate_int8, 2),
                "decode_shape": grouped_shapes,
                "decode_bytes_per_step_mb": [
                    round(decode_step_bytes(cfg_int8, s) / 1e6, 1)
                    for s in grouped_shapes
                ],
                "achieved_hbm_gbps": round(g_gbps, 1),
                "achieved_over_achievable": (
                    round(g_gbps / achievable_gbps, 3) if achievable_gbps else None
                ),
            })
        )
    best_label, best_point = max(
        (c for c in candidates if c[1]),
        key=lambda c: c[1]["profiles_per_sec"],
        default=(None, None),
    )

    # Headline comparison: achieved decode bandwidth over this chip's MEASURED
    # achievable bandwidth (the honest "are we at the wall" number — VERDICT
    # r2 item 8). The reference-API speedup multiple (a strawman: 45 profiles
    # / ~15 min of HTTPS round-trips) is kept as a detail field.
    achieved_over_achievable = (
        round(achieved_gbps / achievable_gbps, 3) if achievable_gbps else None
    )
    # Spec-roofline fraction: only on a device the peaks table lists — a CPU
    # run is never priced against a chip's HBM.
    from fairness_llm_tpu.telemetry.roofline import known_device_peaks

    peaks = known_device_peaks()
    spec_fraction = achieved_gbps / peaks.hbm_gbps if peaks else None
    result = {
        "metric": f"phase1_sweep_decode_throughput[{model_name},{devices[0].platform}]",
        "value": round(profiles_per_sec, 3),
        "unit": "profiles/sec/chip",
        # vs_baseline changed meaning in round 3 (was: speedup multiple over
        # the reference API sweep; now: bandwidth-utilization fraction).
        # schema_version + the explicitly-named duplicate keys exist so
        # cross-round tooling can't silently compare incompatible numbers.
        "schema_version": 2,
        "vs_baseline_kind": "bandwidth_utilization_fraction",
        "vs_baseline": (
            achieved_over_achievable
            if achieved_over_achievable is not None
            else (round(spec_fraction, 3) if spec_fraction is not None
                  else None)
        ),
        "baseline": (
            "fraction of this chip's measured achievable HBM streaming "
            "bandwidth (1.0 = decode at the wall); API-sweep multiple in "
            "detail.vs_reference_api_sweep"
            if achieved_over_achievable is not None
            else "fraction of the device's published HBM peak (no in-run "
                 "bandwidth probe; None on a device without published "
                 "peaks); API-sweep multiple in detail.vs_reference_api_sweep"
        ),
        "detail": {
            "profiles": len(prompts),
            "max_new_tokens": MAX_NEW_TOKENS,
            "decode_tokens_per_sec": round(tokens_per_sec, 1),
            "token_checksum": token_checksum,
            "best_wall_s": round(best, 3),
            "all_wall_s": [round(t, 3) for t in times],
            "decode_shape": sweep_stats,
            "decode_bytes_per_step_mb": round(step_bytes / 1e6, 1),
            "achieved_hbm_gbps": round(achieved_gbps, 1),
            "achievable_hbm_gbps_probe": (
                round(achievable_gbps, 1) if achievable_gbps else None
            ),
            "achieved_over_achievable": achieved_over_achievable,
            "pct_v5e_hbm_roofline": (
                round(100 * spec_fraction, 1) if spec_fraction is not None
                else None
            ),
            "vs_reference_api_sweep": round(
                profiles_per_sec / REFERENCE_PROFILES_PER_SEC, 1
            ),
            "decode_attention_kernel_profiles_per_sec": (
                round(kernel_rate, 3) if kernel_rate else None
            ),
            "large_sweep_profiles_per_sec": round(big_rate, 3) if big_rate else None,
            "large_sweep_int8kv_profiles_per_sec": (
                round(big_rate_int8, 3) if big_rate_int8 else None
            ),
            "speculative": speculative,
            "continuous": continuous,
            "resilience_overhead": resilience,
            "integrity_overhead": integrity,
            "profiling_overhead": profiling,
            "fused_decode": fused_decode,
            "serve_tp": serve_tp,
            "incident_overhead": incidents,
            "memory_overhead": memory,
            "rollout_overhead": rollout,
            "fleet": fleet,
            "overload_overhead": overload,
            "fairness_overhead": fairness,
            "capacity": capacity,
            "large_sweep": large_sweep,
            "large_sweep_int8kv": large_sweep_int8,
            "large_sweep_int8w_int8kv": large_sweep_int8w,
            "best_sustained": (
                {"operating_point": best_label, **best_point} if best_point else None
            ),
            "phase2_listwise": phase2_listwise,
            "flash_memory_proof": flash_proof,
            "int8_70b_fit": int8_70b,
            "llama70b_shard": shard70b,
            "llama3_8b_live": live8b,
            "phase2_7b": phase2_7b_committed(),
            "reference_api_baseline": (
                "reference README: ~15 min for the 45-profile sweep via API "
                "(what vs_reference_api_sweep is measured against)"
            ),
        },
    }
    print(json.dumps(result))
    if baseline_out:
        path = write_bench_baseline(result, baseline_out, model_name)
        print(f"bench baseline: {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
