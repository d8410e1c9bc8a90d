"""CLI orchestrator — ``python -m fairness_llm_tpu.cli.main``.

Mirrors the reference front-end surface (``main.py:184-214``): ``--all``,
``--phase {1,2,3}``, ``--quick``, model/profile-count flags, setup checks,
sequential phase execution with timing, and a cross-phase final summary —
plus the TPU-native knobs (mesh shape, weights dir, backend choice).

Run examples:
    python -m fairness_llm_tpu.cli.main --all --quick
    python -m fairness_llm_tpu.cli.main --phase 1 --model llama3-8b --mesh dp=8
    python -m fairness_llm_tpu.cli.main --phase 3 --variant smart
    python -m fairness_llm_tpu.cli.main --phase 1 --continuous --telemetry-dir tel/
    python -m fairness_llm_tpu.cli.main telemetry-report tel/
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import sys
from typing import Dict, Optional

from fairness_llm_tpu.config import Config, MeshConfig, create_directories, default_config
from fairness_llm_tpu.pipeline.phase1 import print_phase1_summary, run_phase1
from fairness_llm_tpu.pipeline.phase2 import print_phase2_summary, run_phase2
from fairness_llm_tpu.pipeline.phase3 import print_phase3_summary, run_phase3

logger = logging.getLogger(__name__)

BANNER = r"""
==========================================================
  fairness_llm_tpu — LLM recommendation fairness on TPU
  phase 1: bias detection   phase 2: cross-model ranking
  phase 3: FACTER mitigation
==========================================================
"""


def parse_mesh(spec: Optional[str]) -> MeshConfig:
    """'dp=2,tp=4' -> MeshConfig(dp=2, tp=4)."""
    if not spec:
        return MeshConfig()
    kwargs = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        if k.strip() not in ("dp", "tp", "sp"):
            raise SystemExit(f"unknown mesh axis '{k}' (use dp/tp/sp)")
        try:
            kwargs[k.strip()] = int(v)
        except ValueError:
            raise SystemExit(f"bad mesh spec '{part}' (use e.g. dp=2,tp=4)") from None
    return MeshConfig(**kwargs)


def check_setup(config: Config) -> None:
    """Environment probes (reference ``check_setup``, ``main.py:42-76``):
    warn-and-continue on missing data, report the device fleet."""
    import os

    import jax

    create_directories(config)
    devices = jax.devices()
    print(f"devices: {len(devices)} x {devices[0].platform if devices else 'none'}")
    need = config.mesh.num_devices
    if need > len(devices):
        print(f"WARNING: mesh {config.mesh.shape} wants {need} devices, found {len(devices)}")
    if not os.path.exists(os.path.join(config.data_dir, "movies.dat")):
        print(f"WARNING: MovieLens not found at {config.data_dir}; synthetic fallback will be used")
    if config.weights_dir is None:
        print("NOTE: no --weights-dir; real model names will FAIL (no checkpoint to "
              "load) — use --model simulated for the no-weights deterministic backend")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="fairness_llm_tpu",
        description="Three-phase LLM recommendation-fairness study, TPU-native",
    )
    g = p.add_mutually_exclusive_group(required=True)
    g.add_argument("--all", action="store_true", help="run phases 1 -> 2 -> 3")
    g.add_argument("--phase", type=int, choices=(1, 2, 3), help="run one phase")
    p.add_argument("--quick", action="store_true",
                   help="demo mode: 1 profile/combo, fewer items/comparisons")
    p.add_argument("--model", default=None, help="model for phases 1/3 (or 'simulated')")
    p.add_argument("--models", nargs="+", default=None, help="models for phase 2")
    p.add_argument("--profiles", type=int, default=None, help="profiles per demographic combo")
    p.add_argument("--num-items", type=int, default=20, help="phase-2 ranking corpus size")
    p.add_argument("--corpus", default="synthetic", choices=("synthetic", "movielens"),
                   help="phase-2 corpus: reference-compat synthetic docs, or real "
                        "ML-1M titles with genre-derived groups")
    p.add_argument("--num-queries", type=int, default=1,
                   help="phase-2 listwise queries, decoded as one batch")
    p.add_argument("--num-comparisons", type=int, default=30, help="phase-2 pairwise budget")
    p.add_argument("--variant", default="conformal", choices=("conformal", "smart", "aggressive"),
                   help="phase-3 mitigation variant")
    p.add_argument("--strategy", default="demographic_parity",
                   choices=("demographic_parity", "equal_opportunity", "individual_fairness"))
    p.add_argument("--calibration", default="simulated", choices=("simulated", "model", "model-conditional"),
                   help="phase-3 conformal confidences: reference-style simulated "
                        "curve, the model's own unconditional title likelihoods, or "
                        "likelihoods conditioned on the profile's watch history "
                        "(model-conditional; demographics excluded from the context)")
    p.add_argument("--confidence-mapping", default="percentile",
                   choices=("percentile", "probability"),
                   help="with --calibration model or model-conditional: how "
                        "likelihoods map onto the conformal scale (rank-normalized, "
                        "or temperature-scaled probabilities — see "
                        "pipeline.facter.model_confidences)")
    p.add_argument("--confidence-temperature", type=float, default=1.0,
                   help="temperature for --confidence-mapping probability")
    p.add_argument("--max-new-tokens", type=int, default=None,
                   help="global decode-length cap: clamps every model's "
                        "max_tokens (bounds per-sweep decode cost)")
    p.add_argument("--speculate", action="store_true",
                   help="prompt-lookup speculative decoding for engine "
                        "backends (greedy decode only — sampled settings "
                        "silently use the plain path; output is identical "
                        "either way, see runtime/speculative.py)")
    p.add_argument("--draft-len", type=int, default=None,
                   help="with --speculate: drafted tokens verified per step")
    p.add_argument("--ngram-max", type=int, default=None,
                   help="with --speculate: longest lookup n-gram tried first")
    p.add_argument("--continuous", action="store_true",
                   help="serve engine backends through the continuous-"
                        "batching scheduler (serving/): fixed KV slot pool, "
                        "per-step eviction + backfill from a bounded "
                        "admission queue. Greedy output is token-for-token "
                        "identical to the static engine for prompts within "
                        "the serving budget (longer ones truncate, with a "
                        "warning); see docs/SERVING.md")
    p.add_argument("--slots", type=int, default=None,
                   help="with --continuous: concurrent KV slots "
                        "(= decode-step batch rows)")
    p.add_argument("--fuse-steps", type=int, default=None, metavar="K",
                   help="with --continuous: decode chunks fused into ONE "
                        "compiled dispatch (runtime/stepbuilder.py) — the "
                        "step program runs decode_chunk x K steps before "
                        "returning to the host, amortizing per-dispatch "
                        "host sync ~1/K per token at an identical token "
                        "stream (per-row budgets clamp in-program). Not "
                        "combinable with --speculate (its verify window is "
                        "already multi-token; composition lands with tree "
                        "speculation)")
    p.add_argument("--tp", type=int, default=None, metavar="N",
                   help="with --continuous: tensor-parallel serving over an "
                        "N-device tp mesh — every step program (prefill, "
                        "decode, fused) lowers as ONE SPMD "
                        "computation: params sharded by the parallel/ "
                        "rules, the slot KV cache sharded on "
                        "kv heads, collectives inserted by XLA. N must "
                        "divide the model's attention heads and the device "
                        "count (checked at parse time). --tp 1 is "
                        "byte-identical to no flag. Mutually exclusive "
                        "with --mesh; CPU harness: "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    p.add_argument("--overload", action="store_true",
                   help="with --continuous: arm overload control "
                        "(serving/overload.py) — QoS classes (interactive/"
                        "batch/probe) with per-class bounded sub-queues and "
                        "strict-priority-with-aging dequeue, deadline-"
                        "feasibility admission (provably-doomed requests "
                        "shed with finish_reason=shed + retry-after instead "
                        "of burning a prefill), and an SLO-burn-driven shed "
                        "controller walking a brownout ladder: shed batch "
                        "-> cap batch tokens -> interactive-only. See "
                        "docs/SERVING.md §QoS and overload control")
    p.add_argument("--shed-burn-threshold", type=float, default=None,
                   metavar="B",
                   help="with --overload: fast-window SLO burn rate at "
                        "which the shed controller escalates one brownout "
                        "rung (default 2.0)")
    p.add_argument("--shed-healthy-window", type=float, default=None,
                   metavar="S",
                   help="with --overload: seconds of sustained health "
                        "required per de-escalation rung (hysteresis; "
                        "default 5)")
    p.add_argument("--batch-token-cap", type=int, default=None, metavar="T",
                   help="with --overload: max_new_tokens clamp applied to "
                        "batch-class requests at brownout rung 2+ "
                        "(default 32)")
    p.add_argument("--replicas", type=int, default=None, metavar="N",
                   help="with --continuous: serve through N data-parallel "
                        "engine replicas behind a health-aware router "
                        "(serving/fleet.py) — each replica gets its own KV "
                        "slot pool, breakers, watchdog, and rejoin canary; "
                        "a sick replica is fenced and drained, its requests "
                        "migrate to healthy replicas with original "
                        "ids/settings/row-seeds (greedy parity preserved), "
                        "and it rejoins only after a canary warm-up probe. "
                        "See docs/SERVING.md §Replica fleet")
    p.add_argument("--fence-level", type=int, default=None,
                   help="with --replicas: degradation-ladder level at which "
                        "the router fences a replica (default 2 = "
                        "reduced_footprint; 0 disables ladder-driven "
                        "fencing — crash/hang/stall still fence)")
    p.add_argument("--fence-cooldown", type=float, default=None,
                   help="with --replicas: seconds a fenced replica waits "
                        "before its first canary rejoin probe (default 1; "
                        "probes additionally defer until the replica's "
                        "open breakers can half-open, so the effective "
                        "delay is max of this and --breaker-cooldown)")
    p.add_argument("--autoscale", action="store_true",
                   help="with --continuous: SLO-coupled elastic fleet "
                        "(serving/autoscaler.py) — replica membership "
                        "becomes a runtime control loop reading the "
                        "fast-window SLO burn gauges, the overload rung, "
                        "and queue depth; scale-up adds a canary-gated "
                        "standby replica, scale-down retires the lowest-"
                        "load replica through the drain/migration path "
                        "(in-flight requests survive with token parity). "
                        "Implies fleet mode even at --replicas 1. See "
                        "docs/SERVING.md §Elastic fleet & autoscaling")
    p.add_argument("--min-replicas", type=int, default=None, metavar="N",
                   help="with --autoscale: lower membership bound "
                        "(default 1)")
    p.add_argument("--max-replicas", type=int, default=None, metavar="N",
                   help="with --autoscale: upper membership bound "
                        "(default 4)")
    p.add_argument("--max-step-seconds", type=float, default=None,
                   help="resilience watchdog: a compiled prefill/decode step "
                        "slower than this is classified HUNG and contained "
                        "as a fault (requeue-once / chunk-retry); implies "
                        "the per-stage circuit breakers. See "
                        "docs/RESILIENCE.md")
    p.add_argument("--breaker-threshold", type=int, default=None,
                   help="resilience: consecutive faults per stage before "
                        "that stage's circuit breaker opens (default 3); "
                        "implies the breakers even without a watchdog")
    p.add_argument("--breaker-cooldown", type=float, default=None,
                   help="resilience: seconds an open breaker waits before "
                        "half-opening for a probe (default 5)")
    p.add_argument("--serving-journal", default=None, metavar="DIR",
                   help="with --continuous: crash-safe request journal under "
                        "DIR (journal.jsonl) + SIGTERM/SIGINT graceful "
                        "drain; a preempted run's unfinished requests are "
                        "re-served by `resume-serving DIR`")
    p.add_argument("--drain-grace", type=float, default=None,
                   help="with --serving-journal: seconds live slots may "
                        "keep decoding after a drain signal before being "
                        "journaled as unfinished (default 5)")
    p.add_argument("--numerics-guards", action="store_true",
                   help="integrity: fold an on-device finite check of the "
                        "logits into every compiled decode program (one "
                        "reduced flag per chunk); NaN/Inf chunks are "
                        "contained as NumericsFault instead of silently "
                        "decoding garbage. Output is token-for-token "
                        "identical either way. See docs/RESILIENCE.md")
    p.add_argument("--canary-every", type=int, default=None, metavar="N",
                   help="with --continuous: every N backend calls, decode a "
                        "golden prompt through the live scheduler and "
                        "compare token-for-token against a static-engine "
                        "reference; a mismatch trips the breaker "
                        "degradation ladder")
    p.add_argument("--no-verify-manifests", action="store_true",
                   help="skip sha256 manifest verification of weight "
                        "checkpoints at load (on by default where a "
                        "manifest.json exists)")
    p.add_argument("--mesh", default=None, help="device mesh, e.g. 'dp=2,tp=4'")
    p.add_argument("--weights-dir", default=None, help="directory of HF safetensors checkpoints")
    p.add_argument("--weight-quant", default=None, choices=("none", "int8"),
                   help="weight-only quantization for served models: int8 "
                        "stores matmul kernels as int8 with dequant inside "
                        "the Pallas tile (fits llama3-70b tp=8 on a v5e-8)")
    p.add_argument("--data-dir", default=None, help="MovieLens-1M directory")
    p.add_argument("--results-dir", default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--resume", action="store_true", help="resume phase-1 sweep from checkpoints")
    p.add_argument("--trace-dir", default=None,
                   help="write a jax.profiler device trace per phase to this directory")
    p.add_argument("--telemetry-dir", default=None,
                   help="export telemetry here: streamed events.jsonl plus an "
                        "end-of-run registry snapshot (telemetry_snapshot.json "
                        "+ metrics.prom) with TTFT/queue-wait/latency "
                        "histograms, and the device-step timeline as "
                        "trace.json; render with `telemetry-report <dir>` "
                        "(see docs/OBSERVABILITY.md)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write the device-step timeline (prefill/decode/"
                        "compile spans, request lanes, per-replica tracks) "
                        "as Chrome-trace JSON — open at "
                        "https://ui.perfetto.dev. With --telemetry-dir, "
                        "<telemetry-dir>/trace.json is written regardless "
                        "(the copy the validator and --timeline report "
                        "read); this flag adds an extra copy at PATH, or "
                        "enables the export without a telemetry dir")
    p.add_argument("--incidents", action="store_true",
                   help="arm the incident engine (telemetry/incidents.py): "
                        "trigger conditions (breaker open, fence, hang, "
                        "numerics fault, canary mismatch, fairness "
                        "divergence/alert, error-budget burn, heartbeat "
                        "gap) dump self-contained postmortem bundles under "
                        "<telemetry-dir>/incidents — flight-recorder rings, "
                        "decision trail, registry snapshot, trace slice, "
                        "journal tail. Render with `incident-report <dir>`; "
                        "gate with tools/validate_telemetry.py "
                        "--require-incidents / --forbid-incidents. "
                        "Requires --telemetry-dir")
    p.add_argument("--fairness-obs", action="store_true",
                   help="fairness observability (telemetry/fairness.py): "
                        "phases register their profile grid with the "
                        "fairness monitor, sweep requests carry "
                        "group/attribute/pair_id study tags, and the run "
                        "records streaming per-group DP/IF/exposure "
                        "gauges, a counterfactual pair watch with "
                        "serving-event attribution, and a serving-"
                        "neutrality audit (per-group TTFT/queue-wait/"
                        "shed/fault disparity, alerting via "
                        "fairness_alerts_total). Render with "
                        "`fairness-report <telemetry-dir>`; gate with "
                        "tools/validate_telemetry.py --require-fairness. "
                        "See docs/OBSERVABILITY.md §Fairness signals")
    p.add_argument("--slo-ttft-p95", type=float, default=None, metavar="S",
                   help="SLO target: p95 time-to-first-token in seconds "
                        "(default 2.0); burn rates exported as "
                        "slo_burn_rate gauges, rendered by `slo-report`")
    p.add_argument("--slo-e2e-p99", type=float, default=None, metavar="S",
                   help="SLO target: p99 end-to-end request latency in "
                        "seconds (default 30.0)")
    p.add_argument("--slo-error-rate", type=float, default=None, metavar="F",
                   help="SLO target: allowed failed/expired request "
                        "fraction (default 0.01)")
    p.add_argument("--achievable-gbps", type=float, default=None,
                   help="measured achievable HBM streaming bandwidth for "
                        "the live achieved_over_achievable roofline gauges "
                        "(default: the device's published peak, "
                        "telemetry/roofline.py DEVICE_PEAKS; a device "
                        "without one publishes no fraction)")
    p.add_argument("--no-save", action="store_true")
    p.add_argument("-v", "--verbose", action="store_true")
    return p


def config_from_args(args: argparse.Namespace) -> Config:
    config = default_config()
    updates: Dict = {}
    if args.mesh:
        updates["mesh"] = parse_mesh(args.mesh)
    if args.weights_dir:
        updates["weights_dir"] = args.weights_dir
    if args.weight_quant is not None:
        updates["weight_quant"] = args.weight_quant
    if args.data_dir:
        updates["data_dir"] = args.data_dir
    if args.results_dir:
        updates["results_dir"] = args.results_dir
    if args.seed is not None:
        updates["random_seed"] = args.seed
    if args.trace_dir:
        updates["profile_trace_dir"] = args.trace_dir
    if args.telemetry_dir:
        updates["telemetry_dir"] = args.telemetry_dir
    attribution_flags = (args.trace_out, args.slo_ttft_p95, args.slo_e2e_p99,
                         args.slo_error_rate, args.achievable_gbps)
    if args.fairness_obs or any(v is not None for v in attribution_flags):
        from fairness_llm_tpu.config import TelemetryConfig

        tel_kwargs: Dict = {}
        if args.fairness_obs:
            tel_kwargs["fairness_obs"] = True
        if args.trace_out:
            tel_kwargs["trace_out"] = args.trace_out
        if args.achievable_gbps is not None:
            if args.achievable_gbps <= 0:
                raise SystemExit("--achievable-gbps must be > 0")
            tel_kwargs["achievable_gbps"] = args.achievable_gbps
        for val, field, flag in (
            (args.slo_ttft_p95, "slo_ttft_p95_s", "--slo-ttft-p95"),
            (args.slo_e2e_p99, "slo_e2e_p99_s", "--slo-e2e-p99"),
            (args.slo_error_rate, "slo_error_rate", "--slo-error-rate"),
        ):
            if val is not None:
                if val <= 0:
                    raise SystemExit(f"{flag} must be > 0")
                tel_kwargs[field] = val
        updates["telemetry"] = TelemetryConfig(**tel_kwargs)
    if args.max_new_tokens is not None:
        if args.max_new_tokens < 1:
            # A zero cap would reach the engine as a [B, 0] decode buffer and
            # die inside jit with an opaque dynamic_update_slice error.
            raise SystemExit("--max-new-tokens must be >= 1")
        updates["max_new_tokens"] = args.max_new_tokens
    if args.quick:
        updates["profiles_per_combo"] = 1
        args.num_items = min(args.num_items, 10)
        args.num_comparisons = min(args.num_comparisons, 6)
    if args.speculate or args.draft_len is not None or args.ngram_max is not None:
        from fairness_llm_tpu.config import SpeculationConfig

        if not args.speculate:
            raise SystemExit("--draft-len/--ngram-max require --speculate")
        spec_kwargs = {"enabled": True}
        if args.draft_len is not None:
            if args.draft_len < 1:
                raise SystemExit("--draft-len must be >= 1")
            spec_kwargs["draft_len"] = args.draft_len
        if args.ngram_max is not None:
            if args.ngram_max < 1:
                raise SystemExit("--ngram-max must be >= 1")
            spec_kwargs["ngram_max"] = args.ngram_max
        updates["speculation"] = SpeculationConfig(**spec_kwargs)
    if args.continuous or args.slots is not None \
            or args.fuse_steps is not None or args.tp is not None:
        from fairness_llm_tpu.config import ServingConfig

        if not args.continuous:
            raise SystemExit(
                "--slots/--fuse-steps/--tp require --continuous")
        serve_kwargs = {"enabled": True}
        if args.tp is not None:
            # Same parse-time discipline as the --fuse-steps gates: every
            # invalid combination dies HERE with the flag named, not
            # minutes later inside a weight load or a jit trace.
            if args.tp < 1:
                raise SystemExit("--tp must be >= 1")
            if args.mesh:
                raise SystemExit(
                    "--tp cannot combine with --mesh: --tp N builds the "
                    "tp-only serving mesh itself (use --mesh for the "
                    "static-engine dp/sp paths)")
            if args.tp > 1:
                if args.model and args.model not in (
                        "simulated", "simulated-fair", "simulated-biased"):
                    from fairness_llm_tpu.models.configs import (
                        get_model_config,
                    )

                    try:
                        mc = get_model_config(args.model)
                    except KeyError:
                        mc = None
                    if mc is not None and mc.attention != "gqa":
                        raise SystemExit(
                            f"--tp shards GQA heads; {args.model} has "
                            f"{sorted(set(mc.mixer_kinds))} mixers, whose "
                            "caches (a latent, a per-row state) have no "
                            "head axis sharded yet")
                    if mc is not None and "moe" in mc.layer_kinds:
                        raise SystemExit(
                            f"--tp has no expert axis; {args.model} has "
                            "routed experts, whose exchange across chips "
                            "is not built (its GQA layers' rings and rows "
                            "would shard by KV head as a dense model's do)")
                    if mc is not None and (mc.num_heads % args.tp != 0 or
                                           mc.num_kv_heads % args.tp != 0):
                        raise SystemExit(
                            f"--tp {args.tp} must divide {args.model}'s "
                            f"attention heads ({mc.num_heads} q / "
                            f"{mc.num_kv_heads} kv)")
                import jax as _jax

                if _jax.device_count() % args.tp != 0:
                    raise SystemExit(
                        f"--tp {args.tp} must divide the device count "
                        f"({_jax.device_count()}); on CPU set XLA_FLAGS="
                        f"--xla_force_host_platform_device_count={args.tp}")
                serve_kwargs["tp"] = args.tp
                from fairness_llm_tpu.config import MeshConfig

                updates["mesh"] = MeshConfig(tp=args.tp)
        if args.slots is not None:
            if args.slots < 1:
                raise SystemExit("--slots must be >= 1")
            serve_kwargs["num_slots"] = args.slots
        if args.fuse_steps is not None:
            if args.fuse_steps < 1:
                raise SystemExit("--fuse-steps must be >= 1")
            if args.fuse_steps > 1 and args.speculate:
                raise SystemExit(
                    "--fuse-steps cannot combine with --speculate: the "
                    "speculative verify window is already multi-token; "
                    "fused tree speculation is deferred to the "
                    "tree-speculation PR")
            serve_kwargs["fuse_steps"] = args.fuse_steps
        updates["serving"] = ServingConfig(**serve_kwargs)
    overload_flags = (args.shed_burn_threshold, args.shed_healthy_window,
                      args.batch_token_cap)
    if args.overload or any(v is not None for v in overload_flags):
        from fairness_llm_tpu.config import OverloadConfig

        if not args.overload:
            raise SystemExit("--shed-burn-threshold/--shed-healthy-window/"
                             "--batch-token-cap require --overload")
        if not args.continuous:
            raise SystemExit("--overload requires --continuous (overload "
                             "control gates the serving admission queue)")
        ov_kwargs: Dict = {"enabled": True}
        if args.shed_burn_threshold is not None:
            if args.shed_burn_threshold <= 0:
                raise SystemExit("--shed-burn-threshold must be > 0")
            ov_kwargs["burn_threshold"] = args.shed_burn_threshold
        if args.shed_healthy_window is not None:
            if args.shed_healthy_window < 0:
                raise SystemExit("--shed-healthy-window must be >= 0")
            ov_kwargs["healthy_window_s"] = args.shed_healthy_window
        if args.batch_token_cap is not None:
            if args.batch_token_cap < 1:
                raise SystemExit("--batch-token-cap must be >= 1")
            ov_kwargs["batch_token_cap"] = args.batch_token_cap
        updates["overload"] = OverloadConfig(**ov_kwargs)
    fleet_flags = (args.replicas, args.fence_level, args.fence_cooldown)
    if any(v is not None for v in fleet_flags):
        from fairness_llm_tpu.config import FleetConfig

        if not args.continuous:
            raise SystemExit("--replicas/--fence-level/--fence-cooldown "
                             "require --continuous (the fleet routes over "
                             "serving schedulers)")
        fleet_kwargs: Dict = {}
        if args.replicas is not None:
            if args.replicas < 1:
                raise SystemExit("--replicas must be >= 1")
            fleet_kwargs["replicas"] = args.replicas
        if args.fence_level is not None:
            if args.fence_level < 0:
                raise SystemExit("--fence-level must be >= 0")
            fleet_kwargs["fence_ladder_level"] = args.fence_level
        if args.fence_cooldown is not None:
            if args.fence_cooldown < 0:
                raise SystemExit("--fence-cooldown must be >= 0")
            fleet_kwargs["fence_cooldown_s"] = args.fence_cooldown
        updates["fleet"] = FleetConfig(**fleet_kwargs)
    autoscale_flags = (args.min_replicas, args.max_replicas)
    if args.autoscale or any(v is not None for v in autoscale_flags):
        from fairness_llm_tpu.config import AutoscaleConfig

        if not args.autoscale:
            raise SystemExit("--min-replicas/--max-replicas require "
                             "--autoscale")
        if not args.continuous:
            raise SystemExit("--autoscale requires --continuous (the "
                             "autoscaler drives fleet membership over "
                             "serving schedulers)")
        as_kwargs: Dict = {"enabled": True}
        if args.min_replicas is not None:
            if args.min_replicas < 1:
                raise SystemExit("--min-replicas must be >= 1")
            as_kwargs["min_replicas"] = args.min_replicas
        if args.max_replicas is not None:
            if args.max_replicas < (args.min_replicas or 1):
                raise SystemExit("--max-replicas must be >= --min-replicas")
            as_kwargs["max_replicas"] = args.max_replicas
        elif args.min_replicas is not None and \
                args.min_replicas > AutoscaleConfig.max_replicas:
            raise SystemExit(
                f"--min-replicas {args.min_replicas} exceeds the default "
                f"--max-replicas ({AutoscaleConfig.max_replicas}); pass "
                "--max-replicas explicitly")
        updates["autoscale"] = AutoscaleConfig(**as_kwargs)
    resilience_flags = (args.max_step_seconds, args.breaker_threshold,
                        args.breaker_cooldown, args.serving_journal,
                        args.drain_grace)
    if any(v is not None for v in resilience_flags):
        from fairness_llm_tpu.config import ResilienceConfig

        if (args.serving_journal or args.drain_grace is not None) \
                and not args.continuous:
            raise SystemExit("--serving-journal/--drain-grace require "
                             "--continuous (the journal ledgers serving "
                             "requests)")
        res_kwargs: Dict = {"enabled": True}
        if args.max_step_seconds is not None:
            if args.max_step_seconds <= 0:
                raise SystemExit("--max-step-seconds must be > 0")
            res_kwargs["max_step_seconds"] = args.max_step_seconds
        if args.breaker_threshold is not None:
            if args.breaker_threshold < 1:
                raise SystemExit("--breaker-threshold must be >= 1")
            res_kwargs["breaker_threshold"] = args.breaker_threshold
        if args.breaker_cooldown is not None:
            if args.breaker_cooldown < 0:
                raise SystemExit("--breaker-cooldown must be >= 0")
            res_kwargs["breaker_cooldown_s"] = args.breaker_cooldown
        if args.serving_journal:
            res_kwargs["journal_dir"] = args.serving_journal
        if args.drain_grace is not None:
            if args.drain_grace < 0:
                raise SystemExit("--drain-grace must be >= 0")
            res_kwargs["drain_grace_s"] = args.drain_grace
        updates["resilience"] = ResilienceConfig(**res_kwargs)
    if args.numerics_guards or args.canary_every is not None \
            or args.no_verify_manifests:
        from fairness_llm_tpu.config import IntegrityConfig

        integ_kwargs: Dict = {}
        if args.numerics_guards:
            integ_kwargs["numerics_guards"] = True
        if args.canary_every is not None:
            if not args.continuous:
                raise SystemExit("--canary-every requires --continuous (the "
                                 "canary probes the serving scheduler)")
            if args.canary_every < 1:
                raise SystemExit("--canary-every must be >= 1")
            integ_kwargs["canary_every_n"] = args.canary_every
        if args.no_verify_manifests:
            integ_kwargs["verify_manifests"] = False
        updates["integrity"] = IntegrityConfig(**integ_kwargs)
    if updates:
        config = dataclasses.replace(config, **updates)
    return config


def telemetry_report(argv) -> int:
    """``cli telemetry-report <dir|snapshot.json>`` — render a telemetry
    snapshot in the terminal (the ``summarize_trace`` of the metrics world:
    no dashboards required). ``--validate`` also runs the schema /
    percentile-consistency check and fails on problems (the CI smoke
    step's gate)."""
    ap = argparse.ArgumentParser(
        prog="fairness_llm_tpu telemetry-report",
        description="Render (and optionally validate) a telemetry snapshot",
    )
    ap.add_argument("path", help="telemetry dir (uses telemetry_snapshot.json "
                                 "inside) or a snapshot file")
    ap.add_argument("--validate", action="store_true",
                    help="schema-check the snapshot; non-zero exit on problems")
    ap.add_argument("--timeline", action="store_true",
                    help="also summarize the trace.json device-step timeline "
                         "beside the snapshot: top programs by wall, largest "
                         "step gaps, request outcomes")
    a = ap.parse_args(argv)
    import json
    import os

    from fairness_llm_tpu.telemetry import (
        TRACE_FILENAME,
        load_snapshot,
        render_report,
        summarize_chrome_trace,
        validate_snapshot,
    )

    snap = load_snapshot(a.path)
    if a.validate:
        # Validate BEFORE rendering: the renderer assumes a well-formed
        # snapshot, and the user asked for diagnostics, not a traceback.
        problems = validate_snapshot(snap)
        if problems:
            print("SNAPSHOT INVALID:")
            for p in problems:
                print(f"  - {p}")
            return 1
    print(render_report(snap))
    from fairness_llm_tpu.telemetry import has_cost_data, render_cost_report

    if has_cost_data(snap):
        # Cost-ledger section rides along whenever the run recorded the
        # jaxpr cost walk (telemetry/costmodel.py) — the standalone
        # `perf-report` subcommand renders the same decomposition alone.
        print("\n" + render_cost_report(snap))
    from fairness_llm_tpu.telemetry import has_memory_data, render_memory_report

    if has_memory_data(snap):
        # Memory-ledger section rides along whenever the run accounted
        # device memory (telemetry/memory.py); `memory-report` standalone.
        print("\n" + render_memory_report(snap))
    if any(row.get("labels", {}).get("component") == "fairness"
           for section in ("counters", "gauges")
           for row in snap.get(section, [])):
        # Fairness section rides along whenever the run recorded fairness
        # instruments (--fairness-obs / tagged requests); the standalone
        # `fairness-report` subcommand adds the divergent-pair table from
        # events.jsonl.
        from fairness_llm_tpu.telemetry import render_fairness_report

        print("\n" + render_fairness_report(snap))
    from fairness_llm_tpu.serving.rollout import render_rollout_report

    rollout_section = render_rollout_report(snap)
    if rollout_section:
        # Rollout section rides along whenever the run drove a version
        # rollout (cli rollout / tools/rollout_drill.py): wave position,
        # traffic split, transition and rollback-cause tallies.
        print("\n" + rollout_section)
    if a.timeline:
        trace_dir = a.path if os.path.isdir(a.path) else os.path.dirname(a.path)
        trace_path = os.path.join(trace_dir, TRACE_FILENAME)
        if os.path.exists(trace_path):
            with open(trace_path, encoding="utf-8") as f:
                print("\n" + summarize_chrome_trace(json.load(f)))
        else:
            print(f"\n(no {TRACE_FILENAME} beside the snapshot — run with "
                  "--trace-out or --telemetry-dir to produce one)")
    if a.validate:
        print("\nsnapshot schema: OK")
    return 0


def perf_report(argv) -> int:
    """``cli perf-report <dir|snapshot.json>`` — render the decode cost
    ledger and per-program gap attribution a run recorded
    (telemetry/costmodel.py): per compiled program, the jaxpr-walked
    bytes/FLOPs per component, the analytic floor, and the decomposition
    ``measured wall + host gap = floor + dispatch + unattributed + host
    gap`` with the top gap contributor named — the live replacement for
    the offline xplane accounting in tools/account_decode_step.py. See
    docs/PERFORMANCE.md §Round 12."""
    ap = argparse.ArgumentParser(
        prog="fairness_llm_tpu perf-report",
        description="Render the decode cost ledger / gap attribution from "
                    "a telemetry snapshot",
    )
    ap.add_argument("path", help="telemetry dir (uses telemetry_snapshot."
                                 "json inside) or a snapshot file")
    ap.add_argument("--require-ledger", action="store_true",
                    help="exit non-zero when the snapshot has no cost-"
                         "ledger data (a CI gate)")
    a = ap.parse_args(argv)
    from fairness_llm_tpu.telemetry import (
        has_cost_data,
        load_snapshot,
        render_cost_report,
    )

    snap = load_snapshot(a.path)
    print(render_cost_report(snap))
    if a.require_ledger and not has_cost_data(snap):
        return 1
    return 0


def memory_report(argv) -> int:
    """``cli memory-report <dir|snapshot.json>`` — render the HBM memory
    ledger a run recorded (telemetry/memory.py): per-pool residency
    (params / contiguous KV / prefix cache / carried
    logits), the reconciliation verdict against the device's own
    ``memory_stats`` (measured on TPU, indicative on CPU), headroom
    against the limit, and the per-program AOT memory table XLA budgeted
    (``compiled.memory_analysis``). See docs/OBSERVABILITY.md §Memory
    signals."""
    ap = argparse.ArgumentParser(
        prog="fairness_llm_tpu memory-report",
        description="Render the HBM memory ledger from a telemetry "
                    "snapshot",
    )
    ap.add_argument("path", help="telemetry dir (uses telemetry_snapshot."
                                 "json inside) or a snapshot file")
    ap.add_argument("--require-ledger", action="store_true",
                    help="exit non-zero when the snapshot has no memory-"
                         "ledger data (a CI gate)")
    a = ap.parse_args(argv)
    from fairness_llm_tpu.telemetry import (
        has_memory_data,
        load_snapshot,
        render_memory_report,
    )

    snap = load_snapshot(a.path)
    print(render_memory_report(snap))
    if a.require_ledger and not has_memory_data(snap):
        return 1
    return 0


def slo_report(argv) -> int:
    """``cli slo-report <dir|snapshot.json>`` — render the SLO burn rates a
    run recorded: one table per label set (replica in fleet mode), burn per
    (objective, window), alert counts. Burn 1.0 = consuming the error
    budget exactly at the sustainable rate; >1 = an SLO on its way to
    violation. See docs/OBSERVABILITY.md §SLOs and burn rates."""
    ap = argparse.ArgumentParser(
        prog="fairness_llm_tpu slo-report",
        description="Render SLO burn rates from a telemetry snapshot",
    )
    ap.add_argument("path", help="telemetry dir (uses telemetry_snapshot.json "
                                 "inside) or a snapshot file")
    ap.add_argument("--fail-on-burn", action="store_true",
                    help="exit non-zero when any run-window burn rate "
                         "exceeds 1.0 (a CI gate)")
    a = ap.parse_args(argv)
    from fairness_llm_tpu.telemetry import load_snapshot, render_slo_report

    snap = load_snapshot(a.path)
    print(render_slo_report(snap))
    if a.fail_on_burn:
        burning = [
            g for g in snap.get("gauges", [])
            if g.get("name") == "slo_burn_rate"
            and g.get("labels", {}).get("window") == "run"
            and g.get("value", 0.0) > 1.0
        ]
        if burning:
            print(f"\n{len(burning)} SLO(s) burning over the whole run")
            return 1
    return 0


def fairness_report(argv) -> int:
    """``cli fairness-report <dir|snapshot.json>`` — render the fairness
    signals a run recorded (telemetry/fairness.py): streaming vs offline
    DP/IF/exposure, the per-group neutrality audit, disparity gauges with
    alert counts, and the divergent-pair attribution table (joined from
    ``events.jsonl`` when rendering a telemetry dir). See
    docs/OBSERVABILITY.md §Fairness signals."""
    ap = argparse.ArgumentParser(
        prog="fairness_llm_tpu fairness-report",
        description="Render fairness observability from a telemetry "
                    "snapshot",
    )
    ap.add_argument("path", help="telemetry dir (uses telemetry_snapshot."
                                 "json + events.jsonl inside) or a "
                                 "snapshot file")
    ap.add_argument("--fail-on-alert", action="store_true",
                    help="exit non-zero when any fairness_alerts_total is "
                         "nonzero or any pair diverged (a CI gate)")
    a = ap.parse_args(argv)
    import os

    from fairness_llm_tpu.telemetry import (
        load_snapshot,
        read_events,
        render_fairness_report,
    )

    snap = load_snapshot(a.path)
    events = None
    ev_dir = a.path if os.path.isdir(a.path) else os.path.dirname(a.path)
    ev_path = os.path.join(ev_dir, "events.jsonl")
    if os.path.exists(ev_path):
        events = read_events(ev_path)
    print(render_fairness_report(snap, events=events))
    if a.fail_on_alert:
        alerts = sum(c["value"] for c in snap.get("counters", [])
                     if c.get("name") == "fairness_alerts_total")
        diverged = sum(c["value"] for c in snap.get("counters", [])
                       if c.get("name") == "fairness_pair_divergence_total")
        if alerts or diverged:
            print(f"\n{int(alerts)} fairness alert(s), {int(diverged)} "
                  "divergent pair(s)")
            return 1
    return 0


def incident_report(argv) -> int:
    """``cli incident-report <bundle-dir | incidents-dir | telemetry-dir>``
    — render incident postmortem bundles: manifest, the causal chain
    derived from the decision trail ("fence(r1) <- 3x breaker trips <-
    numerics faults <- requests a, b"), flight-recorder ring depths, and
    the implicated decision tail. Given a telemetry dir (or an incidents
    dir), renders every bundle inside; given one bundle, renders it alone.
    See docs/OBSERVABILITY.md §Incidents."""
    ap = argparse.ArgumentParser(
        prog="fairness_llm_tpu incident-report",
        description="Render incident postmortem bundles",
    )
    ap.add_argument("path", help="one bundle dir, an incidents/ dir, or a "
                                 "telemetry dir containing incidents/")
    ap.add_argument("--chain-only", action="store_true",
                    help="print only the one-line causal chain per bundle")
    a = ap.parse_args(argv)
    import os

    from fairness_llm_tpu.telemetry import list_bundles, render_incident_report
    from fairness_llm_tpu.telemetry.incidents import (
        INCIDENTS_DIRNAME,
        MANIFEST_FILENAME,
        causal_chain,
        _read_jsonl,
    )

    path = a.path.rstrip("/")
    if os.path.isfile(os.path.join(path, MANIFEST_FILENAME)):
        bundles = [path]
    else:
        inc_dir = path
        if os.path.isdir(os.path.join(path, INCIDENTS_DIRNAME)):
            inc_dir = os.path.join(path, INCIDENTS_DIRNAME)
        bundles = [m["path"] for m in list_bundles(inc_dir)]
        if not bundles:
            print(f"no incident bundles under {inc_dir} — a clean run, or "
                  "the engine was never armed (--incidents)")
            return 0
    for i, b in enumerate(bundles):
        if a.chain_only:
            import json as _json

            with open(os.path.join(b, MANIFEST_FILENAME),
                      encoding="utf-8") as f:
                manifest = _json.load(f)
            trail = _read_jsonl(os.path.join(b, "decisions.jsonl"))
            implicated = _read_jsonl(
                os.path.join(b, "decisions_implicated.jsonl"))
            print(f"{os.path.basename(b)}: "
                  + causal_chain(manifest, trail, implicated))
        else:
            if i:
                print()
            print(render_incident_report(b))
    return 0


def resume_serving_cmd(argv) -> int:
    """``cli resume-serving <journal-dir>`` — finish the unfinished.

    Loads the serving journal a drained/preempted ``--continuous`` run left
    behind and re-serves every request without a terminal record, with its
    ORIGINAL id, sampler settings, and row seed (greedy survivors decode
    the exact tokens an uninterrupted run would) and its deadline reduced
    by the wall time already spent. See docs/RESILIENCE.md.
    """
    ap = argparse.ArgumentParser(
        prog="fairness_llm_tpu resume-serving",
        description="Re-serve a drained run's journaled unfinished requests",
    )
    ap.add_argument("journal_dir", help="directory holding journal.jsonl "
                                        "(the --serving-journal DIR)")
    ap.add_argument("--model", required=True,
                    help="engine model name (must match the drained run)")
    ap.add_argument("--weights-dir", default=None)
    ap.add_argument("--allow-random", action="store_true",
                    help="serve with randomly initialized weights (smoke "
                         "runs / chaos drills only)")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--max-new-tokens", type=int, default=None,
                    help="serving decode cap (default: the serving default, "
                         "clamped to fit the model's position budget)")
    ap.add_argument("--max-step-seconds", type=float, default=None)
    ap.add_argument("--breaker-threshold", type=int, default=None)
    ap.add_argument("--telemetry-dir", default=None)
    ap.add_argument("-v", "--verbose", action="store_true")
    a = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if a.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    from fairness_llm_tpu.config import ResilienceConfig, ServingConfig
    from fairness_llm_tpu.pipeline.backends import backend_for
    from fairness_llm_tpu.resilience import (
        GracefulDrain,
        ServingJournal,
        resume_serving,
    )

    config = default_config()
    res_kwargs: Dict = {"enabled": True, "journal_dir": a.journal_dir}
    if a.max_step_seconds is not None:
        res_kwargs["max_step_seconds"] = a.max_step_seconds
    if a.breaker_threshold is not None:
        res_kwargs["breaker_threshold"] = a.breaker_threshold
    serve_kwargs: Dict = {"enabled": True}
    if a.slots is not None:
        serve_kwargs["num_slots"] = a.slots
    from fairness_llm_tpu.models.configs import get_model_config

    # The scheduler requires max_new_tokens < the model's max_seq_len (a
    # KV-slot row holds prompt bucket + decode cap). Clamp the DEFAULT so
    # small study models resume without ceremony; an explicit flag is taken
    # verbatim and fails loudly if it can't fit.
    model_seq = get_model_config(a.model).max_seq_len
    serve_kwargs["max_new_tokens"] = (
        a.max_new_tokens if a.max_new_tokens is not None
        else min(ServingConfig().max_new_tokens, model_seq // 2)
    )
    config = dataclasses.replace(
        config,
        weights_dir=a.weights_dir,
        serving=ServingConfig(**serve_kwargs),
        resilience=ResilienceConfig(**res_kwargs),
        telemetry_dir=a.telemetry_dir,
    )
    sink = None
    if a.telemetry_dir:
        from fairness_llm_tpu import telemetry as T

        sink = T.configure(a.telemetry_dir)
    # The backend owns the engine build (weights, quant, single-device
    # guard); its journal handle is the same ledger we resume from, so
    # completions append terminal records and a SECOND preemption during
    # the resume re-journals the still-unfinished tail.
    backend = backend_for(a.model, config, allow_random=a.allow_random)
    journal = backend.journal or ServingJournal(a.journal_dir)
    with GracefulDrain():
        results = resume_serving(
            backend.engine, journal, serving=backend.serving,
            resilience=config.resilience,
        )
    outcomes: Dict[str, int] = {}
    for res in results.values():
        outcomes[res.finish_reason] = outcomes.get(res.finish_reason, 0) + 1
    print(f"resumed {len(results)} request(s): "
          + (", ".join(f"{k}={v}" for k, v in sorted(outcomes.items()))
             or "nothing to do"))
    still = journal.unfinished()
    if still:
        print(f"{len(still)} request(s) remain unfinished (drained again?) — "
              f"re-run resume-serving {a.journal_dir}")
    if a.telemetry_dir:
        from fairness_llm_tpu import telemetry as T

        path = T.write_snapshot(T.get_registry(), a.telemetry_dir)
        print(f"telemetry snapshot: {path}")
        if sink is not None:
            T.install_event_sink(None)
            sink.close()
    return 1 if still else 0


def rollout_cmd(argv) -> int:
    """``cli rollout`` — zero-downtime rolling version upgrade.

    Builds a ``ReplicaSet`` on the current model/weights, then walks it
    to a new immutable version with a :class:`RolloutController` while a
    synthetic workload streams through the fleet: one canary-gated
    standby per wave, stepped traffic shift, planned retirement of each
    old replica, automatic rollback on any deployment gate (manifest
    refusal of the incoming checkpoint, canary mismatch, SLO error burn,
    fairness alert / counterfactual pair divergence attributed to the
    new version, watchdog or breaker trip). Requests keep pinned-version
    affinity throughout: a stream finishes on the version that served
    its first token. See docs/SERVING.md §Rollouts.

    Exit status: 0 = rollout complete; 2 = rolled back (the gate and
    cause are printed and, with ``--telemetry-dir``, bundled under
    incidents/); 1 = requests lost (never expected — file a bug).
    """
    ap = argparse.ArgumentParser(
        prog="fairness_llm_tpu rollout",
        description="Drive a canary-gated rolling upgrade across a "
                    "replica fleet under live traffic",
    )
    ap.add_argument("--model", required=True,
                    help="engine model name for the CURRENT version")
    ap.add_argument("--weights-dir", default=None,
                    help="HF safetensors dir for the current version")
    ap.add_argument("--to-checkpoint", default=None, metavar="DIR",
                    help="HF safetensors dir for the NEW version's weights "
                         "(manifest-verified during PREPARING; a refused "
                         "checkpoint rolls back before any replica joins)")
    ap.add_argument("--to-config", default=None, metavar="MODEL",
                    help="model config name for the new version "
                         "(default: --model)")
    ap.add_argument("--to-version", default=None, metavar="ID",
                    help="immutable version id for the new fleet "
                         "(default: bump the current one, v0 -> v1)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--canary-window", type=float, default=None,
                    metavar="S",
                    help="gate-watch window per traffic step (seconds)")
    ap.add_argument("--traffic-steps", type=int, default=None, metavar="N",
                    help="traffic-shift steps per wave")
    ap.add_argument("--abort-on-fairness-alert",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="treat fairness alerts / counterfactual pair "
                         "divergence attributed to the new version as a "
                         "rollback gate (default: on)")
    ap.add_argument("--requests", type=int, default=32,
                    help="synthetic requests streamed during the rollout")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--slo-ttft-p95", type=float, default=None, metavar="S",
                    help="TTFT p95 target feeding the rollout's SLO burn "
                         "gate (default: the stack default; set generously "
                         "on CPU smoke runs or the gate will fire)")
    ap.add_argument("--slo-e2e-p99", type=float, default=None, metavar="S")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry-dir", default=None)
    ap.add_argument("--allow-random", action="store_true",
                    help="run with randomly initialized weights (smoke "
                         "runs / drills only)")
    ap.add_argument("-v", "--verbose", action="store_true")
    a = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if a.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    if not a.weights_dir and not a.allow_random:
        raise SystemExit("rollout needs --weights-dir (or --allow-random "
                         "for smoke runs)")
    import time

    from fairness_llm_tpu.config import (
        FleetConfig,
        IntegrityConfig,
        ModelSettings,
        ResilienceConfig,
        RolloutConfig,
        ServingConfig,
    )
    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.runtime.engine import DecodeEngine
    from fairness_llm_tpu.runtime.weights import load_checkpoint
    from fairness_llm_tpu.serving import ReplicaSet, Request, RolloutController
    from fairness_llm_tpu.serving.replay import DEFAULT_PROMPTS

    sink = None
    if a.telemetry_dir:
        from fairness_llm_tpu import telemetry as T

        sink = T.configure(a.telemetry_dir)
        T.arm_incidents(a.telemetry_dir)

    if a.slo_ttft_p95 is not None or a.slo_e2e_p99 is not None:
        from fairness_llm_tpu.telemetry.slo import SLOTargets, set_slo_targets

        slo_kwargs = {}
        if a.slo_ttft_p95 is not None:
            slo_kwargs["ttft_p95_s"] = a.slo_ttft_p95
        if a.slo_e2e_p99 is not None:
            slo_kwargs["e2e_p99_s"] = a.slo_e2e_p99
        set_slo_targets(SLOTargets(**slo_kwargs))

    cfg = get_model_config(a.model)
    engine = DecodeEngine(cfg, seed=a.seed)
    if a.weights_dir:
        engine.params = load_checkpoint(cfg, a.weights_dir)

    to_cfg = get_model_config(a.to_config) if a.to_config else cfg

    def new_engine():
        # Built lazily inside the controller's PREPARING step so a
        # manifest refusal of --to-checkpoint lands as a rollback gate,
        # not a CLI traceback.
        eng = DecodeEngine(to_cfg, seed=a.seed)
        if a.to_checkpoint:
            eng.params = load_checkpoint(to_cfg, a.to_checkpoint)
        return eng

    serving = ServingConfig(
        enabled=True, num_slots=2, queue_capacity=max(16, a.requests),
        max_new_tokens=min(a.max_new_tokens, cfg.max_seq_len // 2,
                           to_cfg.max_seq_len // 2),
    )
    fleet = ReplicaSet(
        engine, serving,
        settings=ModelSettings(temperature=0.0,
                               max_tokens=serving.max_new_tokens),
        fleet=FleetConfig(replicas=a.replicas),
        resilience=ResilienceConfig(enabled=True),
        integrity=IntegrityConfig(),
    )
    ro_kwargs = {}
    if a.canary_window is not None:
        ro_kwargs["canary_window_s"] = a.canary_window
    if a.traffic_steps is not None:
        ro_kwargs["traffic_steps"] = a.traffic_steps
    to_version = a.to_version or f"v{int(fleet.version.lstrip('v') or 0) + 1}"
    ro = RolloutController(
        fleet, to_version, engine_fn=new_engine,
        config=RolloutConfig(
            enabled=True,
            abort_on_fairness_alert=a.abort_on_fairness_alert,
            **ro_kwargs,
        ),
    )
    from_version = fleet.version
    ro.start()
    pending = [
        Request(prompt=DEFAULT_PROMPTS[i % len(DEFAULT_PROMPTS)],
                id=f"ro_{i}", settings=fleet.settings)
        for i in range(a.requests)
    ]
    results: Dict[str, object] = {}
    outstanding: list = []
    t0 = time.monotonic()
    while (ro.active or pending or outstanding or fleet.has_work):
        if time.monotonic() - t0 > 600.0:
            print("rollout wall guard tripped (600 s) — aborting")
            break
        if pending and fleet.submit(pending[0]):
            outstanding.append(pending.pop(0).id)
        fleet.tick()
        for rid in list(outstanding):
            res = fleet.take_result(rid)
            if res is not None:
                results[rid] = res
                outstanding.remove(rid)
    pins: Dict[str, int] = {}
    for rid in results:
        ver = fleet.request_version(rid) or "?"
        pins[ver] = pins.get(ver, 0) + 1
    lost = a.requests - len(results)
    print(f"rollout {from_version} -> {to_version}: state={ro.state}"
          + (f" cause={ro.cause}" if ro.cause else ""))
    print(f"served {len(results)}/{a.requests} request(s), pinned "
          + (", ".join(f"{k}={v}" for k, v in sorted(pins.items()))
             or "none"))
    if a.telemetry_dir:
        from fairness_llm_tpu import telemetry as T

        path = T.write_snapshot(T.get_registry(), a.telemetry_dir)
        print(f"telemetry snapshot: {path}")
        if sink is not None:
            T.install_event_sink(None)
            sink.close()
    if lost:
        print(f"LOST {lost} request(s) — this is a bug")
        return 1
    return 0 if ro.state == "complete" else 2


def run_study_phase(phase: int, args: argparse.Namespace, config: Config,
                    save: bool, p1: Optional[Dict] = None,
                    backend=None) -> Dict:
    """Run one study phase the way the CLI runs it — driver call, summary,
    figures — and return its results dict. ``p1`` is phase 1's in-memory
    result (phase 3 mitigates it; None = load or re-run). ``backend``, an
    already-built backend for ``args.model``, replaces the name-resolved
    one: how ``chip_smoke.py`` serves a full-width model from seeded random
    weights (``backend_for(..., allow_random=True)``) through this exact
    path."""
    if phase == 1:
        res = run_phase1(config, args.model, args.profiles, save=save,
                         resume=args.resume, backend=backend)
        print_phase1_summary(res)
        if save:
            from fairness_llm_tpu.reports import (
                generate_phase1_figures,
                generate_summary_report,
            )

            generate_phase1_figures(res, f"{config.results_dir}/visualizations")
            generate_summary_report(
                res, f"{config.results_dir}/phase1/phase1_summary_report.txt"
            )
    elif phase == 2:
        res = run_phase2(config, args.models or ([args.model] if args.model else None),
                         args.num_items, args.num_comparisons, save=save,
                         corpus=args.corpus, num_queries=args.num_queries,
                         backends={args.model: backend} if backend is not None else None)
        print_phase2_summary(res)
        if save:
            from fairness_llm_tpu.reports import generate_phase2_figure

            generate_phase2_figure(res, f"{config.results_dir}/visualizations")
    else:
        res = run_phase3(config, phase1_results=p1, model_name=args.model,
                         num_profiles=args.profiles, variant=args.variant,
                         strategy=args.strategy, save=save, backend=backend,
                         calibration=args.calibration,
                         confidence_mapping=args.confidence_mapping,
                         confidence_temperature=args.confidence_temperature)
        print_phase3_summary(res)
        if save:
            from fairness_llm_tpu.reports import generate_phase3_figure

            generate_phase3_figure(res, f"{config.results_dir}/visualizations")
    return res


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    if argv and argv[0] == "telemetry-report":
        # Subcommand dispatch ahead of the study parser (whose --all/--phase
        # group is required and would reject it).
        return telemetry_report(argv[1:])
    if argv and argv[0] == "perf-report":
        return perf_report(argv[1:])
    if argv and argv[0] == "memory-report":
        return memory_report(argv[1:])
    if argv and argv[0] == "slo-report":
        return slo_report(argv[1:])
    if argv and argv[0] == "fairness-report":
        return fairness_report(argv[1:])
    if argv and argv[0] == "incident-report":
        return incident_report(argv[1:])
    if argv and argv[0] == "resume-serving":
        return resume_serving_cmd(argv[1:])
    if argv and argv[0] == "rollout":
        return rollout_cmd(argv[1:])
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    print(BANNER)
    config = config_from_args(args)
    check_setup(config)
    save = not args.no_save
    telemetry_sink = None
    if args.incidents and not config.telemetry_dir:
        raise SystemExit("--incidents requires --telemetry-dir (bundles "
                         "dump under <telemetry-dir>/incidents)")
    if config.telemetry_dir:
        from fairness_llm_tpu import telemetry as T

        telemetry_sink = T.configure(config.telemetry_dir)
        if args.incidents:
            import os as _os

            from fairness_llm_tpu.telemetry import arm_incidents
            from fairness_llm_tpu.telemetry.incidents import INCIDENTS_DIRNAME

            arm_incidents(_os.path.join(config.telemetry_dir,
                                        INCIDENTS_DIRNAME))
    # Performance attribution setup (telemetry/slo.py, telemetry/roofline.py):
    # install the SLO objectives and the roofline reference BEFORE any
    # backend/scheduler is built, so every evaluator judges against the
    # configured targets from its first request.
    from fairness_llm_tpu.telemetry import (
        SLOTargets,
        set_achievable_gbps,
        set_slo_targets,
    )

    tc = config.telemetry
    set_slo_targets(SLOTargets(
        ttft_p95_s=tc.slo_ttft_p95_s, e2e_p99_s=tc.slo_e2e_p99_s,
        error_rate=tc.slo_error_rate, fast_window_s=tc.slo_fast_window_s,
        slow_window_s=tc.slo_slow_window_s,
    ))
    if tc.achievable_gbps:
        set_achievable_gbps(tc.achievable_gbps)

    from fairness_llm_tpu.utils import maybe_trace, phase_timer

    drain_handler = None
    if config.resilience.enabled and config.serving.enabled:
        # SIGTERM/SIGINT drain: the serving scheduler polls the handler's
        # flag each loop iteration, stops admission, finishes what it can
        # within --drain-grace, and journals the rest (when --serving-journal
        # is set) for `resume-serving`. Second signal = normal kill.
        from fairness_llm_tpu.resilience import GracefulDrain

        drain_handler = GracefulDrain().install()

    from fairness_llm_tpu.utils.failures import SweepFailed

    phases = [1, 2, 3] if args.all else [args.phase]
    timings: Dict[str, float] = {}
    p1 = None
    failure: Optional[str] = None
    for phase in phases:
        if drain_handler is not None and drain_handler.requested:
            # A drain mid-phase already preempted/journaled that phase's
            # serving work; running the REMAINING phases would just burn
            # the preemption window producing instantly-preempted results.
            # Stop at the boundary and get to the snapshot/journal note.
            print(f"\ndrain requested — skipping phase {phase} and beyond")
            break
        try:
            with phase_timer(f"phase {phase}", timings), maybe_trace(
                config.profile_trace_dir, f"phase{phase}"
            ):
                results = run_study_phase(phase, args, config, save, p1)
            if phase == 1:
                p1 = results
        except SweepFailed as e:
            # Per-row containment stops at the sweep: with every row lost
            # there is no study to report, and the later phases would only
            # repeat the failure. The diagnostics below still run; the exit
            # code and the last lines say what happened.
            failure = f"phase {phase}: {e}"
            print(f"\nFAILED {failure}")
            break

    if drain_handler is not None:
        drain_handler.uninstall()
        if drain_handler.requested:
            print("\nNOTE: run was drained by a signal; unfinished serving "
                  "requests (if a --serving-journal was set) can be "
                  "finished with: resume-serving <journal-dir>")

    if config.profile_trace_dir:
        # Terminal-friendly device-op breakdown of the captured trace — the
        # analysis TensorBoard would show, without leaving the shell.
        try:
            from fairness_llm_tpu.utils.profiling import summarize_trace

            # One parse of all planes; prefer the TPU device planes and fall
            # back to host planes on CPU-only runs.
            summaries = summarize_trace(
                config.profile_trace_dir, top_k=8, device_filter=""
            )
            tpu = [s for s in summaries if "TPU" in s.device]
            for summary in tpu or summaries:
                print("\n" + summary.format())
        except Exception as e:  # noqa: BLE001 — diagnostics must not fail the run
            logger.warning("trace summary unavailable: %s", e)

    if config.telemetry_dir:
        # End-of-run snapshot (JSON + Prometheus text) and the terminal
        # report — the telemetry sibling of the trace summary above.
        from fairness_llm_tpu import telemetry as T

        try:
            path = T.write_snapshot(T.get_registry(), config.telemetry_dir)
            print("\n" + T.render_report(T.snapshot(T.get_registry())))
            print(f"\ntelemetry snapshot: {path}")
        except Exception as e:  # noqa: BLE001 — diagnostics must not fail the run
            logger.warning("telemetry snapshot unavailable: %s", e)
        finally:
            if telemetry_sink is not None:
                T.install_event_sink(None)
                telemetry_sink.close()

    # Perfetto timeline export. The telemetry dir ALWAYS gets its bundle
    # copy (trace.json beside the snapshot — what `telemetry-report
    # --timeline` and `validate_telemetry --require-profile` read);
    # --trace-out adds/redirects an extra copy at an explicit path.
    trace_paths = []
    if config.telemetry_dir:
        trace_paths.append(f"{config.telemetry_dir}/trace.json")
    if config.telemetry.trace_out \
            and config.telemetry.trace_out not in trace_paths:
        trace_paths.append(config.telemetry.trace_out)
    if trace_paths:
        from fairness_llm_tpu.telemetry import get_timeline

        try:
            for tp in trace_paths:
                out = get_timeline().export(tp)
                print(f"device-step timeline: {out} "
                      "(open at https://ui.perfetto.dev)")
        except Exception as e:  # noqa: BLE001 — diagnostics must not fail the run
            logger.warning("timeline export unavailable: %s", e)

    print("\n" + "=" * 60)
    print("RUN FAILED" if failure else "RUN COMPLETE")
    for name, dt in timings.items():
        print(f"  {name}: {dt:.1f}s")
    print(f"results under: {config.results_dir}/")
    if failure:
        print(f"FAILED {failure}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
