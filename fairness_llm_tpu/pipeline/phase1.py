"""Phase 1 — bias detection sweep (reference ``run_phase1``,
``phase1_bias_detection.py:266-441``; call stack SURVEY.md §3.2).

Pipeline: MovieLens -> base preferences -> counterfactual profile grid ->
**batched decode of every profile prompt** -> parse -> group -> fairness
metrics -> JSON results.

TPU-first deltas vs the reference:
- The reference's hot loop is 45 sequential API round-trips with sleep-based
  rate limiting; here the whole sweep is tokenized into chunks of
  ``decode_batch_size`` and each chunk is ONE device program.
- Metrics run as jit kernels over interned ID arrays (``metrics/``).
- SNSR/SNSV (Zhang et al. FaiRLLM) computed against a neutral
  (demographics-withheld) decode — the BASELINE.json tracked metric the
  reference only approximates with Jaccard IF.
- Checkpoints are written every ``checkpoint_every`` profiles AND read back:
  ``resume=True`` skips already-decoded profiles (reference writes but never
  reads its checkpoints, SURVEY.md §5.4).
- Equal opportunity matches on canonicalized titles, fixing the reference's
  vacuous EO=1.0 (SURVEY.md §8.2); noted in result metadata.
"""

from __future__ import annotations

import logging
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Set, Tuple

from fairness_llm_tpu import metrics as M
from fairness_llm_tpu.config import Config, default_config
from fairness_llm_tpu.data import (
    create_base_preferences,
    create_profile_grid,
    load_movielens,
)
from fairness_llm_tpu.data.profiles import Profile, profile_pairs
from fairness_llm_tpu.pipeline import results as R
from fairness_llm_tpu.pipeline.backends import DecodeBackend, backend_for
from fairness_llm_tpu.pipeline.parsing import canonicalize, parse_numbered_list
from fairness_llm_tpu.pipeline.prompts import (
    check_late_divergence,
    recommendation_prompt,
)
from fairness_llm_tpu.telemetry import (
    Heartbeat,
    get_fairness_monitor,
    get_registry,
    group_exposure,
    publish_offline_reference,
)
from fairness_llm_tpu.utils.progress import print_progress

logger = logging.getLogger(__name__)


def decode_sweep(
    backend: DecodeBackend,
    prompts: Sequence[str],
    keys: Sequence[str],
    config: Config,
    phase: str,
    done: Optional[Dict[str, Dict]] = None,
    settings=None,
    parse=parse_numbered_list,
    save_checkpoints: bool = True,
) -> Dict[str, Dict]:
    """Chunked batched decode with checkpointing; shared by phases 1 and 3.

    Returns {key: {recommendations, raw_response}} in input order, reusing
    entries already present in ``done`` (resume path).
    """
    from fairness_llm_tpu.pipeline.backends import shared_prefix_ids
    from fairness_llm_tpu.utils import (
        require_some_decoded,
        with_failure_containment,
    )

    generate = with_failure_containment(backend.generate)
    attempted: List[Optional[str]] = []  # this call's texts (None = failed)
    # Prefix-cache key computed over the FULL sweep (not per chunk), so
    # resumed and uninterrupted runs split attention identically.
    prefix_ids = shared_prefix_ids(backend, list(prompts))
    done = dict(done or {})
    chunk = max(config.decode_batch_size, 1)
    # Interactive runs get the reference's live carriage-return bar; the
    # per-chunk log line then drops to DEBUG so it can't splice into the
    # un-newlined bar. Piped/driver runs keep the INFO lines and no bar.
    interactive = getattr(sys.stderr, "isatty", lambda: False)()
    last_drawn = -1
    # Low-frequency liveness pulse for multi-hour sweeps (at most one INFO
    # line + JSONL event per interval) — the per-chunk lines above scroll
    # away or drop to DEBUG; this one is for "is it still moving".
    heartbeat = Heartbeat(interval_s=30.0, name=phase)
    # Chunk over ABSOLUTE positions in the full prompt list (not the remaining
    # todo list) so each chunk's decode seed is identical whether or not the
    # run was resumed mid-sweep — resume must not change sampling.
    for start in range(0, len(keys), chunk):
        batch = [
            (k, p)
            for k, p in zip(keys[start : start + chunk], prompts[start : start + chunk])
            if k not in done
        ]
        if not batch:
            continue
        texts = generate(
            [p for _, p in batch],
            settings,
            seed=config.random_seed + start,
            keys=[k for k, _ in batch],
            prefix_ids=prefix_ids,
        )
        attempted.extend(texts)
        mon = get_fairness_monitor()
        for (k, _), text in zip(batch, texts):
            if text is None:  # contained decode failure — see utils/failures.py
                done[k] = {"recommendations": [], "raw_response": "", "error": "decode_failed"}
            else:
                done[k] = {"recommendations": parse(text), "raw_response": text}
            if mon.active:
                # Streaming fairness accumulators (telemetry/fairness.py):
                # the content side of the pair watch + the per-group
                # DP/IF/exposure folds. Error entries stream too — the
                # offline metrics include their empty rec lists, and the
                # live gauges must match them at end of run.
                mon.observe_output(k, done[k]["recommendations"],
                                   error="error" in done[k])
        if mon.active:
            mon.maybe_refresh()
        completed = len(done)
        if save_checkpoints and config.checkpoint_every and (
            completed % config.checkpoint_every < chunk or start + chunk >= len(keys)
        ):
            # Failed entries stay out of checkpoints so --resume retries them.
            ok = {k: v for k, v in done.items() if "error" not in v}
            R.save_checkpoint(ok, config.results_dir, phase, completed)
        if interactive:
            logger.debug("%s sweep: %d/%d decoded", phase, completed, len(keys))
            print_progress(completed, len(keys), prefix=f"{phase} ")
            last_drawn = completed
        else:
            logger.info("%s sweep: %d/%d decoded", phase, completed, len(keys))
        heartbeat.poke(completed=completed, total=len(keys))
    if 0 <= last_drawn < len(keys):
        # A resume whose tail chunks were all cached leaves the bar mid-line;
        # finish it so subsequent stderr output starts on a fresh line.
        print_progress(len(keys), len(keys), prefix=f"{phase} ")
    # Containment is per row; a sweep that lost every row it attempted is a
    # failed run, not a study over empty lists (utils/failures.SweepFailed).
    require_some_decoded(attempted, f"{phase} sweep")
    mon = get_fairness_monitor()
    if mon.active:
        # Backfill entries the stream never saw (a resume's cached
        # checkpoint rows) — observe_output dedups, so streamed keys
        # no-op and the run-window accumulators cover exactly the
        # returned result set — then refresh the derived gauges.
        for k in keys:
            if k in done:
                mon.observe_output(k, done[k]["recommendations"],
                                   error="error" in done[k])
        mon.refresh()
    return {k: done[k] for k in keys if k in done}


def group_by(profiles: Sequence[Profile], recs: Dict[str, Dict], attr: str) -> Dict[str, List[List[str]]]:
    out: Dict[str, List[List[str]]] = defaultdict(list)
    for p in profiles:
        if p.id in recs:
            out[getattr(p, attr)].append(recs[p.id]["recommendations"])
    return dict(out)


def measure_demographic_parity(
    recommendations_by_group: Dict[str, List[List[str]]],
    group_counts_fn=None,
) -> Tuple[float, Dict]:
    """Reference-parity wrapper (``phase1_bias_detection.py:214-218``).
    ``group_counts_fn`` swaps the count reduction (the dp-psum study path)."""
    return M.demographic_parity(recommendations_by_group, group_counts_fn)


def measure_individual_fairness(
    profiles: Sequence[Profile], recommendations: Dict[str, List[str]]
) -> Tuple[float, List[float]]:
    """Reference-parity wrapper (``phase1_bias_detection.py:220-239``):
    mean Jaccard over counterfactual pairs differing in one attribute."""
    return M.individual_fairness(profile_pairs(profiles), recommendations)


def measure_equal_opportunity(
    recommendations_by_group: Dict[str, List[List[str]]],
    qualified: Set[str],
    group_counts_fn=None,
) -> Tuple[float, Dict[str, float]]:
    """Reference-parity wrapper (``phase1_bias_detection.py:241-263``) with
    canonicalized title matching (fixes the vacuous-1.0 bug, SURVEY.md §8.2).
    The canonicalization policy lives ONLY here — both the host and the
    dp-psum reduction (``group_counts_fn``) paths go through this wrapper."""
    canon_groups = {
        g: [canonicalize(r) for r in lists]
        for g, lists in recommendations_by_group.items()
    }
    return M.equal_opportunity(
        canon_groups, set(canonicalize(sorted(qualified))), group_counts_fn
    )


def register_fairness_study(profiles: Sequence[Profile]):
    """Arm the fairness monitor (telemetry/fairness.py) for one sweep:
    every profile's group memberships and the full counterfactual pair
    grid. Serving requests then carry the tags (``ServingBackend`` stamps
    them), the scheduler's terminal paths feed the neutrality audit, and
    ``decode_sweep``'s parse step feeds the streaming DP/IF/exposure
    accumulators — whose end-of-run values the offline metrics below
    cross-check. Returns the monitor."""
    mon = get_fairness_monitor()
    mon.begin_study()
    by_id = {p.id: p for p in profiles}
    for p in profiles:
        mon.register_request(p.id, {"gender": p.gender, "age": p.age})
    for a, b in profile_pairs(profiles):
        pa, pb = by_id[a], by_id[b]
        attr = next(x for x in ("gender", "age", "occupation")
                    if getattr(pa, x) != getattr(pb, x))
        mon.register_pair(f"{a}|{b}", a, b, attr)
    return mon


def qualified_movies(data, top_n: int = 10, seed: int = 42) -> List[str]:
    """'Qualified' set for equal opportunity: the corpus's top-rated popular
    movies (the reference hard-codes 10 classics that never textually match
    model output — SURVEY.md §8.2; we derive the set from data and canonicalize)."""
    prefs = create_base_preferences(data, num_movies=top_n, seed=seed)
    return prefs["watched_movies"]


def run_phase1(
    config: Optional[Config] = None,
    model_name: Optional[str] = None,
    num_profiles: Optional[int] = None,
    save: bool = True,
    backend: Optional[DecodeBackend] = None,
    resume: bool = False,
) -> Dict:
    """Full bias-detection sweep; returns the reference-shaped results dict."""
    config = config or default_config()
    model_name = model_name or config.default_model_phase1
    t0 = time.time()

    data = load_movielens(config.data_dir, seed=config.random_seed)
    base_prefs = create_base_preferences(data, seed=config.random_seed)
    profiles = create_profile_grid(base_prefs, config, num_profiles)

    if backend is None:
        backend = backend_for(model_name, config, catalog=data.titles)
    settings = config.settings_for(model_name) if model_name != "simulated" else None

    # --- the sweep: demographic prompts + one neutral prompt set for SNSR/SNSV
    prompts = [recommendation_prompt(p) for p in profiles]
    keys = [p.id for p in profiles]
    # Prefix-reuse layout check (pipeline/prompts.py): counterfactual pairs
    # must diverge LATE (demographics last) or serving's admission has
    # nothing to share. Measured every run, warned when violated, recorded
    # in metadata below; tools/prefix_stats.py inspects it pre-run.
    prompt_by_key = dict(zip(keys, prompts))
    divergence = check_late_divergence(
        [(prompt_by_key[a], prompt_by_key[b])
         for a, b in profile_pairs(profiles)],
        phase="phase1",
    )
    neutral_keys = []
    per_combo = num_profiles or config.profiles_per_combo
    for i in range(per_combo):
        neutral_keys.append(f"neutral_{i:04d}")
    neutral_profiles = [
        Profile(
            id=k, gender="", age="", occupation=config.occupation,
            watched_movies=base_prefs["watched_movies"],
            favorite_genres=base_prefs["favorite_genres"],
        )
        for k in neutral_keys
    ]
    neutral_prompts = [recommendation_prompt(p, anonymize=True) for p in neutral_profiles]

    mon = None
    if config.telemetry.fairness_obs:
        mon = register_fairness_study(profiles)

    if hasattr(backend, "spec_totals"):
        # Reused/injected backends may carry speculation counters from
        # earlier runs; this record is THIS sweep's decodes only.
        backend.spec_totals = None
    if hasattr(backend, "serve_totals"):
        backend.serve_totals = None  # same reset for serving counters
    done = R.load_latest_checkpoint(config.results_dir, "phase1") if resume else {}
    recs = decode_sweep(
        backend,
        list(prompts) + neutral_prompts,
        list(keys) + neutral_keys,
        config,
        "phase1",
        done=done,
        settings=settings,
        save_checkpoints=save,
    )
    neutral_recs = [recs.pop(k) for k in neutral_keys if k in recs]

    # --- grouping + metrics (jit kernels over interned IDs)
    by_gender = group_by(profiles, recs, "gender")
    by_age = group_by(profiles, recs, "age")

    # When the sweep itself ran dp-sharded, the metric reduction stays on
    # device too (SURVEY §7.2): per-profile count matrices segment-sum locally
    # and psum over dp; only the [G, V] group summary and final scalars reach
    # the host. Study-level equality with the host path is asserted in
    # tests/test_pipeline_sharded.py.
    mesh = getattr(getattr(backend, "engine", None), "mesh", None)
    use_device_reduction = mesh is not None and mesh.shape.get("dp", 1) > 1
    qualified = set(qualified_movies(data, seed=config.random_seed))
    counts_fn = None
    if use_device_reduction:
        from fairness_llm_tpu.metrics.sharded import mesh_group_counts_fn

        counts_fn = mesh_group_counts_fn(mesh)
    dp_gender, dp_gender_detail = measure_demographic_parity(by_gender, counts_fn)
    dp_age, dp_age_detail = measure_demographic_parity(by_age, counts_fn)
    eo_score, eo_rates = measure_equal_opportunity(by_gender, qualified, counts_fn)
    # Age is the second sensitive axis everywhere else (DP, SNSR/SNSV); give
    # EO the same both-axes treatment (the reference measures gender only).
    eo_age, eo_age_rates = measure_equal_opportunity(by_age, qualified, counts_fn)

    flat_recs = {pid: r["recommendations"] for pid, r in recs.items()}
    if_score, if_sims = measure_individual_fairness(profiles, flat_recs)

    neutral_flat = [t for r in neutral_recs for t in r["recommendations"]]
    recs_by_gender_flat = {
        g: [t for lst in lists for t in lst] for g, lists in by_gender.items()
    }
    snsr, snsv, sns_sims = M.snsr_snsv(neutral_flat, recs_by_gender_flat)
    # FaiRLLM evaluates every sensitive attribute; age is the second axis.
    recs_by_age_flat = {
        a: [t for lst in lists for t in lst] for a, lists in by_age.items()
    }
    snsr_age, snsv_age, sns_sims_age = M.snsr_snsv(neutral_flat, recs_by_age_flat)

    # Fairness observability cross-check (telemetry/fairness.py): publish
    # the OFFLINE scores as fairness_offline_* gauges so `validate_telemetry
    # --require-fairness` can assert the streaming gauges match them to fp
    # tolerance, and carry both sides in the result metadata below.
    fairness_block = None
    if mon is not None and mon.active:
        expo_gender, _ = group_exposure(by_gender)
        expo_age, _ = group_exposure(by_age)
        publish_offline_reference(
            {"gender": dp_gender, "age": dp_age}, if_score=if_score,
            exposure={"gender": expo_gender, "age": expo_age},
        )
        fairness_block = {
            "live": mon.live_values(),
            "offline": {
                "dp": {"gender": dp_gender, "age": dp_age},
                "individual_fairness": if_score,
                "exposure_ratio": {"gender": expo_gender, "age": expo_age},
            },
        }

    elapsed = time.time() - t0
    # Phase-level telemetry (component="phase1"): wall-time distribution
    # across runs of this process plus decode-failure visibility; the
    # results-dict metadata below stays the durable record.
    reg = get_registry()
    reg.histogram("phase_wall_s", component="phase1").observe(elapsed)
    reg.counter("phase_runs_total", component="phase1").inc()
    reg.counter("profiles_decoded_total", component="phase1").inc(len(recs))
    reg.counter("decode_failures_total", component="phase1").inc(
        sum(1 for r in recs.values() if "error" in r)
    )
    results = {
        "metadata": {
            "phase": 1,
            "model": backend.name,
            "num_profiles": len(profiles),
            "timestamp": time.time(),
            "elapsed_seconds": elapsed,
            "notes": (
                "equal_opportunity uses canonicalized titles (reference's raw-string "
                "matching yields vacuous 1.0); snsr/snsv are net-new vs reference"
            ),
            # provenance of the DP/EO reduction: "dp-psum" = on-device over the
            # mesh the sweep decoded on; "host" = single-device numpy+jit path
            "metric_reduction": "dp-psum" if use_device_reduction else "host",
            # the served weight mode, read from the ENGINE (the serving
            # truth), so an int8-weight study record witnesses the quantized
            # path in its own metadata; None for non-engine backends
            "weight_quant": getattr(
                getattr(getattr(backend, "engine", None), "config", None),
                "weight_quant", None,
            ),
            # corpus identity — committed records pin THIS (regression tests
            # compare only when provenance matches) instead of requiring the
            # ML-1M data to be absent
            "corpus": data.provenance(),
            # prompt-lookup speculative decoding counters for the whole sweep
            # (None when speculation was off / inapplicable / non-engine)
            "speculation": (
                backend.spec_totals.as_dict()
                if getattr(backend, "spec_totals", None) is not None else None
            ),
            # continuous-batching serving counters for the whole sweep
            # (None unless the sweep ran through a ServingBackend)
            "serving": (
                backend.serve_totals.as_dict()
                if getattr(backend, "serve_totals", None) is not None else None
            ),
            # fairness-observability snapshot: the streaming gauges' end-of-
            # run values beside the offline scores — the live-vs-offline
            # cross-check this study artifact carries (None when
            # --fairness-obs was off)
            "fairness": fairness_block,
            # counterfactual-pair shared-prefix fractions (byte LCP / max
            # len) — the layout property serving's prefix reuse
            # rides on; see pipeline/prompts.py check_late_divergence
            "prompt_divergence": divergence,
        },
        "profiles": [p.to_dict() for p in profiles],
        "recommendations": {
            pid: {**r, "profile_id": pid, "model": backend.name} for pid, r in recs.items()
        },
        "neutral_recommendations": [r["recommendations"] for r in neutral_recs],
        "metrics": {
            "demographic_parity_gender": {"score": dp_gender, **dp_gender_detail},
            "demographic_parity_age": {"score": dp_age, **dp_age_detail},
            "individual_fairness": {"score": if_score, "num_pairs": len(if_sims)},
            "equal_opportunity": {"score": eo_score, "group_scores": eo_rates},
            "equal_opportunity_age": {"score": eo_age, "group_scores": eo_age_rates},
            "snsr_snsv": {"snsr": snsr, "snsv": snsv, "group_similarities": sns_sims},
            "snsr_snsv_age": {
                "snsr": snsr_age, "snsv": snsv_age, "group_similarities": sns_sims_age,
            },
        },
    }
    if save:
        R.save_results(results, f"{config.results_dir}/phase1/phase1_results.json")
    logger.info(
        "phase1 done in %.1fs: DP(gender)=%.4f DP(age)=%.4f IF=%.4f EO=%.4f SNSR=%.4f",
        elapsed, dp_gender, dp_age, if_score, eo_score, snsr,
    )
    return results


def print_phase1_summary(results: Dict) -> None:
    m = results["metrics"]
    print("\n" + "=" * 60)
    print("PHASE 1 SUMMARY — bias detection")
    print("=" * 60)
    print(f"model: {results['metadata']['model']}   profiles: {results['metadata']['num_profiles']}")
    print(f"demographic parity (gender): {m['demographic_parity_gender']['score']:.4f}")
    print(f"demographic parity (age):    {m['demographic_parity_age']['score']:.4f}")
    print(f"individual fairness:         {m['individual_fairness']['score']:.4f}")
    print(f"equal opportunity:           {m['equal_opportunity']['score']:.4f}")
    if "equal_opportunity_age" in m:
        print(f"equal opportunity (age):     {m['equal_opportunity_age']['score']:.4f}")
    print(f"SNSR/SNSV (gender): {m['snsr_snsv']['snsr']:.4f} / {m['snsr_snsv']['snsv']:.4f}")
    if "snsr_snsv_age" in m:
        print(f"SNSR/SNSV (age):    {m['snsr_snsv_age']['snsr']:.4f} / {m['snsr_snsv_age']['snsv']:.4f}")
    for name, score in (
        ("gender parity", m["demographic_parity_gender"]["score"]),
        ("age parity", m["demographic_parity_age"]["score"]),
    ):
        level = "fair" if score >= 0.8 else ("moderate" if score >= 0.7 else "biased")
        print(f"  -> {name}: {level}")


if __name__ == "__main__":  # standalone entry (reference phase files are executable)
    import argparse

    ap = argparse.ArgumentParser(description="Phase 1: bias detection sweep")
    ap.add_argument("--model", default=None)
    ap.add_argument("--profiles", type=int, default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--no-save", action="store_true")
    a = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    res = run_phase1(
        model_name=a.model, num_profiles=a.profiles, save=not a.no_save, resume=a.resume
    )
    print_phase1_summary(res)
