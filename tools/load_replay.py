"""Load-replay drill: trace-driven synthetic traffic with SLO-coupled
autoscaling — the elastic-fleet proof behind docs/SERVING.md §Elastic
fleet (ISSUE 11).

What it does, in one process, deterministically:

1. generates a seeded synthetic trace (``serving/replay.py``): a diurnal
   session-arrival curve with one flash-crowd burst, heavy-tailed session
   lengths over a million-user id space, and a mixed interactive/batch QoS
   population — then regenerates it and asserts the JSONL is
   byte-identical (same seed -> same trace, half one of the determinism
   contract);
2. replays the trace time-compressed against a ``ReplicaSet`` that starts
   at ONE replica with the autoscaler armed: the burst drives the
   fast-window SLO burn up, the controller adds canary-gated standby
   replicas (which must then actually serve traffic), and a
   ``replica_crashes_at`` schedule kills the first standby in the middle
   of the burst — fence, zero-grace drain, journal migration, canary-gated
   rejoin, all under live replayed load;
3. rides the quiet post-burst tail until the controller retires the
   surplus replicas through the drain/migration path — the full elastic
   cycle (up AND down) in one replay;
4. asserts the zero-loss ledger: every accepted event reached a terminal
   Result (``lost == 0``), migrated == recovered, the journal holds no
   unfinished record, and the final fleet is whole
   (``fleet_healthy_replicas == fleet_replicas``);
5. asserts TOKEN PARITY for every completed request against the static
   engine (one baseline decode per unique (prompt, budget) combo) — so
   migrated and retired-replica survivors provably decoded the same
   stream the engine alone would have;
6. replays a second, fault-free same-seed trace TWICE on fresh fleets and
   asserts the two runs admitted the identical request set and produced
   the identical token map (half two of the determinism contract: a
   same-seed re-run reproduces the admitted-token set exactly);
7. floods a deliberately under-provisioned one-replica fleet (tiny
   queue, autoscaler pinned at 1) with a burst trace so the shed ladder
   refuses admissions WITH retry-after advice, and asserts the driver
   honored the advice (``replay_retry_after_honored_total`` >= 1: the
   replay client backs off and re-offers instead of hammering the gate)
   while the zero-loss ledger still closes (``lost == 0`` — every
   honored retry ends in a terminal Result or a recorded re-shed);
8. writes the telemetry snapshot for
   ``tools/validate_telemetry.py --require-autoscale`` (>=1 scale-up,
   >=1 scale-down, replay accepted == terminal, migrated == recovered,
   final fleet healthy).

Usage (CI runs exactly this):
    JAX_PLATFORMS=cpu python tools/load_replay.py --telemetry-dir replay-tel
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fairness_llm_tpu.config import (  # noqa: E402
    AutoscaleConfig,
    FleetConfig,
    IntegrityConfig,
    ModelSettings,
    OverloadConfig,
    ResilienceConfig,
    ServingConfig,
)
from fairness_llm_tpu.models.configs import get_model_config  # noqa: E402
from fairness_llm_tpu.resilience import ServingJournal  # noqa: E402
from fairness_llm_tpu.runtime.engine import DecodeEngine  # noqa: E402
from fairness_llm_tpu.serving import (  # noqa: E402
    ReplayDriver,
    ReplicaSet,
    TraceConfig,
    generate_trace,
    write_trace,
)
from fairness_llm_tpu.serving.replay import DEFAULT_PROMPTS  # noqa: E402
from fairness_llm_tpu.telemetry.slo import SLOTargets, set_slo_targets  # noqa: E402
from fairness_llm_tpu.utils.failures import ScriptedFaultInjector  # noqa: E402

GREEDY = ModelSettings(temperature=0.0, max_tokens=16)
SERVING = ServingConfig(enabled=True, num_slots=2, queue_capacity=16,
                        max_prompt_len=96, max_new_tokens=16, decode_chunk=4)
RESILIENCE = ResilienceConfig(enabled=True, breaker_threshold=2,
                              breaker_cooldown_s=0.05)
# Harness-shaped SLO targets: the off-burst load meets a 0.4 s TTFT on the
# tiny CPU engine; the burst's queueing blows through it, which is exactly
# the burn the autoscaler exists to act on. A short fast window lets the
# burn decay within the compressed quiet tail.
SLO = SLOTargets(ttft_p95_s=0.4, e2e_p99_s=30.0, error_rate=0.02,
                 fast_window_s=2.0, slow_window_s=20.0)

# The drill's prompt catalog — the module's own sweep-shaped default,
# truncated (six shapes keep the compiled-bucket count small on CPU).
PROMPTS = DEFAULT_PROMPTS[:6]


def trace_config(seed: int, duration: float, burst: bool) -> TraceConfig:
    bursts = ((duration / 3.0, duration / 4.0, 8.0),) if burst else ()
    return TraceConfig(
        seed=seed, duration_s=duration, users=1_000_000,
        base_sessions_per_s=0.5, diurnal_amplitude=0.5,
        diurnal_period_s=duration,  # one "day" spans the trace
        bursts=bursts, session_tail_alpha=1.3, session_max_turns=4,
        think_time_s=3.0, interactive_frac=0.8,
        max_tokens_choices=(4, 6, 8),
    )


def build_fleet(engine, journal=None, injector=None, max_replicas=3,
                compression=4.0, overload=True, name=None) -> ReplicaSet:
    ov = OverloadConfig(
        enabled=True,
        # Time-dependent knobs scale with the compression factor, the same
        # way the driver scales request deadlines: 5 trace-seconds of
        # queue aging is 5/c wall seconds at compression c.
        aging_s=5.0 / compression,
        healthy_window_s=2.0 / compression,
        deadline_admission=False,  # the smoke trace carries no deadlines
        queue_window_s=1.0, eval_interval_s=0.1,
        burn_threshold=8.0,  # the autoscaler acts first; shedding is the
        retry_after_s=0.2,   # last resort at this drill's offered load
    ) if overload else None
    return ReplicaSet(
        engine, SERVING, settings=GREEDY,
        fleet=FleetConfig(replicas=1, fence_cooldown_s=0.3),
        resilience=RESILIENCE,
        journal=journal, fault_injector=injector,
        integrity=IntegrityConfig(canary_max_tokens=8),
        overload=ov, name=name,
        autoscale=AutoscaleConfig(
            enabled=True, min_replicas=1, max_replicas=max_replicas,
            up_burn_threshold=2.0, up_queue_frac=0.75, up_overload_level=1,
            up_window_s=0.15, down_burn_threshold=0.5,
            down_queue_frac=0.1, down_load_frac=0.5, down_window_s=0.8,
            cooldown_s=0.4, eval_interval_s=0.05,
        ),
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--telemetry-dir", default=None,
                    help="write events.jsonl + the validated snapshot here")
    ap.add_argument("--journal-dir", default=None,
                    help="serving journal dir (default: a temp dir)")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--duration", type=float, default=60.0,
                    help="trace span in TRACE seconds (default 60)")
    ap.add_argument("--compression", type=float, default=4.0,
                    help="trace-to-wall time compression (default 4)")
    ap.add_argument("--max-replicas", type=int, default=3)
    ap.add_argument("--max-wall", type=float, default=240.0,
                    help="per-replay wall guard in seconds")
    ap.add_argument("--skip-determinism", action="store_true",
                    help="skip the same-seed re-run phase (faster)")
    a = ap.parse_args()

    from fairness_llm_tpu import telemetry as T

    sink = T.configure(a.telemetry_dir) if a.telemetry_dir else None
    journal_dir = a.journal_dir or tempfile.mkdtemp(prefix="replay-journal-")
    set_slo_targets(SLO)

    problems = []

    def check(ok: bool, what: str) -> None:
        print(("PASS" if ok else "FAIL") + f"  {what}")
        if not ok:
            problems.append(what)

    # -- 1. trace generation + byte determinism ------------------------------
    tcfg = trace_config(a.seed, a.duration, burst=True)
    events = generate_trace(tcfg, PROMPTS)
    lines = [ev.to_json() for ev in events]
    lines2 = [ev.to_json() for ev in generate_trace(tcfg, PROMPTS)]
    check(lines == lines2 and len(events) > 20,
          f"same seed -> byte-identical trace ({len(events)} events, "
          f"{a.duration:g} trace-s, "
          f"{sum(e.qos == 'interactive' for e in events)} interactive / "
          f"{sum(e.qos == 'batch' for e in events)} batch)")
    if a.telemetry_dir:
        write_trace(os.path.join(a.telemetry_dir, "replay_trace.jsonl"),
                    events, tcfg)

    # -- 2-4. the elastic replay ---------------------------------------------
    engine = DecodeEngine(get_model_config("tiny-test"), seed=0)
    journal = ServingJournal(journal_dir)
    # Crash the FIRST standby (r1) in the middle of the burst, in trace
    # time: the burst spans [duration/3, duration/3 + duration/4), so 50%
    # through the trace is deep inside it — r1 has joined and is holding
    # burst backlog, so the fence has live work to migrate.
    crash_t = 0.5 * a.duration
    injector = ScriptedFaultInjector(replica_crashes_at={"r1": crash_t})
    fleet = build_fleet(engine, journal=journal, injector=injector,
                        max_replicas=a.max_replicas,
                        compression=a.compression)
    driver = ReplayDriver(
        fleet, events, compression=a.compression, fault_injector=injector,
        max_wall_s=a.max_wall,
        # Quiet tail: long enough past the last arrival for the burn to
        # decay (fast window) and the scale-down hysteresis + cooldowns to
        # walk the fleet back to min_replicas.
        tail_s=0.8 * a.duration,
    )
    report = driver.run()
    print("replay:", report.summary())

    auto = fleet.autoscaler
    check(not report.timed_out, "replay finished inside the wall guard")
    check(report.lost == 0,
          f"zero accepted-then-lost ({report.accepted} accepted, "
          f"{report.terminal - report.gate_sheds} terminal)")
    check(auto.scale_ups >= 1,
          f"burst drove >=1 burn-driven scale-up ({auto.scale_ups})")
    check(auto.scale_downs >= 1,
          f"quiet tail drove >=1 scale-down ({auto.scale_downs})")
    check(len(fleet.replicas) == 1 and fleet.healthy_count == 1,
          f"fleet back to min_replicas and healthy "
          f"({len(fleet.replicas)} replicas, {fleet.healthy_count} "
          "healthy)")
    check(injector.replica_faults_fired == [("r1", "replica_crash")],
          f"scheduled replica crash fired at trace-t {crash_t:g} "
          f"({injector.replica_faults_fired})")
    reg = T.get_registry()
    fenced = reg.read_value("fleet_fenced_total", component="fleet",
                            replica="r1", reason="replica_crash")
    check(fenced >= 1, "crashed standby was fenced (fleet_fenced_total)")
    migrated = reg.read_value("fleet_migrated_requests_total",
                              component="fleet")
    recovered = reg.read_value("fleet_migrated_recovered_total",
                               component="fleet")
    check(migrated == recovered,
          f"migrated == recovered ({migrated:g} == {recovered:g})")
    served_r1 = sum(
        getattr(m, "value", 0) for m in reg.instruments()
        if getattr(m, "name", "") == "requests_finished_total"
        and getattr(m, "labels", {}).get("replica") == "r1"
    )
    check(served_r1 > 0,
          f"canary-gated standby r1 actually served traffic "
          f"({served_r1:g} requests finished)")
    unfinished = journal.unfinished()
    check(not unfinished,
          f"journal holds no unfinished record ({len(unfinished)})")

    # -- 5. token parity for EVERY completed request -------------------------
    by_id = {e.id: e for e in events}
    combos = sorted({(by_id[rid].prompt, by_id[rid].max_tokens)
                     for rid in report.tokens})
    baseline = {}
    for prompt, budget in combos:
        out = engine.generate(
            [prompt], dataclasses.replace(GREEDY, max_tokens=budget),
            share_prefix=False,
        )
        baseline[(prompt, budget)] = [
            int(t) for t in out.tokens[0] if t != engine.tokenizer.pad_id
        ]
    bad = []
    for rid, toks in report.tokens.items():
        ev = by_id[rid]
        ref = baseline[(ev.prompt, ev.max_tokens)]
        if list(toks) != ref[: len(toks)] or \
                len(toks) < min(len(ref), ev.max_tokens):
            bad.append(rid)
    check(not bad,
          f"token parity vs the static engine for all "
          f"{len(report.tokens)} completed requests "
          f"(incl. migrated/retired-replica survivors); mismatches: {bad[:4]}")

    # -- 6. same-seed re-run determinism -------------------------------------
    if not a.skip_determinism:
        det_cfg = trace_config(a.seed + 1, a.duration / 2.0, burst=False)
        det_events = generate_trace(det_cfg, PROMPTS)
        runs = []
        for run_idx in range(2):
            # Overload control OFF for the determinism phase: the claim is
            # "same seed -> identical admitted-token set", which needs an
            # under-capacity run where nothing sheds — backpressure alone
            # (the driver retries due arrivals) admits every event.
            # Named fleets: an unnamed det fleet would share the drill
            # fleet's label set and overwrite its final
            # fleet_replicas/fleet_healthy_replicas gauges before the
            # snapshot, so --require-autoscale would validate the wrong
            # fleet's wholeness.
            det_fleet = build_fleet(engine, max_replicas=a.max_replicas,
                                    compression=2.0 * a.compression,
                                    overload=False, name=f"det{run_idx}")
            det_driver = ReplayDriver(
                det_fleet, det_events, compression=2.0 * a.compression,
                max_wall_s=a.max_wall, tail_s=0.0,
            )
            runs.append(det_driver.run())
        r1, r2 = runs
        check(r1.lost == 0 and r2.lost == 0
              and r1.outcomes.get("shed", 0) == 0
              and r2.outcomes.get("shed", 0) == 0,
              "determinism runs: zero lost, zero shed (under-capacity)")
        check(set(r1.tokens) == set(r2.tokens)
              and len(r1.tokens) == len(det_events),
              f"same-seed re-run admitted the identical request set "
              f"({len(r1.tokens)} == {len(r2.tokens)} == "
              f"{len(det_events)})")
        check(r1.tokens == r2.tokens,
              "same-seed re-run produced the identical admitted-token set")

    # -- 7. retry-after honoring under deliberate overload --------------------
    # A fleet sized to lose: one replica the autoscaler cannot grow, a
    # queue a fraction of the drill's, and a depth-triggered shed ladder
    # with a short fuse. The burst MUST drive class sheds carrying
    # retry_after_s; the accounting question is what the driver does with
    # them (honor once, then record the retry's verdict).
    honored_before = reg.read_value("replay_retry_after_honored_total",
                                    component="replay")
    ov_cfg = trace_config(a.seed + 2, a.duration / 4.0, burst=True)
    ov_cfg = dataclasses.replace(
        ov_cfg, base_sessions_per_s=4.0, interactive_frac=0.5,
        session_max_turns=2, think_time_s=0.5,
    )
    ov_events = generate_trace(ov_cfg, PROMPTS)
    ov_fleet = ReplicaSet(
        engine,
        dataclasses.replace(SERVING, queue_capacity=6),
        settings=GREEDY,
        fleet=FleetConfig(replicas=1, fence_cooldown_s=0.3),
        resilience=RESILIENCE,
        integrity=IntegrityConfig(canary_max_tokens=8),
        name="ovreplay",
        overload=OverloadConfig(
            enabled=True, aging_s=1.0, deadline_admission=False,
            queue_frac_threshold=0.5, queue_window_s=0.3,
            healthy_window_s=0.3, eval_interval_s=0.02,
            burn_threshold=50.0,  # depth-driven: keep the trigger local
            retry_after_s=0.05,
        ),
        autoscale=AutoscaleConfig(enabled=True, min_replicas=1,
                                  max_replicas=1),
    )
    ov_report = ReplayDriver(
        ov_fleet, ov_events, compression=2.0 * a.compression,
        max_wall_s=a.max_wall, tail_s=0.5 * ov_cfg.duration_s,
    ).run()
    print("overload replay:", ov_report.summary())
    honored = reg.read_value("replay_retry_after_honored_total",
                             component="replay") - honored_before
    check(ov_report.gate_sheds >= 1,
          f"under-provisioned fleet shed at the gate "
          f"({ov_report.gate_sheds} gate sheds)")
    check(honored >= 1,
          f"driver honored retry_after_s on shed results "
          f"({honored:g} backoffs taken before re-offer)")
    check(not ov_report.timed_out and ov_report.lost == 0
          and ov_report.dropped == 0,
          f"overload replay ledger closed: zero accepted-then-lost, zero "
          f"dropped ({ov_report.accepted} accepted, "
          f"{ov_report.terminal} terminal)")

    # -- 8. snapshot ----------------------------------------------------------
    if a.telemetry_dir:
        path = T.write_snapshot(T.get_registry(), a.telemetry_dir)
        bad_snap = T.validate_snapshot(T.load_snapshot(path))
        check(not bad_snap, f"snapshot schema valid ({path})")
        if sink is not None:
            T.install_event_sink(None)
            sink.close()

    print(f"\nload replay drill: {'PASS' if not problems else 'FAIL'} "
          f"({len(problems)} problem(s))")
    return 1 if problems else 0


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    sys.exit(main())
