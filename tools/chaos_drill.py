"""Chaos drill: scripted faults + injected hang + silent corruption + a real
mid-run SIGTERM, then resume — the end-to-end proof behind
docs/RESILIENCE.md (resilience AND integrity layers).

What it does, in one process, deterministically:

1. builds a tiny CPU engine (numerics guards armed) and records an
   UNINTERRUPTED baseline (the greedy tokens every request should decode);
2. re-serves the same workload through a resilience-armed scheduler with a
   scripted fault mix (one transient decode fault, one permanent one, one
   prefill fault), one injected hang (watchdog-classified, no real sleep),
   one injected NaN corruption (guard-classified ``NumericsFault``), and a
   journal — and raises a REAL ``SIGTERM`` at itself the moment the late
   cohort reaches decode, so the ``GracefulDrain`` handler drains the run
   mid-flight;
3. resumes the journal's unfinished requests (``resume_serving``) in a
   fresh scheduler;
4. drills the at-rest integrity path: exports the engine's weights with a
   sha256 manifest, flips one BIT in the shard, and asserts the load is
   refused with an error naming the file;
5. drills the canary: a golden-prompt probe through a live scheduler
   matches its static-engine reference, then a tampered reference
   (standing in for silently-corrupt serving output) trips the decode
   breaker and the degradation ladder;
6. drills the REPLICA FLEET (ISSUE 6): serves the same workload through a
   2-replica ``ReplicaSet`` and kills replica r1 mid-sweep (scripted
   ``replica_crash``) — asserting zero lost requests, migrated survivors
   token-identical to the single-engine greedy baseline, the healthy
   replica serving throughout, and the killed replica rejoining through
   its canary warm-up probe (``fleet_healthy_replicas`` back to 2);
7. drills OVERLOAD CONTROL (ISSUE 8): sheds a provably-doomed deadline at
   admission (no prefill burned), then offers ~3x the queue's capacity
   with mixed QoS classes — asserting interactive TTFT p95 holds its SLO
   while batch sheds with explicit retry-after Results, zero
   accepted-then-lost requests, nonzero ``shed_total`` counters, and the
   controller de-escalating to level 0 after the flood
   (``validate_telemetry --require-overload`` gates it);
8. drills FAIRNESS OBSERVABILITY (ISSUE 9): byte-identical counterfactual
   pair probes (same prompt, different group tag) through a fault-free
   scheduler stay SILENT — every pair joins with zero divergence and no
   neutrality alert — then the same workload with decode faults targeted
   at ONE group's requests must raise ``fairness_alerts_total`` (group
   disparity in the impaired-rate audit) and count the divergent pairs
   with their members' serving events attributed (the requeues the
   injected faults caused); the rendered fairness report is written
   beside the snapshot (``fairness_report.txt``) for failure evidence;
9. drills the FUSED DISPATCH (ISSUE 14): the same workload through a
   ``--fuse-steps 4`` scheduler with an injected NaN landing INSIDE a
   fused window (four chunks in one compiled call) — the guard flag rides
   the fused carry, the whole dispatch is discarded at its boundary as
   one ``NumericsFault``, the poisoned rider requeues once, and every
   survivor decodes token-identical (fusion widens the blast radius per
   fault, never the outcome);
10. validates the ISSUE-4/5/6 acceptance: every request terminal (zero
   lost), survivors token-for-token equal to the baseline (zero corrupt
   records — the NaN chunk was retried, not delivered), the breaker cycle
   + hang + numerics fault + manifest failure + canary mismatch + fleet
   fence/migrate/rejoin all present in the telemetry snapshot
   (``validate_telemetry --require-fleet`` gates the fleet half), and the
   journal empty.

Usage (CI runs exactly this):
    JAX_PLATFORMS=cpu python tools/chaos_drill.py --telemetry-dir chaos-tel
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from fairness_llm_tpu.config import ModelSettings, ResilienceConfig, ServingConfig  # noqa: E402
from fairness_llm_tpu.models.configs import get_model_config  # noqa: E402
from fairness_llm_tpu.resilience import (  # noqa: E402
    GracefulDrain,
    ServingJournal,
    resume_serving,
)
from fairness_llm_tpu.runtime.engine import DecodeEngine  # noqa: E402
from fairness_llm_tpu.serving import ContinuousScheduler, Request, Result  # noqa: E402
from fairness_llm_tpu.utils.failures import ScriptedFaultInjector  # noqa: E402

GREEDY = ModelSettings(temperature=0.0, max_tokens=8)
SERVING = ServingConfig(enabled=True, num_slots=2, queue_capacity=64,
                        max_prompt_len=192, max_new_tokens=32, decode_chunk=4)
# Generous watchdog budget: only the injector's SIMULATED 3600 s stalls may
# classify as hangs — a real chunk on a loaded CI runner (first one includes
# XLA compilation) must never trip it, or the drill turns flaky.
RESILIENCE = ResilienceConfig(enabled=True, max_step_seconds=120.0,
                              breaker_threshold=1, breaker_cooldown_s=0.02,
                              drain_grace_s=30.0)

PROMPTS = {
    "ok0": "the quick brown fox",
    "flaky": "hello there friend",      # one transient decode fault
    "doomed": "abc abc abc abc abc",    # permanent decode fault -> failed
    "pfault": "one two three one two",  # one prefill fault
    "hangme": "recommend ten films please",  # one injected hang
    "nanme": "name five good books",    # one injected NaN corruption
    "late0": "zz zz zz",                # reaching decode triggers SIGTERM
    "late1": "a long prompt that shifts padding and lands in a bucket",
}


class SigtermOnSight(ScriptedFaultInjector):
    """Raises a real SIGTERM at our own process the first time the late
    cohort reaches decode — the GracefulDrain handler (installed around the
    serve) turns it into a drain request the scheduler honors at its next
    loop iteration. Deterministic 'preemption notice mid-run'."""

    def __init__(self, faults, hangs, corruptions=None):
        super().__init__(faults, hangs=hangs, corruptions=corruptions)
        self._fired_sigterm = False

    def maybe_fail(self, request_id, stage):
        if request_id == "late0" and stage == "decode" and not self._fired_sigterm:
            self._fired_sigterm = True
            signal.raise_signal(signal.SIGTERM)
        super().maybe_fail(request_id, stage)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--telemetry-dir", default=None,
                    help="write events.jsonl + the validated snapshot here")
    ap.add_argument("--trace-out", default=None,
                    help="write the device-step timeline as Chrome-trace "
                         "JSON (default <telemetry-dir>/trace.json) — the "
                         "drill's faults/fences/migrations render as "
                         "per-replica Perfetto lanes")
    ap.add_argument("--journal-dir", default=None,
                    help="serving journal dir (default: a temp dir)")
    a = ap.parse_args()

    from fairness_llm_tpu import telemetry as T

    sink = T.configure(a.telemetry_dir) if a.telemetry_dir else None
    journal_dir = a.journal_dir or tempfile.mkdtemp(prefix="chaos-journal-")

    # Incident engine (ISSUE 13): armed for the WHOLE drill with a cooldown
    # longer than any CI run, so per-(class, scope) dedup is absolute —
    # each injected fault family must produce EXACTLY one bundle however
    # many faults the storm lands. Bundles ride the telemetry artifact.
    inc_dir = os.path.join(
        a.telemetry_dir or tempfile.mkdtemp(prefix="chaos-incidents-"),
        "incidents",
    )
    T.arm_incidents(inc_dir, cooldown_s=3600.0)

    problems = []

    def check(ok: bool, what: str) -> None:
        print(("PASS" if ok else "FAIL") + f"  {what}")
        if not ok:
            problems.append(what)

    def bundles(cls: str, scope: str = None):
        found = [m for m in T.list_bundles(inc_dir) if m["class"] == cls]
        if scope is not None:
            found = [m for m in found if m.get("scope") == scope]
        return found

    def bundle_fault_ids(manifest) -> set:
        """Request ids named by the bundle's fault decisions — the
        'decision trail names the injected cause' witness."""
        import json as _json

        ids = set()
        with open(os.path.join(manifest["path"], "decisions.jsonl"),
                  encoding="utf-8") as f:
            for line in f:
                try:
                    d = _json.loads(line)
                except _json.JSONDecodeError:
                    continue
                if d.get("decision") == "fault":
                    ids.update((d.get("signals") or {}).get(
                        "request_ids", ()))
        return ids

    # Numerics guards armed: the injected NaN below must be caught by the
    # on-device finite flag, not delivered as garbage tokens.
    engine = DecodeEngine(get_model_config("tiny-test"), seed=0,
                          numerics_guards=True)

    # 1. Uninterrupted baseline: the tokens every survivor must reproduce.
    baseline = {}
    for rid, prompt in PROMPTS.items():
        out = engine.generate([prompt], GREEDY)
        baseline[rid] = np.asarray(out.tokens[0])

    # 2. The chaos run.
    journal = ServingJournal(journal_dir)
    inj = SigtermOnSight(
        faults={("flaky", "decode"): 1, ("doomed", "decode"): 2,
                ("pfault", "prefill"): 1},
        hangs={("hangme", "decode"): 1},
        corruptions={("nanme", "decode"): 1},
    )
    sched = ContinuousScheduler(engine, SERVING, settings=GREEDY,
                                fault_injector=inj, resilience=RESILIENCE,
                                journal=journal)
    reqs = [Request(prompt=p, id=rid, settings=GREEDY)
            for rid, p in PROMPTS.items()]
    with GracefulDrain():
        results = {r.id: r for r in sched.serve(reqs)}
    preempted = sorted(rid for rid, r in results.items()
                       if r.finish_reason == "preempted")
    print(f"chaos run: { {rid: r.finish_reason for rid, r in results.items()} }")
    check(set(results) == set(PROMPTS), "every request got a phase-1 Result")
    check(bool(preempted), "SIGTERM drained a late cohort to the journal")
    check(inj.hangs_fired == [("hangme", "decode")], "the hang fired once")
    check(inj.corruptions_fired == [("nanme", "decode")],
          "the NaN corruption fired once")
    check(sorted(r["id"] for r in journal.unfinished()) == preempted,
          "journal unfinished == preempted set")

    # Incident bundles for the section-2 fault families (ISSUE 13): the
    # fault STORM above (five scripted faults, re-opens, a hang, a NaN)
    # must dedup to exactly one bundle per family, each one's decision
    # trail naming the injected cause.
    injected = {"flaky", "doomed", "pfault", "hangme", "nanme"}
    bo = bundles("breaker_open", scope="serving")
    check(len(bo) == 1 and bool(bundle_fault_ids(bo[0]) & injected),
          "exactly one breaker_open bundle; decision trail names the "
          f"injected request(s) ({sorted(bundle_fault_ids(bo[0]) & injected) if bo else '-'})")
    wh = bundles("watchdog_hang")
    check(len(wh) == 1
          and "hangme" in wh[0].get("context", {}).get("request_ids", ()),
          "exactly one watchdog_hang bundle naming 'hangme'")
    nf = bundles("numerics_fault")
    check(len(nf) == 1
          and "nanme" in nf[0].get("context", {}).get("request_ids", ()),
          "exactly one numerics_fault bundle naming 'nanme'")

    # 3. Resume.
    resumed = resume_serving(engine, journal, serving=SERVING,
                             resilience=RESILIENCE)
    check(sorted(resumed) == preempted, "resume served exactly the journal")
    check(journal.unfinished() == [], "journal empty after resume")

    # 4. Acceptance: zero lost + survivor parity + breaker cycle visible.
    final = {**results, **resumed}
    lost = set(PROMPTS) - set(final)
    check(not lost, f"zero lost requests (missing: {sorted(lost) or 'none'})")
    check(not final["doomed"].ok and final["doomed"].finish_reason == "failed",
          "permanent fault terminated failed (requeue-once, not forever)")
    parity_ok, survivors = True, 0
    for rid, res in final.items():
        if not res.ok:
            continue
        survivors += 1
        n = len(res.tokens)
        ref = baseline[rid]
        if n == 0 or not np.array_equal(np.asarray(res.tokens), ref[:n]) \
                or not np.all(ref[n:] == engine.tokenizer.pad_id):
            parity_ok = False
            print(f"  parity break: {rid}: {list(res.tokens)} vs {list(ref)}")
    # Survivor floor: each chunk-wide fault (decode fault, hang, NaN chunk)
    # requeues BOTH riders of the 2-slot pool, so with five scripted faults
    # a rider can legitimately burn its single requeue on someone else's
    # fault and terminate failed — terminal and visible, never lost or
    # corrupt. 5-of-8 is this script's deterministic outcome; the hard
    # guarantees are the per-request checks around it.
    check(parity_ok and survivors >= len(PROMPTS) - 3,
          f"{survivors} survivors all token-for-token with baseline")
    nan_res = final["nanme"]
    check(nan_res.ok and np.array_equal(
              np.asarray(nan_res.tokens),
              baseline["nanme"][: len(nan_res.tokens)]),
          "NaN-corrupted request contained + retried to clean tokens")

    # 4. At-rest integrity: a bit-flipped weight shard must be REFUSED at
    # load with a manifest-digest error naming the file.
    from fairness_llm_tpu.integrity.manifest import IntegrityError  # noqa: E402
    from fairness_llm_tpu.runtime.weights import (  # noqa: E402
        load_checkpoint,
        save_checkpoint_hf,
    )

    wdir = tempfile.mkdtemp(prefix="chaos-weights-")
    save_checkpoint_hf(engine.config, engine.params, wdir)
    shard = os.path.join(wdir, "model.safetensors")
    # Clean round-trip first: the manifest must accept what it just hashed.
    load_checkpoint(engine.config, wdir)
    # Flip one bit deep in the tensor data region (past the header).
    ScriptedFaultInjector.flip_bit(shard, (os.path.getsize(shard) - 64) * 8)
    try:
        load_checkpoint(engine.config, wdir)
        check(False, "bit-flipped shard refused at load")
    except IntegrityError as e:
        check("model.safetensors" in str(e),
              f"bit-flipped shard refused, error names the file ({e})")
    itf = bundles("integrity_fault")
    check(len(itf) == 1 and "model.safetensors" in itf[0]["cause"],
          "exactly one integrity_fault bundle naming the flipped shard")

    # 5. Canary: golden-prompt probe through a live scheduler matches the
    # static-engine reference; a tampered reference (the comparator's view
    # of silently-corrupt serving output) trips the degradation ladder.
    from fairness_llm_tpu.integrity.canary import CanaryProbe  # noqa: E402
    from fairness_llm_tpu.resilience import BreakerBoard  # noqa: E402

    board = BreakerBoard(failure_threshold=3, cooldown_s=60.0)
    canary_sched = ContinuousScheduler(engine, SERVING, settings=GREEDY,
                                       breakers=board)
    probe = CanaryProbe.record(engine, max_tokens=8, every_n=1, board=board)
    check(probe.probe(canary_sched), "canary matches on a healthy scheduler")
    probe.reference = probe.reference.copy()
    probe.reference[0] += 1  # silent corruption, as the comparator sees it
    check(not probe.probe(canary_sched) and board.state("decode") == "open"
          and board.ladder.level >= 1,
          "canary mismatch trips the breaker degradation ladder")
    cm = bundles("canary_mismatch")
    check(len(cm) == 1 and "wrong tokens" in cm[0]["cause"],
          "exactly one canary_mismatch bundle (wrong-but-finite captured)")

    # 6. Fleet failover: 2 replicas, kill r1 mid-sweep — zero lost, migrated
    # survivors token-identical to the single-engine baseline, r0 serving
    # throughout, r1 rejoining via its canary probe.
    from fairness_llm_tpu.config import FleetConfig, IntegrityConfig  # noqa: E402
    from fairness_llm_tpu.serving import ReplicaSet  # noqa: E402

    fleet_inj = ScriptedFaultInjector(replica_crashes={"r1": 3})
    fleet = ReplicaSet(
        engine, SERVING, settings=GREEDY,
        fleet=FleetConfig(replicas=2, fence_cooldown_s=0.05),
        resilience=RESILIENCE, fault_injector=fleet_inj,
        integrity=IntegrityConfig(canary_max_tokens=8),
    )
    fleet_reqs = [Request(prompt=p, id=f"fleet_{rid}", settings=GREEDY)
                  for rid, p in PROMPTS.items()]
    fleet_results = {r.id: r for r in fleet.serve(fleet_reqs)}
    check(fleet_inj.replica_faults_fired == [("r1", "replica_crash")],
          "replica r1 crash fired once mid-sweep")
    check(set(fleet_results) == {f"fleet_{rid}" for rid in PROMPTS},
          "fleet: every request got a terminal Result (zero lost)")
    fleet_parity = True
    for rid, prompt in PROMPTS.items():
        res = fleet_results[f"fleet_{rid}"]
        if not res.ok:
            fleet_parity = False
            print(f"  fleet loss: {rid}: {res.finish_reason} ({res.error})")
            continue
        got, ref = np.asarray(res.tokens), baseline[rid]
        n = len(got)
        if n == 0 or not np.array_equal(got, ref[:n]) \
                or not np.all(ref[n:] == engine.tokenizer.pad_id):
            fleet_parity = False
            print(f"  fleet parity break: {rid}: {list(got)} vs {list(ref)}")
    check(fleet_parity,
          "fleet: ALL requests ok, token-identical to the greedy baseline")
    r0, r1 = fleet.replicas
    reg = T.get_registry()
    r0_completed = reg.read_value("serving_completed_total",
                                  component="serving", replica="r0")
    check(r0.fences == 0 and r0_completed > 0,
          f"healthy replica r0 never fenced, served {r0_completed:g} "
          "request(s) throughout")
    check(r1.fences == 1, "crashed replica r1 fenced exactly once")
    migrated = reg.read_value("fleet_migrated_requests_total",
                              component="fleet")
    recovered = reg.read_value("fleet_migrated_recovered_total",
                               component="fleet")
    check(migrated > 0 and migrated == recovered,
          f"fleet: migrated ({migrated:g}) == recovered ({recovered:g})")
    check(fleet.await_recovery(timeout_s=60.0)
          and reg.read_value("fleet_healthy_replicas", component="fleet") == 2,
          "crashed replica rejoined via canary probe; fleet whole again")
    check(fleet.last_failover_s is not None,
          f"failover recovery measured ({fleet.last_failover_s and round(fleet.last_failover_s, 4)}s "
          "fence -> first migrated token)")
    fb = bundles("fence")
    check(len(fb) == 1 and fb[0].get("replica") == "r1"
          and "replica_crash" in fb[0]["cause"],
          "exactly one fence bundle for r1 naming replica_crash")
    if fb:
        # The rendered postmortem: the causal chain must read from the
        # fence back through the decisions that drove it.
        report = T.render_incident_report(fb[0]["path"])
        chain = next((ln for ln in report.splitlines()
                      if ln.strip().startswith("fence(")), "")
        print(f"  incident-report chain: {chain.strip()}")
        check("fence(r1)" in chain,
              "incident-report renders the fence causal chain")

    # 7. Overload brownout (ISSUE 8): offer ~3x the queue's capacity with
    # mixed QoS classes. The shed controller must walk the brownout ladder
    # (batch sheds with explicit retry-after Results), interactive traffic
    # must keep flowing inside its TTFT SLO, no accepted request may be
    # lost, and the controller must de-escalate to level 0 after the flood.
    from fairness_llm_tpu.config import OverloadConfig  # noqa: E402
    from fairness_llm_tpu.telemetry.slo import (  # noqa: E402
        SLOTargets,
        set_slo_targets,
    )

    # Harness-appropriate SLO targets: a tiny CPU model meets 60 s TTFT
    # trivially, so the drill's escalation signal is the deterministic one
    # (queue depth), not compile-time TTFT outliers.
    set_slo_targets(SLOTargets(ttft_p95_s=60.0, e2e_p99_s=120.0))
    ov = OverloadConfig(
        enabled=True, queue_frac_threshold=0.75, queue_window_s=0.5,
        healthy_window_s=0.05, eval_interval_s=0.0, batch_token_cap=4,
        retry_after_s=0.25,
    )
    ov_serving = ServingConfig(enabled=True, num_slots=2, queue_capacity=12,
                               max_prompt_len=192, max_new_tokens=32,
                               decode_chunk=4)
    # The drill's earlier sections leave UNLABELED serving gauges behind
    # (notably a pegged slo_burn_rate from the fault storm); a distinct
    # replica label gives this section's shed controller its own burn
    # signal instead of a stale one — the 1-vCPU flake where warmup
    # escalated off section-5's burn gauge.
    ov_sched = ContinuousScheduler(engine, ov_serving, settings=GREEDY,
                                   overload=ov, replica="ovdrill")

    # Prime this scheduler's OWN prefill/cadence histograms (the deadline
    # estimator reads its replica-labeled p50s and never sheds while
    # telemetry is cold — two served requests warm it deterministically).
    prime = [Request(prompt=PROMPTS["ok0"], id=f"ov_prime_{i}",
                     settings=GREEDY) for i in range(2)]
    prime_ok = all(r.ok for r in ov_sched.serve(prime))
    check(prime_ok, "overload scheduler primed its replica-labeled "
                    "prefill/cadence telemetry")

    # 7a. Deadline-feasibility admission: with six requests stacked ahead
    # on two slots, a 1 ms deadline is provably unmeetable — the gate must
    # shed it AT SUBMIT (no prefill burned, no expiry later), using the
    # prefill/cadence telemetry the priming pass populated.
    warm = [Request(prompt=p, id=f"ov_warm_{i}", settings=GREEDY)
            for i, p in enumerate(list(PROMPTS.values())[:6])]
    for r in warm:
        assert ov_sched.submit(r)
    doomed = Request(prompt=PROMPTS["ok0"], id="ov_doomed", settings=GREEDY,
                     deadline_s=0.001)
    accepted = ov_sched.submit(doomed)
    doomed_res = ov_sched.take_result("ov_doomed")
    check(not accepted and doomed_res is not None
          and doomed_res.finish_reason == "shed"
          and bool(doomed_res.retry_after_s)
          and "unmeetable" in (doomed_res.error or ""),
          "provably-doomed deadline shed at admission with retry-after "
          f"({doomed_res and doomed_res.error})")
    ov_sched.drain()
    warm_ok = all((ov_sched.take_result(r.id) or Result(id=r.id, ok=False)).ok
                  for r in warm)
    check(warm_ok and ov_sched.shed_controller.level == 0,
          "under-capacity warmup served clean at overload level 0")

    # 7b. The flood: 30 batch + 6 interactive (3x the 12-deep queue), batch
    # first — the starvation scenario.
    base_prompts = list(PROMPTS.values())
    flood = [Request(prompt=base_prompts[i % len(base_prompts)],
                     id=f"ov_batch_{i:03d}", settings=GREEDY, qos="batch")
             for i in range(30)]
    flood += [Request(prompt=base_prompts[i % len(base_prompts)],
                      id=f"ov_int_{i}", settings=GREEDY, qos="interactive")
              for i in range(6)]
    flood_results = {r.id: r for r in ov_sched.serve(flood)}
    check(set(flood_results) == {r.id for r in flood},
          "overload flood: every request got a terminal Result")
    interactive = [flood_results[f"ov_int_{i}"] for i in range(6)]
    check(all(r.ok for r in interactive),
          "all interactive requests served through the flood")
    ttfts = sorted(r.ttft_s for r in interactive if r.ttft_s is not None)
    ttft_p95 = ttfts[min(len(ttfts) - 1, int(0.95 * len(ttfts)))] \
        if ttfts else None
    check(ttft_p95 is not None and ttft_p95 <= 60.0,
          f"interactive TTFT p95 ({ttft_p95 and round(ttft_p95, 3)}s) holds "
          "its SLO during the flood")
    batch_res = [flood_results[f"ov_batch_{i:03d}"] for i in range(30)]
    shed = [r for r in batch_res if r.finish_reason == "shed"]
    served_batch = [r for r in batch_res if r.finish_reason != "shed"]
    check(bool(shed) and all(r.retry_after_s for r in shed),
          f"{len(shed)} batch request(s) shed with explicit retry-after")
    check(all(r.ok for r in served_batch),
          f"zero accepted-then-lost: all {len(served_batch)} admitted batch "
          "requests terminal ok")
    parity_ov = True
    for r in interactive + served_batch:
        prompt = next(q.prompt for q in flood if q.id == r.id)
        ref = next(baseline[rid] for rid, p in PROMPTS.items() if p == prompt)
        n = len(r.tokens)
        if n == 0 or not np.array_equal(np.asarray(r.tokens), ref[:n]):
            parity_ov = False
            print(f"  overload parity break: {r.id}")
    check(parity_ov, "admitted requests token-for-token with baseline "
                     "across classes and shed/restore cycles")
    reg = T.get_registry()
    shed_batch = reg.read_value("shed_total", component="serving",
                                replica="ovdrill",
                                **{"class": "batch", "reason": "overload"})
    shed_doomed = reg.read_value("shed_total", component="serving",
                                 replica="ovdrill",
                                 **{"class": "interactive",
                                    "reason": "deadline_infeasible"})
    check(shed_batch > 0 and shed_doomed > 0,
          f"shed_total counters nonzero (overload={shed_batch:g}, "
          f"deadline_infeasible={shed_doomed:g})")
    import time as _time
    ctl = ov_sched.shed_controller
    # Derived-time de-escalation (no sleeps, no wall deadline): the first
    # evaluate sees a depth window aged past queue_window_s (every flood
    # sample pruned -> frac 0), then each further evaluate advances the
    # clock one healthy_window_s past the per-rung hysteresis restart —
    # exactly one rung down per step, however slow the host is.
    t = _time.monotonic() + ov.queue_window_s + 0.01
    for _ in range(16):
        if ctl.evaluate(now=t) == 0:
            break
        t += ov.healthy_window_s + 0.01
    check(ctl.level == 0 and reg.read_value(
              "overload_level", component="serving",
              replica="ovdrill") == 0,
          "shed controller de-escalated to level 0 after the flood")

    # 8. Fairness observability (ISSUE 9): the serving-neutrality audit and
    # counterfactual pair watch. Pair probes are byte-identical prompts
    # tagged with different groups — the serving-layer counterfactual: any
    # output or delivery difference between members is serving treatment,
    # not model bias. Fault-free first (must be silent), then faults
    # targeted at ONE group (must alert, with the pairs attributed).
    from fairness_llm_tpu.telemetry.fairness import get_fairness_monitor

    fair_prompts = [PROMPTS["ok0"], PROMPTS["flaky"], PROMPTS["pfault"],
                    PROMPTS["hangme"]]

    def fairness_requests(tag):
        reqs = []
        for i, p in enumerate(fair_prompts):
            for g in ("g_ctrl", "g_tgt"):
                reqs.append(Request(
                    prompt=p, id=f"fair_{tag}_{g}_{i}", settings=GREEDY,
                    group=g, attribute="drill", pair_id=f"fair_{tag}_p{i}",
                ))
        return reqs

    mon = get_fairness_monitor()
    mon.begin_study()
    fair_sched = ContinuousScheduler(engine, SERVING, settings=GREEDY)
    ctrl = {r.id: r for r in fair_sched.serve(fairness_requests("ctrl"))}
    reg = T.get_registry()
    alerts_before = reg.read_value(
        "fairness_alerts_total", component="fairness", attribute="drill",
        signal="impaired_rate",
    )
    check(all(r.ok for r in ctrl.values())
          and mon.pairs_joined == len(fair_prompts)
          and mon.pairs_divergent == 0 and alerts_before == 0,
          f"fault-free neutrality control silent ({mon.pairs_joined} pairs "
          "joined, zero divergence, no alert)")

    # Fresh study for the biased half: sharing the control run's stats
    # would dilute the end-state disparity to exactly the alert threshold
    # (2 impaired over 8 = 0.25), making the alert depend on terminal
    # ordering; reset makes it 2/4 = 0.5, deterministic.
    mon.begin_study()
    biased_inj = ScriptedFaultInjector(
        faults={(f"fair_biased_g_tgt_{i}", "decode"): 2 for i in (0, 1)},
    )
    biased_sched = ContinuousScheduler(engine, SERVING, settings=GREEDY,
                                       fault_injector=biased_inj)
    biased = {r.id: r for r in biased_sched.serve(
        fairness_requests("biased"))}
    alerts_after = reg.read_value(
        "fairness_alerts_total", component="fairness", attribute="drill",
        signal="impaired_rate",
    )
    targeted_failed = [rid for rid, r in biased.items()
                       if "g_tgt" in rid and not r.ok]
    check(len(targeted_failed) == 2,
          "group-targeted faults failed exactly the targeted requests "
          f"({targeted_failed})")
    check(alerts_after >= 1,
          f"neutrality audit raised fairness_alerts_total "
          f"({alerts_after:g}) on group-targeted faults")
    divergent = [d for d in mon.divergent
                 if d["pair_id"].startswith("fair_biased")]
    attributed = [
        d for d in divergent
        if any("requeued" in e for m in d["members"].values()
               for e in (m.get("events") or []))
    ]
    check(len(divergent) >= 2 and len(attributed) >= 2,
          f"{len(divergent)} divergent pair(s) attributed to the injected "
          f"faults' requeues ({len(attributed)} with requeue events)")
    disparity = reg.read_value("fairness_disparity", component="fairness",
                               attribute="drill", signal="impaired_rate")
    check(disparity >= 0.25,
          f"impaired-rate disparity gauge reflects the bias "
          f"({disparity:g})")
    fa = bundles("fairness_alert", scope="drill")
    pd = bundles("pair_divergence", scope="drill")
    check(len(fa) == 1,
          "exactly one fairness_alert bundle for the biased-fault family")
    check(len(pd) == 1,
          "exactly one pair_divergence bundle (second divergent pair "
          "deduped into it)")
    if a.telemetry_dir:
        # The rendered fairness report rides the telemetry artifact — the
        # failure-evidence upload includes the attribution table.
        from fairness_llm_tpu.telemetry import render_fairness_report

        with open(os.path.join(a.telemetry_dir, "fairness_report.txt"),
                  "w", encoding="utf-8") as f:
            f.write(render_fairness_report(
                T.snapshot(T.get_registry()),
                events=[{"kind": "fairness_pair_divergent", **d}
                        for d in mon.divergent],
            ) + "\n")

    # 9. FUSED multi-step dispatch under faults (ISSUE 14,
    # runtime/stepbuilder.py): the same containment contract with the
    # dispatch boundary MOVED — --fuse-steps 4 folds four decode chunks
    # into one compiled call, and an injected NaN lands INSIDE that fused
    # window. The numerics-guard flag rides the fused carry, so the whole
    # dispatch discards at its boundary as one NumericsFault, every rider
    # requeues once, and survivors decode token-identical: fusion may
    # widen the blast radius per fault (k chunks of work), never the
    # outcome.
    import dataclasses as _dc

    reg = T.get_registry()
    nf_before = reg.read_value("numerics_faults_total",
                               component="serving", stage="decode")
    fused_cfg = _dc.replace(SERVING, fuse_steps=4)
    fused_fam = list(PROMPTS.values())[:4]
    fused_baseline = {
        f"fused{i}": np.asarray(engine.generate([p], GREEDY).tokens[0])
        for i, p in enumerate(fused_fam)
    }
    fused_inj = ScriptedFaultInjector(
        {}, corruptions={("fused1", "decode"): 1})
    fused_sched = ContinuousScheduler(engine, fused_cfg, settings=GREEDY,
                                      fault_injector=fused_inj,
                                      resilience=RESILIENCE)
    fused_res = {r.id: r for r in fused_sched.serve(
        [Request(prompt=p, id=f"fused{i}", settings=GREEDY)
         for i, p in enumerate(fused_fam)]
    )}
    check(len(fused_res) == len(fused_fam)
          and all(r.ok for r in fused_res.values()),
          "fused chaos: zero lost under NaN inside a fused window")
    fused_parity = all(
        np.array_equal(np.asarray(r.tokens),
                       fused_baseline[rid][:len(r.tokens)])
        and np.all(fused_baseline[rid][len(r.tokens):]
                   == engine.tokenizer.pad_id)
        for rid, r in fused_res.items()
    )
    check(fused_parity,
          "fused chaos: survivors token-identical across the moved "
          "dispatch boundary")
    check(fused_res["fused1"].retries == 1,
          "fused chaos: poisoned rider requeued exactly once")
    nf_after = reg.read_value("numerics_faults_total",
                              component="serving", stage="decode")
    check(nf_after > nf_before,
          "fused chaos: the fused window's NaN classified as a "
          f"NumericsFault ({nf_before:g} -> {nf_after:g})")
    from fairness_llm_tpu.runtime.stepbuilder import compile_key as _ck

    check(_ck("serve_step", chunk=SERVING.decode_chunk, guard=True, fuse=4)
          in fused_sched._compiled,
          "fused chaos: the dispatch compiled under the fused key "
          "(chunk, guard, fuse)")

    snap = T.snapshot(T.get_registry())
    # Unlabeled entries only: the fleet section's per-replica boards write
    # breaker_transitions_total{replica=...} rows for the SAME (stage, to)
    # keys, and letting them shadow the single-engine board's entries
    # would validate r1's rejoin cycle in place of the documented
    # sections-1-5 cycle.
    trans = {
        (c["labels"].get("stage"), c["labels"].get("to")): c["value"]
        for c in snap["counters"]
        if c["name"] == "breaker_transitions_total"
        and "replica" not in c["labels"]
    }
    for to in ("open", "half_open", "closed"):
        check(trans.get(("decode", to), 0) >= 1,
              f"breaker_state transition to={to} in snapshot")
    hangs = [c for c in snap["counters"]
             if c["name"] == "watchdog_hangs_total" and c["value"] > 0]
    check(bool(hangs), "watchdog_hangs_total > 0 in snapshot")
    pre = [c for c in snap["counters"]
           if c["name"] == "serving_preempted_total" and c["value"] > 0]
    check(bool(pre), "serving_preempted_total > 0 in snapshot")
    for name in ("numerics_faults_total", "manifest_failures_total",
                 "canary_runs_total", "canary_mismatch_total"):
        hits = [c for c in snap["counters"]
                if c["name"] == name and c["value"] > 0]
        check(bool(hits), f"{name} > 0 in snapshot")
    # Dedup proof (ISSUE 13): the drill's fault storm fired far more
    # triggers than bundles — the suppressed counter is the difference.
    suppressed = sum(c["value"] for c in snap["counters"]
                     if c["name"] == "incident_suppressed_total")
    triggers = sum(c["value"] for c in snap["counters"]
                   if c["name"] == "incident_triggers_total")
    n_bundles = len(T.list_bundles(inc_dir))
    check(suppressed > 0 and triggers == suppressed + n_bundles,
          f"incident dedup: {triggers:g} trigger(s) -> {n_bundles} "
          f"bundle(s) + {suppressed:g} suppressed")
    check(sum(c["value"] for c in snap["counters"]
              if c["name"] == "decisions_total") > 0,
          "decision audit trail recorded (decisions_total > 0)")

    if a.telemetry_dir:
        path = T.write_snapshot(T.get_registry(), a.telemetry_dir)
        bad = T.validate_snapshot(T.load_snapshot(path))
        check(not bad, f"snapshot schema valid ({path})")
        if sink is not None:
            T.install_event_sink(None)
            sink.close()
    trace_path = a.trace_out or (os.path.join(a.telemetry_dir, "trace.json")
                                 if a.telemetry_dir else None)
    if trace_path:
        T.get_timeline().export(trace_path)
        tbad = T.validate_chrome_trace(
            T.get_timeline().to_chrome_trace()
        )
        check(not tbad, f"device-step timeline valid ({trace_path})")

    print(f"\nchaos drill: {'PASS' if not problems else 'FAIL'} "
          f"({len(problems)} problem(s))")
    return 1 if problems else 0


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    sys.exit(main())
