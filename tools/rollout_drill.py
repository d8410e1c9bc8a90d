"""Rollout drill: zero-downtime version upgrades under fire — the
end-to-end proof behind docs/RESILIENCE.md's upgrade-fault rows and the
``validate_telemetry --require-rollout`` CI gate.

What it does, in one process, deterministically:

A. CLEAN UPGRADE: serves a streaming workload through a 2-replica
   ``ReplicaSet`` while a ``RolloutController`` walks the fleet from v0
   to v1 (same weights, new version id) — canary-gated standby per wave,
   stepped traffic shift, planned retirement of each v0 replica —
   asserting ZERO lost requests, every stream token-for-token with the
   reference OF ITS PINNED VERSION (a request finishes on the version
   that admitted it), and the fleet entirely on v1 with the autoscaler
   arbitration counted;
B. CORRUPT NEW WEIGHTS: points the next rollout's ``engine_fn`` at a
   checkpoint with one flipped BIT — the manifest refuses the load
   during PREPARING, the rollout lands terminal ``rolled_back`` before
   any replica joins, live traffic never notices (all results ok,
   membership unchanged), and one ``rollout`` incident bundle names the
   manifest gate;
C. BIASED NEW VERSION: rolls toward an engine with DIFFERENT weights
   while byte-identical counterfactual pairs stream through the fleet.
   The moment the traffic split lands pair members on different
   versions, their outputs diverge — the FairnessMonitor's pair watch
   attributes the divergence to the new replica and the fairness
   deployment gate rolls the wave back mid-flight: every in-flight
   request on the fenced v+1 replica migrates back (migrated ==
   recovered), zero requests lost, EXACTLY one deduplicated ``rollout``
   bundle naming the fairness gate, and the fleet back to all-old
   healthy;
D. MID-ROLLOUT CRASH + RESUME: starts a journaled rollout, abandons the
   fleet mid-wave (the crash), then ``resume_serving(..., version=...)``
   replays the journal's unfinished requests on the OLD version — ids
   pinned to the half-deployed version are restamped and counted
   (``rollout_resume_restamped_total``), every resumed stream decodes
   single-version token-parity clean, and the journal drains empty: the
   wave is rolled back at resume, never a version-mixed migration;
E. validates the telemetry: ``rollout_transitions_total`` shows one
   ``complete`` and two ``rolled_back`` terminals, ``rollout_rollbacks_
   total`` carries the manifest + fairness causes, fleet migration
   counters balance, and the snapshot passes schema validation
   (``validate_telemetry --require-rollout`` gates exactly these).

Usage (CI runs exactly this):
    JAX_PLATFORMS=cpu python tools/rollout_drill.py --telemetry-dir tel
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from fairness_llm_tpu.config import (  # noqa: E402
    FleetConfig,
    IntegrityConfig,
    ModelSettings,
    ResilienceConfig,
    RolloutConfig,
    ServingConfig,
)
from fairness_llm_tpu.models.configs import get_model_config  # noqa: E402
from fairness_llm_tpu.resilience import ServingJournal, resume_serving  # noqa: E402
from fairness_llm_tpu.runtime.engine import DecodeEngine  # noqa: E402
from fairness_llm_tpu.serving import (  # noqa: E402
    ReplicaSet,
    Request,
    RolloutController,
)

GREEDY = ModelSettings(temperature=0.0, max_tokens=8)
SERVING = ServingConfig(enabled=True, num_slots=2, queue_capacity=64,
                        max_prompt_len=192, max_new_tokens=32, decode_chunk=4)
FLEET2 = FleetConfig(replicas=2, fence_cooldown_s=0.02)
RESILIENCE = ResilienceConfig(enabled=True, max_step_seconds=120.0,
                              breaker_threshold=1, breaker_cooldown_s=0.02)
INTEG = IntegrityConfig(canary_max_tokens=8)

PROMPTS = [
    "the quick brown fox",
    "hello there friend",
    "abc abc abc abc",
    "one two three one two",
    "recommend ten films please",
    "name five good books",
    "zz zz zz",
    "a longer prompt that shifts padding and lands in a bucket",
]

WALL_GUARD_S = 240.0  # per-section drive ceiling: a wedge fails loudly


def refs_for(engine) -> dict:
    """Greedy reference rows keyed by prompt — what any stream pinned to
    this engine's version must reproduce token-for-token."""
    return {p: np.asarray(engine.generate([p], GREEDY).tokens[0])
            for p in PROMPTS}


def parity_ok(res, ref) -> bool:
    got = np.asarray(res.tokens)
    n = len(got)
    return n > 0 and np.array_equal(got, ref[:n])


def drive(fleet, ro, reqs) -> dict:
    """Tick the fleet (which drives the rollout) while feeding ``reqs``,
    until the controller is terminal and every request has a Result."""
    results, pending = {}, list(reqs)
    t0 = time.monotonic()
    while True:
        if pending and fleet.submit(pending[0]):
            pending.pop(0)
        fleet.tick()
        for r in reqs:
            if r.id not in results:
                res = fleet.take_result(r.id)
                if res is not None:
                    results[r.id] = res
        if not ro.active and not pending and len(results) == len(reqs):
            break
        if time.monotonic() - t0 > WALL_GUARD_S:
            print(f"  drive wall guard hit: state={ro.state} "
                  f"results={len(results)}/{len(reqs)}")
            break
    return results


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--telemetry-dir", default=None,
                    help="write events.jsonl + the validated snapshot here")
    a = ap.parse_args()

    from fairness_llm_tpu import telemetry as T

    sink = T.configure(a.telemetry_dir) if a.telemetry_dir else None
    inc_dir = os.path.join(
        a.telemetry_dir or tempfile.mkdtemp(prefix="rollout-incidents-"),
        "incidents",
    )
    T.arm_incidents(inc_dir, cooldown_s=3600.0)

    problems = []

    def check(ok: bool, what: str) -> None:
        print(("PASS" if ok else "FAIL") + f"  {what}")
        if not ok:
            problems.append(what)

    def bundles(scope=None):
        found = [m for m in T.list_bundles(inc_dir)
                 if m["class"] == "rollout"]
        if scope is not None:
            found = [m for m in found if m.get("scope") == scope]
        return found

    # Harness-appropriate SLO targets (same stance as the chaos drill): a
    # tiny CPU model meets 60 s TTFT trivially, so the rollout SLO gate
    # only fires on REAL regressions, never on 1-vCPU compile stalls.
    from fairness_llm_tpu.telemetry.slo import SLOTargets, set_slo_targets

    set_slo_targets(SLOTargets(ttft_p95_s=60.0, e2e_p99_s=120.0))

    cfg = get_model_config("tiny-test")
    eng_v0 = DecodeEngine(cfg, seed=0)
    ref_v0 = refs_for(eng_v0)
    reg = T.get_registry()

    # -- A. clean v0 -> v1 upgrade under live streaming traffic -------------
    print("== A: clean upgrade ==")
    fleet = ReplicaSet(eng_v0, SERVING, settings=GREEDY, fleet=FLEET2,
                       resilience=RESILIENCE, integrity=INTEG)
    eng_v1 = DecodeEngine(cfg, seed=0)  # same weights, new version id
    ref_v1 = refs_for(eng_v1)
    ro = RolloutController(
        fleet, "v1", engine=eng_v1,
        config=RolloutConfig(enabled=True, canary_window_s=0.05,
                             traffic_steps=2),
    )
    ro.start()
    reqs_a = [Request(prompt=PROMPTS[i % len(PROMPTS)], id=f"a_q{i}",
                      settings=GREEDY) for i in range(len(PROMPTS) * 2)]
    res_a = drive(fleet, ro, reqs_a)
    check(ro.state == "complete",
          f"rollout reached complete (state={ro.state})")
    check(fleet.version == "v1"
          and all(r.version == "v1" and not r.fenced
                  for r in fleet.replicas)
          and len(fleet.replicas) == FLEET2.replicas,
          "fleet entirely on v1, all replicas healthy")
    check(len(res_a) == len(reqs_a),
          f"zero lost through the upgrade ({len(res_a)}/{len(reqs_a)} "
          "terminal)")
    par, pinned_counts = True, {}
    for r in reqs_a:
        res = res_a.get(r.id)
        if res is None:
            continue
        ver = fleet.request_version(r.id)
        pinned_counts[ver] = pinned_counts.get(ver, 0) + 1
        ref = (ref_v1 if ver == "v1" else ref_v0)[r.prompt]
        if not (res.ok and parity_ok(res, ref)):
            par = False
            print(f"  parity break: {r.id} pinned={ver}")
    check(par, "every stream ok + token-for-token with its PINNED "
               f"version's reference (pins: {pinned_counts})")
    check(reg.read_value("rollout_transitions_total", component="rollout",
                         to="complete") == 1,
          "one terminal complete transition counted")
    check(reg.read_value("rollout_autoscale_paused_total",
                         component="rollout", default=0.0) >= 0.0,
          "autoscaler arbitration surface present")

    # -- B. corrupt v+1 weights: manifest refusal, zero user impact ---------
    print("== B: corrupt new weights ==")
    from fairness_llm_tpu.runtime.weights import (  # noqa: E402
        load_checkpoint,
        save_checkpoint_hf,
    )
    from fairness_llm_tpu.utils.failures import ScriptedFaultInjector  # noqa: E402

    wdir = tempfile.mkdtemp(prefix="rollout-weights-")
    save_checkpoint_hf(eng_v0.config, eng_v0.params, wdir)
    shard = os.path.join(wdir, "model.safetensors")
    ScriptedFaultInjector.flip_bit(shard, (os.path.getsize(shard) - 64) * 8)

    def poisoned_engine():
        # The manifest check inside load_checkpoint raises IntegrityError
        # on the flipped shard — the engine below is never built.
        params = load_checkpoint(eng_v0.config, wdir)
        eng = DecodeEngine(eng_v0.config, seed=0)
        eng.params = params
        return eng

    members_before = {r.name for r in fleet.replicas}
    ro_b = RolloutController(
        fleet, "v2", engine_fn=poisoned_engine,
        config=RolloutConfig(enabled=True, canary_window_s=0.05,
                             traffic_steps=2),
    )
    ro_b.start()
    reqs_b = [Request(prompt=p, id=f"b_q{i}", settings=GREEDY)
              for i, p in enumerate(PROMPTS)]
    res_b = drive(fleet, ro_b, reqs_b)
    check(ro_b.state == "rolled_back"
          and (ro_b.cause or "").startswith("manifest"),
          f"corrupt weights refused during preparing (cause={ro_b.cause})")
    check({r.name for r in fleet.replicas} == members_before
          and fleet.version == "v1",
          "zero membership churn: no v2 replica ever joined")
    check(len(res_b) == len(reqs_b) and all(
              r.ok and parity_ok(r, ref_v1[q.prompt])
              for q, r in ((q, res_b[q.id]) for q in reqs_b)),
          "zero user impact: every request served clean on v1 throughout")
    b_bundles = bundles(scope="fleet:v2")
    check(len(b_bundles) == 1 and "manifest" in b_bundles[0]["cause"],
          "one rollout bundle naming the manifest gate")

    # -- C. biased v+1: fairness deployment gate rolls back mid-wave --------
    print("== C: biased new version ==")
    from fairness_llm_tpu.telemetry.fairness import get_fairness_monitor  # noqa: E402

    eng_biased = DecodeEngine(cfg, seed=7)  # different weights: the "bias"
    mon = get_fairness_monitor()
    mon.begin_study()
    migrated_before = reg.read_value("fleet_migrated_requests_total",
                                     component="fleet", default=0.0)
    ro_c = RolloutController(
        fleet, "v3", engine=eng_biased,
        config=RolloutConfig(enabled=True, canary_window_s=0.6,
                             traffic_steps=4, abort_on_fairness_alert=True),
    )
    ro_c.start()

    # Byte-identical counterfactual pairs, streamed one per tick while
    # the wave shifts traffic: the moment members land on different
    # versions their bytes diverge and the pair watch attributes the new
    # replica. Feeding stops once the controller is terminal; the loop
    # then drains every outstanding stream.
    all_c: list = []
    outstanding_c: list = []
    res_c: dict = {}
    t0 = time.monotonic()
    i = 0
    while time.monotonic() - t0 < WALL_GUARD_S:
        if ro_c.active and i < 40:
            prompt = PROMPTS[i % len(PROMPTS)]
            for g in ("g_a", "g_b"):
                q = Request(prompt=prompt, id=f"c_p{i}_{g}",
                            settings=GREEDY, group=g, attribute="rollout",
                            pair_id=f"c_pair{i}")
                if fleet.submit(q):
                    all_c.append(q)
                    outstanding_c.append(q)
            i += 1
        fleet.tick()
        for q in list(outstanding_c):
            r = fleet.take_result(q.id)
            if r is not None:
                res_c[q.id] = r
                outstanding_c.remove(q)
        if not ro_c.active and not outstanding_c and not fleet.has_work:
            break
    check(ro_c.state == "rolled_back"
          and "pair_divergence" in (ro_c.cause or ""),
          f"fairness gate rolled the wave back (cause={ro_c.cause})")
    check(fleet.version == "v1" and len(fleet.replicas) == FLEET2.replicas
          and all(r.version == "v1" and not r.fenced
                  for r in fleet.replicas),
          "fleet back to all-v1 healthy after rollback")
    check(len(res_c) == len(all_c) and all(r.ok for r in res_c.values()),
          f"zero lost through the aborted wave ({len(res_c)}/{len(all_c)} "
          "terminal ok)")
    ref_biased = refs_for(eng_biased)
    par_c = True
    for q in all_c:
        res = res_c.get(q.id)
        if res is None:
            continue
        ver = fleet.request_version(q.id)
        ref = (ref_biased if ver == "v3" else ref_v1)[q.prompt]
        if not parity_ok(res, ref):
            par_c = False
            print(f"  parity break: {q.id} pinned={ver}")
    check(par_c, "every stream single-version token parity (v3-pinned "
                 "streams match the biased reference, never a mix)")
    migrated = reg.read_value("fleet_migrated_requests_total",
                              component="fleet", default=0.0)
    recovered = reg.read_value("fleet_migrated_recovered_total",
                               component="fleet", default=0.0)
    check(migrated == recovered,
          f"migrated == recovered across the rollback ({migrated:g} == "
          f"{recovered:g})")
    c_bundles = bundles(scope="fleet:v3")
    check(len(c_bundles) == 1 and "pair_divergence" in c_bundles[0]["cause"],
          "exactly one deduplicated rollout bundle naming the fairness "
          "gate")
    check(reg.read_value("rollout_rollbacks_total", component="rollout",
                         cause="pair_divergence") == 1,
          "rollback cause counted under the fairness gate")

    # -- D. mid-rollout crash + resume on the old version -------------------
    print("== D: mid-rollout crash + resume ==")
    jdir = tempfile.mkdtemp(prefix="rollout-journal-")
    journal = ServingJournal(jdir)
    fleet_d = ReplicaSet(eng_v0, SERVING, settings=GREEDY, fleet=FLEET2,
                         resilience=RESILIENCE, integrity=INTEG,
                         journal=journal)
    ro_d = RolloutController(
        fleet_d, "v1", engine=DecodeEngine(cfg, seed=0),
        config=RolloutConfig(enabled=True, canary_window_s=5.0,
                             traffic_steps=2),
    )
    ro_d.start()
    reqs_d = [Request(prompt=PROMPTS[i % len(PROMPTS)], id=f"d_q{i}",
                      settings=GREEDY) for i in range(48)]
    t0 = time.monotonic()
    di, staged = 0, False
    while time.monotonic() - t0 < WALL_GUARD_S:
        # One submission per tick: traffic keeps arriving WHILE the wave
        # shifts, so the error-diffusion steering pins some of it to the
        # half-deployed v1 replica.
        if di < len(reqs_d) and fleet_d.submit(reqs_d[di]):
            di += 1
        fleet_d.tick()
        if ro_d.state == "shifting" and any(
                s.get("version") == "v1" for s in journal.unfinished()):
            # The crash point: mid-wave, with journaled-but-unfinished
            # work pinned to the new version.
            staged = True
            break
        if not ro_d.active:
            break  # completed before staging — the check below fails
    check(staged, "crash staged mid-wave with journaled work pinned to "
                  "the half-deployed v1")
    # The "crash": the fleet is abandoned — no drain, no terminal records
    # for in-flight work. The journal is all that survives.
    del fleet_d

    unfinished = journal.unfinished()
    v1_unfinished = [s["id"] for s in unfinished
                     if s.get("version") == "v1"]
    restamp_before = reg.read_value("rollout_resume_restamped_total",
                                    component="rollout", default=0.0)
    resumed = resume_serving(eng_v0, journal, serving=SERVING,
                             resilience=RESILIENCE, version="v0")
    restamp_after = reg.read_value("rollout_resume_restamped_total",
                                   component="rollout", default=0.0)
    check(len(resumed) == len(unfinished) and all(
              r.ok and parity_ok(r, ref_v0[
                  next(q.prompt for q in reqs_d if q.id == rid)])
              for rid, r in resumed.items()),
          f"resume re-served all {len(unfinished)} unfinished request(s) "
          "token-parity clean on v0")
    check(restamp_after - restamp_before == len(v1_unfinished),
          f"every v1-pinned unfinished id restamped at resume "
          f"({len(v1_unfinished)} counted): wave rolled back, no "
          "version-mixed migration")
    check(not journal.unfinished(), "journal drained empty after resume")
    # Resolve the crashed controller's state machine: the resume on v0 IS
    # the rollback — resume tooling stamps the terminal verdict so the
    # snapshot never shows a rollout abandoned mid-wave.
    ro_d.resolve_crashed("resumed on v0 after mid-wave crash")
    check(ro_d.state == "rolled_back",
          "crashed rollout resolved terminal: wave rolled back at resume")

    # -- E. telemetry acceptance --------------------------------------------
    print("== E: telemetry ==")
    snap = T.snapshot(reg)
    trans = {c["labels"].get("to"): c["value"] for c in snap["counters"]
             if c["name"] == "rollout_transitions_total"}
    check(trans.get("complete", 0) >= 1 and trans.get("rolled_back", 0) >= 2,
          f"terminal transitions counted (complete={trans.get('complete')}"
          f", rolled_back={trans.get('rolled_back')})")
    causes = {c["labels"].get("cause") for c in snap["counters"]
              if c["name"] == "rollout_rollbacks_total" and c["value"] > 0}
    check({"manifest", "pair_divergence"} <= causes,
          f"rollback causes cover the manifest + fairness gates ({causes})")
    if a.telemetry_dir:
        path = T.write_snapshot(reg, a.telemetry_dir)
        bad = T.validate_snapshot(T.load_snapshot(path))
        check(not bad, f"snapshot schema valid ({path})")
        if sink is not None:
            T.install_event_sink(None)
            sink.close()

    print(f"\nrollout drill: {'PASS' if not problems else 'FAIL'} "
          f"({len(problems)} problem(s))")
    return 1 if problems else 0


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    sys.exit(main())
