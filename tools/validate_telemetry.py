"""Validate a telemetry snapshot (schema + percentile self-consistency) and
assert the serving signals the ISSUE-3 acceptance criteria name are present
and nonzero.

Usage:
    python tools/validate_telemetry.py <telemetry-dir-or-snapshot.json>
    python tools/validate_telemetry.py <path> --require-serving
    python tools/validate_telemetry.py <path> --require-breaker
    python tools/validate_telemetry.py <path> --require-integrity
    python tools/validate_telemetry.py <path> --require-fleet
    python tools/validate_telemetry.py <path> --require-profile

Plain mode checks the schema only (`cli telemetry-report --validate` does
the same inline). ``--require-serving`` additionally requires nonzero TTFT,
queue-wait, and per-output-token histograms with p50 <= p95 <= p99 <= max —
the CI smoke step's gate after a ``--continuous --telemetry-dir`` run of the
tiny CPU study. ``--require-breaker`` requires the resilience signals the
chaos smoke step produces: breaker_state gauges, a full
closed->open->half-open->closed transition cycle, and a counted hang.
``--require-integrity`` requires the silent-corruption signals the extended
chaos drill produces: a counted NumericsFault, a manifest digest failure,
and a canary run with at least one mismatch. ``--require-fleet`` requires
the replica-failover signals the fleet drill produces: a nonzero
``fleet_fenced_total``, ``fleet_migrated_requests_total`` equal to
``fleet_migrated_recovered_total`` (every migrated request reached a
terminal Result), and ``fleet_healthy_replicas`` back to
``fleet_replicas`` (the killed replica rejoined via its canary probe).
``--require-profile`` requires the performance-attribution signals
(ISSUE 7): nonzero compile events (``compiles_total``), a populated
``achieved_hbm_gbps`` roofline gauge, a nonzero ``step_gap_s``
histogram, and a schema-valid ``trace.json`` beside the snapshot
containing prefill + decode spans and request lanes.
``--require-overload`` requires the overload-control signals the brownout
drill produces (ISSUE 8): nonzero ``shed_total``, an
``overload_transitions_total`` escalation AND a return to level 0, and
every ``overload_level`` gauge ending at 0.
``--require-autoscale`` requires the elastic-fleet signals the replay
smoke drill produces (ISSUE 11): at least one
``autoscale_events_total{direction="up"}`` AND one ``direction="down"``
(a full elastic cycle), ``replay_accepted_total`` equal to
``replay_terminal_total`` (zero accepted-then-lost across the replay),
``fleet_migrated_requests_total`` equal to
``fleet_migrated_recovered_total``, and every fleet's
``fleet_healthy_replicas`` back to its ``fleet_replicas`` (the trace's
crashed replica rejoined; retired replicas left the gauge entirely).
``--require-costmodel`` requires the decode cost ledger (ISSUE 12):
every program counted in ``compiles_total`` must have published a nonzero
``cost_ledger_bytes`` gauge (the jaxpr-walked analytical bytes per
component), plus nonzero ``cost_wall_s_total`` accumulation so the
``perf-report`` gap decomposition is derivable from the snapshot. A
``*_fused`` program (ISSUE 14's fused multi-step dispatch) additionally
must show its own measured wall AND a nonzero ``cost_host_gap_s_total``
— the host-gap term fusion exists to shrink must be MEASURED, never
assumed; ``--require-profile`` likewise holds a fused program to roofline
gauges under its own label.
``--require-incidents`` requires the incident engine's evidence (ISSUE 13):
at least one complete postmortem bundle under ``<dir>/incidents`` (manifest
with a known class + cause, flight-recorder rings, decision trail, registry
snapshot, trace slice — no torn ``.partial`` leftovers), a nonzero
``decisions_total`` audit trail, and ``incident_bundles_total`` agreeing
with the bundles on disk. ``--forbid-incidents`` is the inverse gate for
fault-free runs: ZERO bundles — an incident bundle from a clean study is
itself a defect.
``--require-rollout`` requires the zero-downtime upgrade evidence the
rollout drill produces (ISSUE 20): at least one
``rollout_transitions_total{to="complete"}`` AND one ``{to="rolled_back"}``
(the clean upgrade and the gate-triggered abort both happened), every
``rollout_rollbacks_total`` entry carrying a NAMED gate cause, every
``rollout_state`` gauge terminal (never abandoned mid-wave), and fleet
migration counters balanced (``fleet_migrated_requests_total`` equal to
``fleet_migrated_recovered_total`` — rollback re-fencing lost nothing).
``--require-fairness`` requires the fairness-observability signals a
fault-free ``--fairness-obs --continuous`` study produces (ISSUE 9):
nonzero ``fairness_requests_total`` and ``fairness_pairs_joined_total``,
populated ``fairness_dp``/``fairness_if``/``fairness_exposure_ratio``
run-window gauges in [0, 1], each streaming gauge matching its
``fairness_offline_*`` counterpart to fp tolerance (the live-vs-offline
cross-check), ZERO ``fairness_pair_divergence_total``, and ZERO
``fairness_alerts_total`` — a fault-free run must be silent.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fairness_llm_tpu.telemetry import load_snapshot, validate_snapshot  # noqa: E402

REQUIRED_SERVING_HISTOGRAMS = ("ttft_s", "queue_wait_s", "per_output_token_s")


def check(path: str, require_serving: bool = False,
          require_breaker: bool = False,
          require_integrity: bool = False,
          require_fleet: bool = False,
          require_profile: bool = False,
          require_overload: bool = False,
          require_fairness: bool = False,
          require_autoscale: bool = False,
          require_costmodel: bool = False,
          require_incidents: bool = False,
          require_memory: bool = False,
          require_rollout: bool = False,
          forbid_incidents: bool = False) -> int:
    snap = load_snapshot(path)
    problems = list(validate_snapshot(snap))
    if require_incidents or forbid_incidents:
        problems.extend(_check_incidents(path, snap,
                                         require=require_incidents,
                                         forbid=forbid_incidents))
    if require_profile:
        problems.extend(_check_profile(path, snap))
    if require_costmodel:
        problems.extend(_check_costmodel(snap))
    if require_memory:
        problems.extend(_check_memory(snap))
    if require_rollout:
        problems.extend(_check_rollout(snap))
    if require_fairness:
        problems.extend(_check_fairness(snap))
    if require_autoscale:
        problems.extend(_check_autoscale(snap))
    if require_overload:
        counters = snap.get("counters", [])

        def total(name):
            return sum(c["value"] for c in counters if c.get("name") == name)

        if not total("shed_total"):
            problems.append(
                "shed_total is zero (overload control never shed anything)"
            )
        trans = [c for c in counters
                 if c.get("name") == "overload_transitions_total"]
        if not any(c["value"] for c in trans
                   if c.get("labels", {}).get("to") not in (None, "0")):
            problems.append(
                "no overload transition to a nonzero level (the brownout "
                "ladder never escalated)"
            )
        if not any(c["value"] for c in trans
                   if c.get("labels", {}).get("to") == "0"):
            problems.append(
                "no overload transition back to level 0 (the controller "
                "never de-escalated)"
            )
        levels = [g for g in snap.get("gauges", [])
                  if g.get("name") == "overload_level"]
        if not levels:
            problems.append("no overload_level gauge (overload control "
                            "never armed)")
        for g in levels:
            if g["value"] != 0:
                problems.append(
                    f"overload_level {g.get('labels', {})} ended at "
                    f"{g['value']:g} (controller did not return to 0)"
                )
    if require_fleet:
        counters = snap.get("counters", [])

        def total(name):
            return sum(c["value"] for c in counters if c.get("name") == name)

        fenced = total("fleet_fenced_total")
        if not fenced:
            problems.append(
                "fleet_fenced_total is zero (no replica was ever fenced)"
            )
        migrated = total("fleet_migrated_requests_total")
        recovered = total("fleet_migrated_recovered_total")
        if not migrated:
            problems.append(
                "fleet_migrated_requests_total is zero (failover never "
                "migrated anything)"
            )
        elif migrated != recovered:
            problems.append(
                f"migrated ({migrated}) != recovered ({recovered}) — "
                "migrated requests were lost"
            )
        # Pair healthy/replicas gauges per LABEL SET: a process can run
        # more than one fleet (one per sampler tuple, each with its own
        # {"fleet": name} label), and flattening by name would let one
        # whole fleet mask another's fenced-forever replica.
        fleets = {}
        for g in snap.get("gauges", []):
            labels = g.get("labels", {})
            if labels.get("component") != "fleet":
                continue
            key = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "component"
            ))
            fleets.setdefault(key, {})[g["name"]] = g["value"]
        sized = {k: v for k, v in fleets.items()
                 if v.get("fleet_replicas", 0) >= 2}
        if not sized:
            problems.append(
                "no fleet_replicas gauge >= 2 (no fleet was armed)"
            )
        for key, vals in sized.items():
            replicas = vals["fleet_replicas"]
            healthy = vals.get("fleet_healthy_replicas", -1)
            if healthy != replicas:
                tag = dict(key).get("fleet", "default")
                problems.append(
                    f"fleet {tag!r}: fleet_healthy_replicas ({healthy}) != "
                    f"fleet_replicas ({replicas}) — a fenced replica never "
                    "rejoined"
                )
    if require_integrity:
        counters = snap.get("counters", [])

        def total(name):
            return sum(c["value"] for c in counters if c.get("name") == name)

        for name in ("numerics_faults_total", "manifest_failures_total",
                     "canary_runs_total", "canary_mismatch_total"):
            if not total(name):
                problems.append(
                    f"{name} is zero (integrity drill didn't exercise it)"
                )
    if require_breaker:
        gauges = [g for g in snap.get("gauges", [])
                  if g.get("name") == "breaker_state"]
        if not gauges:
            problems.append("no breaker_state gauges (resilience not armed?)")
        trans = {
            (c["labels"].get("stage"), c["labels"].get("to")): c["value"]
            for c in snap.get("counters", [])
            if c.get("name") == "breaker_transitions_total"
        }
        for to in ("open", "half_open", "closed"):
            if not any(v for (stage, t), v in trans.items() if t == to):
                problems.append(
                    f"no breaker transition to={to} (cycle incomplete)"
                )
        hangs = [c for c in snap.get("counters", [])
                 if c.get("name") == "watchdog_hangs_total" and c["value"]]
        if not hangs:
            problems.append("watchdog_hangs_total is zero (no hang counted)")
    if require_serving:
        hists = {
            h["name"]: h
            for h in snap.get("histograms", [])
            if h.get("labels", {}).get("component") == "serving"
        }
        for name in REQUIRED_SERVING_HISTOGRAMS:
            h = hists.get(name)
            if h is None:
                problems.append(f"serving histogram {name!r} missing")
            elif not h.get("count"):
                problems.append(f"serving histogram {name!r} is empty")
            elif not (h.get("min") or 0) > 0:
                problems.append(f"serving histogram {name!r} has zero samples")
        # validate_snapshot already enforced p50 <= p95 <= p99 <= max for
        # every non-empty histogram; nothing extra to re-derive here.
    if problems:
        print(f"INVALID: {path}")
        for p in problems:
            print(f"  - {p}")
        return 1
    print(f"OK: {path} "
          f"({len(snap.get('counters', []))} counters, "
          f"{len(snap.get('histograms', []))} histograms)")
    return 0


def _check_incidents(path: str, snap: dict, require: bool,
                     forbid: bool) -> list:
    """The --require-incidents / --forbid-incidents gates (ISSUE 13).
    Bundle shape is validated by the telemetry layer itself
    (``validate_incidents``); this adds the snapshot cross-checks — a
    recorded decision trail and counter/bundle agreement."""
    from fairness_llm_tpu.telemetry import validate_incidents
    from fairness_llm_tpu.telemetry.incidents import (
        INCIDENTS_DIRNAME,
        list_bundles,
    )

    tel_dir = path if os.path.isdir(path) else os.path.dirname(path)
    problems = list(validate_incidents(tel_dir, require=require,
                                       forbid=forbid))
    counters = snap.get("counters", [])

    def total(name):
        return sum(c["value"] for c in counters if c.get("name") == name)

    if forbid:
        # Disk state alone can't see a trigger whose dump FAILED (the
        # contained-exception path cleans its .partial): the snapshot
        # counter can. Any counted trigger in a must-be-clean run is a
        # violation, bundle or no bundle. (The counter only increments
        # while the engine is armed, so an unarmed drill stays clean.)
        fired = total("incident_triggers_total")
        if fired:
            problems.append(
                f"incident_triggers_total = {fired:g} in a run that must "
                "produce no incidents (a trigger fired — even if its "
                "bundle dump failed)"
            )
    if not require:
        return problems
    if not total("decisions_total"):
        problems.append("decisions_total is zero (the decision audit trail "
                        "never recorded — recording switched off?)")
    n_bundles = len(list_bundles(os.path.join(tel_dir, INCIDENTS_DIRNAME)))
    counted = total("incident_bundles_total")
    if n_bundles and counted != n_bundles:
        problems.append(
            f"incident_bundles_total ({counted:g}) != bundles on disk "
            f"({n_bundles}) — bundles from another run, or a dump the "
            "counter missed"
        )
    return problems


def _check_costmodel(snap: dict) -> list:
    """The --require-costmodel gate (ISSUE 12): every compiled program seen
    in ``compiles_total`` published a nonzero jaxpr-walked cost ledger, and
    the gap-attribution accumulators the ``perf-report`` decomposition
    needs (measured wall + per-component floor) are populated."""
    problems = []
    compiled = sorted({
        c.get("labels", {}).get("program")
        for c in snap.get("counters", [])
        if c.get("name") == "compiles_total" and c.get("value")
    } - {None})
    if not compiled:
        problems.append("compiles_total is empty (no compiled program to "
                        "require a ledger for)")
    ledgered = {}
    for g in snap.get("gauges", []):
        if g.get("name") != "cost_ledger_bytes":
            continue
        prog = g.get("labels", {}).get("program")
        ledgered[prog] = ledgered.get(prog, 0.0) + float(g.get("value", 0.0))
    for prog in compiled:
        if ledgered.get(prog, 0.0) <= 0:
            problems.append(
                f"compiled program {prog!r} has no nonzero cost_ledger_bytes "
                "gauge (the jaxpr cost walk never ran for it)"
            )
    walls = {
        g.get("labels", {}).get("program"): float(g.get("value", 0.0))
        for g in snap.get("gauges", [])
        if g.get("name") == "cost_wall_s_total"
    }
    if not any(v > 0 for v in walls.values()):
        problems.append("no nonzero cost_wall_s_total gauge (gap "
                        "attribution has no measured wall to decompose)")
    # The floor is priced against the device's published peaks
    # (telemetry/roofline.py DEVICE_PEAKS); a run on a device without any
    # publishes no reference gauges and owes no floor.
    priced = any(g.get("name") == "cost_reference_gbps"
                 for g in snap.get("gauges", []))
    floors = [g for g in snap.get("gauges", [])
              if g.get("name") == "cost_component_min_s_total"
              and g.get("value", 0.0) > 0]
    if priced and not floors:
        problems.append("cost_component_min_s_total is empty (no invocation "
                        "ever folded its ledger into the floor)")
    # Fused dispatch programs (ISSUE 14, runtime/stepbuilder.py): a
    # *_fused program in compiles_total publishes under its OWN label, so
    # beyond the every-program ledger check above it must show a measured
    # wall and a nonzero measured host gap — a fused program whose whole
    # point is host-gap amortization that never accumulated one means the
    # dispatch boundary instrumentation is broken, not that gaps are zero
    # (the between-dispatch eviction/admission work is never literally 0s).
    host_gaps = {
        g.get("labels", {}).get("program"): float(g.get("value", 0.0))
        for g in snap.get("gauges", [])
        if g.get("name") == "cost_host_gap_s_total"
    }
    for prog in compiled:
        # Mesh-sharded programs carry a @tp<k> label suffix
        # (runtime/stepbuilder.program_label); strip it before the
        # *_fused structural check so fused sharded programs are held to
        # the same contract as their single-device twins.
        if not prog.split("@", 1)[0].endswith("_fused"):
            continue
        if walls.get(prog, 0.0) <= 0:
            problems.append(
                f"fused program {prog!r} has no measured cost_wall_s_total "
                "(its invocations were never accumulated)"
            )
        if host_gaps.get(prog, 0.0) <= 0:
            problems.append(
                f"fused program {prog!r} has no nonzero "
                "cost_host_gap_s_total (the fused-dispatch boundary never "
                "measured a host gap)"
            )
    # Tensor-parallel programs (the stepbuilder's mesh axis): a @tp<k>
    # program runs real collectives, so its ledger must carry a nonzero
    # `collectives` component — a sharded run whose ledger shows none
    # means the collectives attribution (jaxpr prims, xplane regexes, or
    # the analytic GSPMD rows) silently fell through.
    coll = {}
    for g in snap.get("gauges", []):
        if (g.get("name") == "cost_ledger_bytes"
                and g.get("labels", {}).get("component") == "collectives"):
            prog = g.get("labels", {}).get("program")
            coll[prog] = coll.get(prog, 0.0) + float(g.get("value", 0.0))
    for prog in compiled:
        if "@tp" not in prog:
            continue
        if coll.get(prog, 0.0) <= 0:
            problems.append(
                f"sharded program {prog!r} has no nonzero collectives "
                "component in cost_ledger_bytes (tensor-parallel comm "
                "never attributed)"
            )
    return problems


def _check_rollout(snap: dict) -> list:
    """The --require-rollout gate (ISSUE 20): the rollout drill completed
    one upgrade AND rolled at least one back through a named gate, every
    rollout reached a terminal state, and migration accounting balanced
    (no request lost crossing a fenced new-version replica)."""
    problems = []
    counters = snap.get("counters", [])

    def total(name, **want):
        return sum(
            c["value"] for c in counters if c.get("name") == name
            and all(c.get("labels", {}).get(k) == v
                    for k, v in want.items())
        )

    if not total("rollout_transitions_total", to="complete"):
        problems.append(
            "no rollout reached complete (the clean-upgrade half of the "
            "drill never finished a wave sequence)"
        )
    if not total("rollout_transitions_total", to="rolled_back"):
        problems.append(
            "no rollout ever rolled back (the gate half of the drill "
            "never fired)"
        )
    causes = {
        c.get("labels", {}).get("cause")
        for c in counters
        if c.get("name") == "rollout_rollbacks_total" and c["value"] > 0
    }
    if not causes or None in causes:
        problems.append(
            "rollout_rollbacks_total carries no named gate cause (a "
            "rollback must say WHICH deployment gate fired)"
        )
    # Terminal-state proof: every fleet's rollout_state gauge must end in
    # rolled_back or complete — a mid-wave state in the final snapshot
    # means a rollout was abandoned, not resolved.
    terminal = {6.0, 7.0}  # ROLLOUT_STATES indices: rolled_back, complete
    for g in snap.get("gauges", []):
        if g.get("name") == "rollout_state" \
                and g.get("value") not in terminal:
            problems.append(
                f"rollout_state {g.get('labels', {})} ended mid-wave "
                f"(value {g.get('value')}) — rollout neither completed "
                "nor rolled back"
            )
    migrated = total("fleet_migrated_requests_total")
    recovered = total("fleet_migrated_recovered_total")
    if migrated != recovered:
        problems.append(
            f"fleet migration accounting unbalanced across the rollout "
            f"({migrated:g} migrated != {recovered:g} recovered)"
        )
    return problems


def _check_memory(snap: dict) -> list:
    """The --require-memory gate (ISSUE 18): the HBM memory ledger
    accounted real pools, every compiled program seen in ``compiles_total``
    (including ``*_fused`` and ``@tpN``) published its AOT
    ``program_memory_bytes``, and where a device limit exists the ledger
    total respects it."""
    problems = []
    gauges = snap.get("gauges", [])
    # Pool residency: the params pool and the KV pool must be nonzero — a
    # serving run that allocated neither accounted nothing.
    pool_bytes = {}
    for g in gauges:
        lb = g.get("labels", {})
        if g.get("name") != "hbm_bytes" or "shard" in lb:
            continue
        pool = lb.get("pool")
        pool_bytes[pool] = pool_bytes.get(pool, 0.0) + float(
            g.get("value", 0.0))
    if pool_bytes.get("params", 0.0) <= 0:
        problems.append("hbm_bytes{pool=params} is zero or absent (no "
                        "engine ever registered its param tree)")
    if pool_bytes.get("kv_contiguous", 0.0) <= 0:
        problems.append("hbm_bytes{pool=kv_contiguous} is zero or absent "
                        "(no scheduler ever registered its KV state)")
    # Ledger total vs limit: where a limit exists (device-reported or
    # injected analytic), the accounted total must fit under it.
    total = sum(float(g.get("value", 0.0)) for g in gauges
                if g.get("name") == "hbm_bytes_total")
    limit = sum(float(g.get("value", 0.0)) for g in gauges
                if g.get("name") == "hbm_bytes_limit")
    if total <= 0:
        problems.append("hbm_bytes_total is zero or absent (the ledger "
                        "never reconciled)")
    if limit > 0 and total > limit:
        problems.append(
            f"ledger total {total:.0f} B exceeds the HBM limit "
            f"{limit:.0f} B (the accounting claims more memory than the "
            "device has)"
        )
    # Per-program AOT memory: every program compiled this run must have
    # published its memory_analysis — same every-program contract as the
    # cost ledger, and the @tpN / *_fused labels get no exemption (each
    # label IS its own compiled program).
    compiled = sorted({
        c.get("labels", {}).get("program")
        for c in snap.get("counters", [])
        if c.get("name") == "compiles_total" and c.get("value")
    } - {None})
    if not compiled:
        problems.append("compiles_total is empty (no compiled program to "
                        "require AOT memory analysis for)")
    prog_kinds = {}
    for g in gauges:
        if g.get("name") != "program_memory_bytes":
            continue
        lb = g.get("labels", {})
        prog_kinds.setdefault(lb.get("program"), set()).add(lb.get("kind"))
    for prog in compiled:
        kinds = prog_kinds.get(prog, set())
        missing = {"argument", "output", "temp"} - kinds
        if missing:
            problems.append(
                f"compiled program {prog!r} missing program_memory_bytes "
                f"kinds {sorted(missing)} (AOT memory_analysis never "
                "captured for it)"
            )
    return problems


def _check_autoscale(snap: dict) -> list:
    """The --require-autoscale gate (ISSUE 11): a full elastic cycle
    (scale-up AND scale-down), zero accepted-then-lost across the replay,
    migrated == recovered, and every fleet whole at the end."""
    problems = []
    counters = snap.get("counters", [])

    def total(name, **want):
        return sum(
            c["value"] for c in counters
            if c.get("name") == name and all(
                c.get("labels", {}).get(k) == v for k, v in want.items()
            )
        )

    ups = total("autoscale_events_total", direction="up")
    downs = total("autoscale_events_total", direction="down")
    if not ups:
        problems.append("no autoscale_events_total{direction=up} (the "
                        "burst never drove a scale-up)")
    if not downs:
        problems.append("no autoscale_events_total{direction=down} (the "
                        "quiet tail never drove a scale-down)")
    accepted = total("replay_accepted_total")
    terminal = total("replay_terminal_total")
    if not accepted:
        problems.append("replay_accepted_total is zero (no replay ran)")
    elif accepted != terminal:
        problems.append(
            f"replay accepted ({accepted:g}) != terminal ({terminal:g}) — "
            "accepted requests were lost"
        )
    migrated = total("fleet_migrated_requests_total")
    recovered = total("fleet_migrated_recovered_total")
    if migrated != recovered:
        problems.append(
            f"migrated ({migrated:g}) != recovered ({recovered:g}) — "
            "migrated requests were lost"
        )
    # Final fleet wholeness, per label set (same pairing rule as
    # --require-fleet; a retired replica shrinks fleet_replicas, so a
    # scaled-down fleet still reads whole here).
    fleets = {}
    for g in snap.get("gauges", []):
        labels = g.get("labels", {})
        if labels.get("component") != "fleet":
            continue
        key = tuple(sorted(
            (k, v) for k, v in labels.items() if k != "component"
        ))
        fleets.setdefault(key, {})[g["name"]] = g["value"]
    saw_fleet = False
    for key, vals in fleets.items():
        if "fleet_replicas" not in vals:
            continue
        saw_fleet = True
        replicas = vals["fleet_replicas"]
        healthy = vals.get("fleet_healthy_replicas", -1)
        if healthy != replicas:
            tag = dict(key).get("fleet", "default")
            problems.append(
                f"fleet {tag!r}: fleet_healthy_replicas ({healthy:g}) != "
                f"fleet_replicas ({replicas:g}) — the final fleet is not "
                "healthy"
            )
    if not saw_fleet:
        problems.append("no fleet_replicas gauge (no fleet was armed)")
    return problems


# |live - offline| bound for the streaming-vs-offline fairness cross-check:
# identical kernels over identically-valued count matrices, differing only
# in float summation order (vocab interning order) and float32-vs-float64
# mean accumulation — observed deltas are ~1e-7; 1e-4 leaves margin without
# letting a real aggregation bug (wrong group, dropped list) through.
FAIRNESS_TOLERANCE = 1e-4


def _check_fairness(snap: dict) -> list:
    """The --require-fairness gate (ISSUE 9): streaming group metrics
    populated and matching the offline scores, pair watch joined, and a
    fault-free run SILENT (zero divergence, zero neutrality alerts)."""
    problems = []
    counters = snap.get("counters", [])
    gauges = snap.get("gauges", [])

    def total(name):
        return sum(c["value"] for c in counters if c.get("name") == name)

    def gauge_rows(name, **want):
        out = []
        for g in gauges:
            lb = g.get("labels", {})
            if g.get("name") == name and all(lb.get(k) == v
                                             for k, v in want.items()):
                out.append(g)
        return out

    if not total("fairness_requests_total"):
        problems.append("fairness_requests_total is zero (no tagged "
                        "request ever finished — was --fairness-obs on?)")
    if not total("fairness_pairs_joined_total"):
        problems.append("fairness_pairs_joined_total is zero (the pair "
                        "watch never joined a counterfactual pair)")
    for name in ("fairness_dp", "fairness_if", "fairness_exposure_ratio"):
        rows = gauge_rows(name, window="run")
        if not rows:
            problems.append(f"no run-window {name} gauge (streaming "
                            "accumulators never refreshed)")
        for g in rows:
            if not 0.0 <= g["value"] <= 1.0:
                problems.append(f"{name} {g.get('labels', {})} = "
                                f"{g['value']:g} outside [0, 1]")
    # Live-vs-offline cross-check: every published offline reference must
    # have a streaming counterpart within tolerance.
    offline_of = {"fairness_offline_dp": "fairness_dp",
                  "fairness_offline_if": "fairness_if",
                  "fairness_offline_exposure": "fairness_exposure_ratio"}
    checked = 0
    for off_name, live_name in offline_of.items():
        for off in gauge_rows(off_name):
            attr = off.get("labels", {}).get("attribute")
            live = gauge_rows(live_name, attribute=attr, window="run")
            if not live:
                problems.append(f"{off_name}{{attribute={attr}}} has no "
                                f"run-window {live_name} counterpart")
                continue
            checked += 1
            delta = abs(live[0]["value"] - off["value"])
            if delta > FAIRNESS_TOLERANCE:
                problems.append(
                    f"{live_name}{{attribute={attr}}} = "
                    f"{live[0]['value']:.6f} vs offline {off['value']:.6f} "
                    f"(|delta| {delta:.2e} > {FAIRNESS_TOLERANCE:g}) — "
                    "streaming accumulation diverged from the batch metric"
                )
    if not checked:
        problems.append("no fairness_offline_* gauges (the phase never "
                        "published its offline reference scores)")
    # A fault-free run must be SILENT: serving treated every group equally
    # and no pair's delivery was impaired.
    if total("fairness_pair_divergence_total"):
        problems.append("fairness_pair_divergence_total is nonzero in a "
                        "fault-free run (serving impaired a pair member)")
    if total("fairness_alerts_total"):
        problems.append("fairness_alerts_total is nonzero in a fault-free "
                        "run (the neutrality audit saw group disparity)")
    return problems


def _check_profile(path: str, snap: dict) -> list:
    """The --require-profile gate: compile events, roofline gauges, step
    gaps, and a schema-valid trace.json with the span kinds the ISSUE-7
    acceptance names (prefill/decode/request on tracks) and the named
    spans of the scheduler's iteration."""
    import json

    from fairness_llm_tpu.telemetry import TRACE_FILENAME, validate_chrome_trace

    problems = []
    if not sum(c["value"] for c in snap.get("counters", [])
               if c.get("name") == "compiles_total"):
        problems.append("compiles_total is zero (no compile event recorded)")
    # achieved_hbm_gbps needs no peak, so every device publishes it; the
    # achieved_over_achievable fraction exists only where the device has a
    # published peak (or --achievable-gbps installed one).
    aoa = [g for g in snap.get("gauges", [])
           if g.get("name") == "achieved_hbm_gbps"]
    if not aoa:
        problems.append("no achieved_hbm_gbps gauge (roofline accounting "
                        "never ran)")
    elif not any(g["value"] > 0 for g in aoa):
        problems.append("achieved_hbm_gbps is zero everywhere")
    gaps = [h for h in snap.get("histograms", [])
            if h.get("name") == "step_gap_s"]
    if not any(h.get("count") for h in gaps):
        problems.append("step_gap_s histogram empty (no consecutive decode "
                        "chunks recorded)")
    # A fused step program (ISSUE 14) must publish roofline gauges under
    # its OWN label — fused chunks dividing by actual fused steps is the
    # per-iteration correctness the satellite pins, and a fused program
    # silently folding into the unfused label would hide it.
    fused = sorted({
        c.get("labels", {}).get("program")
        for c in snap.get("counters", [])
        if c.get("name") == "compiles_total" and c.get("value")
        and str(c.get("labels", {}).get("program", ""))
        .split("@", 1)[0].endswith("_fused")  # @tp<k> mesh suffix strips off
    })
    for prog in fused:
        if not any(g.get("labels", {}).get("program") == prog
                   and g["value"] > 0 for g in aoa):
            problems.append(
                f"fused program {prog!r} has no nonzero "
                "achieved_hbm_gbps gauge (fused chunks must feed "
                "the roofline under their own label)"
            )
    trace_dir = path if os.path.isdir(path) else os.path.dirname(path)
    trace_path = os.path.join(trace_dir, TRACE_FILENAME)
    if not os.path.exists(trace_path):
        problems.append(f"{trace_path} missing (run with --trace-out or "
                        "--telemetry-dir)")
        return problems
    with open(trace_path, encoding="utf-8") as f:
        trace = json.load(f)
    problems.extend(f"trace.json: {p}" for p in validate_chrome_trace(trace))
    cats = {ev.get("cat") for ev in trace.get("traceEvents", [])
            if ev.get("ph") == "X"}
    for want in ("prefill", "decode"):
        if want not in cats:
            problems.append(f"trace.json has no cat={want!r} spans")
    # The pieces of the scheduler's iteration, by name (docs/OBSERVABILITY.md
    # "Spans inside the iteration"): a serving run that decoded anything
    # has every one of them.
    names = {ev.get("name") for ev in trace.get("traceEvents", [])
             if ev.get("ph") == "X"}
    if "decode" in cats:
        for want in ("sched.iterate", "sched.prefill.dispatch",
                     "sched.decode.dispatch", "sched.decode.sync"):
            if want not in names:
                problems.append(f"trace.json has no {want!r} span")
    if not any(ev.get("ph") == "b" for ev in trace.get("traceEvents", [])):
        problems.append("trace.json has no request spans (async b/e events)")
    return problems


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--require-serving", action="store_true")
    ap.add_argument("--require-breaker", action="store_true")
    ap.add_argument("--require-integrity", action="store_true")
    ap.add_argument("--require-fleet", action="store_true")
    ap.add_argument("--require-profile", action="store_true")
    ap.add_argument("--require-overload", action="store_true")
    ap.add_argument("--require-fairness", action="store_true")
    ap.add_argument("--require-autoscale", action="store_true")
    ap.add_argument("--require-costmodel", action="store_true")
    ap.add_argument("--require-incidents", action="store_true")
    ap.add_argument("--require-memory", action="store_true")
    ap.add_argument("--require-rollout", action="store_true")
    ap.add_argument("--forbid-incidents", action="store_true")
    a = ap.parse_args()
    return check(a.path, require_serving=a.require_serving,
                 require_breaker=a.require_breaker,
                 require_integrity=a.require_integrity,
                 require_fleet=a.require_fleet,
                 require_profile=a.require_profile,
                 require_overload=a.require_overload,
                 require_fairness=a.require_fairness,
                 require_autoscale=a.require_autoscale,
                 require_costmodel=a.require_costmodel,
                 require_incidents=a.require_incidents,
                 require_memory=a.require_memory,
                 require_rollout=a.require_rollout,
                 forbid_incidents=a.forbid_incidents)


if __name__ == "__main__":
    sys.exit(main())
