"""SLO-coupled autoscaler: replica membership as a runtime control loop.

Until now the fleet's size was a startup choice (``--replicas N``): the
stack could *fence* a sick replica and *shed* excess load, but it could
never ADD capacity when the SLO burn said users were hurting, nor give
capacity back when the diurnal trough left replicas idle. This module
closes that loop. One ``Autoscaler`` rides the ``ReplicaSet``'s tick and
reads three signals the stack already exports — nothing new is measured:

- **SLO burn** (``slo_burn_rate{window="fast"}``, telemetry/slo.py): the
  hottest replica's fast-window error/TTFT burn — the earliest "users are
  hurting" signal, the same one the router discounts placement by and the
  brownout ladder escalates on;
- **overload level** (``serving/overload.py``): the fleet shed controller
  already browning out is capacity pressure by definition — scaling out
  is the remedy that doesn't refuse anybody;
- **queue depth**: fleet-held backlog relative to the admission queue's
  capacity.

Decisions drive membership through the machinery PR 6 built, so scaling
inherits its guarantees instead of reimplementing them:

- **scale-up** = ``ReplicaSet.add_replica()``: a standby replica (its own
  scheduler / SlotPool / BreakerBoard / watchdog over the shared engine
  params) that must pass the fleet's REJOIN canary probe before it takes
  any traffic — a standby that cannot decode the golden prompt never
  joins (counted ``fleet_standby_denied_total``, retried after cooldown);
- **scale-down** = ``ReplicaSet.retire_replica(lowest-load)``: the victim
  drains with zero grace through the journal path and its in-flight
  requests MIGRATE to the survivors with original ids/settings/row_seeds
  — token-for-token survivor parity, the same contract a fence keeps.
  Retirement is planned, so it counts ``fleet_retired_total`` (not
  ``fleet_fenced_total``) and stays out of the failover-recovery clock.

Hysteresis: a hot signal must hold for ``up_window_s`` before a scale-up
and every signal must stay cold for ``down_window_s`` before a
scale-down; each membership change starts a shared ``cooldown_s`` during
which the controller only watches. The windows reset whenever the signal
flips, so a flapping burn rate can never saw the fleet. Bounds are
absolute: membership stays in [``min_replicas``, ``max_replicas``].

Every decision is observable: the ``fleet_replicas_target`` gauge (what
the controller currently wants), ``autoscale_events_total{direction}``
counters (``up`` / ``down`` / ``up_denied``), ``autoscale_up`` /
``autoscale_down`` / ``autoscale_denied`` JSONL events carrying the
triggering signal, and ``scale_up`` / ``scale_down`` timeline instants on
the affected replica's track. ``tools/validate_telemetry.py
--require-autoscale`` gates the replay drill on a full elastic cycle.
See docs/SERVING.md §Elastic fleet & autoscaling.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Optional

from fairness_llm_tpu.config import AutoscaleConfig
from fairness_llm_tpu.telemetry import emit_event, get_registry
from fairness_llm_tpu.telemetry.flightrecorder import get_flight_recorder
from fairness_llm_tpu.telemetry.incidents import record_decision

logger = logging.getLogger(__name__)


class Autoscaler:
    """One membership controller per ``ReplicaSet`` (duck-typed: anything
    exposing ``replicas`` / ``queue`` / ``_pending`` / ``router`` /
    ``add_replica`` / ``retire_replica`` / ``_max_replica_burn`` serves).
    ``clock`` is injectable for deterministic hysteresis tests."""

    def __init__(self, fleet, config: Optional[AutoscaleConfig] = None,
                 clock=time.monotonic):
        self.fleet = fleet
        self.config = config or AutoscaleConfig(enabled=True)
        cfg = self.config
        if cfg.min_replicas < 1:
            raise ValueError(
                f"autoscale.min_replicas must be >= 1, got {cfg.min_replicas}"
            )
        if cfg.max_replicas < cfg.min_replicas:
            raise ValueError(
                f"autoscale.max_replicas ({cfg.max_replicas}) < "
                f"min_replicas ({cfg.min_replicas})"
            )
        self._clock = clock
        self._labels = dict(getattr(fleet, "_fleet_labels", {}) or {})
        self._hot_since: Optional[float] = None
        self._cold_since: Optional[float] = None
        self._last_action: Optional[float] = None
        self._last_eval: Optional[float] = None
        # Membership the controller WANTS but was refused (a standby that
        # keeps failing its canary gate): keeps fleet_replicas_target
        # honestly above fleet_replicas while the hot signal persists.
        self._denied_want: Optional[int] = None
        self.scale_ups = 0
        self.scale_downs = 0
        self.denied = 0
        # Target gauge exists from construction: a snapshot of a healthy
        # run still shows the controller was armed and content.
        self._target_gauge().set(len(fleet.replicas))

    # -- instruments ---------------------------------------------------------

    def _target_gauge(self):
        return get_registry().gauge("fleet_replicas_target",
                                    component="fleet", **self._labels)

    def _count_event(self, direction: str) -> None:
        get_registry().counter(
            "autoscale_events_total", component="fleet",
            direction=direction, **self._labels,
        ).inc()

    # -- signals -------------------------------------------------------------

    def _queue_frac(self) -> float:
        held = len(self.fleet.queue) + len(self.fleet._pending)
        return held / max(self.fleet.serving.queue_capacity, 1)

    def _overload_level(self) -> int:
        ctl = getattr(self.fleet, "shed_controller", None)
        return ctl.level if ctl is not None else 0

    def _load_frac(self) -> float:
        """Mean outstanding-work fraction across unfenced replicas (live
        slots + replica-queued, over slot capacity) — the scale-down
        guard: a cold-burn fleet still crunching a backlog is not idle."""
        live = [r for r in self.fleet.replicas if not r.fenced]
        if not live:
            return 1.0
        fracs = []
        for rep in live:
            sched = rep.sched
            outstanding = sched.pool.occupancy + len(sched.queue) \
                + len(sched._pending)
            fracs.append(outstanding / max(sched.num_slots, 1))
        return sum(fracs) / len(fracs)

    def _headroom_frac(self) -> float:
        """HBM headroom as a fraction of the device limit, from the memory
        ledger (ISSUE 18). 1.0 when no limit is known (CPU without an
        injected budget) — unknown must read as 'no opinion', never as
        pressure."""
        from fairness_llm_tpu.telemetry.memory import (  # lazy: no cycle
            get_memory_ledger,
        )

        frac = get_memory_ledger().headroom_frac()
        return 1.0 if frac is None else frac

    def signals(self) -> Dict[str, float]:
        """The controller's current inputs, for events and reports."""
        return {
            "burn": round(self.fleet._max_replica_burn(), 3),
            "queue_frac": round(self._queue_frac(), 3),
            "overload_level": self._overload_level(),
            "load_frac": round(self._load_frac(), 3),
            "headroom_frac": round(self._headroom_frac(), 3),
        }

    def _hot_reason(self, sig: Dict[str, float]) -> Optional[str]:
        cfg = self.config
        if sig["burn"] >= cfg.up_burn_threshold:
            return f"slo_burn {sig['burn']:.2f}"
        if sig["queue_frac"] >= cfg.up_queue_frac:
            return f"queue_depth {sig['queue_frac']:.2f}x capacity"
        if cfg.up_overload_level > 0 and \
                sig["overload_level"] >= cfg.up_overload_level:
            return f"overload_level {sig['overload_level']}"
        # Opt-in (up_headroom_frac > 0): a measured-HBM headroom collapse
        # is a capacity signal like a deep queue — scaling up spreads the
        # KV pools across more replicas' devices. Soft by design: the
        # ledger forewarns, it never blocks an admission.
        if cfg.up_headroom_frac > 0 and \
                sig["headroom_frac"] <= cfg.up_headroom_frac:
            return f"hbm_headroom {sig['headroom_frac']:.2f}"
        return None

    def _cold(self, sig: Dict[str, float]) -> bool:
        cfg = self.config
        return (sig["burn"] <= cfg.down_burn_threshold
                and sig["queue_frac"] <= cfg.down_queue_frac
                and sig["load_frac"] <= cfg.down_load_frac)

    # -- the control loop ----------------------------------------------------

    def maybe_tick(self, now: Optional[float] = None) -> bool:
        """Throttled ``tick`` for the fleet loop (one controller step per
        ``eval_interval_s`` at most). Returns True when membership
        actually changed."""
        t = self._clock() if now is None else now
        if self._last_eval is not None and \
                t - self._last_eval < self.config.eval_interval_s:
            return False
        return self.tick(now=t) is not None

    def tick(self, now: Optional[float] = None) -> Optional[str]:
        """One controller step: read the signals, walk the hysteresis
        windows, and — at most one membership change per call — scale.
        Returns "up"/"down" when membership changed, else None."""
        cfg = self.config
        t = self._clock() if now is None else now
        self._last_eval = t
        n = len(self.fleet.replicas)
        sig = self.signals()
        hot = self._hot_reason(sig)
        action: Optional[str] = None
        in_cooldown = (self._last_action is not None
                       and t - self._last_action < cfg.cooldown_s)
        if n < cfg.min_replicas or n > cfg.max_replicas:
            # Bounds are absolute, not just caps on signal-driven moves: a
            # fleet started (or reconfigured) outside [min, max] converges
            # regardless of temperature — one membership change per
            # cooldown, scale-ups still canary-gated, retirements still
            # draining through migration. No hysteresis window applies;
            # neither direction banks one while out of bounds.
            self._hot_since = None
            self._cold_since = None
            if not in_cooldown:
                if n < cfg.min_replicas:
                    action = self._scale_up(
                        f"below min_replicas ({n} < {cfg.min_replicas})",
                        sig, t)
                else:
                    action = self._scale_down(sig, t)
        elif hot is not None:
            # A hot signal invalidates any cold streak immediately — the
            # two windows can never accumulate at once.
            self._cold_since = None
            if self._hot_since is None:
                self._hot_since = t
            if (not in_cooldown and n < cfg.max_replicas
                    and t - self._hot_since >= cfg.up_window_s):
                action = self._scale_up(hot, sig, t)
        elif self._cold(sig):
            self._hot_since = None
            if self._cold_since is None:
                self._cold_since = t
            if (not in_cooldown and n > cfg.min_replicas
                    and t - self._cold_since >= cfg.down_window_s):
                action = self._scale_down(sig, t)
        else:
            # The lukewarm middle: neither escalation nor retirement may
            # bank time here — each direction needs its own unbroken run.
            self._hot_since = None
            self._cold_since = None
        if hot is None:
            # The pressure that wanted the denied standby has passed.
            self._denied_want = None
        self._target_gauge().set(
            self._denied_want or len(self.fleet.replicas))
        return action

    def _scale_up(self, reason: str, sig: Dict[str, float],
                  now: float) -> Optional[str]:
        self._last_action = now
        self._hot_since = None  # the next rung needs a fresh hot window
        rep = self.fleet.add_replica()
        if rep is None:
            self.denied += 1
            self._denied_want = len(self.fleet.replicas) + 1
            self._count_event("up_denied")
            emit_event("autoscale_denied", reason=reason, **sig,
                       **self._labels)
            # Decision audit trail (telemetry/incidents.py): the denial
            # with the signals that wanted the standby — a postmortem of a
            # capacity incident must show the controller TRIED.
            record_decision("autoscale", "up_denied",
                            signals={"reason": reason, **sig})
            return None
        self._denied_want = None
        self.scale_ups += 1
        self._count_event("up")
        emit_event("autoscale_up", replica=rep.name, reason=reason,
                   replicas=len(self.fleet.replicas), **sig, **self._labels)
        record_decision("autoscale", "up",
                        signals={"reason": reason, **sig},
                        replica=rep.name)
        get_flight_recorder().transition(
            "fleet_replicas", self._labels.get("fleet") or "fleet",
            len(self.fleet.replicas))
        logger.warning("autoscale UP -> %d replicas (%s): %s",
                       len(self.fleet.replicas), rep.name, reason)
        return "up"

    def _scale_down(self, sig: Dict[str, float],
                    now: float) -> Optional[str]:
        live = [r for r in self.fleet.replicas if not r.fenced]
        if len(live) < 2:
            # Retiring the only healthy replica would strand the fenced
            # rest's eventual migrations; wait for a rejoin instead.
            return None
        self._last_action = now
        self._cold_since = None  # the next retirement needs a fresh window
        self._denied_want = None  # retiring supersedes any stale up-want
        victim = min(live, key=lambda r: (self.fleet.router.load(r), r.name))
        migrated = self.fleet.retire_replica(victim)
        self.scale_downs += 1
        self._count_event("down")
        emit_event("autoscale_down", replica=victim.name, migrated=migrated,
                   replicas=len(self.fleet.replicas), **sig, **self._labels)
        record_decision("autoscale", "down",
                        signals={"migrated": migrated, **sig},
                        replica=victim.name)
        get_flight_recorder().transition(
            "fleet_replicas", self._labels.get("fleet") or "fleet",
            len(self.fleet.replicas))
        logger.warning("autoscale DOWN -> %d replicas (retired %s, "
                       "%d migrated)", len(self.fleet.replicas),
                       victim.name, migrated)
        return "down"


__all__ = ["Autoscaler", "AutoscaleConfig"]
