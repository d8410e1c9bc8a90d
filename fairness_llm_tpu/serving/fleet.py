"""Replica fleet: N engine replicas behind one bounded admission queue.

Everything below the fleet protects exactly ONE engine: the watchdog, the
per-stage breakers, the degradation ladder, the numerics guards, the canary
— all of it keeps one scheduler's loop alive, and a single hung or
NaN-poisoned engine still takes the whole serving stack down with it. The
``ReplicaSet`` is the next containment boundary up — *a sick replica
drains, not the fleet*:

- **N independent replicas**, each a full serving stack of its own: its own
  ``ContinuousScheduler`` (KV slot pool + compiled programs), its own
  ``BreakerBoard`` + degradation ladder, its own step watchdog, and its own
  rejoin canary — every instrument labeled ``{"replica": name}`` so one
  registry holds N distinguishable health states. Replicas may share one
  engine's params (the CPU-harness shape: one weight tree, N slot pools) or
  carry one engine each (the multi-chip topology this scaffolds — ROADMAP
  item 2(b) plugs real-mesh TP=8 engines into exactly this seam, and 2(c)
  splits prefill/decode replicas over it).
- **Health-aware routing** (``serving/router.py``): admissions pop from the
  fleet's bounded ``AdmissionQueue`` and land on the healthiest,
  least-loaded replica — breaker states, ladder level, canary freshness,
  and queue-depth high-water marks all discount a replica's share, so a
  struggling replica sheds traffic *before* it needs fencing.
- **Fencing**: a replica whose ladder climbs past
  ``FleetConfig.fence_ladder_level``, whose open-breaker count reaches
  ``fence_open_breakers``, whose stall probe fires, or that takes an
  injected ``replica_crash``/``replica_hang`` is FENCED: drained through
  the existing ``GracefulDrain``/journal path with **zero grace** (a sick
  replica must not keep decoding work that should migrate), its breakers
  forced open for crash-class reasons, and every unfinished request
  **migrated** — re-routed to healthy replicas with its ORIGINAL id,
  settings, and row_seed, so survivors keep token-for-token greedy parity
  (the same identity contract ``resume-serving`` relies on). Migration
  resets the per-request retry budget: the requeue-once rule is a
  per-replica fault domain, and a request that burned its retry on a dying
  replica's fault gets a fresh budget on a healthy one.
- **Canary-gated rejoin**: a fenced replica is half-open at fleet
  granularity, mirroring the per-stage breaker machine — after
  ``fence_cooldown_s`` it must pass a warm-up probe (greedy workloads: a
  golden-prompt decode through its own scheduler, token-compared against
  one shared static-engine reference; sampled workloads: a smoke decode)
  before taking traffic again. A failed probe re-fences and restarts the
  cooldown. The probe's decode is itself the replica's breakers' half-open
  probe, so rejoin and breaker recovery are one motion.
- **Zero-loss accounting**: every request accepted by ``serve`` either
  reaches a terminal ``Result`` or survives in the (fleet-shared) journal
  — a process-wide ``GracefulDrain`` drains every replica with the
  configured grace and preempts the fleet-held tail, exactly like the
  single-scheduler contract.

- **Elastic membership** (``serving/autoscaler.py``, ``autoscale=``):
  replica count is a RUNTIME control loop, not a startup constant —
  ``add_replica`` instantiates a standby (canary-gated through the rejoin
  probe before it takes traffic) and ``retire_replica`` removes the
  lowest-load replica through the same zero-grace drain + migration path
  a fence uses, so in-flight work survives a scale-down with token
  parity. The ``submit``/``tick``/``take_result`` streaming surface lets
  external drivers (the trace replay, ``serving/replay.py``) feed the
  fleet without a blocking ``serve``.

Fleet telemetry: ``fleet_replicas`` / ``fleet_healthy_replicas`` gauges,
``fleet_fenced_total{replica,reason}`` / ``fleet_rejoins_total{replica}`` /
``fleet_migrated_requests_total`` / ``fleet_migrated_recovered_total``
counters, and ``fleet_failover_recovery_s`` (fence -> first migrated
token) — ``tools/validate_telemetry.py --require-fleet`` gates a drill on
them; ``--require-autoscale`` gates the elastic cycle
(``fleet_retired_total`` / ``fleet_standby_denied_total`` /
``autoscale_events_total``).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

from fairness_llm_tpu.config import (
    AutoscaleConfig,
    FleetConfig,
    IntegrityConfig,
    ModelSettings,
    OverloadConfig,
    ResilienceConfig,
    ServingConfig,
)
from fairness_llm_tpu.resilience.drain import ServingJournal, drain_requested
from fairness_llm_tpu.serving.overload import (
    DeadlineEstimator,
    ShedController,
    count_shed,
)
from fairness_llm_tpu.serving.queue import AdmissionQueue, ClassedAdmissionQueue
from fairness_llm_tpu.serving.request import QOS_CLASSES, QOS_PRIORITY, Request, Result
from fairness_llm_tpu.serving.router import HealthRouter
from fairness_llm_tpu.serving.scheduler import ContinuousScheduler
from fairness_llm_tpu.telemetry import emit_event, get_registry
from fairness_llm_tpu.telemetry.fairness import get_fairness_monitor
from fairness_llm_tpu.telemetry.incidents import maybe_trigger, record_decision
from fairness_llm_tpu.telemetry.timeline import get_timeline
from fairness_llm_tpu.utils.profiling import ServingStats
from fairness_llm_tpu.utils.ratelimit import RateLimiter

logger = logging.getLogger(__name__)

# Fence reasons that arrive as SIGNALS (injected faults, the stall probe)
# rather than inferred breaker/ladder state: the replica's serving stages
# are presumed dead, so its breakers are forced open at fence time and
# rejoin must pass through their half-open machinery, not just the fleet
# cooldown timer.
CRASH_CLASS_REASONS = ("replica_crash", "replica_hang", "stalled")


class _FleetDeadlineEstimator(DeadlineEstimator):
    """Fleet-wide feasibility: the per-replica schedulers' histograms are
    labeled ``{"replica": name}``, so the fleet's lower bound reads the
    FASTEST replica's p50s (min across replicas — optimistic, which is
    exactly what a provable lower bound needs)."""

    def __init__(self, replicas, safety: float = 0.5):
        super().__init__(safety=safety)
        self._replicas = replicas

    def _p50(self, name: str):
        vals = []
        for rep in self._replicas:
            h = get_registry().peek(name, component="serving",
                                    replica=rep.name)
            if h is not None and getattr(h, "count", 0):
                vals.append(h.percentile(50))
        return min(vals) if vals else None


class Replica:
    """One fault domain: a scheduler (with its own slot pool, board, and
    watchdog), fence state, and the fleet's bookkeeping of what is
    currently routed to it."""

    def __init__(self, name: str, engine, sched: ContinuousScheduler,
                 version: str = "v0"):
        self.name = name
        self.engine = engine
        self.sched = sched
        # Immutable engine/config version id (serving/rollout.py): which
        # rollout generation this replica serves. Requests pin to the
        # version that admitted them, so greedy parity holds per version.
        self.version = version
        self.stats = ServingStats(num_slots=sched.num_slots)
        self.fenced = False
        self.fenced_at: Optional[float] = None
        self.fence_reason: Optional[str] = None
        self.fences = 0
        self.rejoins = 0
        # Request ids currently routed here -> their Request objects (the
        # migration source of truth: Results only carry ids).
        self.assigned: Dict[str, Request] = {}
        # Lazily-built rejoin canary (shares the fleet's recorded
        # reference; see ReplicaSet._rejoin_probe).
        self.canary = None


class ReplicaSet:
    """N replicas + the router, presenting the ``ContinuousScheduler``
    surface the ``ServingBackend`` consumes (``serve``, ``last_stats``,
    ``num_slots``...), so phases run through the fleet unchanged.

    ``engines``: one engine (shared params — every replica gets its own KV
    pool and compiled programs but streams the same weight tree; the
    CPU-harness and single-host shape) or a sequence of ``fleet.replicas``
    engines (one per chip — the production topology).
    """

    def __init__(
        self,
        engines,
        serving: Optional[ServingConfig] = None,
        settings: Optional[ModelSettings] = None,
        fleet: Optional[FleetConfig] = None,
        resilience: Optional[ResilienceConfig] = None,
        journal: Optional[ServingJournal] = None,
        fault_injector=None,
        integrity: Optional[IntegrityConfig] = None,
        name: Optional[str] = None,
        overload: Optional[OverloadConfig] = None,
        autoscale: Optional[AutoscaleConfig] = None,
    ):
        # ``name`` namespaces this fleet's instruments when a process runs
        # MORE THAN ONE ReplicaSet (ServingBackend keeps one per sampler
        # tuple): replica labels become "<name>.r0" and fleet-level
        # metrics gain a {"fleet": name} label — without it, two fleets'
        # r0 watchdogs would stamp one shared liveness gauge (masking a
        # stall) and overwrite each other's healthy-replica gauge. None
        # (the default, and always the backend's FIRST fleet) keeps the
        # plain r0/r1 labels every drill and doc example uses.
        self.name = name
        self._fleet_labels = {"fleet": name} if name else {}
        self.serving = serving or ServingConfig(enabled=True)
        self.settings = settings or ModelSettings()
        self.fleet = fleet or FleetConfig(replicas=2)
        if self.fleet.replicas < 1:
            raise ValueError(
                f"fleet.replicas must be >= 1, got {self.fleet.replicas}"
            )
        self.resilience = resilience
        self.journal = journal
        self.fault_injector = fault_injector
        self.integrity = integrity or IntegrityConfig()
        self.router = HealthRouter(self.fleet)
        if isinstance(engines, (list, tuple)):
            if len(engines) != self.fleet.replicas:
                raise ValueError(
                    f"{len(engines)} engines for {self.fleet.replicas} "
                    "replicas — pass one engine per replica, or a single "
                    "engine to share its params"
                )
            per_replica = list(engines)
        else:
            per_replica = [engines] * self.fleet.replicas
        # Replica schedulers: rate limiting stays at the FLEET queue (one
        # quota for the fleet, not N), everything else per-replica.
        self._rep_serving = dataclasses.replace(
            self.serving, admission_per_minute=None
        )
        # The engine pool a SCALE-UP draws from (serving/autoscaler.py):
        # shared-params fleets reuse the one engine; per-replica-engine
        # fleets round-robin the original pool (a standby replica shares
        # params with a retired sibling's engine — the CPU-harness shape;
        # real multi-chip elasticity would plug fresh engines in here).
        self._engine_pool = per_replica
        # Monotone replica naming: retired names are never reused, so a
        # fleet that scaled 1 -> 2 -> 1 -> 2 reads r0/r1/r2 in telemetry
        # instead of two different lifetimes aliasing one "r1" label.
        self._replica_seq = self.fleet.replicas
        # Version axis (serving/rollout.py): the fleet's CURRENT stable
        # version; every replica carries the version it was built at, and
        # every request pins to the version that admits it (migration
        # stays same-version while that version has a live replica, so
        # greedy token parity survives a mid-rollout fence).
        self.version = "v0"
        self._request_version: Dict[str, str] = {}
        # The attached RolloutController, when a rollout is in flight
        # (drives its wave machine from _tick; pauses the autoscaler —
        # exactly one owner of replica membership at a time).
        self.rollout = None
        self.replicas: List[Replica] = []
        for i, eng in enumerate(per_replica):
            rep_name = f"{name}.r{i}" if name else f"r{i}"
            sched = ContinuousScheduler(
                eng, self._rep_serving, settings=self.settings,
                fault_injector=fault_injector, resilience=resilience,
                journal=journal, replica=rep_name,
            )
            sched.journal_version = self.version
            self.replicas.append(
                Replica(rep_name, eng, sched, version=self.version)
            )
        # Stats of replicas retired mid-run (scale-down): folded into the
        # next _finish_stats so their completed/shed/token counts are not
        # lost from the fleet record with the replica.
        self._retired_stats: List[ServingStats] = []
        # Overload control (serving/overload.py): the fleet intake is the
        # front door in fleet mode, so the gate lives HERE — replica
        # schedulers stay plain (gating again after routing would
        # double-shed a request the fleet already accepted). The
        # controller's burn signal aggregates per-replica gauges; the
        # feasibility bound reads the fastest replica's p50s.
        self.overload = overload if (overload is not None
                                     and overload.enabled) else None
        if self.overload is not None:
            self.shed_controller: Optional[ShedController] = ShedController(
                self.overload, labels=self._fleet_labels,
                burn_fn=self._max_replica_burn,
            )
            self.deadline_estimator: Optional[DeadlineEstimator] = (
                _FleetDeadlineEstimator(
                    self.replicas, safety=self.overload.feasibility_safety,
                ) if self.overload.deadline_admission else None
            )
        else:
            self.shed_controller = None
            self.deadline_estimator = None
        self._shed_fleet = 0  # fleet-level sheds since the last stats close
        # The fleet's own bounded admission queue — the backpressure
        # boundary callers see; the router feeds replica queues from it.
        fleet_rate = (RateLimiter(self.serving.admission_per_minute)
                      if self.serving.admission_per_minute else None)
        if self.overload is not None:
            self.queue: AdmissionQueue = ClassedAdmissionQueue(
                capacity=self.serving.queue_capacity,
                rate_limiter=fleet_rate, overload=self.overload,
            )
        else:
            self.queue = AdmissionQueue(
                capacity=self.serving.queue_capacity,
                rate_limiter=fleet_rate,
            )
        self._pending: Deque[Request] = deque()
        self._migrating: Deque[Request] = deque()
        self._results: Dict[str, Result] = {}
        self._migrated_ids: set = set()
        self._recovered_ids: set = set()
        self._canary_rr = 0  # periodic-canary round-robin cursor
        self._rejected_taken = 0
        # Rejoin-canary references, one per VERSION (lazy): replicas of a
        # version share one static-engine reference — a v+1 standby must
        # be judged against v+1's own golden decode, not v's (every new
        # version would fail a cross-version canary by construction).
        self._canary_refs: Dict[str, object] = {}
        self._probe_seq = 0
        self._fence_t: Optional[float] = None
        self._failover_pending = False
        self.last_failover_s: Optional[float] = None
        self.last_stats: Optional[ServingStats] = None
        reg = get_registry()
        reg.gauge("fleet_replicas", component="fleet",
                  **self._fleet_labels).set(len(self.replicas))
        reg.gauge("fleet_healthy_replicas", component="fleet",
                  **self._fleet_labels).set(len(self.replicas))
        # Elastic membership (serving/autoscaler.py): with autoscale armed,
        # the fleet's tick runs an SLO-coupled controller that adds
        # canary-gated standby replicas under sustained burn/queue pressure
        # and retires the lowest-load replica through the drain/migration
        # path when the fleet is sustainedly cold.
        if autoscale is not None and autoscale.enabled:
            from fairness_llm_tpu.serving.autoscaler import Autoscaler

            self.autoscaler: Optional[Autoscaler] = Autoscaler(
                self, autoscale
            )
        else:
            self.autoscaler = None

    # -- ContinuousScheduler-surface compatibility ---------------------------

    @property
    def num_slots(self) -> int:
        """Total concurrent KV slots across the fleet (what the backend
        reports as the decode batch)."""
        return sum(r.sched.num_slots for r in self.replicas)

    @property
    def max_prompt_bucket(self) -> int:
        return self.replicas[0].sched.max_prompt_bucket

    @property
    def cache_len(self) -> int:
        return self.replicas[0].sched.cache_len

    @property
    def healthy_count(self) -> int:
        return sum(1 for r in self.replicas if not r.fenced)

    # -- overload gate (serving/overload.py) ---------------------------------

    def _max_replica_burn(self) -> float:
        """The fleet controller's burn signal: the hottest fast-window
        burn across every replica's own SLO gauges."""
        reg = get_registry()
        return max(
            (reg.read_value("slo_burn_rate", default=0.0,
                            component="serving", replica=rep.name,
                            slo=slo, window="fast")
             for rep in self.replicas
             for slo in ("error_rate", "ttft_p95")),
            default=0.0,
        )

    def _queued_ahead(self, qos: str) -> int:
        """Same-or-higher-priority work still fleet-held (queued or
        pending) — the feasibility bound's wave count."""
        if isinstance(self.queue, ClassedAdmissionQueue):
            ahead = sum(
                d for c, d in self.queue.class_depths().items()
                if QOS_PRIORITY[c] <= QOS_PRIORITY[qos]
            )
        else:
            ahead = len(self.queue)
        return ahead + sum(
            1 for r in self._pending
            if QOS_PRIORITY[r.qos] <= QOS_PRIORITY[qos]
        )

    def _deliver_shed(self, req: Request, reason: str, error: str,
                      retry_after: float, journaled: bool) -> None:
        count_shed(req.qos, reason, labels=self._fleet_labels)
        # Outcome counter parity with the scheduler front door: a
        # fleet-intake shed never reached a replica's tracer (no span
        # lane), but dashboards summing requests_finished_total across
        # components must still see it as a terminal outcome.
        get_registry().counter(
            "requests_finished_total", component="fleet", outcome="shed",
            **self._fleet_labels,
        ).inc()
        self._shed_fleet += 1
        # Decision audit trail (telemetry/incidents.py): the refusal with
        # its inputs — rung and retry-after — keyed to the refused request.
        ctl = self.shed_controller
        record_decision(
            "shed", reason,
            signals={"qos": req.qos, "retry_after_s": retry_after,
                     "level": ctl.level if ctl is not None else 0,
                     "front_door": "fleet"},
            request_id=req.id,
        )
        # A fleet-intake shed is exactly the group-unequal treatment the
        # neutrality audit must see — no replica scheduler will ever
        # observe this request.
        get_fairness_monitor().observe_request(req, "shed")
        if journaled and self.journal is not None:
            self.journal.record_terminal(req.id, "shed")
        self._deliver(req.id, Result(
            id=req.id, ok=False, finish_reason="shed", error=error,
            retries=req.retries,
            latency_s=time.monotonic() - req.submitted_at,
            retry_after_s=retry_after,
        ))

    def _overload_gate(self, req: Request, journaled: bool = True) -> bool:
        """True when the fleet terminally shed ``req`` (Result delivered
        with a retry-after). Mirrors the scheduler's gate — brownout class
        admission, then deadline feasibility — at the fleet's front door."""
        ctl = self.shed_controller
        if ctl is None:
            return False
        if req.qos == "interactive":
            ctl.note_interactive()
        if not ctl.admits(req.qos):
            self._deliver_shed(
                req, "overload",
                f"overload level {ctl.level} ({ctl.rung}) sheds "
                f"{req.qos}-class admissions; retry after "
                f"{ctl.retry_after()}s",
                ctl.retry_after(), journaled,
            )
            return True
        if self.deadline_estimator is not None and req.deadline_s is not None:
            est = self.deadline_estimator.infeasible(
                req, self._queued_ahead(req.qos), self.num_slots,
                self.replicas[0].sched.decode_chunk,
            )
            if est is not None:
                self._deliver_shed(
                    req, "deadline_infeasible",
                    "deadline provably unmeetable at fleet intake "
                    f"(estimated earliest first token {est:.3f}s)",
                    ctl.retry_after(est), journaled,
                )
                return True
        return False

    # -- serve ---------------------------------------------------------------

    def serve(self, requests: Sequence[Request]) -> List[Result]:
        """Serve ``requests`` across the fleet; Results come back in
        submission order. The loop interleaves every replica's scheduler
        one iteration at a time (``ContinuousScheduler.step``), routing
        admissions by health, fencing/migrating sick replicas, and probing
        fenced ones for rejoin — until every request is terminal."""
        now = time.monotonic()
        ids = [r.id for r in requests]
        if len(set(ids)) != len(ids):
            dup = sorted({i for i in ids if ids.count(i) > 1})
            raise ValueError(f"duplicate request ids in serve() batch: {dup}")
        for req in requests:
            # Same loud sampler-mismatch guard as the scheduler's, before
            # any work starts (one fleet = one compiled sampler tuple).
            self.replicas[0].sched._check_settings(req)
        for req in requests:
            req.submitted_at = now
            # Overload gate before acceptance (never journaled — a shed
            # request was refused, not accepted): the Result is already
            # delivered, so the serve loop below sees it as terminal.
            if self._overload_gate(req, journaled=False):
                continue
            if self.journal is not None:
                # Fleet-level intake ledger: a request preempted while
                # still fleet-held (never reached a replica scheduler)
                # must survive for resume-serving too.
                self.journal.record_submitted(req)
            self._pending.append(req)
        expected = set(ids)
        while not expected.issubset(self._results):
            if drain_requested():
                self._drain_all()
                break
            if not self._tick():
                # Nothing moved: every routable replica idle/refused, or
                # the whole fleet fenced mid-cooldown. Yield instead of
                # spinning (rejoin probes re-arm on a later tick).
                time.sleep(0.002)
        self._finish_stats()
        out = [self._results.pop(rid) for rid in ids]
        for rid in ids:
            self._migrated_ids.discard(rid)
            self._recovered_ids.discard(rid)
        return out

    def await_recovery(self, timeout_s: float = 30.0,
                       poll_s: float = 0.01) -> bool:
        """Keep probing fenced replicas until the fleet is whole (True) or
        ``timeout_s`` elapses (False). A fault landing near the end of a
        sweep leaves its replica fenced at ``serve`` return — drills (and
        operators waiting to hand traffic back) call this to see the
        canary-gated rejoin through."""
        deadline = time.monotonic() + timeout_s
        while any(r.fenced for r in self.replicas):
            for rep in self.replicas:
                if rep.fenced:
                    self._maybe_rejoin(rep)
            if not any(r.fenced for r in self.replicas):
                break
            if time.monotonic() >= deadline:
                return False
            time.sleep(poll_s)
        return True

    # -- streaming surface (submit/tick/take_result) -------------------------
    #
    # The serve() path above is the batch surface the phases consume; the
    # trio below is the STREAMING surface external drivers use — the load
    # replay (serving/replay.py) submits trace events as their arrival
    # times come due and ticks the fleet between arrivals, mirroring the
    # ContinuousScheduler's own submit()/step()/take_result() hooks.

    def submit(self, request: Request, restamp: bool = True,
               count_rejection: bool = True) -> bool:
        """Queue one request at the fleet intake; False = backpressure
        (fleet queue full / class bound / rate quota — nothing enqueued,
        the caller may retry) OR a terminal overload shed — the two read
        apart via ``take_result``: a shed leaves a claimable
        ``finish_reason="shed"`` Result with a retry-after hint,
        backpressure leaves nothing. Accepted requests are journaled at
        intake (the zero-accepted-then-lost ledger) and routed to a
        replica on a later ``tick``. ``count_rejection=False`` marks a
        RE-offer of an arrival whose first refusal was already counted
        (the replay driver's retry loop): capacity and quota still apply,
        but the stats don't count a fresh rejection per poll."""
        self.replicas[0].sched._check_settings(request)
        if restamp:
            request.submitted_at = time.monotonic()
        if self._overload_gate(request, journaled=False):
            return False
        accepted = self.queue.submit(request, count_rejection=count_rejection)
        if accepted and self.journal is not None:
            self.journal.record_submitted(request)
        return accepted

    def tick(self) -> bool:
        """ONE fleet loop iteration — route, step every replica, fence /
        rejoin / autoscale as due. Honors a process-wide drain request
        exactly as ``serve`` does. Returns True when any work moved."""
        if drain_requested():
            self._drain_all()
            return False
        return self._tick()

    def take_result(self, request_id: str) -> Optional[Result]:
        """Claim (and remove) the Result of a request submitted via
        ``submit()`` that has since terminated — the retrieval half of the
        streaming surface."""
        res = self._results.pop(request_id, None)
        if res is not None:
            self._migrated_ids.discard(request_id)
            self._recovered_ids.discard(request_id)
        return res

    @property
    def has_work(self) -> bool:
        """Anything still owed a Result: fleet-held (pending, queued,
        awaiting migration) or live on a replica."""
        return bool(self._pending or len(self.queue) or self._migrating
                    or any(r.sched.has_work for r in self.replicas))

    def request_version(self, request_id: str) -> Optional[str]:
        """The version ``request_id`` is pinned to (the version whose
        replica first admitted it — the engine its final token stream
        belongs to), or None before placement. Pins are kept for the run
        (a small per-request entry, like the journal's intake ledger) so
        drills can assert per-version token parity after Results are
        claimed."""
        return self._request_version.get(request_id)

    def drain(self) -> ServingStats:
        """Run the fleet loop until nothing is owed, then close out the
        stats window — the streaming companion to ``serve()``. Terminated
        requests' Results wait in ``take_result``."""
        while self.has_work:
            if drain_requested():
                self._drain_all()
                break
            if not self._tick():
                time.sleep(0.002)
        self._finish_stats()
        return self.last_stats

    # -- the fleet loop ------------------------------------------------------

    def _tick(self) -> bool:
        if self.shed_controller is not None:
            # One depth sample (fleet-held work vs fleet capacity) + a
            # throttled ladder step per tick — the fleet-mode twin of the
            # scheduler loop's controller tick.
            self.shed_controller.observe_queue_depth(
                len(self.queue) + len(self._pending),
                self.serving.queue_capacity,
            )
            self.shed_controller.maybe_evaluate()
        progressed = False
        rollout_active = self.rollout is not None and self.rollout.active
        if self.autoscaler is not None and not rollout_active:
            # Membership control BEFORE routing: a replica added this tick
            # takes traffic this tick, and a retired one has already
            # migrated its work into _migrating for _route to place.
            # During an active rollout the autoscaler is PAUSED — the
            # RolloutController owns replica membership for the wave's
            # duration (two controllers adding/retiring replicas against
            # each other would thrash the canary gate), and it notes the
            # arbitration in rollout_autoscale_paused_total.
            progressed |= self.autoscaler.maybe_tick()
        if rollout_active:
            # Same placement as the autoscaler: a v+1 standby added this
            # tick takes traffic this tick, and a rollback's evacuated
            # work is already in _migrating for _route to place.
            progressed |= self.rollout.maybe_tick()
        progressed |= self._expire_held()
        progressed |= self._route()
        # list(): the autoscaler (above) is not the only mutation source —
        # a fence-triggered retire queued by a future controller must
        # never invalidate this iteration mid-walk.
        for rep in list(self.replicas):
            if rep.fenced:
                progressed |= self._maybe_rejoin(rep)
                continue
            injected = None
            injector_fault = getattr(
                self.fault_injector, "maybe_replica_fault", None
            )
            if injector_fault is not None:
                injected = injector_fault(rep.name)
            if injected is not None:
                self._fence(rep, injected)
                progressed = True
                continue
            # Decay the replica's SLO burn windows even when it is IDLE —
            # step() only runs with work, and the router reads this
            # replica's fast-window burn on every placement: a
            # burning-then-shedded replica must recover by the window
            # aging out, not by waiting for a trickle request to finalize.
            rep.sched.tracer.slo.maybe_evaluate()
            if rep.sched.has_work:
                progressed |= rep.sched.step(rep.stats)
                self._collect(rep)
            reason = self.router.should_fence(rep)
            if reason is not None:
                self._fence(rep, reason)
                progressed = True
        return progressed

    def _expire_held(self) -> bool:
        """Deadline-expire requests still FLEET-held (pending, queued, or
        awaiting migration) — replica schedulers expire what they hold,
        but a request stranded while the whole fleet is fenced must still
        terminate ``deadline``, never hang the serve loop forever."""
        now = time.monotonic()
        expired: List[Request] = list(self.queue.drain_expired(now))
        for held in (self._pending, self._migrating):
            live = [r for r in held if not r.expired(now)]
            if len(live) != len(held):
                expired.extend(r for r in held if r.expired(now))
                held.clear()
                held.extend(live)
        for req in expired:
            if self.journal is not None:
                self.journal.record_terminal(req.id, "expired")
            self._deliver(req.id, Result(
                id=req.id, ok=False, finish_reason="deadline",
                error="deadline expired before a healthy replica could "
                      "take the request", retries=req.retries,
                latency_s=now - req.submitted_at,
            ))
        return bool(expired)

    def _route(self) -> bool:
        """Feed the fleet queue from pending overflow, then place migrated
        requests (front of line — they were admitted once already) and
        queued admissions on the healthiest replicas."""
        moved = False
        if self.shed_controller is None:
            while self._pending and not self.queue.full:
                if not self.queue.submit(self._pending[0],
                                         count_rejection=False):
                    break  # rate-limited; retry next tick
                self._pending.popleft()
                moved = True
        else:
            # QoS mode: re-gate pending at each feed (the ladder may have
            # climbed since intake), and never let one bounded class
            # head-of-line-block the others — same one-pass class-skip
            # scan as the scheduler's _feed.
            blocked: set = set()
            kept: Deque[Request] = deque()
            while self._pending:
                if len(blocked) == len(QOS_CLASSES):
                    kept.extend(self._pending)
                    self._pending.clear()
                    break
                req = self._pending.popleft()
                if req.qos in blocked:
                    kept.append(req)
                    continue
                if self._overload_gate(req):  # journaled at intake
                    moved = True
                    continue
                if not self.queue.submit(req, count_rejection=False):
                    blocked.add(req.qos)
                    kept.append(req)
                else:
                    moved = True
            self._pending = kept
        while self._migrating:
            req = self._migrating[0]
            # Pinned-version affinity (serving/rollout.py): a migrated
            # request lands ONLY on a replica of the version that admitted
            # it — cross-version migration would splice two engines' token
            # streams and break greedy parity. While the pinned version
            # has a live unfenced replica, an unroutable pick HOLDS (the
            # bounded-queue backpressure stance); only when the version
            # has no live replica at all (rollback retired it, or its
            # last replica fenced) is the pin restamped — the request
            # re-decodes from scratch on the surviving version, so its
            # final stream is still single-version.
            pinned = self._request_version.get(req.id)
            rep = self.router.pick(self.replicas, qos=req.qos,
                                   require_version=pinned)
            if rep is None and pinned is not None and not any(
                r.version == pinned and not r.fenced for r in self.replicas
            ):
                rep = self.router.pick(self.replicas, qos=req.qos)
                if rep is not None:
                    get_registry().counter(
                        "rollout_affinity_restamped_total",
                        component="rollout", **self._fleet_labels,
                    ).inc()
                    record_decision(
                        "rollout", "restamp",
                        signals={"from_version": pinned,
                                 "to_version": rep.version},
                        request_id=req.id, replica=rep.name,
                    )
                    emit_event("rollout_affinity_restamped",
                               request_id=req.id, from_version=pinned,
                               to_version=rep.version)
            if rep is None:
                break
            self._migrating.popleft()
            # front=True: a migrated request already waited through its
            # fenced replica's queue — on the new replica it goes ahead of
            # work that hasn't, which is also what bounds failover
            # recovery (fence -> first migrated token) to roughly one
            # admission+chunk instead of the healthy replica's backlog.
            # restamp=False everywhere in _route: the deadline/latency
            # clock started at FLEET intake and must keep running through
            # routing waits and migrations — never silently extend.
            if not rep.sched.submit(req, front=True, restamp=False):
                self._migrating.appendleft(req)
                break
            rep.assigned[req.id] = req
            self._request_version[req.id] = rep.version
            moved = True
        while len(self.queue):
            req = self.queue.pop(1)[0]
            # qos-aware placement (serving/router.py): non-interactive
            # traffic prefers replicas not burning their fast-window SLO
            # budgets, so bulk load steers away from replicas already
            # failing their users.
            rep = self.router.pick(self.replicas, qos=req.qos)
            if rep is None:
                self.queue.requeue(req)
                break
            if not rep.sched.submit(req, restamp=False):
                self.queue.requeue(req)
                break
            rep.assigned[req.id] = req
            # Pin at FIRST placement: the request completes on this
            # version (its first token is this engine's), and any later
            # migration must stay on it.
            self._request_version[req.id] = rep.version
            moved = True
        return moved

    def _collect(self, rep: Replica) -> None:
        """Claim terminal Results for everything routed to ``rep``."""
        for rid in list(rep.assigned):
            res = rep.sched.take_result(rid)
            if res is None:
                continue
            del rep.assigned[rid]
            self._deliver(rid, res, rep=rep)

    def _deliver(self, rid: str, res: Result,
                 rep: Optional[Replica] = None) -> None:
        """Hand one terminal Result to the caller-visible set, crediting
        the migrated==recovered gate ONCE per request: recovered means a
        migrated request reached a terminal outcome (not lost) — whatever
        the outcome and wherever it terminated (a healthy replica, a
        fleet-held deadline expiry, or a process-wide drain's
        preemption-to-journal). Counting unique requests on both sides is
        what makes migrated == recovered a real invariant even when a
        request migrates twice (its first replica's successor fences
        too)."""
        self._results[rid] = res
        if rid in self._migrated_ids and rid not in self._recovered_ids:
            self._recovered_ids.add(rid)
            get_registry().counter(
                "fleet_migrated_recovered_total", component="fleet",
                **self._fleet_labels,
            ).inc()
            self._record_failover(rep, res)

    def _record_failover(self, rep: Optional[Replica], res: Result) -> None:
        """Failover recovery time: fence -> the first migrated request's
        first token on its new replica. The first-token wall comes from
        the collecting replica's tracer spans (``submitted_at`` keeps the
        FLEET intake stamp across migration, so it cannot be used);
        fallback is delivery time — an upper bound, chunk-granular like
        every TTFT here."""
        if not self._failover_pending or self._fence_t is None:
            return
        self._failover_pending = False
        recovery = None
        if rep is not None:
            for row, evs in rep.sched.tracer.finished:
                if row.request_id == res.id:
                    stamps = [e.t for e in evs if e.event == "first_token"]
                    if stamps:
                        recovery = stamps[-1] - self._fence_t
        if recovery is None:
            recovery = time.monotonic() - self._fence_t
        recovery = max(recovery, 0.0)
        self.last_failover_s = recovery
        reg = get_registry()
        reg.gauge("fleet_failover_recovery_s", component="fleet",
                  **self._fleet_labels).set(recovery)
        reg.histogram("fleet_failover_recovery_dist_s", component="fleet",
                      **self._fleet_labels).observe(recovery)
        emit_event("fleet_failover_recovered",
                   replica=rep.name if rep is not None else None,
                   request_id=res.id, recovery_s=round(recovery, 4))

    # -- fence / migrate / rejoin -------------------------------------------

    def _fence(self, rep: Replica, reason: str) -> None:
        if rep.fenced:
            return
        now = time.monotonic()
        rep.fenced = True
        rep.fenced_at = now
        rep.fence_reason = reason
        rep.fences += 1
        if not self._failover_pending:
            # The failover clock measures the OLDEST unrecovered fence: a
            # second fence landing before the first fence's migrated work
            # produced a token must not restart the clock (it would
            # under-report fleet_failover_recovery_s).
            self._fence_t = now
        reg = get_registry()
        reg.counter("fleet_fenced_total", component="fleet",
                    replica=rep.name, reason=reason).inc()
        self._update_health_gauge()
        emit_event("replica_fenced", replica=rep.name, reason=reason,
                   live=rep.sched.pool.occupancy,
                   queued=len(rep.sched.queue))
        get_timeline().record_instant("fence", rep.name, t=now,
                                      reason=reason)
        logger.warning(
            "fencing replica %s (%s): %d live, %d queued — draining and "
            "migrating", rep.name, reason, rep.sched.pool.occupancy,
            len(rep.sched.queue),
        )
        migrated = self._evacuate(rep, reason)
        emit_event("replica_fence_complete", replica=rep.name,
                   reason=reason, migrated=migrated)
        # Incident engine (telemetry/incidents.py): a fence IS an incident
        # — capture the moment-of-failure state (breaker/ladder edges, the
        # decision trail that inferred sickness, the migrated cohort)
        # while it still exists. The decision carries the signal values;
        # the trigger dumps the bundle (deduped per replica).
        record_decision(
            "fence", reason,
            signals={"migrated": migrated,
                     "health_score": round(get_registry().read_value(
                         "replica_health_score", default=-1.0,
                         component="fleet", replica=rep.name), 4),
                     "open_breakers": (rep.sched.breakers.open_count()
                                       if rep.sched.breakers is not None
                                       else 0),
                     "ladder_level": (rep.sched.breakers.ladder.level
                                      if rep.sched.breakers is not None
                                      else 0)},
            replica=rep.name,
        )
        maybe_trigger("fence", f"replica {rep.name} fenced: {reason}",
                      scope=rep.name, replica=rep.name, migrated=migrated)

    def _evacuate(self, rep: Replica, reason: str,
                  count_failover: bool = True) -> int:
        """Drain ``rep`` with ZERO grace through the journal path and
        migrate everything unfinished — the shared mechanics of a FENCE (a
        replica judged sick must not keep decoding work that should
        migrate) and a RETIREMENT (a scale-down's victim hands its
        in-flight work to the survivors). Returns the migrated count.
        ``count_failover=False`` (retirement) keeps the planned evacuation
        out of the fleet_failover_recovery_s clock — failover time
        measures incidents, not scaling decisions."""
        rep.sched.request_drain(grace_s=0.0)
        rep.sched.step(rep.stats)
        if reason in CRASH_CLASS_REASONS and rep.sched.breakers is not None:
            # The signal says the stages are DEAD, not merely flaky: force
            # the breakers open so the rejoin canary must pass through
            # their half-open machinery (fleet-level half-open mirrors the
            # per-stage machine).
            rep.sched.breakers.trip("prefill")
            rep.sched.breakers.trip("decode")
        migrated, newly_migrated = 0, 0
        for rid in list(rep.assigned):
            req = rep.assigned.pop(rid)
            res = rep.sched.take_result(rid)
            if res is not None and res.finish_reason != "preempted":
                # Terminal before the fence took hold — deliver as-is.
                self._deliver(rid, res, rep=rep)
                continue
            # Unfinished on the fenced replica: migrate with the ORIGINAL
            # id/settings/row_seed (greedy parity for survivors) and a
            # fresh retry budget (per-replica fault domain — its requeue
            # was spent on a replica now out of the fleet).
            req.retries = 0
            # Pair-watch attribution (telemetry/fairness.py): a tagged
            # request's migration — and which replica it fled — shows up
            # in the divergent-pair table (tagged= because a direct-tagged
            # request's pairs only register at terminal time, and the
            # migration also resets retries, so nothing else would record
            # the event).
            get_fairness_monitor().note_event(
                rid, f"migrated:{rep.name}",
                tagged=(req.group is not None or req.pair_id is not None),
            )
            self._migrating.append(req)
            migrated += 1
            if rid not in self._migrated_ids:
                # Unique-request counting: a request re-migrated by a
                # SECOND fence must not inflate the migrated side of the
                # migrated==recovered invariant.
                self._migrated_ids.add(rid)
                newly_migrated += 1
        if newly_migrated:
            get_registry().counter(
                "fleet_migrated_requests_total", component="fleet",
                **self._fleet_labels,
            ).inc(newly_migrated)
        if migrated:
            if count_failover:
                self._failover_pending = True
            get_timeline().record_instant("migrate", rep.name,
                                          migrated=migrated)
        return migrated

    # -- elastic membership (serving/autoscaler.py) --------------------------

    def add_replica(self, engine=None, version: Optional[str] = None,
                    serving: Optional[ServingConfig] = None
                    ) -> Optional[Replica]:
        """Instantiate a STANDBY replica — its own scheduler, slot pool,
        breakers, and watchdog over the engine pool's params — and
        canary-gate it through the fleet's rejoin probe BEFORE it joins:
        a standby that cannot decode the golden prompt (or complete a
        smoke decode, for sampled fleets) never takes traffic. Returns the
        joined Replica, or None when the probe refused it (counted in
        ``fleet_standby_denied_total``; the autoscaler retries after its
        cooldown). Names are monotone (``r<seq>``) so a scaled-away
        replica's telemetry is never aliased by a later arrival.

        ``engine``/``version``/``serving`` (serving/rollout.py): a rollout
        adds its v+1 standby here with the NEW engine/config and version
        id — the canary gate then judges it against its own version's
        golden reference. Defaults (the autoscaler path) draw from the
        engine pool at the fleet's current version."""
        i = self._replica_seq
        self._replica_seq += 1
        rep_name = f"{self.name}.r{i}" if self.name else f"r{i}"
        if engine is None:
            engine = self._engine_pool[i % len(self._engine_pool)]
        version = version or self.version
        sched = ContinuousScheduler(
            engine, serving or self._rep_serving, settings=self.settings,
            fault_injector=self.fault_injector, resilience=self.resilience,
            journal=self.journal, replica=rep_name,
        )
        sched.journal_version = version
        rep = Replica(rep_name, engine, sched, version=version)
        if not self._rejoin_probe(rep):
            get_registry().counter(
                "fleet_standby_denied_total", component="fleet",
                replica=rep_name,
            ).inc()
            emit_event("replica_standby_denied", replica=rep_name)
            logger.warning("standby replica %s failed its canary gate; "
                           "not joining the fleet", rep_name)
            return None
        self.replicas.append(rep)
        reg = get_registry()
        reg.counter("fleet_scale_ups_total", component="fleet",
                    **self._fleet_labels).inc()
        reg.gauge("fleet_replicas", component="fleet",
                  **self._fleet_labels).set(len(self.replicas))
        self._update_health_gauge()
        emit_event("replica_added", replica=rep_name,
                   replicas=len(self.replicas))
        get_timeline().record_instant("scale_up", rep_name)
        logger.warning("replica %s passed its standby canary; joined the "
                       "fleet (%d replicas)", rep_name, len(self.replicas))
        return rep

    def retire_replica(self, rep: Replica) -> int:
        """Remove ``rep`` from the fleet through the zero-grace
        drain + journal-migration path: its in-flight requests migrate to
        the survivors with original ids/settings/row_seeds (token-for-token
        parity — the fence's contract) and its stats fold into the fleet
        record. Distinct from a fence: retirement is a PLANNED exit (no
        fence counter, no failover clock, no rejoin — the replica is
        gone). Returns the migrated count."""
        if len(self.replicas) <= 1:
            raise ValueError("cannot retire the last replica")
        if rep not in self.replicas:
            raise ValueError(f"replica {rep.name!r} is not in this fleet")
        # Fenced-flagged during evacuation so the router never places new
        # work on a replica mid-retirement.
        rep.fenced = True
        rep.fence_reason = "retired"
        reg = get_registry()
        reg.counter("fleet_retired_total", component="fleet",
                    replica=rep.name).inc()
        emit_event("replica_retiring", replica=rep.name,
                   live=rep.sched.pool.occupancy,
                   queued=len(rep.sched.queue))
        get_timeline().record_instant("scale_down", rep.name)
        logger.warning(
            "retiring replica %s: %d live, %d queued — draining and "
            "migrating to survivors", rep.name, rep.sched.pool.occupancy,
            len(rep.sched.queue),
        )
        migrated = self._evacuate(rep, "retired", count_failover=False)
        # Retirement is the permanent exit: the scheduler's device trees
        # (cache/logits) leave with it, so its memory-ledger entries
        # go too — a fence keeps the replica AND its memory, so the fence
        # path deliberately does not release (ISSUE 18).
        rep.sched.release_memory()
        # Fold the retired replica's stats into the next stats close so
        # its completed/shed/token counts survive the membership change.
        rep.sched.finish_stats(rep.stats)
        self._retired_stats.append(rep.stats)
        self.replicas.remove(rep)
        reg.gauge("fleet_replicas", component="fleet",
                  **self._fleet_labels).set(len(self.replicas))
        self._update_health_gauge()
        emit_event("replica_retired", replica=rep.name, migrated=migrated,
                   replicas=len(self.replicas))
        return migrated

    def _maybe_rejoin(self, rep: Replica) -> bool:
        """Probe a fenced replica once its cooldown elapses; rejoin on a
        passed probe, restart the cooldown on a failed one. Returns True
        when a probe actually ran (the tick progressed)."""
        now = time.monotonic()
        if rep.fenced_at is None or \
                now - rep.fenced_at < self.fleet.fence_cooldown_s:
            return False
        board = rep.sched.breakers
        if board is not None and any(
            (board.seconds_until_probe(stage) or 0) > 0
            for stage in board.breakers
        ):
            # An OPEN breaker still inside its cooldown cannot half-open:
            # the probe's serve() would sleep-spin the single-threaded
            # fleet loop until it can (freezing every HEALTHY replica for
            # the remainder of the breaker cooldown — e.g. the default
            # fence_cooldown_s 1.0 < breaker_cooldown_s 5.0). Defer the
            # probe until the breakers are probeable; the fleet keeps
            # serving meanwhile.
            return False
        if not self._rejoin_probe(rep):
            rep.fenced_at = now  # re-fence: cooldown restarts
            get_registry().counter(
                "fleet_rejoin_denied_total", component="fleet",
                replica=rep.name,
            ).inc()
            emit_event("replica_rejoin_denied", replica=rep.name)
            record_decision(
                "rejoin", "denied",
                signals={"fence_reason": rep.fence_reason,
                         "fences": rep.fences},
                replica=rep.name,
            )
            logger.warning("replica %s failed its rejoin probe; staying "
                           "fenced", rep.name)
            return True
        rep.fenced = False
        rep.fenced_at = None
        rep.fence_reason = None
        rep.rejoins += 1
        get_registry().counter("fleet_rejoins_total", component="fleet",
                               replica=rep.name).inc()
        self._update_health_gauge()
        emit_event("replica_rejoined", replica=rep.name)
        record_decision("rejoin", "ok", signals={"rejoins": rep.rejoins},
                        replica=rep.name)
        get_timeline().record_instant("rejoin", rep.name)
        logger.warning("replica %s passed its rejoin probe; back in the "
                       "fleet", rep.name)
        return True

    def _greedy_settings(self) -> bool:
        s = self.settings
        return s.temperature == 0.0 and s.top_k == 0 and s.top_p == 1.0

    def _rejoin_probe(self, rep: Replica) -> bool:
        """The canary warm-up gate. Greedy fleets decode the golden prompt
        through the fenced replica's own scheduler and compare
        token-for-token against ONE static-engine reference recorded on
        first use (``CanaryProbe``); sampled fleets — where no
        deterministic reference exists — gate on a smoke decode completing
        cleanly. Either way the probe's decode IS the replica breakers'
        half-open probe, so a pass closes them and walks the ladder back
        to 0 before traffic returns. The journal is detached for the
        probe's duration: probes are synthetic traffic a successor process
        must never resume."""
        saved_journal, rep.sched.journal = rep.sched.journal, None
        try:
            if self._greedy_settings():
                return self._replica_canary(rep).probe(rep.sched)
            self._probe_seq += 1
            smoke = Request(
                prompt="warm-up probe: list three colors.",
                id=f"__fleet_probe_{rep.name}_{self._probe_seq}__",
                settings=dataclasses.replace(self.settings, max_tokens=min(
                    self.settings.max_tokens, self.integrity.canary_max_tokens
                )),
                row_seed=0,
                qos="probe",
            )
            res = rep.sched.serve([smoke])[0]
            get_registry().counter(
                "canary_runs_total", component="serving", replica=rep.name
            ).inc()
            return bool(res.ok)
        finally:
            rep.sched.journal = saved_journal

    def _replica_canary(self, rep: Replica):
        """The replica's probe, built lazily from ONE shared static-engine
        reference — used by both the rejoin gate and the backend's
        periodic canary (same object, same board)."""
        if rep.canary is None:
            from fairness_llm_tpu.integrity.canary import CanaryProbe

            ref = self._canary_refs.get(rep.version)
            if ref is None:
                # Clamped to the serving decode cap: the probe decodes
                # through the replica's scheduler, which clamps every
                # request to max_new_tokens — a reference recorded longer
                # than the scheduler can decode would fail the
                # pads-beyond-prefix check on a perfectly healthy replica.
                # Keyed by VERSION: a rollout's v+1 standby is compared
                # against its own engine's golden decode.
                ref = CanaryProbe.record(
                    rep.engine,
                    max_tokens=min(self.integrity.canary_max_tokens,
                                   self.serving.max_new_tokens),
                )
                self._canary_refs[rep.version] = ref
            rep.canary = ref.for_replica(
                rep.name, board=rep.sched.breakers
            )
        return rep.canary

    def periodic_canary(self) -> bool:
        """The backend's ``--canary-every`` path in fleet mode: probe ONE
        unfenced replica (round-robin) with its own per-replica canary —
        a mismatch trips THAT replica's decode breaker, so the
        ladder/router/fence machinery contains it exactly like any other
        replica fault (a fleet-level probe through the router couldn't
        attribute a mismatch to a replica, and with no backend board it
        would contain nothing). Greedy fleets only — sampled output has
        no deterministic reference. Returns the probe result (True when
        nothing was probeable)."""
        if not self._greedy_settings():
            return True
        live = [r for r in self.replicas if not r.fenced]
        if not live:
            return True
        rep = live[self._canary_rr % len(live)]
        self._canary_rr += 1
        probe = self._replica_canary(rep)
        saved_journal, rep.sched.journal = rep.sched.journal, None
        try:
            return probe.probe(rep.sched)
        finally:
            rep.sched.journal = saved_journal

    def _update_health_gauge(self) -> None:
        get_registry().gauge(
            "fleet_healthy_replicas", component="fleet", **self._fleet_labels
        ).set(self.healthy_count)

    # -- process-wide drain / stats ------------------------------------------

    def _drain_all(self) -> None:
        """A process-wide drain (SIGTERM via ``GracefulDrain``) drains
        every replica with the CONFIGURED grace — this is preemption, not
        sickness — and preempts the fleet-held tail; journal records stay
        unfinished for ``resume-serving``."""
        logger.warning("fleet drain: %d replica(s), %d fleet-held "
                       "request(s)",
                       len(self.replicas),
                       len(self._pending) + len(self.queue)
                       + len(self._migrating))
        for rep in self.replicas:
            if rep.sched.has_work:
                rep.sched.step(rep.stats)  # step() honors the drain flag
            for rid in list(rep.assigned):
                res = rep.sched.take_result(rid)
                if res is not None:
                    del rep.assigned[rid]
                    self._deliver(rid, res, rep=rep)
        hint = (f"resume with: resume-serving {self.journal.journal_dir}"
                if self.journal is not None
                else "no serving journal configured; request is lost at exit")
        held = list(self._pending) + self.queue.pop(len(self.queue)) \
            + list(self._migrating)
        self._pending.clear()
        self._migrating.clear()
        for req in held:
            self._deliver(req.id, Result(
                id=req.id, ok=False, finish_reason="preempted",
                error=f"drained before routing ({hint})",
                retries=req.retries,
                latency_s=time.monotonic() - req.submitted_at,
            ))
        get_registry().counter("serving_preempted_total", component="fleet",
                               **self._fleet_labels).inc(len(held))

    def _finish_stats(self) -> None:
        agg = ServingStats(num_slots=0)
        # Replicas retired mid-window first: their schedulers already
        # closed out at retirement, but the work they did belongs to this
        # window's fleet record.
        for st in self._retired_stats:
            agg = agg.merge(st)
        self._retired_stats = []
        for rep in self.replicas:
            rep.sched.finish_stats(rep.stats)
            agg = agg.merge(rep.stats)
            rep.stats = ServingStats(num_slots=rep.sched.num_slots)
        agg.num_slots = self.num_slots
        agg.rejected += self.queue.rejected - self._rejected_taken
        self._rejected_taken = self.queue.rejected
        agg.shed += self._shed_fleet
        self._shed_fleet = 0
        self.last_stats = agg
