"""``ServingBackend``: the continuous-batching server behind the
``DecodeBackend`` protocol (``pipeline/backends.py:31``), so phases 1-3 run
through the server unchanged — ``backend_for`` returns one when
``Config.serving.enabled`` (CLI ``--continuous``).

Differences from ``EngineBackend`` that callers should know:

- each row decodes independently in its own KV slot; the sweep-wide shared
  prefix (``prefix_ids``) is accepted and IGNORED — the engine's per-batch
  prefix mechanism doesn't fit per-request admission. The scheduler finds
  sharing itself: at admission a row copies the longest prefix some live
  row already holds (``slots.LiveRowPrefixes``) and prefills the rest.
  Greedy output is token-for-token identical either way (the parity
  contract is vs ``DecodeEngine.generate`` alone, which is how the tests
  pin it).
- per-request failures come back as ``None`` texts (the
  ``with_failure_containment`` sentinel convention) instead of failing the
  chunk, because the scheduler already contains faults per-request.
- serving counters accumulate in ``serve_totals`` (a ``ServingStats``)
  exactly like ``EngineBackend.spec_totals``, and the last call's
  ``GenerateOutput`` (with ``stats["serving"]``) is kept on
  ``last_output`` for byte/shape accounting.
- with ``resilience`` enabled, one ``BreakerBoard`` is shared by every
  scheduler AND the engine's speculate gate, and the degradation ladder's
  last rung lives here: at level 3 (``static_fallback``) new ``generate``
  calls route through the static ``DecodeEngine`` path — the numerically-
  reference program — until the ladder retreats.
- with ``integrity.canary_every_n`` set, a golden-prompt canary
  (``integrity/canary.py``) decodes through the live scheduler every N
  generate calls, compared token-for-token against a static-engine
  reference; a mismatch trips the decode breaker and the ladder above.
- with ``fleet.replicas`` > 1 (CLI ``--replicas N``), each sampler tuple
  gets a :class:`ReplicaSet` (``serving/fleet.py``) instead of a single
  scheduler: N replica fault domains behind a health-aware router, where
  a sick replica is fenced/drained/migrated instead of degrading the
  whole backend — resilience state is then per-replica, and the
  static-fallback rung above is replaced by fence/rejoin.
"""

from __future__ import annotations

import logging
from typing import List, Optional, Sequence

import numpy as np

from fairness_llm_tpu.config import (
    AutoscaleConfig,
    FleetConfig,
    IntegrityConfig,
    ModelSettings,
    OverloadConfig,
    ResilienceConfig,
    ServingConfig,
)
from fairness_llm_tpu.resilience.breaker import BreakerBoard
from fairness_llm_tpu.resilience.drain import ServingJournal
from fairness_llm_tpu.serving.fleet import ReplicaSet
from fairness_llm_tpu.serving.request import Request
from fairness_llm_tpu.serving.scheduler import ContinuousScheduler
from fairness_llm_tpu.telemetry.timeline import get_timeline
from fairness_llm_tpu.telemetry import get_registry

logger = logging.getLogger(__name__)


class ServingBackend:
    # decode_sweep's shared_prefix_ids checks this before computing the
    # sweep-wide token LCP — serving ignores prefix_ids, so don't pay for
    # it (admission discovers sharing from the live rows' ids itself).
    use_shared_prefix = False

    def __init__(self, engine, serving: Optional[ServingConfig] = None,
                 name: Optional[str] = None, fault_injector=None,
                 resilience: Optional[ResilienceConfig] = None,
                 journal: Optional[ServingJournal] = None,
                 integrity: Optional[IntegrityConfig] = None,
                 fleet: Optional[FleetConfig] = None,
                 overload: Optional[OverloadConfig] = None,
                 autoscale: Optional[AutoscaleConfig] = None):
        self.engine = engine
        self.serving = serving or ServingConfig(enabled=True)
        self.name = name or engine.config.name
        self.fault_injector = fault_injector
        self.resilience = resilience
        self.journal = journal
        self.integrity = integrity
        # Overload control (serving/overload.py): QoS classes + deadline
        # admission + the shed controller, gated at the serving front door
        # (the scheduler, or the ReplicaSet intake in fleet mode). This
        # backend's sweep traffic is marked qos="batch" — exactly the
        # class a brownout sheds first so interactive traffic survives.
        self.overload = overload if (overload is not None
                                     and overload.enabled) else None
        # Elastic membership (serving/autoscaler.py): --autoscale puts the
        # SLO-coupled controller on each fleet's tick. It implies fleet
        # mode even at --replicas 1 — a one-replica FLEET can grow; a bare
        # scheduler cannot.
        self.autoscale = autoscale if (autoscale is not None
                                       and autoscale.enabled) else None
        # Replica fleet (serving/fleet.py): fleet.replicas > 1 makes
        # scheduler_for build a ReplicaSet per sampler tuple instead of a
        # single scheduler — N fault domains behind the health-aware
        # router, sharing this backend's engine params.
        if fleet is not None and fleet.replicas > 1:
            self.fleet = fleet
        elif self.autoscale is not None:
            self.fleet = fleet or FleetConfig(replicas=1)
        else:
            self.fleet = None
        self._fleet_seq = 0  # ReplicaSets built by this backend, ever
        # Canary probe (integrity/canary.py): built lazily on the first
        # generate() — recording its reference costs one static-engine
        # decode, which must not land in backend construction (weight
        # loading time for big models).
        self._canary = None
        self._canary_sched = None
        self._canary_calls = 0  # fleet-mode tick counter (no CanaryProbe)
        self.board: Optional[BreakerBoard] = None
        if self.fleet is not None:
            # Fleet mode: resilience state is PER-REPLICA (each replica's
            # scheduler builds its own BreakerBoard/watchdog, labeled
            # {"replica": name}), and the last containment rung is the
            # fleet's fence/migrate/rejoin instead of this backend's
            # static-engine fallback — one shared board would re-couple
            # the fault domains the fleet exists to separate.
            pass
        elif resilience is not None and resilience.enabled:
            # ONE board for the whole backend: every scheduler's prefill/
            # decode breakers and the engine's speculate gate share state,
            # so the ladder sees the process's health, not one sampler
            # tuple's.
            self.board = BreakerBoard(
                failure_threshold=resilience.breaker_threshold,
                cooldown_s=resilience.breaker_cooldown_s,
            )
            self.engine.breakers = self.board
            if resilience.max_step_seconds > 0 and self.engine.watchdog is None:
                from fairness_llm_tpu.resilience.watchdog import StepWatchdog

                # The static-fallback rung runs engine.generate directly;
                # it gets the same hang classification the scheduler has.
                self.engine.watchdog = StepWatchdog(
                    resilience.max_step_seconds, component="engine"
                )
        self.serve_totals = None  # Optional[ServingStats], set lazily
        self.last_output = None  # GenerateOutput of the most recent call
        self._schedulers: dict = {}

    def scheduler_for(self, settings: ModelSettings) -> ContinuousScheduler:
        """One scheduler per sampler tuple (sampling is compiled into the
        step program) — or one :class:`ReplicaSet` per tuple in fleet mode
        (the fleet presents the same ``serve``/``last_stats`` surface).
        The persistent KV pool is the scheduler's dominant memory, so only
        a small working set is kept (LRU, like the engine's prefix-KV
        cache)."""
        key = (settings.temperature, settings.top_k, settings.top_p)
        sched = self._schedulers.get(key)
        if sched is not None:
            self._schedulers[key] = self._schedulers.pop(key)  # LRU refresh
            return sched
        if self.fleet is not None:
            # The backend's FIRST fleet keeps the default r0/r1 labels;
            # later sampler tuples get a namespacing name ("s1", ...) so
            # two fleets' replicas never alias instruments (liveness
            # gauges, healthy-replica counts) in one registry.
            sched = ReplicaSet(
                self.engine, self.serving, settings=settings,
                fleet=self.fleet, resilience=self.resilience,
                journal=self.journal, fault_injector=self.fault_injector,
                integrity=self.integrity,
                name=None if self._fleet_seq == 0 else f"s{self._fleet_seq}",
                overload=self.overload,
                autoscale=self.autoscale,
            )
            self._fleet_seq += 1
        else:
            sched = ContinuousScheduler(
                self.engine, self.serving, settings=settings,
                fault_injector=self.fault_injector,
                resilience=self.resilience, journal=self.journal,
                breakers=self.board, overload=self.overload,
            )
        keys = list(self._schedulers)
        while len(keys) >= 2:
            del self._schedulers[keys.pop(0)]
        self._schedulers[key] = sched
        return sched

    def _maybe_canary(self, live_sched=None) -> None:
        """Arm (lazily) and run the canary probe when due: every
        ``integrity.canary_every_n`` generate calls, the golden prompt
        decodes through the live scheduler and is compared token-for-token
        against the static-engine reference recorded on first use. A
        mismatch trips the decode breaker — the degradation ladder handles
        the rest (see integrity/canary.py). Runs BEFORE the user batch, so
        detected corruption degrades the path before more traffic lands on
        it."""
        integ = self.integrity
        if integ is None or integ.canary_every_n <= 0:
            return
        if isinstance(live_sched, ReplicaSet):
            # Fleet mode: the probe must be attributable to a replica (and
            # trip THAT replica's board) or a mismatch would contain
            # nothing — ReplicaSet.periodic_canary probes one unfenced
            # replica of the fleet serving THIS call, round-robin, with
            # per-replica references/boards/labels (greedy fleets only;
            # it no-ops where no deterministic reference exists).
            self._canary_calls += 1
            if self._canary_calls % integ.canary_every_n == 0:
                live_sched.periodic_canary()
            return
        if self._canary is None:
            from fairness_llm_tpu.integrity.canary import CanaryProbe

            self._canary = CanaryProbe.record(
                self.engine,
                max_tokens=integ.canary_max_tokens,
                every_n=integ.canary_every_n,
                board=self.board,
            )
        if self._canary.tick():
            self._canary.probe(self._canary_scheduler())

    def _canary_scheduler(self) -> ContinuousScheduler:
        """The scheduler the canary decodes through. When user traffic is
        itself greedy, that's the LIVE user scheduler (the probe then
        exercises the exact compiled programs + KV pool serving requests);
        otherwise a dedicated greedy scheduler held OUTSIDE the LRU —
        routing it through ``scheduler_for`` would evict a warm user
        scheduler (KV pool + compiled step) every ``canary_every_n`` calls.
        The dedicated scheduler shares the board (its outcomes must feed
        the same breakers) but not the journal: probes are synthetic
        traffic a successor process must never resume. Sampled-settings
        schedulers are NOT probed token-for-token — only greedy decode has
        a deterministic reference — so for sampled workloads the canary
        covers the shared engine/model/weights path, not that scheduler's
        own sampler program."""
        s = self._canary.settings
        live = self._schedulers.get((s.temperature, s.top_k, s.top_p))
        if live is not None:
            return live
        if self._canary_sched is None:
            self._canary_sched = ContinuousScheduler(
                self.engine, self.serving, settings=s,
                resilience=self.resilience, breakers=self.board,
            )
        return self._canary_sched

    def generate(
        self,
        prompts: Sequence[str],
        settings: Optional[ModelSettings] = None,
        seed: int = 0,
        keys: Optional[Sequence[str]] = None,
        prefix_ids: Optional[Sequence[int]] = None,  # accepted, unused
    ) -> List[Optional[str]]:
        from fairness_llm_tpu.pipeline.backends import _stable_hash
        from fairness_llm_tpu.runtime.engine import GenerateOutput

        settings = settings or ModelSettings()
        if not prompts:
            self.last_output = GenerateOutput(
                texts=[], tokens=np.zeros((0, 0), np.int32), steps=0
            )
            return []
        if self.board is not None and self.board.ladder.level >= 3 \
                and not (self.board.allow("prefill")
                         and self.board.allow("decode")):
            # Degradation rung 3: the continuous scheduler has proven
            # unhealthy enough (repeated breaker trips) that new calls take
            # the static DecodeEngine path — the least-clever, numerically-
            # reference program. Greedy output is identical; what is lost
            # is slot-recycling throughput. The allow() consults above are
            # what make this rung RECOVERABLE: once the open breakers'
            # cooldowns elapse they half-open on the consult and the call
            # falls through to the scheduler as the probe — its outcomes
            # close (or re-open) the breakers and the ladder walks back
            # down. Without them, nothing would ever exercise the serving
            # breakers again and level 3 would be permanent.
            logger.warning(
                "degradation level %d (%s): serving %d prompt(s) through "
                "the static engine", self.board.ladder.level,
                self.board.ladder.rung, len(prompts),
            )
            get_registry().counter(
                "static_fallback_calls_total", component="serving"
            ).inc()
            # Same row-seed formula as EngineBackend/the scheduler path, so
            # greedy AND sampled outputs stay identical across the fallback
            # boundary. last_output keeps its contract (the docstring's
            # byte/shape accounting promise) — serve_totals does NOT count
            # these calls (nothing was served); static_fallback_calls_total
            # is the degraded-traffic signal.
            row_seeds = None
            if keys is not None:
                row_seeds = [(_stable_hash(k) ^ seed) & 0xFFFFFFFF
                             for k in keys]
            out = self.engine.generate(
                prompts, settings, seed=seed, row_seeds=row_seeds,
                share_prefix=False,
            )
            self.last_output = out
            return list(out.texts)
        sched = self.scheduler_for(settings)
        self._maybe_canary(sched)
        tl, track = get_timeline(), getattr(sched, "_track", "serving")
        with tl.span("backend.submit", "backend", track, stage="build",
                     rows=len(prompts)):
            requests = self._build_requests(prompts, settings, seed, keys)
        results = sched.serve(requests)
        stats = sched.last_stats
        if stats is not None:
            self.serve_totals = (
                stats if self.serve_totals is None
                else self.serve_totals.merge(stats)
            )
        with tl.span("backend.collect", "backend", track,
                     rows=len(results)):
            self.last_output = self._collect(results, sched, stats)
        # None (not "") for failed rows — the decode_sweep/failure-containment
        # sentinel convention, so resumes retry them.
        return [r.text if r.ok else None for r in results]

    def _build_requests(self, prompts, settings: ModelSettings, seed: int,
                        keys) -> List[Request]:
        """One ``Request`` per prompt, with its stable id and row seed."""
        from fairness_llm_tpu.pipeline.backends import _stable_hash

        # Study tags (telemetry/fairness.py): a phase that registered its
        # profile grid with the fairness monitor gets its sweep requests
        # stamped with (attribute, group, pair_id), so the serving layer's
        # treatment of each demographic group is observable per request.
        from fairness_llm_tpu.telemetry.fairness import get_fairness_monitor

        mon = get_fairness_monitor()
        requests = []
        for i, p in enumerate(prompts):
            if keys is not None:
                # Same row-seed formula as EngineBackend: stable identity,
                # so resumed sweeps reproduce uninterrupted ones.
                rid, row_seed = keys[i], (_stable_hash(keys[i]) ^ seed) & 0xFFFFFFFF
            else:
                rid, row_seed = f"call{seed}_{i:05d}", (seed * 1_000_003 + i) & 0xFFFFFFFF
            tags = mon.request_tags(rid) if mon.active else None
            requests.append(Request(
                prompt=p, id=rid, settings=settings, row_seed=row_seed,
                # Phase sweeps are throughput traffic: the class a
                # brownout sheds first (shed rows return None below — the
                # resumable-sentinel convention, so a shed sweep row is
                # retried by the pipeline's containment, not lost).
                qos="batch",
                attribute=tags[0] if tags else None,
                group=tags[1] if tags else None,
                pair_id=tags[2] if tags else None,
            ))
        return requests

    def _collect(self, results, sched, stats):
        """The call's results as the token matrix ``last_output`` holds."""
        from fairness_llm_tpu.runtime.engine import GenerateOutput

        cap = max((len(r.tokens) for r in results), default=0)
        toks = np.full((len(results), cap), self.engine.tokenizer.pad_id,
                       np.int32)
        for i, r in enumerate(results):
            toks[i, : len(r.tokens)] = r.tokens
        return GenerateOutput(
            texts=[r.text if r.ok else "" for r in results],
            tokens=toks,
            steps=sched.serving.max_new_tokens,
            stats={
                "batch": sched.num_slots,
                "prompt_len": sched.max_prompt_bucket,
                "prefix_len": 0,
                "cache_slots": sched.cache_len,
                "decode_kernel": bool(
                    self.engine.config.use_decode_attention_kernel
                ),
                "serving": stats.as_dict() if stats is not None else None,
            },
        )
