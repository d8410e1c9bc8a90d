"""Failure containment for decode sweeps.

The reference wraps every API call in try/except returning empty-result
sentinels so one failure doesn't kill a 45-call sweep
(``phase1_bias_detection.py:202-211``, SURVEY.md §5.3) — but it has no
retries. Local decode fails differently (compile OOM, a device fault, bad
checkpoint), and a whole CHUNK fails at once; this wrapper retries a failed
chunk once (fresh attempt covers transient device errors) and then degrades
to per-prompt empty sentinels, keeping the sweep alive and the failure
visible in the results. Per-row containment stops at the sweep: a sweep that
lost EVERY row raises :class:`SweepFailed` (``require_some_decoded``), so a
compile rejection or an OOM can never pass for a completed study.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Dict, List, Optional, Sequence

# Module scope, not the fault hot path: these imports used to run inside
# every ``maybe_fail`` hit and every contained chunk failure. No cycle:
# telemetry imports nothing from utils.
from fairness_llm_tpu.telemetry import get_registry

logger = logging.getLogger(__name__)


class DecodeFault(RuntimeError):
    """A (possibly injected) per-request decode failure.

    The continuous-batching scheduler (``serving/scheduler.py``) treats this
    as a SLOT-level event, not a process-level one: the hit request is
    requeued once (fresh prefill, fresh slot) and, if it faults again,
    surfaced as a failed ``Result`` — the step loop itself never dies."""


class HangFault(DecodeFault):
    """A compiled step classified as hung by the watchdog
    (``resilience/watchdog.py``: wall time past ``max_step_seconds``).

    Subclasses ``DecodeFault`` so every existing containment path — slot
    release + requeue-once in the scheduler, chunk retry in
    ``with_failure_containment`` — absorbs it without new plumbing; the
    distinct type is what telemetry labels key on, so chaos reports can
    tell a hang from an ordinary decode fault."""


class NumericsFault(DecodeFault):
    """A chunk whose logits contained NaN/Inf, caught by the on-device
    numerics guard (``integrity/numerics.py``) before its tokens could be
    delivered.

    Subclasses ``DecodeFault`` for the same reason ``HangFault`` does: the
    scheduler releases the chunk's slots and requeues each rider once (a
    fresh prefill re-derives every activation, so a transient flip heals),
    ``with_failure_containment`` retries the engine chunk once then emits
    ``None`` sentinels, and the breakers see a persistent numeric sickness
    as consecutive failures. The distinct type keys the telemetry labels
    (``kind="numerics"``) and the ``numerics_faults_total`` breakdown."""


class SweepFailed(RuntimeError):
    """Every row of one decode sweep failed, so there is nothing to compute
    a study from. Per-row containment (``None`` sentinels, ``decode_failed``
    rows) keeps a sweep alive through SOME failures; a sweep that lost
    everything — a program the compiler rejected, an out-of-memory device, a
    misconfigured server — must stop the run instead of reporting metrics
    over empty lists."""


def require_some_decoded(texts: Sequence[Optional[str]], what: str) -> None:
    """Raise :class:`SweepFailed` when every entry of a non-empty ``texts``
    is the ``None`` failure sentinel."""
    if texts and all(t is None for t in texts):
        raise SweepFailed(
            f"{what}: all {len(texts)} row(s) failed to decode (see the "
            "contained-failure log lines above for the cause)"
        )


class ScriptedFaultInjector:
    """Deterministic fault injection for serving tests and chaos drills.

    ``faults`` maps ``(request_id, stage)`` — or plain ``request_id`` for any
    stage — to the number of times that request should fault. Stages are
    ``"prefill"`` and ``"decode"``. Each ``maybe_fail`` hit decrements the
    budget, so "fail once then succeed" is ``{rid: 1}`` and "fail
    permanently" is ``{rid: 2}`` (the scheduler requeues exactly once).

    ``hangs`` (same key scheme) scripts HANGS instead: each ``maybe_hang``
    hit returns ``hang_seconds`` of *simulated* stall, which the scheduler
    feeds to the step watchdog as extra elapsed time — a watchdog-classified
    ``HangFault`` without ever sleeping, so hang containment is testable in
    milliseconds.

    ``corruptions`` (same key scheme) scripts SILENT CORRUPTION: each
    ``maybe_corrupt`` hit tells the scheduler to poison that request's
    carried logits (``corruption_mode``: "nan" or "inf") before the next
    decode chunk — so the on-device numerics guard
    (``integrity/numerics.py``) has something real to catch on the CPU
    harness, with no device fault hardware required. ``flip_bit`` is the
    at-rest sibling: one flipped bit in an artifact file, for manifest
    drills.

    ``replica_crashes`` / ``replica_hangs`` script REPLICA-level faults for
    the fleet router (``serving/fleet.py``): keys are replica names, values
    the number of health polls the replica survives before the fault fires
    ONCE (0 = on the first poll; a few polls lets the replica serve some
    chunks first, so the drill exercises mid-flight migration, not just
    cold routing). A "crash" stands in for the replica process dying
    outright; a "hang" for the silent stall the watchdog's external probe
    exists to catch. Both are counted with their own ``kind`` labels
    (``injected_replica_crash`` / ``injected_replica_hang``) so a fleet
    drill's telemetry reads apart from single-engine chaos.

    TIME-INDEXED schedule (the load-replay sibling of the count-based
    budgets above): ``faults_at`` / ``hangs_at`` / ``corruptions_at``
    (same key scheme, values in SECONDS) and ``replica_crashes_at`` /
    ``replica_hangs_at`` (replica name -> seconds) fire ONCE the first
    time the corresponding ``maybe_*`` hook runs at or after that many
    seconds on the injector's clock. The clock starts at the first hook
    call — or at ``arm()``, which the replay driver
    (``serving/replay.py``) calls with its own trace clock, so a replica
    crash pins to trace-time "middle of the burst" regardless of the
    time-compression factor, instead of counting calls whose cadence the
    workload shape changes.
    """

    def __init__(
        self,
        faults: Optional[Dict[object, int]] = None,
        hangs: Optional[Dict[object, int]] = None,
        hang_seconds: float = 3600.0,
        corruptions: Optional[Dict[object, int]] = None,
        corruption_mode: str = "nan",
        replica_crashes: Optional[Dict[str, int]] = None,
        replica_hangs: Optional[Dict[str, int]] = None,
        faults_at: Optional[Dict[object, float]] = None,
        hangs_at: Optional[Dict[object, float]] = None,
        corruptions_at: Optional[Dict[object, float]] = None,
        replica_crashes_at: Optional[Dict[str, float]] = None,
        replica_hangs_at: Optional[Dict[str, float]] = None,
        clock: Optional[Callable[[], float]] = None,
    ):
        if corruption_mode not in ("nan", "inf"):
            raise ValueError(
                f"corruption_mode must be 'nan' or 'inf', got {corruption_mode!r}"
            )
        self._budget = dict(faults or {})
        self._hang_budget = dict(hangs or {})
        self._corruption_budget = dict(corruptions or {})
        self._replica_delay: Dict[str, tuple] = {}
        for name, delay in (replica_crashes or {}).items():
            self._replica_delay[name] = (int(delay), "replica_crash")
        for name, delay in (replica_hangs or {}).items():
            if name in self._replica_delay:
                raise ValueError(
                    f"replica {name!r} scripted for both crash and hang"
                )
            self._replica_delay[name] = (int(delay), "replica_hang")
        self._at = {
            "fault": dict(faults_at or {}),
            "hang": dict(hangs_at or {}),
            "corruption": dict(corruptions_at or {}),
        }
        self._replica_at: Dict[str, tuple] = {}
        for name, at in (replica_crashes_at or {}).items():
            if name in self._replica_delay:
                raise ValueError(
                    f"replica {name!r} scripted for more than one fault"
                )
            self._replica_at[name] = (float(at), "replica_crash")
        for name, at in (replica_hangs_at or {}).items():
            if name in self._replica_at or name in self._replica_delay:
                raise ValueError(
                    f"replica {name!r} scripted for more than one fault"
                )
            self._replica_at[name] = (float(at), "replica_hang")
        self._clock: Callable[[], float] = clock or time.monotonic
        self._t0: Optional[float] = None
        self.corruption_mode = corruption_mode
        self.hang_seconds = float(hang_seconds)
        self.fired: List[tuple] = []  # (request_id, stage) audit log
        self.hangs_fired: List[tuple] = []
        self.corruptions_fired: List[tuple] = []
        self.replica_faults_fired: List[tuple] = []  # (replica, kind)

    # -- the time-indexed clock ----------------------------------------------

    def arm(self, clock: Optional[Callable[[], float]] = None) -> None:
        """Start (or restart) the schedule clock — ``at_seconds`` entries
        are relative to this moment. ``clock`` replaces the injector's
        clock for the rest of the run (the replay driver passes its trace
        clock, so schedule times are TRACE seconds). Never called: the
        clock self-arms at the first ``maybe_*`` hook, in wall seconds."""
        if clock is not None:
            self._clock = clock
        self._t0 = self._clock()

    def _elapsed(self) -> float:
        if self._t0 is None:
            self._t0 = self._clock()
        return self._clock() - self._t0

    def _due(self, kind: str, request_id: str, stage: str) -> bool:
        """One consumed time-schedule hit for ``kind`` (fault/hang/
        corruption), matching the count-based key scheme."""
        sched = self._at[kind]
        if not sched:
            return False
        elapsed = self._elapsed()
        for key in ((request_id, stage), request_id):
            at = sched.get(key)
            if at is not None and elapsed >= at:
                del sched[key]
                return True
        return False

    def maybe_fail(self, request_id: str, stage: str) -> None:
        due = self._due("fault", request_id, stage)
        if not due:
            for key in ((request_id, stage), request_id):
                n = self._budget.get(key, 0)
                if n > 0:
                    self._budget[key] = n - 1
                    due = True
                    break
        if due:
            self.fired.append((request_id, stage))
            # Injected faults are labeled apart from device-raised ones
            # (the scheduler counts those kind="device") so a chaos
            # drill's telemetry can't be mistaken for a real incident.
            get_registry().counter(
                "faults_total", component="serving", kind="injected",
                stage=stage,
            ).inc()
            raise DecodeFault(
                f"injected {stage} fault for request {request_id!r}"
            )

    def maybe_hang(self, request_id: str, stage: str) -> float:
        """Simulated stall seconds this request contributes to the current
        step (0.0 almost always). Consumes one hang budget (or due
        time-schedule entry) per hit."""
        due = self._due("hang", request_id, stage)
        if not due:
            for key in ((request_id, stage), request_id):
                n = self._hang_budget.get(key, 0)
                if n > 0:
                    self._hang_budget[key] = n - 1
                    due = True
                    break
        if due:
            self.hangs_fired.append((request_id, stage))
            get_registry().counter(
                "faults_total", component="serving",
                kind="injected_hang", stage=stage,
            ).inc()
            return self.hang_seconds
        return 0.0

    def maybe_corrupt(self, request_id: str, stage: str) -> Optional[str]:
        """Corruption mode ("nan"/"inf") the scheduler should poison this
        request's carried logits with before the next compiled step — None
        almost always. Consumes one corruption budget (or due
        time-schedule entry) per hit. The poison happens host-side on the
        carry (not inside the program), so the guarded program itself
        stays the production one."""
        due = self._due("corruption", request_id, stage)
        if not due:
            for key in ((request_id, stage), request_id):
                n = self._corruption_budget.get(key, 0)
                if n > 0:
                    self._corruption_budget[key] = n - 1
                    due = True
                    break
        if due:
            self.corruptions_fired.append((request_id, stage))
            get_registry().counter(
                "faults_total", component="serving",
                kind="injected_corruption", stage=stage,
            ).inc()
            return self.corruption_mode
        return None

    def maybe_replica_fault(self, replica: str) -> Optional[str]:
        """Replica-level fault due this health poll — ``"replica_crash"``,
        ``"replica_hang"``, or None (almost always). The scripted delay
        counts down one per poll; at zero the fault fires once and the
        script entry is consumed (a crashed replica doesn't crash twice —
        it fences, migrates its work, and rejoins through the canary).
        ``replica_crashes_at`` entries instead fire at their scheduled
        second — whichever poll first observes the clock past it."""
        kind = None
        at_entry = self._replica_at.get(replica)
        if at_entry is not None and self._elapsed() >= at_entry[0]:
            del self._replica_at[replica]
            kind = at_entry[1]
        if kind is None:
            entry = self._replica_delay.get(replica)
            if entry is None:
                return None
            delay, k = entry
            if delay > 0:
                self._replica_delay[replica] = (delay - 1, k)
                return None
            del self._replica_delay[replica]
            kind = k
        self.replica_faults_fired.append((replica, kind))
        get_registry().counter(
            "faults_total", component="fleet", kind=f"injected_{kind}",
            stage="replica", replica=replica,
        ).inc()
        return kind

    @staticmethod
    def flip_bit(path: str, bit_index: int) -> None:
        """Flip one bit of a file in place — the scripted cosmic ray for
        artifact-corruption drills. Pair with an integrity manifest
        (``integrity/manifest.py``): the flipped file must then be refused
        at load with an error naming it."""
        with open(path, "r+b") as f:
            f.seek(bit_index // 8)
            byte = f.read(1)
            if not byte:
                raise ValueError(
                    f"bit_index {bit_index} beyond end of {path}"
                )
            f.seek(bit_index // 8)
            f.write(bytes([byte[0] ^ (1 << (bit_index % 8))]))
        get_registry().counter(
            "faults_total", component="integrity", kind="injected_bitflip",
            stage="artifact",
        ).inc()


def with_failure_containment(
    generate: Callable[..., List[str]],
    retries: int = 1,
) -> Callable[..., List[Optional[str]]]:
    """Wrap a backend ``generate`` so chunk failures return ``None`` sentinels
    instead of raising (after ``retries`` fresh attempts).

    ``None`` — not "" — so callers can tell a failed decode apart from a model
    that legitimately emitted empty text, keep failures OUT of resume
    checkpoints (a failed prompt must be retried on --resume, not skipped),
    and still surface the gap in results like the reference's empty-result
    sentinels (``phase1_bias_detection.py:202-211``)."""

    def wrapped(
        prompts: Sequence[str], settings=None, seed: int = 0, keys=None,
        prefix_ids=None,
    ) -> List[Optional[str]]:
        last: Optional[Exception] = None
        for attempt in range(retries + 1):
            try:
                return list(generate(
                    prompts, settings, seed=seed, keys=keys, prefix_ids=prefix_ids
                ))
            except Exception as e:  # noqa: BLE001 — containment is the point
                last = e
                # error_type label so a chaos report can split HangFault
                # from DecodeFault from raw device errors without parsing
                # logs (the bare total is the sum over types).
                get_registry().counter(
                    "contained_chunk_failures_total", component="pipeline",
                    error_type=type(e).__name__,
                ).inc()
                logger.warning(
                    "decode chunk failed (attempt %d/%d): %s",
                    attempt + 1, retries + 1, e,
                )
        logger.error("decode chunk failed permanently; emitting None sentinels: %s", last)
        return [None for _ in prompts]

    return wrapped
