"""Profiling/tracing hooks (SURVEY.md §5.1 — the reference has only wall-clock
phase timers at ``main.py:87-125``; this adds real device traces).

``maybe_trace(config)`` wraps a region in ``jax.profiler.trace`` when
``config.profile_trace_dir`` is set — the trace opens in XProf/TensorBoard and
shows per-op device time, HBM traffic, and fusion boundaries. Zero overhead
when unset (no-op context).

``summarize_trace(trace_dir)`` aggregates a captured trace's device events
per op WITHOUT TensorBoard — the terminal-friendly analysis that produced
the decode-step breakdown (ROADMAP.md S1): total device time,
event counts, and the top ops by accumulated duration.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import logging
import os
import time
from typing import Iterator, List, Optional, Tuple

logger = logging.getLogger(__name__)


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str], label: str = "region") -> Iterator[None]:
    # The region ALWAYS lands on the host timeline (telemetry/timeline.py)
    # as a cat="phase" span — the Perfetto export (--trace-out) then shows
    # phase1/2/3 regions over the device-step lanes, with or without an
    # XProf capture riding along. Device-side capture stays gated on
    # trace_dir exactly as before.
    from fairness_llm_tpu.telemetry.timeline import get_timeline

    t0 = time.monotonic()
    try:
        if not trace_dir:
            yield
            return
        import jax

        logger.info("profiling %s -> %s", label, trace_dir)
        # Annotate the traced region with its label: a multi-phase --all
        # capture writes one timestamped directory per phase, but inside
        # XProf the host planes were indistinguishable — the TraceAnnotation
        # puts "phase1" / "phase2" / "phase3" spans on the trace-viewer
        # timeline itself.
        with jax.profiler.trace(trace_dir), jax.profiler.TraceAnnotation(label):
            yield
    finally:
        get_timeline().record_span(label, "phase", "host", t0,
                                   time.monotonic() - t0,
                                   xprof=bool(trace_dir))


@dataclasses.dataclass
class TraceSummary:
    """Per-device aggregation of one ``jax.profiler.trace`` capture."""

    device: str
    total_ms: float
    num_events: int
    top_ops: List[Tuple[str, float, int]]  # (op name, total ms, count)

    def format(self, width: int = 80) -> str:
        lines = [
            f"{self.device}: {self.total_ms:.1f} ms device time, "
            f"{self.num_events} events"
        ]
        for name, ms, cnt in self.top_ops:
            lines.append(f"  {ms:9.2f} ms  x{cnt:6d}  {name[:width]}")
        return "\n".join(lines)


def _xplane_proto():
    """The XSpace proto, importable from whichever package ships it. The
    generated module may need pure-python protobuf parsing
    (PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python) with some installed
    protobuf majors — callers get a clear error naming the knob."""
    for mod in (
        "tensorflow.tsl.profiler.protobuf.xplane_pb2",
        "tsl.profiler.protobuf.xplane_pb2",
        "tensorflow.core.profiler.protobuf.xplane_pb2",
    ):
        try:
            import importlib

            return importlib.import_module(mod)
        except Exception:  # noqa: BLE001 — try the next location
            continue
    raise ImportError(
        "no xplane_pb2 module available (needs tensorflow's tsl profiler "
        "protos); if import fails with a protobuf Descriptor error, set "
        "PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python"
    )


def summarize_trace(
    trace_dir: str, top_k: int = 15, device_filter: str = "TPU",
    latest_only: bool = False,
) -> List[TraceSummary]:
    """Aggregate every capture under ``trace_dir`` by device op.

    A multi-phase run (``--all --trace-dir``) writes one timestamped capture
    per phase, and a multi-host run one file per host — each becomes its own
    ``TraceSummary``, labeled ``<capture>/<file>: <plane>`` so phases/hosts
    aren't conflated (``latest_only=True`` restricts to the newest capture).
    ``device_filter`` is a plane-name substring; "" for all planes including
    host. Event durations sum per op name across a capture — for a decode
    loop that means per-step ops show up with count == steps executed, which
    is how the round-3 analysis attributed the 2.12 ms/step to its
    slice/copy/matmul parts.
    """
    pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not pbs:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    if latest_only:
        # newest CAPTURE (timestamped directory), keeping every host's file
        # in it — a flat [-1:] would drop all but one host of a pod trace
        newest = os.path.dirname(pbs[-1])
        pbs = [p for p in pbs if os.path.dirname(p) == newest]
    xplane_pb2 = _xplane_proto()

    out: List[TraceSummary] = []
    for pb in pbs:
        xs = xplane_pb2.XSpace()
        with open(pb, "rb") as f:
            xs.ParseFromString(f.read())
        label = os.path.join(
            os.path.basename(os.path.dirname(pb)),
            os.path.basename(pb).replace(".xplane.pb", ""),
        )
        out.extend(_summarize_planes(xs, label, top_k, device_filter))
    return out


def _summarize_planes(xs, label: str, top_k: int, device_filter: str) -> List[TraceSummary]:
    out: List[TraceSummary] = []
    for plane in xs.planes:
        if device_filter and device_filter not in plane.name:
            continue
        meta = {k: v.name for k, v in plane.event_metadata.items()}
        # A device plane carries NESTED aggregation levels as separate lines:
        # "XLA Modules" (one event per program execution), "XLA Ops" (the ops
        # inside, where a while-loop op's span contains its body's ops), and
        # "Async XLA Ops" (DMA copies overlapping compute). Summing across
        # lines double-counts, so: total device time comes from the Modules
        # line (true busy time), per-op rows from the exact Ops line (a loop
        # op's row includes its children — it reads as "time under this op").
        # Host planes (nested TraceMe threads) have no such levels; their
        # totals are "sum of event durations", not wall time.
        by_name = {l.name: l for l in plane.lines}
        op_line = by_name.get("XLA Ops")
        if op_line is not None:
            lines = [op_line]
        elif "XLA Modules" in by_name:
            # Device plane without op-level recording (reduced verbosity):
            # fall back to module granularity ONLY — mixing in async-copy
            # lines would double-count against the module spans.
            lines = [by_name["XLA Modules"]]
        else:
            lines = list(plane.lines)  # host plane: nested TraceMe threads
        totals: collections.Counter = collections.Counter()
        counts: collections.Counter = collections.Counter()
        for line in lines:
            for ev in line.events:
                name = meta.get(ev.metadata_id, "?")
                totals[name] += ev.duration_ps / 1e9  # ps -> ms
                counts[name] += 1
        if not totals:
            continue
        if "XLA Modules" in by_name:
            total_ms = sum(ev.duration_ps / 1e9 for ev in by_name["XLA Modules"].events)
        else:
            total_ms = sum(totals.values())
        top = [
            (name, round(ms, 3), counts[name])
            for name, ms in totals.most_common(top_k)
        ]
        out.append(
            TraceSummary(
                device=f"{label}: {plane.name}",
                total_ms=round(total_ms, 2),
                num_events=sum(counts.values()),
                top_ops=top,
            )
        )
    return out


@dataclasses.dataclass
class SpeculationStats:
    """Prompt-lookup speculative-decoding counters for one decode call (or a
    whole sweep, via ``merge``) — the observability half of
    ``runtime/speculative.py``. Surfaced in ``GenerateOutput.stats``
    ["speculation"] next to the decode-shape byte accounting, aggregated per
    sweep by ``pipeline.backends.EngineBackend``, and reported by bench.py's
    ``speculative`` entry.

    - ``drafted``: draft tokens proposed across all verify steps x live rows
    - ``accepted``: drafted tokens actually emitted (the free ones — every
      accepted token skips one full decode step's HBM streaming)
    - ``verify_steps``: compiled verify-forward invocations. The batch
      decodes in lockstep, so plain decode's while_loop trip count is the
      MAX per-row token count; ``verify_steps`` replaces that, and the
      wall-clock win tracks (max row tokens) / verify_steps.
    - ``emitted``: real tokens produced across all rows (incl. each step's
      greedy token); ``tokens_per_step`` = emitted / verify_steps is a
      batch-summed convenience, not the per-row compression ratio.
    """

    drafted: int = 0
    accepted: int = 0
    verify_steps: int = 0
    emitted: int = 0
    draft_len: int = 0
    ngram_max: int = 0

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def tokens_per_step(self) -> float:
        return self.emitted / self.verify_steps if self.verify_steps else 0.0

    @classmethod
    def from_dict(cls, d: dict) -> "SpeculationStats":
        """Inverse of ``as_dict`` (computed keys like acceptance_rate are
        derived, not stored, so they're dropped on the way in)."""
        return cls(**{k: v for k, v in d.items() if k in cls.__dataclass_fields__})

    def merge(self, other: "SpeculationStats") -> "SpeculationStats":
        return SpeculationStats(
            drafted=self.drafted + other.drafted,
            accepted=self.accepted + other.accepted,
            verify_steps=self.verify_steps + other.verify_steps,
            emitted=self.emitted + other.emitted,
            draft_len=other.draft_len or self.draft_len,
            ngram_max=other.ngram_max or self.ngram_max,
        )

    def as_dict(self) -> dict:
        return {
            "drafted": self.drafted,
            "accepted": self.accepted,
            "verify_steps": self.verify_steps,
            "emitted": self.emitted,
            "acceptance_rate": round(self.acceptance_rate, 4),
            "tokens_per_step": round(self.tokens_per_step, 3),
            "draft_len": self.draft_len,
            "ngram_max": self.ngram_max,
        }

    def publish(self, registry=None, component: str = "engine") -> None:
        """Mirror this object's counters into the telemetry registry
        (``telemetry/registry.py``), making the dataclass a registry-backed
        view: the engine publishes each per-call stats object exactly once,
        so registry totals equal the merged sweep totals while ``as_dict``
        stays the byte-compatible phase-metadata format."""
        from fairness_llm_tpu.telemetry import get_registry

        reg = registry if registry is not None else get_registry()
        reg.counter("spec_drafted_total", component=component).inc(self.drafted)
        reg.counter("spec_accepted_total", component=component).inc(self.accepted)
        reg.counter("spec_verify_steps_total", component=component).inc(
            self.verify_steps
        )
        reg.counter("spec_emitted_total", component=component).inc(self.emitted)


@dataclasses.dataclass
class ServingStats:
    """Continuous-batching serving counters for one ``ContinuousScheduler``
    drain (or a whole sweep, via ``merge``) — the observability half of
    ``serving/``. Surfaced in ``GenerateOutput``-style stats by
    ``serving.backend.ServingBackend`` (``serve_totals``), and recorded in
    phase result metadata exactly like ``SpeculationStats`` above.

    - ``admitted``: requests admitted into KV slots (a requeued request
      counts again on its second admission)
    - ``completed`` / ``failed`` / ``expired``: terminal request outcomes
      (``expired`` = deadline passed before completion)
    - ``preempted``: requests a graceful drain handed to the serving
      journal instead of finishing (``resilience/drain.py``) — terminal
      for this process, resumable by the next
    - ``shed``: requests overload control refused with an explicit
      terminal Result + retry-after (``serving/overload.py``) — class
      brownout or deadline-infeasibility, broken down in
      ``shed_total{class,reason}``
    - ``rejected``: submissions refused at the queue (capacity/rate)
    - ``requeued``: fault-hit slots sent back for one retry
    - ``prefill_batches`` / ``prefill_tokens``: compiled prefill calls and
      the REAL prompt tokens admitted through them, whether their positions
      were computed or copied
    - ``prefill_tokens_reused``: of those, the positions a row copied from a
      resident row with the same leading ids instead of computing them
    - ``decode_steps`` / ``decoded_tokens``: compiled decode-step forwards
      and real tokens emitted; tokens/step measures how full the slot pool
      ran (max = ``num_slots``)
    - ``occupancy_sum``: live slots summed over decode steps (avg occupancy
      = occupancy_sum / decode_steps)
    - ``queue_depth_sum`` / ``queue_depth_max`` / ``loop_iterations``:
      admission-queue pressure over the scheduler loop
    - ``moe_tokens`` / ``moe_local_pairs`` / ``moe_fullest_expert_pairs``:
      routed-expert layers only, counted on the device and summed over
      layers, decode steps and prefills: real tokens routed, the (token,
      expert) pairs whose expert this chip holds, and the pairs of the
      fullest held expert (x held experts / local pairs = load max over mean)
    - ``row_state_bytes_stepped`` / ``row_state_resets``: models with a
      layer that keeps a fixed state per row only. Bytes of that state the
      decode steps read and wrote (steps x slots x bytes a row, from shapes:
      the step program streams every slot's state, live or idle), and rows
      whose state ``serve_prefill`` zeroed on admission
    - ``kv_bytes_stepped``: bytes of keys and values (or latents) the decode
      steps fetched. Through ``ops/pool_attention``: the blocks of every
      grid step of its two kernels (block-rounded; an idle row none).
      Otherwise steps x slots x the bytes a row's layers RETAIN, from
      shapes: every slot's, live or idle; a window layer's ring counts its
      own length
    - ``kv_positions_attended`` / ``kv_positions_from_lead``: row-positions
      the live rows attended over decode steps and layers (a window layer's
      window), and of those the ones a row read through another row's lead
      span (``scheduler._decode_plan``; 0 where the step takes the XLA path)
    - ``prefill_donors_refused_ring``: admitted rows whose longest-sharing
      resident row could not be their donor because a window layer's ring
      had moved past what they needed of it
    - ``prefill_leader_batches`` / ``prefill_rows_deferred``: in a bucket
      of ``stepbuilder.DONOR_COND_WINDOWS`` windows and more, admission
      batches in which no row copied (they ran the wide-window program),
      and the times a row that found no resident donor went back to the
      front of the queue to wait one batch for a row ahead of it
      (``scheduler._plan_prefill``); both 0 in shorter buckets
    """

    num_slots: int = 0
    admitted: int = 0
    completed: int = 0
    failed: int = 0
    expired: int = 0
    preempted: int = 0
    shed: int = 0
    rejected: int = 0
    requeued: int = 0
    prefill_batches: int = 0
    prefill_tokens: int = 0
    prefill_tokens_reused: int = 0
    decode_steps: int = 0
    decoded_tokens: int = 0
    occupancy_sum: int = 0
    queue_depth_sum: int = 0
    queue_depth_max: int = 0
    loop_iterations: int = 0
    moe_tokens: int = 0
    moe_local_pairs: int = 0
    moe_fullest_expert_pairs: int = 0
    row_state_bytes_stepped: int = 0
    row_state_resets: int = 0
    kv_bytes_stepped: int = 0
    kv_positions_attended: int = 0
    kv_positions_from_lead: int = 0
    prefill_donors_refused_ring: int = 0
    prefill_leader_batches: int = 0
    prefill_rows_deferred: int = 0

    @property
    def tokens_per_step(self) -> float:
        return self.decoded_tokens / self.decode_steps if self.decode_steps else 0.0

    @property
    def avg_occupancy(self) -> float:
        return self.occupancy_sum / self.decode_steps if self.decode_steps else 0.0

    @property
    def avg_queue_depth(self) -> float:
        return (
            self.queue_depth_sum / self.loop_iterations
            if self.loop_iterations else 0.0
        )

    @classmethod
    def from_dict(cls, d: dict) -> "ServingStats":
        return cls(**{k: v for k, v in d.items() if k in cls.__dataclass_fields__})

    def merge(self, other: "ServingStats") -> "ServingStats":
        summed = {
            f.name: getattr(self, f.name) + getattr(other, f.name)
            for f in dataclasses.fields(self)
        }
        summed["num_slots"] = other.num_slots or self.num_slots
        summed["queue_depth_max"] = max(self.queue_depth_max, other.queue_depth_max)
        return ServingStats(**summed)

    def as_dict(self) -> dict:
        out = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        out["tokens_per_step"] = round(self.tokens_per_step, 3)
        out["avg_occupancy"] = round(self.avg_occupancy, 3)
        out["avg_queue_depth"] = round(self.avg_queue_depth, 3)
        return out

    def publish(self, registry=None, component: str = "serving",
                labels=None) -> None:
        """Mirror one drain's counters into the telemetry registry (same
        contract as ``SpeculationStats.publish``: call once per drain so the
        registry carries process totals). ``num_slots`` and
        ``queue_depth_max`` are level/high-water quantities, not event
        counts, so they publish as gauges. ``labels`` adds extra instrument
        labels (the fleet's per-replica schedulers pass
        ``{"replica": name}``)."""
        from fairness_llm_tpu.telemetry import get_registry

        reg = registry if registry is not None else get_registry()
        lbl = dict(labels or {})
        for name in (
            "admitted", "completed", "failed", "expired", "preempted",
            "shed", "rejected", "requeued", "prefill_batches",
            "prefill_tokens", "prefill_tokens_reused", "decode_steps",
            "decoded_tokens",
            "loop_iterations",
        ):
            reg.counter(f"serving_{name}_total", component=component,
                        **lbl).inc(getattr(self, name))
        reg.gauge("serving_num_slots", component=component,
                  **lbl).set(self.num_slots)
        reg.gauge("serving_queue_depth_max", component=component,
                  **lbl).set_max(self.queue_depth_max)


@contextlib.contextmanager
def phase_timer(name: str, sink: Optional[dict] = None) -> Iterator[None]:
    """Wall-clock phase timing (the reference's orchestrator pattern), with an
    optional dict sink for machine-readable timings."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        logger.info("%s took %.2fs", name, dt)
        if sink is not None:
            sink[name] = dt
