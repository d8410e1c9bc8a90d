"""Unified telemetry subsystem: metrics registry, request-lifecycle tracing,
and exporters.

Before this package the framework's observability was three disconnected
mechanisms: wall-clock ``phase_timer`` logs, XProf device traces
(``--trace-dir``), and per-feature counter dataclasses
(``SpeculationStats``/``ServingStats``). None of them could answer the
serving questions the ROADMAP's "as fast as the hardware allows" goal is
judged on — TTFT and per-output-token latency DISTRIBUTIONS, queue-wait
attribution, occupancy over time. This package is the shared substrate:

- ``registry``  — process-wide counters/gauges/log-bucket histograms,
  labeled by component (``engine``, ``serving``, ``phase1..3``);
  percentiles derive from bucket counts (no sample retention).
- ``tracing``   — per-request lifecycle spans in the serving scheduler
  (submitted -> admitted -> prefill_start -> first_token -> terminal),
  yielding queue-wait / TTFT / per-output-token / e2e histograms.
- ``export``    — JSONL event sink, snapshot dump (JSON + Prometheus text),
  schema validation, and the ``cli telemetry-report`` terminal renderer.
- ``heartbeat`` — low-frequency liveness pulse for long sweeps, with
  missed-beat gap detection (``heartbeat_gap_s``).
- ``timeline``  — device-step timeline: ``Timeline.span`` (ring entry +
  profiler annotation) round every piece of the scheduler's iteration,
  scheduler instants + request lanes, per-replica tracks,
  Chrome-trace/Perfetto export (``--trace-out``), and the ``step_gap_s``
  host-sync histogram.
- ``compilestats`` — compile observability: ``compiles_total{program,
  reason}``, and from JAX's own monitoring events
  ``compile_stage_seconds_total{program, stage}``,
  ``compile_cache_persistent_total{program, outcome}``,
  ``compile_trace_fn_seconds_total{program, fn, outer}`` (tracing by the
  function traced), ``startup_seconds_total{stage}`` (the constructors and
  each program's first call beyond its stages) and ``compile.<program>`` /
  ``startup.<stage>`` spans.
- ``roofline``  — the bytes-per-step model as LIVE gauges
  (``decode_step_bytes`` / ``achieved_hbm_gbps`` /
  ``achieved_over_achievable`` per program/replica).
- ``slo``       — SLO targets + multi-window burn rates
  (``slo_burn_rate{slo,window}``) and alert events; rendered by the
  ``slo-report`` CLI subcommand, consumed by the fleet router.

Instrumentation is always-on (host-side integer arithmetic, zero device
cost); the EXPORTERS are opt-in via ``--telemetry-dir``. The pre-existing
stats dataclasses remain the phase-metadata serialization format — they now
``publish()`` into the registry, so both views agree by construction.

See docs/OBSERVABILITY.md.
"""

from __future__ import annotations

from typing import Optional

from fairness_llm_tpu.telemetry.registry import (
    Counter,
    DEFAULT_COUNT_BOUNDS,
    DEFAULT_LATENCY_BOUNDS,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
    use_registry,
)
from fairness_llm_tpu.telemetry.export import (
    JsonlSink,
    load_snapshot,
    read_events,
    render_report,
    snapshot,
    to_prometheus,
    validate_snapshot,
    write_snapshot,
)
from fairness_llm_tpu.telemetry.timeline import (
    TRACE_FILENAME,
    Timeline,
    attribution_on,
    get_timeline,
    set_attribution,
    set_timeline,
    summarize_chrome_trace,
    use_timeline,
    validate_chrome_trace,
)
from fairness_llm_tpu.telemetry.compilestats import record_compile
from fairness_llm_tpu.telemetry.costmodel import (
    COMPONENT_TITLES,
    COMPONENTS,
    CostLedger,
    classify,
    classify_eqn,
    gap_decomposition,
    has_cost_data,
    instrument_jit,
    jaxpr_ledger,
    note_invocation,
    render_cost_report,
    set_achievable_gflops,
    set_dispatch_s,
)
from fairness_llm_tpu.telemetry.fairness import (
    FairnessMonitor,
    get_fairness_monitor,
    group_exposure,
    publish_offline_reference,
    render_fairness_report,
    set_fairness_monitor,
    use_fairness_monitor,
)
from fairness_llm_tpu.telemetry.roofline import (
    DEVICE_PEAKS,
    decode_step_bytes,
    device_peaks,
    observe_decode,
    set_achievable_gbps,
)
from fairness_llm_tpu.telemetry.slo import (
    SLOEvaluator,
    SLOTargets,
    get_slo_targets,
    render_slo_report,
    set_slo_targets,
)
from fairness_llm_tpu.telemetry.tracing import (
    RequestTracer,
    SpanEvent,
    TraceSummaryRow,
    assert_span_order,
)
from fairness_llm_tpu.telemetry.heartbeat import Heartbeat
from fairness_llm_tpu.telemetry.flightrecorder import (
    FlightRecorder,
    get_flight_recorder,
    recording_on,
    set_flight_recorder,
    set_recording,
    use_flight_recorder,
)
from fairness_llm_tpu.telemetry.incidents import (
    DecisionRecord,
    IncidentManager,
    arm_incidents,
    causal_chain,
    get_incident_manager,
    list_bundles,
    maybe_trigger,
    record_decision,
    render_incident_report,
    set_incident_manager,
    use_incident_manager,
    validate_incidents,
)
from fairness_llm_tpu.telemetry.memory import (
    MemoryLedger,
    POOLS,
    aot_memory_capture_on,
    get_memory_ledger,
    has_memory_data,
    render_memory_report,
    set_aot_memory_capture,
    set_memory_ledger,
    set_memory_obs,
    tree_device_bytes,
    use_memory_ledger,
)

# -- process-wide event sink --------------------------------------------------
# One sink per process, installed by the CLI when --telemetry-dir is set
# (and by tests directly). emit_event is a no-op without one, so span
# recording costs nothing in un-exported runs.

_event_sink: Optional[JsonlSink] = None


def install_event_sink(sink: Optional[JsonlSink]) -> Optional[JsonlSink]:
    """Install (or, with None, remove) the process event sink; returns the
    previous one so callers can restore it."""
    global _event_sink
    prev, _event_sink = _event_sink, sink
    return prev


def event_sink() -> Optional[JsonlSink]:
    return _event_sink


def emit_event(kind: str, **fields) -> None:
    if _event_sink is not None:
        _event_sink.emit(kind, **fields)


def configure(telemetry_dir: str,
              events_max_bytes: Optional[int] = None) -> JsonlSink:
    """Stand up the exporters for a run: mkdir the telemetry dir and install
    the JSONL event sink there, size-rotated (``events.jsonl.1..N`` kept;
    see export.py — a million-user replay must not grow one file forever).
    Snapshot writing stays explicit (``write_snapshot`` at end of run) — a
    snapshot mid-run is valid too, it just reflects less."""
    import os

    from fairness_llm_tpu.telemetry.export import (
        EVENTS_FILENAME,
        EVENTS_MAX_BYTES,
    )

    os.makedirs(telemetry_dir, exist_ok=True)
    sink = JsonlSink(
        os.path.join(telemetry_dir, EVENTS_FILENAME),
        max_bytes=(events_max_bytes if events_max_bytes is not None
                   else EVENTS_MAX_BYTES),
    )
    install_event_sink(sink)
    # Exported runs also arm the per-program AOT memory capture (memory.py):
    # it costs one extra XLA compile per program, which a run that stands up
    # the exporters has signed up for — bare library/test use stays free.
    set_aot_memory_capture(True)
    return sink


__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "DEFAULT_COUNT_BOUNDS",
    "DEFAULT_LATENCY_BOUNDS",
    "get_registry",
    "set_registry",
    "use_registry",
    "RequestTracer",
    "SpanEvent",
    "TraceSummaryRow",
    "assert_span_order",
    "JsonlSink",
    "Heartbeat",
    "snapshot",
    "write_snapshot",
    "load_snapshot",
    "validate_snapshot",
    "to_prometheus",
    "render_report",
    "read_events",
    "install_event_sink",
    "event_sink",
    "emit_event",
    "configure",
    "TRACE_FILENAME",
    "Timeline",
    "get_timeline",
    "set_timeline",
    "use_timeline",
    "attribution_on",
    "set_attribution",
    "validate_chrome_trace",
    "summarize_chrome_trace",
    "record_compile",
    "COMPONENTS",
    "COMPONENT_TITLES",
    "CostLedger",
    "classify",
    "classify_eqn",
    "gap_decomposition",
    "has_cost_data",
    "instrument_jit",
    "jaxpr_ledger",
    "note_invocation",
    "render_cost_report",
    "set_achievable_gflops",
    "set_dispatch_s",
    "FairnessMonitor",
    "get_fairness_monitor",
    "set_fairness_monitor",
    "use_fairness_monitor",
    "group_exposure",
    "publish_offline_reference",
    "render_fairness_report",
    "decode_step_bytes",
    "observe_decode",
    "DEVICE_PEAKS",
    "device_peaks",
    "set_achievable_gbps",
    "SLOEvaluator",
    "SLOTargets",
    "get_slo_targets",
    "set_slo_targets",
    "render_slo_report",
    "FlightRecorder",
    "get_flight_recorder",
    "set_flight_recorder",
    "use_flight_recorder",
    "recording_on",
    "set_recording",
    "DecisionRecord",
    "IncidentManager",
    "arm_incidents",
    "causal_chain",
    "get_incident_manager",
    "set_incident_manager",
    "use_incident_manager",
    "list_bundles",
    "maybe_trigger",
    "record_decision",
    "render_incident_report",
    "validate_incidents",
    "MemoryLedger",
    "POOLS",
    "get_memory_ledger",
    "set_memory_ledger",
    "use_memory_ledger",
    "set_memory_obs",
    "set_aot_memory_capture",
    "aot_memory_capture_on",
    "tree_device_bytes",
    "has_memory_data",
    "render_memory_report",
]
