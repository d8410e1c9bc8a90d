"""Compile observability: counters/histograms/spans for XLA compilation.

The engine and the serving scheduler both keep hand-rolled compiled-program
caches keyed on shape tuples (``DecodeEngine._compiled``,
``ContinuousScheduler._compiled``), and the key space has been multiplying:
the numerics-guard flag doubled every key (PR 5), the degradation ladder
made ``decode_chunk`` mutable mid-run (PR 4), fleets build per-replica
schedulers with their own caches (PR 6). A recompile storm — the ladder
flapping between chunk sizes, a workload cycling prompt buckets — today
shows up only as mysteriously slow steps. These helpers make it first-class:

- ``compiles_total{program, reason}`` — one count per freshly-built
  compiled program (reason: ``shape`` = first use of a shape bucket,
  ``decode_chunk`` = the ladder resized the chunk mid-run);
- the STAGES of a compile, from JAX's own monitoring events (this module
  registers one duration listener and one event listener with
  ``jax.monitoring`` when it is imported), booked to the program whose
  call they fired in (``program_scope``, entered by
  ``costmodel.InstrumentedJit`` round every call of a step program):
  ``compile_stage_seconds_total{program, stage}`` with stage ``trace``
  (jaxpr tracing), ``lower`` (jaxpr to MLIR), ``backend_compile`` (XLA),
  ``cache_load`` (the persistent cache held the executable: reading and
  loading it) or ``ledger_trace`` (what the cost ledger's first-call walk
  takes beyond the tracing JAX reports inside it: JAX 0.9 keeps the jaxpr
  that ``make_jaxpr`` traced, so the ``jit`` call that follows does not
  trace again, and that tracing stands under ``trace``);
  ``compile_cache_persistent_total{program, outcome}`` with outcome ``hit``
  or ``miss``; ``compile_seconds{program}``, one observation per call in
  which a stage fired: the sum of its stages. A stage that fires outside
  every scope is booked to program ``unscoped``;
- a ``compile.<program>`` span on the timeline with one child per stage
  (``compile.trace``, ``compile.lower``, ``compile.backend`` or
  ``compile.cache_load``, ``compile.ledger_trace``) whenever a stage fires
  inside a call, at whatever point of the run; under a profiler session
  ``compile.<program>`` is also a host event in the device trace, from the
  end of the first stage to the end of the call (JAX reports a stage when
  it ends).

An inner ``jit`` traced while an outer one is traced reports its own
``trace`` duration inside the outer's: a stage's booked seconds are the
top-level intervals only.

Gated, like the whole attribution layer, on ``timeline.attribution_on()``.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax.monitoring
from jax.profiler import TraceAnnotation

from fairness_llm_tpu.telemetry.registry import get_registry
from fairness_llm_tpu.telemetry.timeline import attribution_on, get_timeline

_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_OUTCOME_OF_EVENT = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_SPAN_OF_STAGE = {"backend_compile": "compile.backend"}
UNSCOPED = "unscoped"
_KEPT = 32  # booked intervals an unscoped thread remembers, to net nesting
# A step program's lowering re-reports dozens of cached inner traces of a few
# microseconds each: booked like any other, but not worth a span apiece.
_SPAN_FLOOR_S = 1e-3


def record_compile(program: str, reason: str, seconds: float,
                   key=None, labels: Optional[Dict[str, str]] = None) -> None:
    """Count one freshly built program and say why it was built, as a
    counter and a JSONL event. ``seconds`` is the compiling call's wall, for
    the event only: the stages' own seconds are booked by the listeners
    below. ``key`` is the compile key, for diagnostics."""
    if not attribution_on():
        return
    lbl = labels or {}
    get_registry().counter("compiles_total", component="compile",
                           program=program, reason=reason, **lbl).inc()
    from fairness_llm_tpu.telemetry import emit_event  # lazy: no cycle

    emit_event("compile", program=program, reason=reason,
               seconds=round(float(seconds), 4), key=repr(key), **lbl)


# -- stages, from JAX's monitoring events ---------------------------------------


class _Scope:
    """The stages that fired inside one call of one program."""

    __slots__ = ("program", "booked", "hit", "ann")

    def __init__(self, program: str):
        self.program = program
        # stage, t0, t1, and the seconds of [t0, t1) that are this entry's
        self.booked: List[Tuple[str, float, float, float]] = []
        self.hit = False  # a persistent-cache hit awaits its load's duration
        self.ann = None

    def book(self, stage: str, seconds: float, around: bool = False,
             **args) -> None:
        """``seconds`` of ``stage`` ended now. What was booked to the same
        stage inside that interval (an inner jit's trace) is part of it and
        is dropped; with ``around``, what ANY stage booked inside it stays
        and only the rest of the interval is this stage's."""
        t1 = time.monotonic()
        t0 = t1 - max(float(seconds), 0.0)
        inside = [b for b in self.booked
                  if b[1] >= t0 and (around or b[0] == stage)]
        within = sum(b[3] for b in inside)
        net = max((t1 - t0) - within, 0.0)
        if around:
            self.booked.append((stage, t0, t1, net))
        else:  # this entry stands for what it absorbed, too
            self.booked = [b for b in self.booked if b not in inside]
            self.booked.append((stage, t0, t1, net + within))
        get_registry().gauge(
            "compile_stage_seconds_total", component="compile",
            program=self.program, stage=stage).add(net)
        if self.program == UNSCOPED:
            del self.booked[:-_KEPT]
            get_timeline().record_span(
                "compile." + UNSCOPED, "compile", "host", t0, t1 - t0,
                stage=stage, **args)
        elif self.ann is None:
            self.ann = TraceAnnotation("compile." + self.program)
            self.ann.__enter__()

    def __enter__(self) -> "_Scope":
        _local.stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _local.stack.pop()
        if not self.booked:
            return
        if self.ann is not None:
            self.ann.__exit__(*exc)
        tl = get_timeline()
        outer = tl.open_span()
        track = outer.track if outer is not None else "host"
        name = "compile." + self.program
        start = min(b[1] for b in self.booked)
        total = sum(b[3] for b in self.booked)
        get_registry().histogram("compile_seconds", component="compile",
                                 program=self.program).observe(total)
        tl.record_span(name, "compile", track, start,
                       time.monotonic() - start,
                       parent=outer.name if outer is not None else None,
                       stage_seconds=round(total, 6))
        for stage, t0, t1, _ in self.booked:
            if t1 - t0 >= _SPAN_FLOOR_S:
                tl.record_span(_SPAN_OF_STAGE.get(stage, "compile." + stage),
                               "compile", track, t0, t1 - t0, parent=name)


_NO_SCOPE = contextlib.nullcontext()


class _Local(threading.local):
    def __init__(self):
        self.stack: List[_Scope] = []
        self.unscoped = _Scope(UNSCOPED)


_local = _Local()


def program_scope(program: str):
    """``with program_scope("serve_step"): fn(*args)``: every compile stage
    JAX reports inside the block is booked to ``program``. Costs one object
    per call; off, one test."""
    if not attribution_on():
        return _NO_SCOPE
    return _Scope(program)


def _current() -> _Scope:
    return _local.stack[-1] if _local.stack else _local.unscoped


def book_stage(stage: str, seconds: float) -> None:
    """Book to ``stage`` of the current scope's program what is left of the
    ``seconds`` that ended now once JAX's own reports inside them are taken
    out: for a stage JAX does not report (``ledger_trace``)."""
    if attribution_on():
        _current().book(stage, seconds, around=True)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    stage = _STAGE_OF_EVENT.get(event)
    if stage is None or not attribution_on():
        return
    scope = _current()
    if stage == "backend_compile" and scope.hit:
        stage, scope.hit = "cache_load", False
    scope.book(stage, duration, fun_name=str(kwargs.get("fun_name", "")))


def _on_event(event: str, **kwargs) -> None:
    outcome = _OUTCOME_OF_EVENT.get(event)
    if outcome is None or not attribution_on():
        return
    scope = _current()
    scope.hit = outcome == "hit"
    get_registry().counter(
        "compile_cache_persistent_total", component="compile",
        program=scope.program, outcome=outcome).inc()


# One pair of listeners per process, for its whole life: JAX has no scoped
# registration, and a compile can happen at any point of a run.
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
