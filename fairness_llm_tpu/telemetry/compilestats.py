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
  or ``miss``. A stage that fires outside every scope is booked to program
  ``unscoped``;
- the same tracing by the function traced:
  ``compile_trace_fn_seconds_total{program, fn, outer}``, each function's
  self time (its interval less those of the functions traced inside it:
  JAX reports every ``jit``, ``jnp`` wrappers and inner jits included).
  ``outer="1"`` is the program's own outermost function, wherever it is
  traced, and takes the ``ledger_trace`` seconds too: per program the sum
  over ``fn`` is its ``trace`` + ``ledger_trace``;
- the rest of set-up: ``startup_seconds_total{stage}``, stage ``engine`` and
  ``scheduler`` (``startup_stage``, round the constructors) and
  ``first_call`` (with ``program``: what a program's first call takes beyond
  its compile stages, the MLIR to XLA conversion, the persistent cache's
  key, argument handling and the dispatch);
- a ``compile.<program>`` span on the timeline with one child per stage
  (``compile.trace``, ``compile.lower``, ``compile.backend`` or
  ``compile.cache_load``, ``compile.ledger_trace``, and on a first call a
  ``compile.first_call`` for each stretch of the call no stage covers)
  whenever a stage fires inside a call, at whatever point of the run; under
  a profiler session ``compile.<program>`` is also a host event in the
  device trace, from the end of the first stage to the end of the call (JAX
  reports a stage when it ends). ``startup.<stage>`` spans go through
  ``Timeline.span``.

Every second is booked once, to the innermost stage it lies in: a stage's
seconds are its interval less the intervals booked inside it (an inner
``jit``'s trace inside its outer's, the tracing inside the ledger's walk,
the compile stages inside a constructor or a first call). JAX reports a
stage when it ends, so what was booked inside an interval is the tail of
the intervals booked so far: each booking looks at that tail only.

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
_TRACING = ("trace", "ledger_trace")  # the stages booked by function too
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

    __slots__ = ("program", "fn", "first", "booked", "sums", "hit", "ann",
                 "t_enter", "base")

    def __init__(self, program: str, fn: str = "", first: bool = False):
        self.program = program
        self.fn = fn  # the program's outermost function, as JAX names it
        self.first = first  # the first call of its program: book the rest
        # stage, t0, t1, and the seconds of [t0, t1) booked in all: the
        # outermost intervals booked so far, in the order they ended
        self.booked: List[Tuple[str, float, float, float]] = []
        # (stage, fn) -> seconds, published when the call returns: a
        # program's tracing reports thousands of functions, and what a
        # booking costs reads as tracing
        self.sums: Dict[Tuple[str, str], float] = {}
        self.hit = False  # a persistent-cache hit awaits its load's duration
        self.ann = None

    def book(self, stage: str, seconds: float, fn: str = "") -> None:
        """``seconds`` of ``stage`` ended now: the interval less what was
        booked inside it (the tail of ``booked`` that starts in it, which
        it then stands for) is this stage's. A ``trace`` is booked to its
        function ``fn`` too, ``ledger_trace`` to the outermost one."""
        t1 = time.monotonic()
        t0 = t1 - max(float(seconds), 0.0)
        booked = self.booked
        i = len(booked)
        while i and booked[i - 1][1] >= t0:
            i -= 1
        within = sum(b[3] for b in booked[i:])
        net = max((t1 - t0) - within, 0.0)
        del booked[i:]
        booked.append((stage, t0, t1, net + within))
        _local.booked_s += net
        if self.program == UNSCOPED:
            _publish(UNSCOPED, "", stage, fn, net)
            del booked[:-_KEPT]
            get_timeline().record_span(
                "compile." + UNSCOPED, "compile", "host", t0, t1 - t0,
                stage=stage, fun_name=fn)
            return
        if stage == "ledger_trace":
            fn = self.fn
        key = (stage, fn if stage in _TRACING else "")
        self.sums[key] = self.sums.get(key, 0.0) + net
        if self.ann is None:
            self.ann = TraceAnnotation("compile." + self.program)
            self.ann.__enter__()

    def __enter__(self) -> "_Scope":
        _local.stack.append(self)
        if self.first:
            self.t_enter, self.base = time.monotonic(), _local.booked_s
        return self

    def __exit__(self, *exc) -> None:
        _local.stack.pop()
        if not self.booked and not self.first:
            return
        if self.ann is not None:
            self.ann.__exit__(*exc)
        now = time.monotonic()
        for (stage, fn), seconds in self.sums.items():
            _publish(self.program, self.fn, stage, fn, seconds)
        tl = get_timeline()
        outer = tl.open_span()
        track = outer.track if outer is not None else "host"
        name = "compile." + self.program
        args = {"stage_seconds": round(sum(b[3] for b in self.booked), 6)}
        if self.first:
            start = self.t_enter
            rest = max((now - start) - (_local.booked_s - self.base), 0.0)
            _local.booked_s += rest
            get_registry().gauge(
                "startup_seconds_total", component="startup",
                stage="first_call", program=self.program).add(rest)
            args["first_call_s"] = round(rest, 6)
        else:
            start = self.booked[0][1]
        tl.record_span(name, "compile", track, start, now - start,
                       parent=outer.name if outer is not None else None,
                       **args)
        edge = start
        for stage, t0, t1, _ in self.booked + [("", now, now, 0.0)]:
            if self.first and t0 - edge >= _SPAN_FLOOR_S:
                tl.record_span("compile.first_call", "compile", track, edge,
                               t0 - edge, parent=name)
            if stage and t1 - t0 >= _SPAN_FLOOR_S:
                tl.record_span(_SPAN_OF_STAGE.get(stage, "compile." + stage),
                               "compile", track, t0, t1 - t0, parent=name)
            edge = t1


def _publish(program: str, outer: str, stage: str, fn: str,
             seconds: float) -> None:
    reg = get_registry()
    reg.gauge("compile_stage_seconds_total", component="compile",
              program=program, stage=stage).add(seconds)
    if stage in _TRACING:
        reg.gauge("compile_trace_fn_seconds_total", component="compile",
                  program=program, fn=fn,
                  outer="1" if fn and fn == outer else "0").add(seconds)


_NO_SCOPE = contextlib.nullcontext()


class _Local(threading.local):
    def __init__(self):
        self.stack: List[_Scope] = []
        self.unscoped = _Scope(UNSCOPED)
        self.booked_s = 0.0  # every second this thread booked, to net nesting


_local = _Local()


def program_scope(program: str, fn: str = "", first: bool = False):
    """``with program_scope("serve_step"): fn(*args)``: every compile stage
    JAX reports inside the block is booked to ``program``. ``fn`` is the
    name JAX traces the program's own function under (``outer="1"``);
    ``first`` books what the block takes beyond its stages to
    ``startup_seconds_total{stage="first_call", program}``. Costs one
    object per call; off, one test."""
    if not attribution_on():
        return _NO_SCOPE
    return _Scope(program, fn, first)


@contextlib.contextmanager
def startup_stage(stage: str):
    """A ``startup.<stage>`` span round a block of set-up (as a decorator,
    round a constructor), booked to ``startup_seconds_total{stage}`` less
    the compile stages booked inside it, which stay their programs' (those
    outside every step program's call are ``unscoped``). Off, one test."""
    if not attribution_on():
        yield
        return
    with get_timeline().span("startup." + stage, "startup", "host"):
        t0, base = time.monotonic(), _local.booked_s
        try:
            yield
        finally:
            net = max((time.monotonic() - t0) - (_local.booked_s - base), 0.0)
            _local.booked_s += net
            get_registry().gauge("startup_seconds_total", component="startup",
                                 stage=stage).add(net)


def _current() -> _Scope:
    return _local.stack[-1] if _local.stack else _local.unscoped


def book_stage(stage: str, seconds: float) -> None:
    """Book to ``stage`` of the current scope's program what is left of the
    ``seconds`` that ended now once what was booked inside them is taken
    out: for a stage JAX does not report (``ledger_trace``)."""
    if attribution_on():
        _current().book(stage, seconds)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    stage = _STAGE_OF_EVENT.get(event)
    if stage is None or not attribution_on():
        return
    scope = _current()
    if stage == "backend_compile" and scope.hit:
        stage, scope.hit = "cache_load", False
    scope.book(stage, duration, str(kwargs.get("fun_name", "")))


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
