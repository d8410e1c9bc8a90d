"""Host time of the program's own spans in the traced stretch, on the
profiler's clock: the summed duration of the spans named ``spans`` over the
count of the spans named ``per``, in milliseconds. The spans are the
scheduler's (``Timeline.span`` enters a ``TraceAnnotation`` of the same
name), read from the trace's host plane beside the device's module runs.

A ``sched.*`` span outside every ``sched.iterate`` means the scheduler's
iteration no longer contains its pieces, and the quotient would mix
iterations with strays: that raises. A program without these spans (the
commit before they existed) reads as nothing."""

_ROOT = "sched.iterate"
_SLACK_NS = 1000  # the profiler stamps a child and its parent apart


def read(ctx, spans, per, scale: float = 1e-6):
    if ctx.trace is None:
        return None
    events = [e for e in ctx.trace.host_events if e[0].startswith("sched.")]
    roots = sorted((e[1], e[1] + e[2]) for e in events if e[0] == _ROOT)
    if not roots:
        return None
    for name, start, dur in events:
        if name != _ROOT and not any(
                a - _SLACK_NS <= start and start + dur <= b + _SLACK_NS
                for a, b in roots):
            raise ValueError(
                f"span {name} at {start} ns lies outside every {_ROOT}: "
                "the iteration no longer contains its pieces")
    count = sum(1 for e in events if e[0] in per)
    if not count:
        return None
    return scale * sum(e[2] for e in events if e[0] in spans) / count
