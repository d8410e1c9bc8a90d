"""A percentile, over the requests the window served, of the time between
two lifecycle instants of the program's ``RequestTracer`` (``submitted``,
``admitted``, ``prefill_start``, ``first_token``, ...), which it writes into
the process-wide timeline with the request's id. A request admitted twice
(requeued after a fault) counts from its last ``start`` before the ``end``.

The timeline is a ring: once it has dropped events the sample is no longer
the window's, and that raises."""

import numpy as np


def read(ctx, start: str, end: str, percentile: float, scale: float = 1e3):
    from fairness_llm_tpu.telemetry.timeline import get_timeline

    timeline = get_timeline()
    if timeline.dropped > 0:
        raise ValueError(
            f"the timeline dropped {timeline.dropped} events: the lifecycle "
            "instants of the window are no longer all there")
    wanted = {s.spec.id for s in ctx.window.served if s.ok}
    opened, samples = {}, []
    for ev in timeline.events():
        if ev["type"] != "instant" or ev.get("cat") != "lifecycle":
            continue
        rid = ev["args"].get("request_id")
        if rid not in wanted:
            continue
        if ev["name"] == start:
            opened[rid] = ev["t0"]
        elif ev["name"] == end and rid in opened:
            samples.append(ev["t0"] - opened.pop(rid))
    if not samples:
        return None
    return scale * float(np.percentile(samples, percentile))
