"""A kernel's share of its roofline: the least time the chip could take for
every one of its calls (from the call's shapes), over the device time the
calls took. ``cost`` names the shape-to-work rule: ``perfbench/costs/<cost>.py``
with one ``cost(result, operands, cfg)`` that returns (FLOPs, bytes)."""

import importlib

from perfbench.lib import flops


def read(ctx, kernel: str, cost: str):
    if ctx.trace is None:
        return None
    rule = importlib.import_module("perfbench.costs." + cost).cost
    least = took = 0.0
    for result, operands, seconds, runs in ctx.trace.kernel_events(kernel):
        f, b = rule(result, operands, ctx.cfg)
        least += runs * flops.min_time_s(f, b, ctx.peaks)
        took += seconds
    if not took:
        return None
    share = 100.0 * least / took
    if share > 100.0:
        raise ValueError(f"{kernel} reads {share:.1f}% of its roofline: its "
                         "operations or bytes are counted too high")
    return share
