"""A kernel's share of its roofline: the least time the chip could take for
every one of its calls (from the call's shapes), over the device time the
calls took. ``cost`` names the shape-to-work rule."""

from perfbench.lib import flops


def _int8_matmul(result, operands, cfg):
    x = next(o for o in operands if o[0] in ("bf16", "f16", "f32")
             and len(o[1]) == 2)
    w = next(o for o in operands if o[0] == "s8")
    (m, k), (_, n) = x[1], w[1]
    return flops.int8_matmul_cost(m, k, n, out_dtype=result[0])


def _causal_attention(result, operands, cfg):
    four = [o for o in operands if len(o[1]) == 4]
    (b, h, s, d), (_, h_kv, _, _) = four[0][1], four[1][1]
    return flops.causal_attention_cost(b, h, h_kv, s, d,
                                       window=cfg.get("window"))


_COSTS = {"int8_matmul": _int8_matmul, "causal_attention": _causal_attention}


def read(ctx, kernel: str, cost: str):
    if ctx.trace is None:
        return None
    least = took = 0.0
    for result, operands, seconds, runs in ctx.trace.kernel_events(kernel):
        f, b = _COSTS[cost](result, operands, ctx.cfg)
        least += runs * flops.min_time_s(f, b, ctx.peaks)
        took += seconds
    if not took:
        return None
    share = 100.0 * least / took
    if share > 100.0:
        raise ValueError(f"{kernel} reads {share:.1f}% of its roofline: its "
                         "operations or bytes are counted too high")
    return share
