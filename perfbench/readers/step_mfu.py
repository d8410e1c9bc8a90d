"""The whole step's share of the chip's peak: model FLOPs of every prefill
and decode call in the traced stretch (real rows and tokens only) over the
traced seconds times the peak. Nothing excluded from the time."""

from perfbench.lib import flops


def read(ctx):
    t = ctx.window.traced
    if ctx.trace is None or not t or not t.get("seconds"):
        return None
    if not (t["prompt_tokens"] or t["decoded"]):
        return None
    work = flops.model_flops(
        ctx.cfg, prefill_tokens=t["prompt_tokens"], prefill_rows=t["rows"],
        decode_tokens=t["decoded"],
        prefill_context=t["prefill_context_sum"] / max(t["prompt_tokens"], 1),
        decode_context=t["decode_context_sum"] / max(t["decoded"], 1))
    share = 100.0 * work / (t["seconds"] * ctx.peaks["flops_per_s"]
                            * ctx.chips)
    if share > 100.0:
        raise ValueError(f"step mfu {share:.1f}% is over 100%: the FLOPs are "
                         "counted too high or the time leaves work out")
    return share
