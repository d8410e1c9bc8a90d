"""The whole step's share of the chip's peak: model FLOPs of every prefill
and decode call in the traced stretch (real rows and tokens only, counted by
the configuration's family) over the traced seconds times the peak. Nothing
excluded from the time."""

from perfbench import families


def read(ctx):
    t = ctx.window.traced
    if ctx.trace is None or not t or not t.get("seconds"):
        return None
    if not (t["prompt_tokens"] or t["decoded"]):
        return None
    work = families.of(ctx.cfg).model_flops(ctx.cfg, **t)
    share = 100.0 * work / (t["seconds"] * ctx.peaks["flops_per_s"]
                            * ctx.chips)
    if share > 100.0:
        raise ValueError(f"step mfu {share:.1f}% is over 100%: the FLOPs are "
                         "counted too high or the time leaves work out")
    return share
