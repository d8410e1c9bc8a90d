"""Causal self-attention over the call's own ``[b, h, s, d]`` operands, cut to
the configuration's window where it binds."""

from perfbench.lib import flops


def cost(result, operands, cfg):
    four = [o for o in operands if len(o[1]) == 4]
    (b, h, s, d), (_, h_kv, _, _) = four[0][1], four[1][1]
    return flops.causal_attention_cost(b, h, h_kv, s, d,
                                       window=cfg.get("window"))
