"""``x[m,k] @ dequant(w[k,n] int8, scale[n])``: the shapes from the call's own
operands."""

from perfbench.lib import flops


def cost(result, operands, cfg):
    x = next(o for o in operands if o[0] in ("bf16", "f16", "f32")
             and len(o[1]) == 2)
    w = next(o for o in operands if o[0] == "s8")
    (m, k), (_, n) = x[1], w[1]
    return flops.int8_matmul_cost(m, k, n, out_dtype=result[0])
