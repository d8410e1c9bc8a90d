"""The grouped product over the held experts' stacked int8 kernels
(``wq[e, k, n]``, ``scale[e, 1, n]``): what one call cannot do without.

Bytes: every held expert's int8 kernel and float32 scales once. That is the
least only where no held expert is idle in the call, which the cell's sizes
make so (192 rows, top 8 of 192: an expert is idle in a step with probability
(23/24)^192 = 0.03%; a prefill call has more rows). FLOPs and the bytes of the
rows are left at 0: the operands' shapes give the capacity of the sorted rows,
not how many of them are real, and a count the shapes cannot prove could read
over 100%. Bytes bind every call of the cell anyway: a prefill call of at most
2048 tokens has about 1024 local pairs, 0.45 ms of FLOPs at the chip's peak
against 0.65 ms of weight bytes."""


def cost(result, operands, cfg):
    e, k, n = next(o for o in operands if o[0] == "s8" and len(o[1]) == 3)[1]
    return 0, e * k * n + e * n * 4
