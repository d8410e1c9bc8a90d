"""Operations and bytes the algorithm needs, from shapes alone. Kept with the
benchmark so that no later change to the program can move a roofline share by
recounting its own work. A whole model's FLOPs are its family's
(``perfbench/families``: ``model_flops``); a kernel's shape-to-work rule is a
file under ``perfbench/costs``.

Conventions: a multiply-add is 2 FLOPs; int8 weights are read once per call at
1 byte each; bf16 activations are read and written once at 2 bytes each;
whatever implements the call, these are the least the chip must do.
"""

from __future__ import annotations

_ITEMSIZE = {"s8": 1, "u8": 1, "bf16": 2, "f16": 2, "f32": 4, "s32": 4,
             "u32": 4, "pred": 1}


def itemsize(dtype: str) -> int:
    return _ITEMSIZE[dtype]


def int8_matmul_cost(m: int, k: int, n: int, out_dtype: str = "bf16"):
    """``x[m,k] (bf16) @ dequant(w[k,n] int8, scale[n] f32) -> y[m,n]``."""
    flops = 2 * m * k * n
    nbytes = k * n * 1 + m * k * 2 + m * n * itemsize(out_dtype) + n * 4
    return flops, nbytes


def causal_attention_cost(b: int, h: int, h_kv: int, s: int, d: int,
                          window=None, itemsize_: int = 2):
    """Causal self-attention over ``s`` positions: per query head, QK^T and
    PV over the visible keys only (the lower triangle, cut to the window
    where it binds). q and the output are read/written once, k and v once
    per KV head."""
    if window is None or window >= s:
        visible = s * (s + 1) // 2
    else:
        visible = window * (window + 1) // 2 + (s - window) * window
    flops = 4 * b * h * visible * d
    nbytes = itemsize_ * b * s * d * (2 * h + 2 * h_kv)
    return flops, nbytes


def min_time_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
