"""The chip's published peaks, keyed by ``device_kind``. The one table every
roofline or peak share in this benchmark divides by. A device that is not
listed is an error, never a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/lib/peaks.py; "
            "add its published peaks with their source") from None
