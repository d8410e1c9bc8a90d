"""Which implementation each kernel gate chose, where a run can read it.

The Pallas kernels sit behind static gates (``quant_matmul``'s tile gate,
``Attention._flash_ok``): off the TPU, or on a shape the kernel cannot tile,
the call takes an XLA expression instead. For the int8 matmul that XLA
expression is the one XLA hoists a bf16 copy of the weight tree for, so a
silent miss on the chip is a capacity bug, not a detail. Every gate decision
that depends on the device or the shape is counted here at trace time as
``kernel_path_total{kernel, path, reason, shape, blocks}`` and logged once per
distinct label set, so a run (``chip_smoke.py``) can fail on a miss instead
of measuring the wrong program.
"""

from __future__ import annotations

import logging
from typing import List, Tuple

import jax

from fairness_llm_tpu.telemetry.registry import get_registry

logger = logging.getLogger(__name__)

COUNTER = "kernel_path_total"


def note_kernel_path(kernel: str, path: str, shape: Tuple[int, ...],
                     reason: str = "", blocks: str = "") -> None:
    """Count one gate decision. ``path`` is ``"pallas"`` or ``"xla"``;
    ``reason`` says why a gate refused (``"not_tpu"``, ``"untileable"``,
    ``"mesh_axes"``); ``blocks`` what a kernel's block rule chose for the
    call (``int8_matmul``: ``rows:bm x bk x bn``), so a run says which arm of
    the rule each shape took."""
    c = get_registry().counter(
        COUNTER, component="ops", kernel=kernel, path=path, reason=reason,
        shape="x".join(str(d) for d in shape), blocks=blocks,
    )
    if c.value == 0 and path == "xla":
        level = logging.WARNING if jax.default_backend() == "tpu" \
            else logging.DEBUG
        logger.log(level, "%s %s takes the XLA path (%s)", kernel,
                   c.labels["shape"], reason)
    elif c.value == 0 and blocks:
        logger.info("%s %s takes blocks %s", kernel, c.labels["shape"], blocks)
    c.inc()


def xla_paths() -> List[dict]:
    """Label sets of every gate decision that took the XLA path so far."""
    return [dict(m.labels) for m in get_registry().instruments()
            if m.name == COUNTER and m.labels.get("path") == "xla"
            and m.value > 0]
