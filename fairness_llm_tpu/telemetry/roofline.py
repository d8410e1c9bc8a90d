"""Live roofline accounting: the bytes-per-step model as registry gauges.

The decode step is memory-bound: its floor is (HBM bytes the step must
stream) / (bandwidth the chip can actually sustain). That model existed
only offline — ``bench.py`` computed ``decode_step_bytes`` per bench round
and ``tools/account_decode_step.py`` classified a captured device trace —
so the bench's headline fraction (``achieved_over_achievable``) could not
be watched during a run, per replica, per program. This
module folds the same byte model into live gauges fed per decode chunk:

- ``decode_step_bytes{program, ...}`` — HBM bytes one step of this compiled
  program streams (params at compute width + the pool's KV slots + shared
  prefix KV), the model ``bench.decode_step_bytes`` now imports from here;
- ``achieved_hbm_gbps{program, ...}`` — bytes * steps / wall for the last
  chunk, plus an ``achieved_hbm_gbps_dist`` histogram of the same;
- ``achieved_over_achievable{program, ...}`` — the headline fraction
  against this device's reference streaming bandwidth (published only on a
  device that has one).

The reference bandwidth comes from ``DEVICE_PEAKS``, the one table of
published per-chip peaks keyed by ``device_kind``. A device that is not in
the table has no reference: asking for one raises, and the fraction gauge is
simply not published there (a CPU run never writes a device-rate fraction).
Override with ``set_achievable_gbps`` (``TelemetryConfig.achievable_gbps``)
when a measured figure exists.

Gated, like the whole attribution layer, on ``timeline.attribution_on()``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax

from fairness_llm_tpu.telemetry.registry import get_registry
from fairness_llm_tpu.telemetry.timeline import attribution_on


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    hbm_gbps: float  # HBM streaming bandwidth, GB/s
    bf16_gflops: float  # dense bf16 matmul peak, GFLOP/s


# Published peaks of ONE chip, keyed by ``jax.devices()[0].device_kind``.
# Every roofline/cost-model reference in the repo reads this table; a device
# that is not listed is an error where a peak is asked for, never a default.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip,
    # 16 GB HBM2e at 819 GB/s.
    "TPU v5 lite": DevicePeaks(hbm_gbps=819.0, bf16_gflops=197_000.0),
}

_achievable_override: Optional[float] = None


def known_device_peaks() -> Optional[DevicePeaks]:
    """The running device's row of ``DEVICE_PEAKS``, or None when it has
    none — the publishers' question ("is there a peak to divide by?")."""
    return DEVICE_PEAKS.get(jax.devices()[0].device_kind)


def device_peaks(device_kind: Optional[str] = None) -> DevicePeaks:
    """Published peaks of ``device_kind`` (default: the running device).
    Raises ``LookupError`` for a device that is not in the table."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to telemetry/roofline.py "
            "DEVICE_PEAKS with its source, or install a measured reference "
            "(set_achievable_gbps / --achievable-gbps)"
        ) from None


def set_achievable_gbps(gbps: Optional[float]) -> None:
    """Install a measured achievable-bandwidth reference (None restores the
    device's published peak). ``TelemetryConfig.achievable_gbps`` routes
    here."""
    global _achievable_override
    _achievable_override = float(gbps) if gbps else None


def publishable_gbps() -> Optional[float]:
    """The denominator of ``achieved_over_achievable``: the override when
    installed, else the running device's published HBM bandwidth, else None
    — an unlisted device publishes nothing that needs a peak."""
    if _achievable_override is not None:
        return _achievable_override
    peaks = known_device_peaks()
    return peaks.hbm_gbps if peaks is not None else None


def decode_step_bytes(config, stats) -> int:
    """HBM bytes one decode step must stream (the decode-time roofline
    model; moved here from bench.py so serving can evaluate it live).

    Per step: every parameter once (matmuls touch all weights), each row's
    KV cache (its remainder-prompt + generated slots), and the shared
    prefix KV once per step (read once for the whole batch — the
    prefix-cache win). ``stats`` carries ``batch`` / ``cache_slots`` /
    ``prefix_len`` (the ``GenerateOutput.stats`` shape), and ``ring`` where
    the cache is the slot pool's.

    Param width: the COMPUTE dtype, not the storage dtype — the round-3
    device trace shows XLA hoists the f32->bf16 cast of a bf16-config
    model's f32-stored tree out of the decode loop, so each step streams
    2 bytes/param even when storage is f32. Using the storage width
    overstated step bytes ~25% and inflated achieved_hbm_gbps accordingly.
    """
    model_item = 2 if config.dtype == "bfloat16" else 4
    if config.weight_quant == "int8":
        # Matmul kernels stream int8 (dequant-in-tile, ops/quant_matmul.py);
        # embeddings/norms stay float. quantized = approx - embed whether or
        # not embeddings are tied (the untied lm_head is itself quantized).
        embed = config.vocab_size * config.d_model
        params = (config.approx_param_count - embed) * 1 + embed * model_item
    else:
        params = config.approx_param_count * model_item
    # What a layer keeps for one token is the configuration's own count
    # (k and v per KV head, or one latent): never 2 x n_kv x head_dim here.
    # A layer that keeps a state per row instead reads and writes it whole.
    per_token = sum(config.layer_cache_values)
    # ``stats["ring"]``: the slot pool's form, in which a window layer keeps
    # a ring shorter than ``cache_slots`` (``config.retained_len``; an int8
    # cache keeps every position: ``models/transformer.init_cache``).
    lengths = config.retained_len(stats["cache_slots"]) \
        if stats.get("ring") and not config.kv_cache_quant \
        else (stats["cache_slots"],) * config.num_layers
    row_values = sum(n * v for n, v in zip(lengths,
                                           config.layer_cache_values))
    if config.kv_cache_quant:
        # int8 values + the per-(slot, head) f32 scale the step also reads —
        # same accounting as parallel/sharding.per_device_kv_cache_bytes.
        row = row_values + stats["cache_slots"] * \
            2 * config.num_kv_heads * 4 * config.num_layers
    else:
        row = row_values * model_item
    kv = stats["batch"] * (row + 2 * config.row_state_bytes)
    # _prefix_fn dequantizes the shared prefix to the model dtype, so its
    # per-step read is model-dtype-wide even under kv_cache_quant.
    prefix = stats["prefix_len"] * per_token * model_item
    return params + kv + prefix


def observe_decode(config, stats: Dict, steps: int, wall_s: float,
                   program: str,
                   labels: Optional[Dict[str, str]] = None) -> Optional[Dict]:
    """Fold one decode invocation into the live roofline gauges. ``stats``
    as in ``decode_step_bytes``; ``steps`` the decode steps the call
    actually ran; ``wall_s`` its host wall. Returns the computed numbers
    (or None when gated off / nothing ran)."""
    if not attribution_on() or steps <= 0 or wall_s <= 0:
        return None
    lbl = labels or {}
    step_bytes = decode_step_bytes(config, stats)
    gbps = step_bytes * steps / wall_s / 1e9
    achievable = publishable_gbps()
    frac = gbps / achievable if achievable else None
    reg = get_registry()
    reg.gauge("decode_step_bytes", component="roofline", program=program,
              **lbl).set(step_bytes)
    reg.gauge("achieved_hbm_gbps", component="roofline", program=program,
              **lbl).set(gbps)
    reg.histogram("achieved_hbm_gbps_dist", component="roofline",
                  program=program, **lbl).observe(gbps)
    if frac is not None:
        reg.gauge("achieved_over_achievable", component="roofline",
                  program=program, **lbl).set(frac)
    # Flight-recorder roofline ring (telemetry/flightrecorder.py): the
    # last-K per-chunk samples — a bundle shows whether bandwidth was
    # degrading INTO the incident, which the last-write gauge cannot.
    from fairness_llm_tpu.telemetry.flightrecorder import (  # lazy: no cycle
        get_flight_recorder,
    )

    get_flight_recorder().record(
        "roofline", program=program, steps=steps,
        gbps=round(gbps, 3),
        fraction=(round(frac, 4) if frac is not None else None),
        replica=lbl.get("replica"),
    )
    return {"step_bytes": step_bytes, "gbps": gbps,
            "achievable_gbps": achievable, "fraction": frac}
