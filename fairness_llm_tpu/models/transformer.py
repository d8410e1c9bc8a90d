"""Configurable decoder-only transformer (Flax linen), TPU-first.

Design notes (vs the reference, which has no local model at all — its "model layer"
is remote OpenAI calls, ``phase1_bias_detection.py:180-188``):

- One forward path for every family; ``ModelConfig`` flags choose RoPE vs learned
  positions, RMSNorm vs LayerNorm, gated vs plain MLP, sliding window, GQA ratio.
- Everything is static-shape and jit-friendly. Batched decode uses **left-padded**
  prompts so the KV write index is uniform across the batch (one
  ``dynamic_update_slice`` per layer per step — no per-row scatters).
- Weights carry flax *logical* partitioning axes ("embed", "q_heads", "kv_heads",
  "ff", "vocab"); ``parallel/sharding.py`` maps them onto the ("dp", "tp", "sp")
  device mesh, so TP=8 sharding is a rule change, not a model change.
- Matmuls run in the config dtype (bfloat16 on TPU -> MXU); softmax and norms
  accumulate in float32.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from fairness_llm_tpu.models.configs import ModelConfig


def _dtype_of(config: ModelConfig):
    return jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# KV cache (functional pytree, fixed max_len)
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class LayerCache:
    """k/v are either the model dtype, or int8 with per-(slot, head) float32
    scales when ``config.kv_cache_quant`` — halving the HBM bytes each decode
    step must stream (decode is KV-read-bound; see runtime/engine.py)."""

    k: jnp.ndarray  # [B, max_len, n_kv, head_dim]
    v: jnp.ndarray  # [B, max_len, n_kv, head_dim]
    k_scale: Optional[jnp.ndarray] = None  # [B, max_len, n_kv] float32
    v_scale: Optional[jnp.ndarray] = None


def _quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[B, S, H, D] -> (int8 values, [B, S, H] scales), symmetric per-vector."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _cache_write(buf: jnp.ndarray, upd: jnp.ndarray, index, write_offsets):
    """Write ``upd`` into ``buf`` along the slot axis (axis 1 of [B, L, ...]).

    ``write_offsets=None``: one uniform ``dynamic_update_slice`` at ``index``
    (the left-padded batch invariant — every row writes the same slots).
    ``write_offsets=[B]``: per-row slot offsets (speculative verify steps,
    where rows advance by their own accepted counts) — one scatter of the
    [S, ...] update windows. The start is clamped as ``dynamic_update_slice``
    clamps it, so a row's slots are in bounds, distinct and ascending. The
    scatter says so: without those promises the TPU compiler expands it into
    a loop over the rows (seven device ops per row, layer and step).
    """
    zero = jnp.zeros((), jnp.int32)
    if write_offsets is None:
        return jax.lax.dynamic_update_slice(
            buf, upd, (zero, index) + (zero,) * (buf.ndim - 2)
        )
    S = upd.shape[1]
    steps = jnp.arange(S, dtype=jnp.int32)

    def one(b, u, off):
        slots = jnp.clip(off, 0, b.shape[0] - S) + steps
        return b.at[slots].set(
            u, mode="promise_in_bounds", unique_indices=True,
            indices_are_sorted=True,
        )

    return jax.vmap(one)(buf, upd, write_offsets)


def _ring_view(rows: jnp.ndarray, last: jnp.ndarray, R: int) -> jnp.ndarray:
    """A row-long ``[B, L]`` array by slot, as a ring of ``R`` that has been
    written through slot ``last`` (``[B, 1]`` or a scalar) shows it: entry
    ``r`` is ``rows[:, last - (last - r) mod R]``, and zero where that slot
    is negative (the ring has not come round yet). The ring holds the ``R``
    slots up to ``last`` rotated by ``last + 1``, so this is two gathers of
    whole slices a row; one index an element costs 2 ms a step at 64 x 3072
    on the chip (``PERF.md`` section 6, PR 35)."""
    B, L = rows.shape
    nxt = jnp.broadcast_to(jnp.reshape(last, (-1,)) + 1, (B,))  # [B]

    def slices(operand, starts):
        return jax.lax.gather(
            operand, jnp.stack([jnp.arange(B, dtype=jnp.int32), starts],
                               axis=1),
            jax.lax.GatherDimensionNumbers(
                offset_dims=(1,), collapsed_slice_dims=(0,),
                start_index_map=(0, 1)),
            (1, R), mode="promise_in_bounds")

    # R zeros in front: slot ``last - R + 1`` sits at ``last + 1``
    front = jnp.concatenate([jnp.zeros((B, R), rows.dtype), rows], axis=1)
    newest = slices(front, nxt.astype(jnp.int32))  # slots last-R+1 .. last
    turn = jnp.mod(-nxt, R).astype(jnp.int32)  # rotate right by nxt mod R
    return slices(jnp.concatenate([newest, newest], axis=1), turn)


@flax.struct.dataclass
class LatentCache:
    """What a latent-attention (MLA) layer keeps per token: the normed
    compressed key/value latent and the one rotated key every head shares.
    ``kv_lora_rank + qk_rope_head_dim`` values a token, whatever the number
    of heads."""

    c_kv: jnp.ndarray  # [B, max_len, kv_lora_rank]
    k_rope: jnp.ndarray  # [B, max_len, qk_rope_head_dim]


@flax.struct.dataclass
class RowState:
    """What a delta-rule linear-attention layer keeps per ROW, whatever the
    row's length: the recurrence's state and the short convolution's last
    ``conv_width - 1`` inputs (the q, k and v projections side by side,
    before the convolution). No leaf has a position axis."""

    state: jnp.ndarray  # [B, *config.row_state_shape] float32
    conv: jnp.ndarray  # [B, conv_width - 1, config.conv_channels], the model dtype


@flax.struct.dataclass
class KVCache:
    """Decode state shared across layers.

    ``index`` is the uniform next-write slot (left-padding makes it batch-uniform);
    ``key_valid`` marks real (non-pad) cached keys; ``key_positions`` holds RoPE
    positions of cached keys (needed for Mistral's sliding-window test);
    ``lengths`` counts real tokens per row (the next RoPE position).

    ``layers`` holds, per layer, whatever that layer keeps per request, and
    the layer's class and its leaves' length say which of three kinds they
    are. A ``LayerCache`` (k and v per KV head) or a ``LatentCache`` keeps
    something per position: every leaf is ``[B, max_len, ...]``. A
    ``LayerCache`` of a layer that sees only a window may be a RING: every
    leaf is ``[B, R, ...]`` with ``R < max_len``, slot ``j`` of the row lives
    at ``j mod R``, and a ring slot holds the newest slot written there.
    Which slot that is, whether it is valid and at what position its key
    stands are not stored per ring: they follow from where the call writes
    and from the one ``key_valid`` / ``key_positions`` of the row
    (``Attention``), so a recycled slot shows its last tenant in a ring no
    more than in a full leaf. A ``RowState`` keeps a fixed state per row:
    every leaf is ``[B, ...]`` with no position axis.
    That is all the step programs rely on (``runtime/stepbuilder.py``): a
    per-position leaf is gathered over the ``[:P]`` extent of its own length,
    may take a donor's positions (rolled within its own length), and is
    scattered back by slot and extent; a per-row
    leaf is gathered and scattered by slot alone, starts from zero in the
    prefill call that names its row (one call, as wide as the bucket), and
    can be copied from no other row, whose state stands at that row's own
    last token. The forward leaves a row's per-row leaves untouched wherever
    ``token_valid`` is False (padding, idle slots, batch-bucket pad rows).
    ``counters`` is None unless the model counts something on the device
    (routed experts: tokens, local pairs, fullest expert); the forward adds
    to it and the serving step hands it to the host with the chunk's tokens.
    """

    layers: Tuple[Any, ...]
    key_valid: jnp.ndarray  # [B, max_len] bool
    key_positions: jnp.ndarray  # [B, max_len] int32
    index: jnp.ndarray  # scalar int32
    lengths: jnp.ndarray  # [B] int32
    counters: Optional[jnp.ndarray] = None  # [3] int32, see SparseMLP

    @property
    def max_len(self) -> int:
        return self.key_valid.shape[1]


MOE_COUNTERS = ("moe_tokens", "moe_local_pairs", "moe_fullest_expert_pairs")


def init_cache(config: ModelConfig, batch_size: int, max_len: int, dtype=None,
               ring: bool = False) -> KVCache:
    """A fresh cache of ``max_len`` positions a row. ``ring``: the slot
    pool's form, in which a layer that sees a window keeps
    ``config.retained_len(max_len)`` of them; otherwise (the static engine,
    the parity tests' oracle) every layer keeps every position and a window
    is a mask. An int8 cache keeps every position too (no int8 ring)."""
    dtype = dtype or _dtype_of(config)
    kv_heads = config.heads_held[1]
    shape = (batch_size, max_len, kv_heads, config.head_dim)
    kept = config.retained_len(max_len) if ring and not config.kv_cache_quant \
        else (max_len,) * config.num_layers
    if config.row_state_bytes:
        if config.kv_cache_quant:
            raise NotImplementedError("no int8 form of a per-row state")
        layers = tuple(
            LayerCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
            if kind == "gqa" else RowState(
                state=jnp.zeros((batch_size,) + config.row_state_shape,
                                jnp.float32),
                conv=jnp.zeros((batch_size, config.conv_width - 1,
                                config.conv_channels), dtype))
            for kind in config.mixer_kinds)
    elif config.attention == "mla":
        if config.kv_cache_quant:
            raise NotImplementedError("no int8 form of the latent cache")
        layers = tuple(
            LatentCache(
                c_kv=jnp.zeros(shape[:2] + (config.kv_lora_rank,), dtype),
                k_rope=jnp.zeros(shape[:2] + (config.qk_rope_head_dim,), dtype),
            )
            for _ in range(config.num_layers)
        )
    elif config.kv_cache_quant:
        layers = tuple(
            LayerCache(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                k_scale=jnp.zeros(shape[:3], jnp.float32),
                v_scale=jnp.zeros(shape[:3], jnp.float32),
            )
            for _ in range(config.num_layers)
        )
    else:
        def leaf(n):
            return jnp.zeros((batch_size, n) + shape[2:], dtype)

        layers = tuple(LayerCache(k=leaf(n), v=leaf(n)) for n in kept)
    return KVCache(
        layers=layers,
        key_valid=jnp.zeros((batch_size, max_len), jnp.bool_),
        key_positions=jnp.zeros((batch_size, max_len), jnp.int32),
        index=jnp.zeros((), jnp.int32),
        lengths=jnp.zeros((batch_size,), jnp.int32),
        counters=(jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
                  if "moe" in config.layer_kinds else None),
    )


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.with_logical_partitioning(nn.initializers.ones, ("embed",)), (x.shape[-1],)
        )
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale.astype(jnp.float32)).astype(x.dtype)


def _norm(config: ModelConfig, name: str):
    if config.norm == "rmsnorm":
        return RMSNorm(eps=config.norm_eps, name=name)
    return nn.LayerNorm(epsilon=config.norm_eps, name=name, dtype=jnp.float32)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary position embedding. x: [B, S, H, D], positions: [B, S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float, beta_fast: float,
                  beta_slow: float, original_max: int):
    """YaRN's rotary frequencies ``[dim / 2]`` (the DeepSeek-V3 convention):
    dimensions that turn more than ``beta_fast`` times over the original
    context keep ``theta^(-2i/dim)``, those that turn fewer than
    ``beta_slow`` times are divided by ``factor``, and a linear ramp over the
    pair index joins the two. ``factor`` 1 is plain RoPE."""
    import numpy as np

    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return extra.astype(np.float32)

    def correction_dim(rotations):
        return (dim * np.log(original_max / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(correction_dim(beta_fast)), 0)
    high = min(np.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                     inv_freq) -> jnp.ndarray:
    """Rotary embedding whose pairs are NEIGHBOURS, (x[2i], x[2i+1]), as the
    DeepSeek-V3 family stores its rotary dimensions. x: [B, S, ..., D],
    positions: [B, S]. The result comes back with the rotated firsts in the
    leading half and the seconds in the trailing half (the published code's
    layout after its own de-interleave); q and k are permuted alike, so
    their products are those of rotating in place."""
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3)
                            + angles.shape[-1:])
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _activation(name: str):
    if name == "silu":
        return nn.silu
    if name == "gelu":
        return nn.gelu
    if name == "gelu_tanh":
        return lambda x: nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name}")


# ---------------------------------------------------------------------------
# int8 weight-only quantized dense (serving; see ops/quant_matmul.py)
# ---------------------------------------------------------------------------


def _int8_kernel_init(key, shape):
    """Random int8 kernel whose dequantized values (under the nominal scale)
    follow ~N(0, 0.02) like the float init — exact distribution parity is
    irrelevant for random-weight use; real checkpoints overwrite both."""
    return jnp.clip(
        jnp.round(jax.random.normal(key, shape) * 42.0), -127, 127
    ).astype(jnp.int8)


_NOMINAL_SCALE = 0.02 / 42.0


def _scale_init(key, shape):
    del key
    return jnp.full(shape, _NOMINAL_SCALE, jnp.float32)


class QuantDense(nn.Module):
    """Dense layer storing its kernel as int8 + per-output-channel f32 scales.

    The matmul dequantizes inside the Pallas tile loop (``ops/quant_matmul``),
    so HBM never holds a float copy of the weight — the property that lets
    llama3-70b tp=8 fit a v5e-8 (the naive dequant-at-use expression gets
    hoisted out of the decode loop by XLA and materializes the full bf16
    tree; ops/quant_matmul.py). When the enclosing ``with mesh:``
    context shards the kernel's logical axes, the matmul runs as a
    FULL-manual shard_map over every mesh axis (column-parallel local,
    row-parallel + f32 psum, batch sharding encoded in the specs — Mosaic
    kernels can't lower partially-auto; see quant_matmul_sharded).
    """

    features: int
    in_axis: str
    out_axis: str
    use_bias: bool = False
    dtype: Any = jnp.bfloat16
    out_dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        from fairness_llm_tpu.ops.quant_matmul import (
            quant_matmul,
            quant_matmul_sharded,
        )
        from fairness_llm_tpu.parallel.sharding import current_mesh

        in_dim = x.shape[-1]
        wq = self.param(
            "kernel_q",
            nn.with_logical_partitioning(
                _int8_kernel_init, (self.in_axis, self.out_axis)
            ),
            (in_dim, self.features),
        )
        scale = self.param(
            "kernel_scale",
            nn.with_logical_partitioning(_scale_init, (self.out_axis,)),
            (self.features,),
        )
        lead = x.shape[:-1]
        x2 = x.reshape(-1, in_dim).astype(self.dtype)
        out_dtype = self.out_dtype or self.dtype

        mesh = current_mesh()
        if mesh is None or all(s == 1 for s in mesh.shape.values()):
            y = quant_matmul(x2, wq, scale, out_dtype=out_dtype)
        else:
            from fairness_llm_tpu.parallel.sharding import resolve_logical_axis

            k_axis, n_axis, b_axis, s_axis = (
                resolve_logical_axis(a, mesh)
                for a in (self.in_axis, self.out_axis, "batch", "seq")
            )
            if b_axis is not None and x2.shape[0] % mesh.shape[b_axis] != 0:
                # batch=1 shared-prefix forward (rows = sequence positions),
                # or any batch not divisible by dp: replicate rows instead.
                # (Matmul rows are independent, so ANY row layout is correct;
                # divisibility is shard_map's hard requirement.)
                b_axis = None
            if s_axis is not None and x.ndim >= 3 and x.shape[1] > 1:
                # Sequence-sharded activations: x2's rows interleave B and S
                # shards, which P(b_axis, ...) cannot express. The XLA dequant
                # matmul is fine here — sp meshes are the training/scoring
                # forward, which runs OUTSIDE any decode loop (nothing for
                # XLA to hoist a float tree across).
                y = jnp.dot(
                    x2, wq.astype(x2.dtype), preferred_element_type=jnp.float32
                )
                y = (y * scale[None, :].astype(jnp.float32)).astype(out_dtype)
            else:
                y = quant_matmul_sharded(
                    x2, wq, scale, mesh, k_axis, n_axis, b_axis,
                    out_dtype=out_dtype,
                )
        y = y.reshape(*lead, self.features)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.features,))
            y = y + bias.astype(y.dtype)
        return y


class Attention(nn.Module):
    """GQA softmax attention. The window and the rotation are this LAYER's
    (``config.layer_windows[layer]``, ``config.layer_rotary[layer]``), not
    the model's: a query at position ``p`` sees a key at ``q <= p`` iff ``p -
    q < window`` (None: every earlier key), and queries and keys are rotated
    or not.

    A cache leaf shorter than the row (``[B, R, ...]`` against ``key_valid``
    ``[B, L]``, ``R < L``) is a RING, which only a window layer of the slot
    pool has: slot ``j`` of the row lives at ``j mod R``. After this call has
    written through slot ``last``, ring slot ``r`` holds slot ``j_r = last -
    (last - r) mod R`` (nothing while that is negative); its validity and
    its key's position are the row's ``key_valid`` / ``key_positions`` at
    ``j_r``, gathered here, and the causal rule is over ``j_r``. What a ring
    no longer holds lies a window or more behind every query the call makes,
    provided the call writes no more than ``R - window + 1`` slots
    (``configs.ring_len``); a ring slot whose claimed slot was never written
    by this tenant (a donor that had moved on: ``scheduler._plan_prefill``)
    claims a position outside every later query's window and is overwritten
    when the ring comes round."""

    config: ModelConfig
    layer: int = 0  # which layer's answers to ask of the configuration

    @property
    def window(self) -> Optional[int]:
        return self.config.layer_windows[self.layer]

    @property
    def rotary(self) -> bool:
        return self.config.layer_rotary[self.layer]

    def _flash_ok(self, seq_len: int, left_padded: bool) -> bool:
        """Static gate for the Pallas fast path.

        Requires TPU, tile-compatible shapes, AND the caller's explicit promise
        that batches are left-padded (the kernel reconstructs the padding mask
        from a per-row length, which is only correct when valid tokens occupy
        the trailing slots). The decode engine always left-pads; other callers
        must opt in via ``left_padded=True``.

        Under a sharded mesh the kernel runs per-shard via shard_map (see
        ``_flash_dispatch``); that wrap is only correct when q and kv heads
        shard the SAME way — the tp=16 GQA fallback (q sharded, kv
        replicated) would change the per-shard head ratio, so it stays on
        the XLA dense path.
        """
        if not (self.config.use_flash_attention and left_padded) or seq_len <= 1:
            return False
        from fairness_llm_tpu.ops import flash_supported
        from fairness_llm_tpu.ops.dispatch import note_kernel_path
        from fairness_llm_tpu.ops.quant_matmul import lowering_for_tpu

        # From here on the caller asked for the kernel; a refusal is a gate
        # decision and is witnessed (ops/dispatch.py), never silent.
        shape = (seq_len, self.config.head_dim)
        _, qh_ax, kv_ax = self._mesh_axes()
        if not lowering_for_tpu():
            reason = "not_tpu"
        elif qh_ax != kv_ax:
            reason = "mesh_axes"
        elif not flash_supported(*shape):
            reason = "untileable"
        else:
            note_kernel_path("flash_attention", "pallas", shape)
            return True
        note_kernel_path("flash_attention", "xla", shape, reason)
        return False

    def _mesh_axes(self):
        """(batch, q_heads, kv_heads) mesh axes actually sharded (>1) under
        the enclosing mesh + logical-rules context, else Nones.

        Axes resolve one at a time (``resolve_logical_axis``): a joint
        PartitionSpec lookup may use each mesh axis only once, so q_heads
        would claim "tp" and kv_heads silently resolve to None (observed:
        the sharded flash gate quietly never engaged)."""
        from fairness_llm_tpu.parallel.sharding import current_mesh, resolve_logical_axis

        mesh = current_mesh()
        if mesh is None:
            return None, None, None
        return tuple(
            resolve_logical_axis(a, mesh) for a in ("batch", "q_heads", "kv_heads")
        )

    def _flash_dispatch(self, q, k, v, lengths):
        """Run flash attention; under a sharded mesh, per-shard.

        A bare Mosaic ``pallas_call`` cannot be partitioned by GSPMD — a
        multi-chip program must wrap it in ``shard_map``. Heads are
        per-kernel-instance, and each batch row is masked independently, so
        sharding batch over dp and heads over tp is exactly local; other
        mesh axes stay GSPMD-auto. (Single-chip callers skip the wrap.)
        """
        from fairness_llm_tpu.ops import flash_attention
        from fairness_llm_tpu.parallel.sharding import current_mesh

        window = self.window

        def call(q, k, v, lengths):
            return flash_attention(q, k, v, lengths, causal=True, window=window)

        mesh = current_mesh()
        if mesh is None or all(s == 1 for s in mesh.shape.values()):
            return call(q, k, v, lengths)
        # Full-manual: Mosaic kernels refuse partially-auto SPMD contexts
        # (see ops/quant_matmul.quant_matmul_sharded); unnamed spec entries
        # are replicated per shard.
        b_ax, qh_ax, kv_ax = self._mesh_axes()
        if b_ax is not None and q.shape[0] % mesh.shape[b_ax] != 0:
            # The engine's shared-prefix prefill runs batch=1 ([1, Pc]
            # tokens) on any mesh; an indivisible batch dim replicates
            # instead of sharding (shard_map requires exact divisibility).
            b_ax = None
        from jax.sharding import PartitionSpec as P

        from fairness_llm_tpu.parallel.sharding import compat_shard_map

        return compat_shard_map(
            call,
            mesh,
            in_specs=(
                P(b_ax, qh_ax, None, None),
                P(b_ax, kv_ax, None, None),
                P(b_ax, kv_ax, None, None),
                P(b_ax),
            ),
            out_specs=P(b_ax, qh_ax, None, None),
        )(q, k, v, lengths)

    def _decode_kernel_ok(
        self, seq_len: int, cache_layer, batch: int, cache_len: int,
        shared_len: int = 0, multi_q: bool = False,
    ) -> bool:
        """Static gate for the fused decode-attention kernel: TPU, a cached
        SINGLE-token step (key_valid alone encodes causality there) or a
        short multi-token speculative verify step (``multi_q`` — per-row
        write offsets supply the causal window), XLA-path semantics (no
        ring), no sliding window (mask not implemented in the kernel), and
        tile-compatible shapes. An int8 cache takes the dequant-in-tile
        kernel mode (the kernel streams int8 + scales, so its VMEM envelope
        is ~4x the f32 accounting)."""
        cfg = self.config
        q_ok = seq_len == 1 or (multi_q and seq_len <= 16)
        if not (cfg.use_decode_attention_kernel and q_ok and cache_layer is not None):
            return False
        if self.window is not None:
            return False
        if cfg.attention_impl != "xla" or jax.default_backend() != "tpu":
            return False
        if jax.device_count() > 1:
            # Multi-chip: a bare pallas_call inside a GSPMD-partitioned
            # program would need a shard_map wrapper; not validated on real
            # multi-chip hardware, so the sharded path keeps XLA attention.
            return False
        from fairness_llm_tpu.ops.decode_attention import decode_attn_supported

        if cfg.kv_cache_quant:
            itemsize = 1
        else:
            itemsize = 2 if cfg.dtype == "bfloat16" else 4
        return decode_attn_supported(
            batch, cache_len, cfg.head_dim, shared_len, kv_itemsize=itemsize,
            q_len=seq_len,
        )

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,  # [B, S, D]
        positions: jnp.ndarray,  # [B, S]
        cache_layer: Optional[LayerCache],
        cache_index: Optional[jnp.ndarray],
        key_valid: jnp.ndarray,  # [B, K] for the post-update key set
        key_positions: jnp.ndarray,  # [B, K]
        left_padded: bool = False,
        shared_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
        write_offsets: Optional[jnp.ndarray] = None,
        pool=None,
    ):
        # ``pool``: the serving step's plan over the slot pool
        # (``ops/pool_attention.PoolPlan``): its one token attends through
        # the pool kernels, each position read once.
        #
        # ``shared_kv``: (k, v) each [Pc, Hkv, D] — a prompt prefix COMMON to
        # every batch row, computed once and read once per step instead of
        # B times (prefix caching; decode is KV-read-bound). Shared keys sit
        # at global positions 0..Pc-1, strictly before every query, so they
        # are always causally visible; per-row positions are offset by Pc.
        #
        # ``write_offsets``: [B] int32 per-row cache-slot offsets for the new
        # tokens (speculative verify steps — rows advance at their own
        # accepted rates, so the uniform ``cache_index`` cannot serve).
        # When given, it replaces ``cache_index`` for BOTH the cache writes
        # and the causal rule: query i of row b may see own-cache slot j iff
        # j <= write_offsets[b] + i (the "small causal window" against the
        # already-valid cache).
        cfg = self.config
        dtype = _dtype_of(cfg)
        # qwen2 carries biases on q/k/v only (o_proj and MLP stay bias-free).
        if cfg.weight_quant == "int8":
            dense = lambda feats, axes, name: QuantDense(  # noqa: E731
                feats, in_axis="embed", out_axis=axes,
                use_bias=cfg.use_bias or cfg.qkv_bias, dtype=dtype, name=name,
            )
        else:
            dense = lambda feats, axes, name: nn.DenseGeneral(  # noqa: E731
                feats,
                use_bias=cfg.use_bias or cfg.qkv_bias,
                dtype=dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("embed", axes)
                ),
                name=name,
            )
        B, S, _ = x.shape
        # What this chip holds of the heads: all of them, or its share.
        H, Hkv, _ = cfg.heads_held
        q_dim, kv_dim = H * cfg.head_dim, Hkv * cfg.head_dim
        q = dense(q_dim, "q_heads", "q_proj")(x).reshape(B, S, H, cfg.head_dim)
        k = dense(kv_dim, "kv_heads", "k_proj")(x).reshape(B, S, Hkv, cfg.head_dim)
        v = dense(kv_dim, "kv_heads", "v_proj")(x).reshape(B, S, Hkv, cfg.head_dim)

        if cfg.qk_norm:
            # Per head, over head_dim, a learned scale; before any rotation.
            with jax.named_scope("attn.qk_norm"):
                q = RMSNorm(eps=cfg.norm_eps, name="q_norm")(q)
                k = RMSNorm(eps=cfg.norm_eps, name="k_norm")(k)
        elif cfg.qk_norm_whole:
            # Over the whole projection, every head at once (OLMo 2).
            with jax.named_scope("attn.qk_norm"):
                q = RMSNorm(eps=cfg.norm_eps, name="q_norm")(
                    q.reshape(B, S, q_dim)).reshape(q.shape)
                k = RMSNorm(eps=cfg.norm_eps, name="k_norm")(
                    k.reshape(B, S, kv_dim)).reshape(k.shape)

        if self.rotary:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)

        if cfg.attention_impl == "ring" and cache_layer is not None:
            raise ValueError("attention_impl='ring' supports only the no-cache path")

        # A leaf shorter than the row is a ring (see the class docstring):
        # the write lands at the slot modulo the ring's length.
        ring = cache_layer is not None and \
            cache_layer.k.shape[1] < key_valid.shape[1]
        if ring:
            R = cache_layer.k.shape[1]
            if self.window is None or cfg.kv_cache_quant:
                raise ValueError("only a bfloat16/float32 window layer "
                                 "keeps a ring")
            if shared_kv is not None:
                raise NotImplementedError(
                    "a shared prefix (the static engine's prefix cache) is "
                    "not built over a ring: the engine keeps every position")
            if write_offsets is not None and S > 1:
                raise NotImplementedError(
                    "a multi-token verify step over a ring is not built "
                    "(its rejected slots have already overwritten what they "
                    "would roll back to): no speculation over a ring")
            if write_offsets is None and (R % S or R < self.window + S - 1):
                raise ValueError(
                    f"a call of {S} positions into a ring of {R} (window "
                    f"{self.window}) would wrap inside the call or lose a "
                    "key its first query still sees")
            last = (cache_index if write_offsets is None
                    else write_offsets[:, None]) + (S - 1)  # [] or [B, 1]
            cache_index = None if cache_index is None else cache_index % R
            write_offsets = None if write_offsets is None \
                else write_offsets % R

        # Shared cache write (prefill records the prompt for later decode steps).
        if cache_layer is not None:
            if cfg.kv_cache_quant:
                qk, k_sc = _quantize_kv(k)
                qv, v_sc = _quantize_kv(v)
                ck = _cache_write(cache_layer.k, qk, cache_index, write_offsets)
                cv = _cache_write(cache_layer.v, qv, cache_index, write_offsets)
                cks = _cache_write(cache_layer.k_scale, k_sc, cache_index, write_offsets)
                cvs = _cache_write(cache_layer.v_scale, v_sc, cache_index, write_offsets)
                new_cache_layer = LayerCache(k=ck, v=cv, k_scale=cks, v_scale=cvs)
                keys = _dequantize_kv(ck, cks, dtype)
                values = _dequantize_kv(cv, cvs, dtype)
            else:
                keys = _cache_write(cache_layer.k, k.astype(dtype), cache_index, write_offsets)
                values = _cache_write(cache_layer.v, v.astype(dtype), cache_index, write_offsets)
                new_cache_layer = LayerCache(k=keys, v=values)
        else:
            keys, values = k, v
            new_cache_layer = None

        if cfg.attention_impl == "ring":
            # Ring attention over the sp axis (parallel/ring.py): exact
            # attention with each device holding a sequence shard; requires
            # tracing inside shard_map with axis "sp" bound (training /
            # scoring forward). GQA kv stay unexpanded on the ring.
            from fairness_llm_tpu.parallel.ring import ring_attention

            out = ring_attention(
                q, k, v, positions, positions, key_valid,
                axis_name="sp", window=self.window,
            ).astype(dtype)
        elif pool is not None:
            from fairness_llm_tpu.ops.pool_attention import pool_attention

            out = pool_attention(q[:, 0], keys, values, pool,
                                 window=self.window)[:, None]
        elif shared_kv is None and not ring and self._flash_ok(S, left_padded):
            # Training (no cache) or first prefill (cache present but empty —
            # S > 1 is the engine's static marker; a chunked-prefill caller
            # must set use_flash_attention=False). In both cases the NEW k/v
            # are the entire key set, so the kernel sees only [B, S].
            # With an int8 cache, later decode steps attend to the quantization
            # round-trip of these keys/values — attend to the same dequantized
            # tensors here so flash-eligible and fallback shapes agree.
            fk, fv = (keys[:, :S], values[:, :S]) if (
                cfg.kv_cache_quant and cache_layer is not None
            ) else (k, v)
            out = self._flash_dispatch(
                q.transpose(0, 2, 1, 3),
                fk.astype(dtype).transpose(0, 2, 1, 3),
                fv.astype(dtype).transpose(0, 2, 1, 3),
                jnp.sum(key_valid[:, :S], axis=1, dtype=jnp.int32),
            ).transpose(0, 2, 1, 3)
        elif self._decode_kernel_ok(
            S, cache_layer, keys.shape[0], keys.shape[1],
            0 if shared_kv is None else shared_kv[0].shape[0],
            multi_q=write_offsets is not None,
        ):
            # Cached decode: the Pallas fused kernel. For S == 1, key_valid
            # alone is the mask (slots past the write index are invalid, so
            # causality is already encoded). For a speculative verify step
            # (S == k+1, write_offsets given) the kernel additionally applies
            # the small causal window j <= offsets[b] + i over the newly
            # written slots.
            from fairness_llm_tpu.ops.decode_attention import decode_attention

            sh = None if shared_kv is None else (
                shared_kv[0].astype(dtype), shared_kv[1].astype(dtype)
            )
            kq = q[:, 0] if S == 1 else q  # [B, H, D] or [B, S, H, D]
            if cfg.kv_cache_quant:
                # Raw int8 cache + scales straight into the kernel; the
                # dequantized `keys`/`values` computed above are unused in
                # this branch and get dead-code-eliminated, so the step
                # streams HALF the cache bytes of the bf16 path.
                out = decode_attention(
                    kq, new_cache_layer.k, new_cache_layer.v, key_valid,
                    shared_kv=sh,
                    k_scale=new_cache_layer.k_scale,
                    v_scale=new_cache_layer.v_scale,
                    q_offsets=write_offsets,
                ).reshape(B, S, H, cfg.head_dim)
            else:
                out = decode_attention(
                    kq, keys.astype(dtype), values.astype(dtype), key_valid,
                    shared_kv=sh, q_offsets=write_offsets,
                ).reshape(B, S, H, cfg.head_dim)
        else:
            with jax.named_scope(
                    "attn.full" if self.window is None else "attn.window"):
                kv_valid, kv_positions = key_valid, key_positions
                if ring:
                    # What each ring slot holds once this call has written
                    # through slot ``last``, and whether and where it counts.
                    held = last - jnp.mod(last - jnp.arange(R)[None, :], R)
                    held = jnp.broadcast_to(held, (B, R))
                    kv_valid = _ring_view(key_valid, last, R)
                    kv_positions = _ring_view(key_positions, last, R)
                    q_idx = last[..., None] - (S - 1) + jnp.arange(S)[:, None]
                    causal = held[:, None, :] <= q_idx  # [B, S, R]
                elif cache_layer is not None:
                    K = keys.shape[1]
                    j_idx = jnp.arange(K)[None, :]
                    if write_offsets is None:
                        # causal: new query i (global slot index+i) sees key slot
                        # j iff j <= index+i
                        q_idx = cache_index + jnp.arange(S)[:, None]
                        causal = (j_idx <= q_idx)[None, :, :]  # [1, S, K]
                    else:
                        # per-row window: query i of row b wrote slot offsets[b]+i
                        q_idx = (
                            write_offsets[:, None, None]
                            + jnp.arange(S)[None, :, None]
                        )  # [B, S, 1]
                        causal = j_idx[None, :, :] <= q_idx  # [B, S, K]
                else:
                    K = S
                    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, :, :]

                allowed = causal & kv_valid[:, None, :]  # [B, S, K]
                if self.window is not None:
                    delta = positions[:, :, None] - kv_positions[:, None, :]
                    allowed = allowed & (delta < self.window)

                # GQA: query head h reads kv head h // rep, so q splits into
                # [Hkv, rep] and both contractions keep the kv-head axis: the
                # cache is read once, never written out rep times.
                rep = H // Hkv
                q5 = q.reshape(B, S, Hkv, rep, cfg.head_dim)

                scale = cfg.head_dim ** -0.5
                scores = jnp.einsum("bqgrd,bkgd->bgrqk", q5, keys).astype(jnp.float32) * scale
                scores = jnp.where(allowed[:, None, None, :, :], scores, -1e30)
                if shared_kv is not None:
                    sk, sv = shared_kv  # [Pc, Hkv, D]
                    # [B,Hkv,rep,S,Pc] — note sk has no batch dim: read once, not B times
                    s_sh = jnp.einsum("bqgrd,kgd->bgrqk", q5, sk.astype(q.dtype)).astype(jnp.float32) * scale
                    if self.window is not None:
                        sh_pos = jnp.arange(sk.shape[0])
                        sh_allowed = (positions[:, :, None] - sh_pos[None, None, :]) < self.window
                        s_sh = jnp.where(sh_allowed[:, None, None, :, :], s_sh, -1e30)
                    joint = jnp.concatenate([s_sh, scores], axis=-1)
                    probs = jax.nn.softmax(joint, axis=-1).astype(dtype)
                    p_sh, p_own = probs[..., : sk.shape[0]], probs[..., sk.shape[0]:]
                    out = jnp.einsum("bgrqk,kgd->bqgrd", p_sh, sv.astype(dtype))
                    out = out + jnp.einsum("bgrqk,bkgd->bqgrd", p_own, values)
                else:
                    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
                    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, values)

        out = out.reshape(B, S, q_dim)
        if cfg.attention_gate:
            # y = W_o (attn * sigmoid(W_gate x)), elementwise over heads x
            # head_dim; the sigmoid in float32.
            with jax.named_scope("attn.gate"):
                gate = jax.nn.sigmoid(
                    dense(q_dim, "q_heads", "gate_proj")(x).astype(jnp.float32))
                out = (out.astype(jnp.float32) * gate).astype(dtype)
        if cfg.weight_quant == "int8":
            out = QuantDense(
                cfg.d_model, in_axis="q_heads", out_axis="embed",
                use_bias=cfg.use_bias, dtype=dtype, name="o_proj",
            )(out)
        else:
            out = nn.DenseGeneral(
                cfg.d_model,
                use_bias=cfg.use_bias,
                dtype=dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), ("q_heads", "embed")
                ),
                name="o_proj",
            )(out)
        return out, new_cache_layer


class MLP(nn.Module):
    config: ModelConfig
    width: Optional[int] = None  # a shared expert's; None: config.d_ff

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        d_ff = self.width or cfg.d_ff
        dtype = _dtype_of(cfg)
        act = _activation(cfg.activation)
        use_bias = cfg.use_bias
        if cfg.weight_quant == "int8":
            up_d = lambda name: QuantDense(  # noqa: E731
                d_ff, in_axis="embed", out_axis="ff",
                use_bias=use_bias, dtype=dtype, name=name,
            )
            down_d = QuantDense(
                cfg.d_model, in_axis="ff", out_axis="embed",
                use_bias=use_bias, dtype=dtype, name="down_proj",
            )
        else:
            up_init = nn.with_logical_partitioning(nn.initializers.normal(0.02), ("embed", "ff"))
            down_init = nn.with_logical_partitioning(nn.initializers.normal(0.02), ("ff", "embed"))
            up_d = lambda name: nn.DenseGeneral(  # noqa: E731
                d_ff, use_bias=use_bias, dtype=dtype, kernel_init=up_init, name=name,
            )
            down_d = nn.DenseGeneral(
                cfg.d_model, use_bias=use_bias, dtype=dtype, kernel_init=down_init,
                name="down_proj",
            )
        if cfg.mlp == "glu":
            h = act(up_d("gate_proj")(x)) * up_d("up_proj")(x)
        else:
            h = act(up_d("up_proj")(x))
        return down_d(h)


class _Kernel(nn.Module):
    """The leaves of a linear map, handed back raw for a caller that does
    its own multiplying: ``(kernel_q int8, kernel_scale f32)`` with the scale
    over the last axis when ``quant``, else ``(kernel, None)``. The names are
    ``QuantDense``'s and ``DenseGeneral``'s, so a tree is laid out alike
    whichever module owns the product."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    quant: bool

    @nn.compact
    def __call__(self):
        if self.quant:
            return (
                self.param("kernel_q", nn.with_logical_partitioning(
                    _int8_kernel_init, self.axes), self.shape),
                self.param("kernel_scale", nn.with_logical_partitioning(
                    _scale_init, self.axes[:-2] + self.axes[-1:]),
                    self.shape[:-2] + self.shape[-1:]),
            )
        return self.param("kernel", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), self.axes), self.shape), None


def _linear(cfg: ModelConfig, features: int, in_axis, out_axis, name: str):
    """A bias-free projection in the configuration's weight format."""
    dtype = _dtype_of(cfg)
    if cfg.weight_quant == "int8":
        return QuantDense(features, in_axis=in_axis, out_axis=out_axis,
                          dtype=dtype, name=name)
    return nn.DenseGeneral(
        features, use_bias=False, dtype=dtype, name=name,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(0.02), (in_axis, out_axis)))


class LatentAttention(nn.Module):
    """Multi-head latent attention. Queries go through a low-rank pair
    (``q_a_proj``, norm, ``q_b_proj``); keys and values through one shared
    latent: ``[c_kv | k_r] = x W_kva``, ``c_kv`` normed, ``k_r`` rotated once
    for all heads, and ``[k_nope | v]`` per head ``= c_kv W_kvb``. The cache
    keeps only ``c_kv`` and ``k_r`` (``LatentCache``).

    Two compositions of the same mathematics. *Expanded*, when the new
    tokens are the whole key set (no cache, or a first prefill, which the
    callers mark as for the flash path: left-padded, S > 1, uniform write):
    k and v are made from the latent and attended to as usual. *Absorbed*,
    whenever the cache is read: ``W_kvb``'s key half moves into the query
    (``q' = q_nope W_k^T``, ``kv_lora_rank`` wide per head), scores and the
    value sum run over the latent itself, and the value half is applied to
    the result, so a step reads ``kv_lora_rank + qk_rope_head_dim`` values a
    token and never expands the cache.
    """

    config: ModelConfig

    @nn.compact
    def __call__(self, x, positions, cache_layer, cache_index, key_valid,
                 key_positions, left_padded=False, shared_kv=None,
                 write_offsets=None):
        cfg = self.config
        if shared_kv is not None:
            raise NotImplementedError(
                "a shared prefix of latents (the static engine's prefix "
                "cache) is not built for latent attention")
        if cfg.attention_impl != "xla":
            raise NotImplementedError("latent attention has the XLA path only")
        dtype = _dtype_of(cfg)
        B, S, _ = x.shape
        H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        dv, rank = cfg.v_head_dim, cfg.kv_lora_rank
        inv_freq = yarn_inv_freq(dr, cfg.rope_theta, cfg.rope_factor,
                                 cfg.rope_beta_fast, cfg.rope_beta_slow,
                                 cfg.rope_original_max)
        f32 = jnp.float32

        c_q = RMSNorm(eps=cfg.norm_eps, name="q_a_norm")(
            _linear(cfg, cfg.q_lora_rank, "embed", None, "q_a_proj")(x))
        q = _linear(cfg, H * (dn + dr), None, "q_heads", "q_b_proj")(c_q)
        q = q.reshape(B, S, H, dn + dr)
        q_nope = q[..., :dn]
        q_rope = rope_interleaved(q[..., dn:], positions, inv_freq)
        kv_a = _linear(cfg, rank + dr, "embed", None, "kv_a_proj")(x)
        c_kv = RMSNorm(eps=cfg.norm_eps, name="kv_a_norm")(kv_a[..., :rank])
        k_r = rope_interleaved(kv_a[..., rank:], positions, inv_freq)
        w_kvb, w_scale = _Kernel((rank, H * (dn + dv)), (None, "q_heads"),
                                 cfg.weight_quant == "int8",
                                 name="kv_b_proj")()

        if cache_layer is not None:
            lat = _cache_write(cache_layer.c_kv, c_kv.astype(dtype),
                               cache_index, write_offsets)
            rot = _cache_write(cache_layer.k_rope, k_r.astype(dtype),
                               cache_index, write_offsets)
            new_cache_layer = LatentCache(c_kv=lat, k_rope=rot)
        else:
            lat, rot, new_cache_layer = c_kv, k_r, None

        expanded = cache_layer is None or (
            left_padded and S > 1 and write_offsets is None)
        if expanded:
            with jax.named_scope("mla.expand"):
                if w_scale is not None:
                    from fairness_llm_tpu.ops.quant_matmul import quant_matmul

                    kv = quant_matmul(
                        c_kv.reshape(B * S, rank).astype(dtype), w_kvb,
                        w_scale, out_dtype=dtype)
                else:
                    kv = jnp.dot(c_kv.reshape(B * S, rank).astype(dtype),
                                 w_kvb.astype(dtype))
                kv = kv.reshape(B, S, H, dn + dv)
            allowed = (jnp.tril(jnp.ones((S, S), jnp.bool_))[None]
                       & key_valid[:, None, :S])
            with jax.named_scope("mla.attend"):
                scores = (
                    jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :dn],
                               preferred_element_type=f32)
                    + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r.astype(dtype),
                                 preferred_element_type=f32)
                ) * cfg.softmax_scale
                scores = jnp.where(allowed[:, None], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
                out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:])
        else:
            K = lat.shape[1]
            j_idx = jnp.arange(K)[None, :]
            if write_offsets is None:
                q_idx = cache_index + jnp.arange(S)[:, None]
                causal = (j_idx <= q_idx)[None, :, :]
            else:
                q_idx = (write_offsets[:, None, None]
                         + jnp.arange(S)[None, :, None])
                causal = j_idx[None, :, :] <= q_idx
            allowed = causal & key_valid[:, None, :]  # [B, S, K]
            # The per-channel scale commutes with both absorbed products, so
            # it goes onto the activations ([.., H, dn] and [.., H, dv]) and
            # the int8 kernel is only cast: no dequantised copy of W_kvb is
            # made in every step of the decode loop.
            w = w_kvb.astype(dtype).reshape(rank, H, dn + dv)
            scale = None if w_scale is None else \
                w_scale.astype(f32).reshape(H, dn + dv)
            with jax.named_scope("mla.absorb"):
                q_in = q_nope if scale is None else \
                    (q_nope * scale[:, :dn]).astype(dtype)
                q_lat = jnp.einsum("bqhd,rhd->bqhr", q_in, w[..., :dn])
            with jax.named_scope("mla.attend"):
                scores = (
                    jnp.einsum("bqhr,bkr->bhqk", q_lat, lat,
                               preferred_element_type=f32)
                    + jnp.einsum("bqhd,bkd->bhqk", q_rope, rot,
                                 preferred_element_type=f32)
                ) * cfg.softmax_scale
                scores = jnp.where(allowed[:, None], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
                o_lat = jnp.einsum("bhqk,bkr->bqhr", probs, lat)
            with jax.named_scope("mla.absorb"):
                out = jnp.einsum("bqhr,rhd->bqhd", o_lat, w[..., dn:],
                                 preferred_element_type=f32)
                if scale is not None:
                    out = out * scale[:, dn:]
                out = out.astype(dtype)

        out = _linear(cfg, cfg.d_model, "q_heads", "embed", "o_proj")(
            out.reshape(B, S, H * dv))
        return out, new_cache_layer


def _conv_init(key, shape):
    """Short-convolution taps of order one, so that a random-weight layer's
    q, k and v are not lost under the norms' epsilons."""
    return jax.random.normal(key, shape) * 0.5


class DeltaRuleAttention(nn.Module):
    """A delta-rule linear-attention layer over the heads this chip holds,
    Kimi Delta Attention (arXiv 2510.26692) or Gated DeltaNet (arXiv
    2412.06464) as ``config.linear_mixer`` says (``LinearHybridConfig``).
    Per token ``t``, with ``x`` the layer's input:

    - ``q~, k~, v~ = W x`` (``dk``, ``dk`` and ``dv`` a head; one ``[q | k |
      v]`` projection for Gated DeltaNet); a causal depthwise convolution of
      ``conv_width`` taps over time on each channel (tap ``conv_width - 1``
      on the current token, zeros before a row's first real token), then
      SiLU;
    - ``q = l2norm(q^) / sqrt(dk)``, ``k = l2norm(k^)``, ``v = v^``, per head;
    - the decay: per key channel, ``a = exp(-exp(A_log[h]) softplus(W_fb
      (W_fa x) + dt_bias))`` in (0, 1)^dk; per head, ``a = exp(-exp(A_log[h])
      softplus(w_a,h . x + dt_bias[h]))``, one scalar;
    - ``b = 2 sigmoid(w_beta . x)`` in (0, 2);
    - ``S = (I - b k k^T) Diag(a) S + b k v^T``, ``o = S^T q``
      (``ops/delta_rule.py``), ``S`` ``[dk, dv]``, zero before the row's
      first token;
    - ``y = W_o concat_h(RMSNorm(o) * gate)``, the gate ``sigmoid(W_gb (W_ga
      x))`` or ``SiLU(W_g x)``.

    Float32: the state, ``a``, ``b``, the l2 norms, the convolution's sum
    and the update. What the layer keeps between calls is a ``RowState``: the
    state (``config.row_state_shape``: a per-head decay's keeps heads side by
    side) and the last ``conv_width - 1`` rows of ``[q~ | k~ | v~]``. A token
    whose ``token_valid`` is False (padding, an idle slot) moves neither. One
    promise is asked of the caller: within a call a row's valid tokens are
    contiguous (left- or right-padded), which every caller here keeps.

    One token with a cache is the decode step (``kda_step`` or
    ``gdn_step``, a Pallas kernel over the whole pool on the TPU); anything
    longer runs the recurrence as a ``lax.scan`` over its positions.
    """

    config: ModelConfig

    @nn.compact
    def __call__(self, x, cache_layer: Optional[RowState], token_valid,
                 shared_kv=None, write_offsets=None):
        from fairness_llm_tpu.ops.delta_rule import (
            gdn_step,
            gdn_update,
            kda_step,
            kda_update,
        )

        cfg = self.config
        B, S, d = x.shape
        if shared_kv is not None:
            raise NotImplementedError(
                "a shared prefix cannot stand in for a per-row state: the "
                "state after the prefix would have to be restored per row "
                "(snapshots are not built)")
        if write_offsets is not None and S > 1:
            raise NotImplementedError(
                "a multi-token verify step cannot roll a per-row state back "
                "to the last accepted token: no speculation over "
                "linear-attention layers")
        dtype, f32 = _dtype_of(cfg), jnp.float32
        H, dk, W = cfg.heads_held[2], cfg.linear_head_dim, cfg.conv_width
        dv = cfg.value_head_dim
        C, Cv = H * dk, H * dv
        widths = (C, C, Cv)
        per_head = cfg.linear_mixer == "gdn"  # and a fused qkv, a SiLU gate

        if per_head:
            pre = _linear(cfg, sum(widths), "embed", None, "qkv_proj")(
                x).astype(dtype)
        else:
            pre = jnp.concatenate(
                [_linear(cfg, n, "embed", None, name)(x).astype(dtype)
                 for n, name in zip(widths, ("q_proj", "k_proj", "v_proj"))],
                axis=-1)
        taps = jnp.concatenate(
            [self.param(name, _conv_init, (W, n))
             for n, name in zip(widths, ("conv_q", "conv_k", "conv_v"))],
            axis=-1)

        with jax.named_scope("kda.conv"):
            history = (jnp.zeros((B, W - 1, sum(widths)), dtype)
                       if cache_layer is None else cache_layer.conv)
            ext = jnp.concatenate([history, pre], axis=1)  # [B, W-1+S, .]
            # The kept inputs must sit right before the row's first valid
            # token, whatever padding leads the call: entry j of the line
            # the convolution reads is the history while j - lead is under
            # W - 1, and the call's own token j - (W - 1) after that.
            lead = jnp.where(jnp.any(token_valid, axis=1),
                             jnp.argmax(token_valid, axis=1), S)[:, None]
            j = jnp.arange(S + W - 1, dtype=jnp.int32)[None, :]
            src = jnp.where(j >= lead + W - 1, j, jnp.clip(j - lead, 0, None))
            line = jnp.take_along_axis(ext, src[:, :, None], axis=1)
            mixed = sum(line[:, i:i + S].astype(f32) * taps[i].astype(f32)
                        for i in range(W))
            mixed = jax.nn.silu(mixed)
            new_conv = line[:, S:]  # unchanged for a row with no valid token
            if dv == dk:  # slices would compile a square state's layers
                # to another program than the one Solar's cell measures
                mixed = mixed.reshape(B, S, 3, H, dk)
                q, k, v = mixed[:, :, 0], mixed[:, :, 1], mixed[:, :, 2]
            else:
                q = mixed[..., :C].reshape(B, S, H, dk)
                k = mixed[..., C:2 * C].reshape(B, S, H, dk)
                v = mixed[..., 2 * C:].reshape(B, S, H, dv)
            q = q * jax.lax.rsqrt(
                jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)

        with jax.named_scope("kda.gates"):
            a_log = self.param("A_log", nn.initializers.zeros, (H,))
            dt_bias = self.param("dt_bias", nn.initializers.zeros,
                                 (H if per_head else C,))
            w_beta, _ = _Kernel((d, H), ("embed", None), False,
                                name="beta_proj")()
            if per_head:
                w_a, _ = _Kernel((d, H), ("embed", None), False,
                                 name="a_proj")()
                f = jnp.einsum("bsd,dh->bsh", x.astype(dtype),
                               w_a.astype(dtype), preferred_element_type=f32)
                a = jnp.exp(-jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                    f + dt_bias.astype(f32)))  # [B, S, H]
            else:
                rank = cfg.gate_rank
                f = _linear(cfg, C, None, None, "f_b_proj")(
                    _linear(cfg, rank, "embed", None, "f_a_proj")(x))
                decay = jax.nn.softplus(f.astype(f32) + dt_bias.astype(f32))
                a = jnp.exp(-jnp.exp(a_log.astype(f32))[:, None]
                            * decay.reshape(B, S, H, dk))
            b = 2.0 * jax.nn.sigmoid(jnp.einsum(
                "bsd,dh->bsh", x.astype(dtype), w_beta.astype(dtype),
                preferred_element_type=f32))
            if per_head:
                g = jax.nn.silu(_linear(cfg, Cv, "embed", None, "g_proj")(
                    x).astype(f32))
            else:
                g = jax.nn.sigmoid(_linear(cfg, C, None, None, "g_b_proj")(
                    _linear(cfg, cfg.gate_rank, "embed", None, "g_a_proj")(
                        x)).astype(f32))
            # A token that is not valid decays nothing and writes nothing.
            tv = token_valid[:, :, None]
            a = jnp.where(tv if per_head else tv[..., None], a, 1.0)
            b = jnp.where(tv, b, 0.0)

        step, update = (gdn_step, gdn_update) if per_head else \
            (kda_step, kda_update)
        with jax.named_scope("kda.recurrence"):
            state = (jnp.zeros((B,) + cfg.row_state_shape, f32)
                     if cache_layer is None else cache_layer.state)
            if S == 1 and cache_layer is not None:
                state, o = step(state, q[:, 0], k[:, 0], v[:, 0], a[:, 0],
                                b[:, 0])
                o = o[:, None]
            else:
                def token(st, qkvab):
                    new, out = update(st, *qkvab[:5])
                    keep = qkvab[5][:, None, None, None]
                    return jnp.where(keep, new, st), out

                over_time = [t.swapaxes(0, 1) for t in (q, k, v, a, b)]
                state, o = jax.lax.scan(
                    token, state, (*over_time, token_valid.swapaxes(0, 1)))
                o = o.swapaxes(0, 1)  # [B, S, H, dv]

        o = RMSNorm(eps=cfg.norm_eps, name="o_norm")(o)
        out = (o.astype(f32).reshape(B, S, Cv) * g).astype(dtype)
        out = _linear(cfg, cfg.d_model, None, "embed", "o_proj")(out)
        new_cache = None if cache_layer is None else \
            RowState(state=state, conv=new_conv)
        return out, new_cache


def route_tokens(x: jnp.ndarray, router: jnp.ndarray, cfg: ModelConfig,
                 bias: Optional[jnp.ndarray] = None):
    """``(chosen [T, k] int32 over ALL experts, weights [T, k] float32)``:
    sigmoid scores in float32 at the highest matmul precision, the k largest
    (ties to the lower index), normalised over the chosen, times
    ``routed_scaling``. ``bias`` [experts], where the configuration has one,
    is added to the scores to CHOOSE and never to weigh."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if bias is None:
        top, chosen = jax.lax.top_k(scores, cfg.experts_per_token)
    else:
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                  cfg.experts_per_token)
        top = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), top * cfg.routed_scaling


class SparseMLP(nn.Module):
    """Routed experts with a shared expert, as one rank of the expert axis.

    ``p = sigmoid(x W_g)`` over ALL experts, in float32 (with random or
    trained weights a near-tie picks another expert in bfloat16); the
    ``experts_per_token`` largest (of ``p + b`` where the configuration has
    a selection bias ``b``: ``router_bias``); ``w = routed_scaling * p_sel / sum(p_sel)``
    over all the chosen, held here or not; ``y = Shared(x) + sum_i w_i
    E_i(x)`` over the chosen experts this rank holds
    (``config.experts_held``). What the other ranks' experts would add is
    left out: on one chip there is no exchange, and nothing stands in for it.
    Returns ``(y, int32 [3])``: valid tokens, local (token, expert) pairs,
    and the fullest held expert's pairs (``MOE_COUNTERS``).
    """

    config: ModelConfig

    @nn.compact
    def __call__(self, x, token_valid):
        from fairness_llm_tpu.ops.expert_dispatch import routed_experts

        cfg = self.config
        dtype = _dtype_of(cfg)
        quant = cfg.weight_quant == "int8"
        B, S, d = x.shape
        first, held = cfg.experts_held
        ff = cfg.expert_d_ff
        router, _ = _Kernel((d, cfg.num_experts), ("embed", None), False,
                            name="router")()
        experts = [
            _Kernel((held,) + shape, ("expert",) + axes, quant, name=name)()
            for name, shape, axes in (
                ("experts_gate", (d, ff), ("embed", "ff")),
                ("experts_up", (d, ff), ("embed", "ff")),
                ("experts_down", (ff, d), ("ff", "embed")))
        ]
        bias = self.param("router_bias", nn.initializers.zeros,
                          (cfg.num_experts,)) if cfg.router_bias else None
        x2 = x.reshape(B * S, d)
        with jax.named_scope("moe.route"):
            chosen, top = route_tokens(x2, router, cfg, bias)
        # Kept only by an apply that asks for "intermediates" (a diagnostic:
        # perfbench/tools/router_flips.py); otherwise not even traced.
        self.sow("intermediates", "chosen", chosen.reshape(B, S, -1))
        with jax.named_scope("moe.experts"):
            routed, counters = routed_experts(
                x2.astype(dtype), chosen, top,
                token_valid.reshape(B * S), *experts, first=first,
                num_experts=cfg.num_experts,
                activation=_activation(cfg.activation))
        shared = MLP(cfg, width=cfg.num_shared_experts * ff, name="shared")(x)
        y = shared + routed.astype(dtype).reshape(B, S, d)
        return y, counters


class Block(nn.Module):
    config: ModelConfig
    kind: str = "dense"  # this layer's MLP: config.layer_kinds[i]
    mixer: str = "gqa"  # this layer's mixer: config.mixer_kinds[i]
    layer: int = 0  # i: a GQA layer asks the configuration its window by it

    @nn.compact
    def __call__(self, x, positions, cache_layer, cache_index, key_valid, key_positions,
                 left_padded=False, shared_kv=None, write_offsets=None,
                 token_valid=None, pool=None):
        """Returns ``(x, the layer's new cache, its counters or None)``.
        ``pool``: the serving step's plan, for a softmax layer."""
        # ``pre_norms`` off (OLMo 2): the mixer and the MLP read the stream
        # itself, and only their outputs are normed.
        pre = self.config.pre_norms
        normed = _norm(self.config, "attn_norm")(x) if pre else x
        if self.mixer == "kda":
            attn_out, new_cache = DeltaRuleAttention(self.config, name="attn")(
                normed, cache_layer, token_valid, shared_kv=shared_kv,
                write_offsets=write_offsets)
        else:
            attention = LatentAttention(self.config, name="attn") \
                if self.mixer == "mla" else Attention(
                    self.config, layer=self.layer, name="attn")
            extra = {} if self.mixer == "mla" else dict(pool=pool)
            attn_out, new_cache = attention(
                normed, positions, cache_layer, cache_index, key_valid,
                key_positions, left_padded=left_padded, shared_kv=shared_kv,
                write_offsets=write_offsets, **extra,
            )
        # ``post_norms``: h = x + N2(Mixer(N1(x))), y = h + N4(MLP(N3(h))).
        post = self.config.post_norms
        if post:
            attn_out = _norm(self.config, "attn_post_norm")(attn_out)
        x = x + attn_out
        counters = None
        mlp_in = _norm(self.config, "mlp_norm")(x) if pre else x
        if self.kind == "moe":
            y, counters = SparseMLP(self.config, name="mlp")(
                mlp_in, token_valid)
        else:
            y = MLP(self.config, name="mlp")(mlp_in)
        if post:
            y = _norm(self.config, "mlp_post_norm")(y)
        x = x + y
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        return x, new_cache, counters


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


class Transformer(nn.Module):
    """Decoder-only LM.

    Call patterns:
      - training / scoring: ``logits, None = apply(params, tokens, positions, token_valid)``
      - prefill/decode:     ``logits, cache = apply(..., cache=cache)`` where
        ``tokens`` occupy cache slots ``[cache.index, cache.index + S)``.
    """

    config: ModelConfig

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,  # [B, S] int32
        positions: jnp.ndarray,  # [B, S] int32 (RoPE/learned positions, pad rows clamped)
        token_valid: Optional[jnp.ndarray] = None,  # [B, S] bool
        cache: Optional[KVCache] = None,
        left_padded: bool = False,  # promise: valid tokens occupy trailing slots
        last_only: bool = False,  # return logits for the final position only
        shared_layers: Optional[Tuple] = None,  # per-layer (k, v) [Pc, Hkv, D] prefix KV
        write_offsets: Optional[jnp.ndarray] = None,  # [B] per-row cache slots
        pool_spans: Optional[Tuple] = None,  # (lead, shared) [B]: the pool's plan
    ) -> Tuple[jnp.ndarray, Optional[KVCache]]:
        cfg = self.config
        dtype = _dtype_of(cfg)
        B, S = tokens.shape
        if token_valid is None:
            token_valid = jnp.ones((B, S), jnp.bool_)

        embed = self.param(
            "embedding",
            nn.with_logical_partitioning(nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model),
        )
        x = embed[tokens].astype(dtype)
        if cfg.embed_scale:  # gemma
            x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
        if cfg.pos_emb == "learned":
            wpe = self.param(
                "pos_embedding",
                nn.with_logical_partitioning(nn.initializers.normal(0.02), (None, "embed")),
                (cfg.max_seq_len, cfg.d_model),
            )
            x = x + wpe[positions].astype(dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        if cache is not None:
            # Static guard against silent dynamic_update_slice clamping: a single
            # call can never write more new tokens than the cache holds. The
            # engine guarantees max_len >= prompt_len + max_new_tokens.
            if S > cache.max_len:
                raise ValueError(
                    f"writing {S} tokens into a cache of max_len {cache.max_len}"
                )
            key_valid = _cache_write(cache.key_valid, token_valid, cache.index, write_offsets)
            key_positions = _cache_write(cache.key_positions, positions, cache.index, write_offsets)
        else:
            key_valid = token_valid
            key_positions = positions

        pool = None
        if pool_spans is not None:
            # The serving step over the slot pool (``ops/pool_attention``):
            # one token a row, written at ``write_offsets``.
            if S != 1 or cache is None or write_offsets is None:
                raise ValueError("pool_spans is the serving step's: one "
                                 "token a row, per-row write offsets")
            from fairness_llm_tpu.ops.pool_attention import PoolPlan

            # A model that keeps a per-row state takes no donor, so its
            # plan names no lead (``scheduler._plan_prefill``).
            pool = PoolPlan(*pool_spans, live=token_valid[:, 0],
                            pos=positions[:, 0], last=write_offsets,
                            max_len=cache.max_len,
                            leads=not cfg.row_state_bytes)
        new_layers = []
        counters = cache.counters if cache is not None else None
        for i, (kind, mixer) in enumerate(zip(cfg.layer_kinds,
                                              cfg.mixer_kinds)):
            layer_cache = cache.layers[i] if cache is not None else None
            x, new_layer, counted = Block(cfg, kind=kind, mixer=mixer,
                                          layer=i, name=f"layer_{i}")(
                x, positions,
                layer_cache, cache.index if cache is not None else None,
                key_valid, key_positions, left_padded=left_padded,
                shared_kv=shared_layers[i] if shared_layers is not None else None,
                write_offsets=write_offsets, token_valid=token_valid,
                pool=pool,
            )
            new_layers.append(new_layer)
            if counted is not None and counters is not None:
                counters = counters + counted

        x = _norm(cfg, "final_norm")(x)
        if last_only:
            # Prefill only needs the final position's distribution; skipping the
            # [B, S, V] projection saves B·(S-1)·D·V FLOPs (for a gpt2-small
            # 64x896 prefill that's ~2 TFLOP of pure waste).
            x = x[:, -1:, :]

        # Logits matmul: operands stay in the model dtype (bf16 -> full MXU
        # rate; the [D, V] projection dominates each decode step's FLOPs) with
        # float32 accumulation — the standard precision recipe. float32
        # configs are unaffected.
        if cfg.tie_embeddings:
            logits = jnp.einsum(
                "bsd,vd->bsv", x, embed.astype(x.dtype),
                preferred_element_type=jnp.float32,
            )
        elif cfg.weight_quant == "int8":
            logits = QuantDense(
                cfg.vocab_size, in_axis="embed", out_axis="vocab",
                use_bias=False, dtype=_dtype_of(cfg), out_dtype=jnp.float32,
                name="lm_head",
            )(x)
        else:
            lm_head = self.param(
                "lm_head",
                nn.with_logical_partitioning(nn.initializers.normal(0.02), ("embed", "vocab")),
                (cfg.d_model, cfg.vocab_size),
            )
            logits = jnp.einsum(
                "bsd,dv->bsv", x, lm_head.astype(x.dtype),
                preferred_element_type=jnp.float32,
            )
        logits = nn.with_logical_constraint(logits, ("batch", "seq", "vocab"))

        new_cache = None
        if cache is not None:
            new_cache = KVCache(
                layers=tuple(new_layers),
                key_valid=key_valid,
                key_positions=key_positions,
                index=cache.index + S,
                lengths=cache.lengths + jnp.sum(token_valid, axis=1, dtype=jnp.int32),
                counters=counters,
            )
        return logits, new_cache


def init_params_lowmem(config: ModelConfig, rng: jax.Array, dtype=None) -> Any:
    """Random params WITHOUT materializing the float32 init tree.

    ``flax`` init allocates every param in float32 (param_dtype default); for a
    multi-billion-param model that transient f32 tree alone can exceed one
    chip's HBM. This path gets shapes from ``jax.eval_shape`` (no memory) and
    fills each leaf directly in the target dtype: kernels/embeddings ~ N(0,
    0.02), biases zero, norm scales one — the same families as the real init
    (exact distribution parity is irrelevant for random-weight use).
    """
    dtype = dtype or (jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32)
    model = Transformer(config)
    tokens = jnp.zeros((1, 8), jnp.int32)
    positions = jnp.zeros((1, 8), jnp.int32)
    abstract = jax.eval_shape(model.init, jax.random.key(0), tokens, positions)
    abstract = nn.meta.unbox(abstract["params"])

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        name = "/".join(str(p.key) for p in path if hasattr(p, "key"))
        key = jax.random.fold_in(rng, i)
        if name.endswith("kernel_q"):  # before the generic "scale"/bias rules
            leaves.append(_int8_kernel_init(key, leaf.shape))
        elif name.endswith("kernel_scale"):
            leaves.append(_scale_init(key, leaf.shape))
        elif name.endswith("scale"):
            leaves.append(jnp.ones(leaf.shape, dtype))
        elif name.endswith("bias"):
            leaves.append(jnp.zeros(leaf.shape, dtype))
        else:
            leaves.append((jax.random.normal(key, leaf.shape, dtype) * 0.02))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def init_params(config: ModelConfig, rng: jax.Array, seq_len: int = 8) -> Any:
    """Initialize parameters with a tiny dummy batch (shape doesn't matter for params).

    The init is run under ``jit``: unjitted flax init dispatches op-by-op, and
    per-op XLA mini-compiles are orders of magnitude slower than one fused
    compile (observed 45 s eager vs 3 s jitted for the tiny test model).
    """
    model = Transformer(config)
    tokens = jnp.zeros((1, seq_len), jnp.int32)
    positions = jnp.tile(jnp.arange(seq_len, dtype=jnp.int32)[None, :], (1, 1))
    variables = jax.jit(model.init)(rng, tokens, positions)
    # Strip the LogicallyPartitioned metadata boxes; sharding specs are recovered
    # separately via eval_shape + nn.get_partition_spec (parallel/sharding.py).
    return nn.meta.unbox(variables["params"])
