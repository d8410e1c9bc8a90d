"""Mesh construction + logical-axis -> mesh-axis sharding rules.

The model (``models/transformer.py``) annotates every weight with *logical* axis
names ("embed", "q_heads", "kv_heads", "ff", "vocab") and every activation with
("batch", "seq", "embed"/"vocab"). This module decides how those logical axes map
onto the physical ``("dp", "tp", "sp")`` mesh:

- "batch"            -> "dp"   (the profile sweep is data-parallel)
- "q_heads"/"kv_heads"/"ff"/"vocab" -> "tp"  (megatron-style tensor parallel:
  column-parallel QKV/up projections, row-parallel o/down projections; XLA GSPMD
  inserts the all-reduces the NCCL world would do by hand)
- "seq"              -> "sp"   (sequence/context parallel for long prompts)
- "embed"            -> replicated
- "expert"           -> replicated: the routed experts' stacked kernels carry
  the axis name, and which of them a chip holds is its rank on that axis
  (``held_experts``). No mesh built here has the axis, so its size is 1 and
  the rank is the configuration's own ``expert_rank``: the exchange between
  expert ranks does not exist yet, and a mesh that asks for one is refused.

An axis is only mapped when its size divides the mesh axis (GQA models with few
KV heads fall back to replicated KV, which is also what production TP serving
does when kv_heads < tp).

The reference has no equivalent — its "distributed backend" is HTTPS to OpenAI
(SURVEY.md §5.8); this is the XLA-collectives-over-ICI replacement.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fairness_llm_tpu.config import MeshConfig
from fairness_llm_tpu.models.configs import ModelConfig

AxisRules = Tuple[Tuple[str, Optional[str]], ...]


def make_mesh(mesh_config: MeshConfig, devices: Optional[List] = None) -> Mesh:
    """Build a ("dp", "tp", "sp") mesh over the available devices."""
    devices = devices if devices is not None else jax.devices()
    n = mesh_config.num_devices
    if len(devices) < n:
        raise ValueError(
            f"mesh {mesh_config.shape} needs {n} devices, have {len(devices)}"
        )
    arr = np.asarray(devices[:n]).reshape(mesh_config.shape)
    # Auto axis types: we annotate weights/activations and let GSPMD propagate
    # through gathers/scans (Explicit mode would require per-gather
    # out_sharding annotations inside the model).
    axis_types = (jax.sharding.AxisType.Auto,) * len(mesh_config.axis_names)
    return Mesh(arr, mesh_config.axis_names, axis_types=axis_types)


def compat_shard_map(f, mesh: Mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with ``check_vma=False`` (the "don't prove
    replication" escape hatch every caller here needs). ``axis_names`` is
    the set of MANUAL axes; None means all of them."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    if axis_names is not None:
        kwargs["axis_names"] = axis_names
    return jax.shard_map(f, **kwargs)


def current_mesh() -> Optional[Mesh]:
    """The physical mesh of the enclosing ``with mesh:`` block, or None.

    The engine and train step run every traced call inside ``with mesh,
    nn.logical_axis_rules(...)`` — the same thread-local flax reads for
    ``with_logical_constraint``. Modules that must make trace-time sharding
    decisions (QuantDense's shard_map over the weight's tp axis) read it
    here instead of threading a mesh attribute through every layer.
    """
    from jax._src import mesh as jax_mesh  # no public accessor as of jax 0.9

    m = jax_mesh.thread_resources.env.physical_mesh
    return None if m.empty else m


def resolve_logical_axis(name: str, mesh: Mesh) -> Optional[str]:
    """The mesh axis a SINGLE logical axis maps to under the enclosing flax
    rules context, or None when unmapped / size 1.

    One name at a time on purpose: a joint
    ``logical_to_mesh_axes((a, b, ...))`` builds one PartitionSpec, where a
    mesh axis may appear only once — querying q_heads and kv_heads together
    silently resolves the second "tp" mapping to None (this bug once made
    the sharded flash gate never engage).
    """
    import flax.linen as fnn

    axis = tuple(fnn.logical_to_mesh_axes((name,)))[0]
    return axis if axis and mesh.shape.get(axis, 1) > 1 else None


def held_experts(num_experts: int, shards: int, rank: int) -> Tuple[int, int]:
    """(first, count) of the routed experts that rank ``rank`` of ``shards``
    on the expert axis holds: equal contiguous ranges, ``[rank * n, rank * n
    + n)`` with ``n = num_experts / shards``."""
    if shards < 1 or num_experts % shards or not 0 <= rank < shards:
        raise ValueError(f"{num_experts} experts over {shards} ranks, rank "
                         f"{rank}: not an equal share")
    count = num_experts // shards
    return rank * count, count


def held_heads(num_heads: int, shards: int, rank: int) -> Tuple[int, int]:
    """(first, count) of a mixer's heads that rank ``rank`` of ``shards``
    holds, as tensor parallelism cuts them: the same equal contiguous ranges
    as the experts'. Nothing here exchanges between the ranks: a
    configuration that holds a share (``LinearHybridConfig``) computes its
    own heads' part of ``o_proj``'s sum, and the other ranks' is left out."""
    return held_experts(num_heads, shards, rank)


def require_gqa(model_config: ModelConfig, what: str) -> None:
    """Raise for a layer kind ``what`` reckons GQA's sizes for and does not
    know: a wrong answer in GQA's terms is worse than none."""
    # Asked of every layer: a model of which only some layers are GQA (the
    # rest keep a per-row state) is not a GQA model.
    if model_config.attention != "gqa" or set(model_config.layer_kinds) != {
            "dense"}:
        raise NotImplementedError(
            f"{what} knows dense GQA layers only; {model_config.name} has "
            f"{sorted(set(model_config.mixer_kinds))} mixers and "
            f"{sorted(set(model_config.layer_kinds))} layers"
            + ("; a per-row state has no head axis sharded here and its "
               "heads' exchange is not built"
               if model_config.row_state_bytes else ""))


def make_axis_rules(model_config: ModelConfig, mesh: Mesh) -> AxisRules:
    """Logical->mesh axis rules, dropping mappings that don't divide evenly.

    Head projections shard at HEAD granularity (num_heads % tp), not just dim
    granularity: a dim-divisible split that bisects heads would force GSPMD to
    re-gather around every attention einsum. GQA models with fewer KV heads
    than tp fall back to replicated KV (llama3-70b kv_heads=8 shards exactly
    1 head/chip at tp=8 but replicates at tp=16) — the same fallback
    production TP serving uses.
    """
    tp = mesh.shape.get("tp", 1)
    sp = mesh.shape.get("sp", 1)
    if tp > 1 or mesh.shape.get("ep", 1) > 1:
        # Heads of a latent cache and experts across chips (with their
        # exchange) are not built: refuse rather than shard as GQA would.
        require_gqa(model_config, "tensor- or expert-parallel sharding")

    def fits(size: int) -> bool:
        return tp > 1 and size % tp == 0

    rules = [
        ("batch", "dp"),
        ("seq", "sp" if sp > 1 else None),
        ("embed", None),
        ("q_heads", "tp" if fits(model_config.num_heads) else None),
        ("kv_heads", "tp" if fits(model_config.num_kv_heads) else None),
        ("ff", "tp" if fits(model_config.d_ff) else None),
        ("vocab", "tp" if fits(model_config.vocab_size) else None),
        ("expert", None),
    ]
    return tuple(rules)


@functools.lru_cache(maxsize=8)
def _abstract_params(model_config: ModelConfig):
    """(partition specs, abstract shapes) from one metadata-only init trace.

    Cached: an 80-layer abstract trace costs seconds, and engine construction
    needs it for both shardings and the byte estimate.
    """
    from fairness_llm_tpu.models.transformer import Transformer

    model = Transformer(model_config)
    tokens = jnp.zeros((1, 8), jnp.int32)
    abstract = jax.eval_shape(model.init, jax.random.key(0), tokens, tokens)
    specs = nn.get_partition_spec(abstract)["params"]
    shapes = nn.meta.unbox(abstract["params"])
    return specs, shapes


def param_shardings(model_config: ModelConfig, mesh: Mesh, rules: Optional[AxisRules] = None) -> Any:
    """Pytree of NamedSharding for every model parameter.

    Uses ``jax.eval_shape`` over ``model.init`` (no FLOPs, no memory) to recover
    the logical partitioning metadata, then maps it through the axis rules.
    """
    if rules is None:
        rules = make_axis_rules(model_config, mesh)
    specs, _ = _abstract_params(model_config)
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, _resolve_spec(spec, rules)),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _resolve_spec(spec: P, rules: AxisRules) -> P:
    table = dict(rules)
    return P(*(table.get(axis) if axis is not None else None for axis in spec))


def shard_params(params: Any, shardings: Any) -> Any:
    """Place a (host or single-device) param pytree onto the mesh."""
    return jax.tree.map(jax.device_put, params, shardings)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [B, ...] token batches: batch over dp, rest replicated."""
    return NamedSharding(mesh, P("dp"))


def kv_heads_sharded(model_config: ModelConfig, mesh: Mesh) -> bool:
    """True when the KV head axis shards under this mesh's rules — the same
    divisibility fallback :func:`make_axis_rules` applies (GQA models with
    kv_heads % tp != 0 replicate their KV, like production TP serving)."""
    tp = mesh.shape.get("tp", 1)
    return tp > 1 and model_config.num_kv_heads % tp == 0


def kv_tree_shardings(model_config: ModelConfig, mesh: Mesh, tree: Any) -> Any:
    """NamedSharding pytree for the serving ``KVCache``.

    Its k/v leaves (and the int8 path's scales) carry the KV head axis at
    position 2: ``[rows, slots, n_kv, head_dim]``. Those leaves shard on
    ``tp`` at the head axis when it divides (so each shard holds its own
    heads' KV); every bookkeeping leaf (key_valid, positions, lengths,
    index) and a non-dividing head axis replicate. The row axis never
    shards here: the slot scatter is not dp-aware, which is exactly why
    the scheduler accepts tp-only meshes.
    """
    shard_heads = kv_heads_sharded(model_config, mesh)
    n_kv = model_config.num_kv_heads

    def spec_for(leaf) -> NamedSharding:
        if (shard_heads and getattr(leaf, "ndim", 0) >= 3
                and leaf.shape[2] == n_kv):
            return NamedSharding(
                mesh, P(*([None, None, "tp"] + [None] * (leaf.ndim - 3)))
            )
        return NamedSharding(mesh, P())

    return jax.tree.map(spec_for, tree)


def logits_sharding(model_config: ModelConfig, mesh: Mesh) -> NamedSharding:
    """Sharding for the scheduler's carried ``[num_slots, vocab]`` sampler
    logits: vocab over tp when it divides (matching the lm head's
    ("batch", "seq", "vocab") activation constraint, so the decode
    program's output lands where its input was), else replicated."""
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and model_config.vocab_size % tp == 0:
        return NamedSharding(mesh, P(None, "tp"))
    return NamedSharding(mesh, P())


def per_device_param_bytes(model_config: ModelConfig, mesh: Mesh,
                           rules: Optional[AxisRules] = None,
                           itemsize: Optional[int] = None) -> int:
    """Analytic per-device parameter bytes under the sharding rules.

    Walks the same eval_shape partition specs ``param_shardings`` uses; each
    leaf contributes size/prod(mapped mesh axes). This is what lets the CLI
    flag a config that cannot fit before any weight streams off disk — e.g.
    llama3-70b bf16 at tp=8 is ~17.6 GB/chip, OVER a v5e's 16 GB HBM (the fit
    paths are tp=16 across two v5e-8 slices, or int8 weights).

    ``itemsize`` overrides the config-dtype byte width for FLOAT leaves —
    the engine stores small bf16-config models in float32 (see DecodeEngine
    param policy) and passes its actual storage width. Integer leaves (the
    int8 kernels of a ``weight_quant`` model) always count at their own
    width: storage policy never widens them.
    """
    if rules is None:
        rules = make_axis_rules(model_config, mesh)
    specs, shapes = _abstract_params(model_config)
    if itemsize is None:
        itemsize = 2 if model_config.dtype == "bfloat16" else 4

    total = 0
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    flat_shapes = jax.tree.leaves(shapes)
    for spec, leaf in zip(flat_specs, flat_shapes):
        resolved = _resolve_spec(spec, rules)
        div = 1
        for axis in resolved:
            if axis is not None:
                div *= mesh.shape.get(axis, 1)
        item = (
            itemsize if jnp.issubdtype(leaf.dtype, jnp.floating)
            else jnp.dtype(leaf.dtype).itemsize
        )
        total += int(np.prod(leaf.shape)) * item // div
    return total


def per_device_kv_cache_bytes(model_config: ModelConfig, mesh: Mesh, batch: int,
                              max_len: int, rules: Optional[AxisRules] = None) -> int:
    """Per-device KV-cache bytes for a decode of ``batch`` rows x ``max_len``
    slots: [B, L, Hkv, D] x 2 (k and v) x num_layers, batch split over dp and
    kv heads over tp when the rules shard them (int8 quant halves it but adds
    the f32 scales)."""
    if rules is None:
        rules = make_axis_rules(model_config, mesh)
    dp = mesh.shape.get("dp", 1)
    if model_config.attention != "gqa":
        # Each layer's own count: a latent cache has no head axis to shard,
        # and a per-row state no position axis to multiply by.
        item = 2 if model_config.dtype == "bfloat16" else 4
        return -(-batch // dp) * (
            max_len * sum(model_config.layer_cache_values) * item
            + model_config.row_state_bytes)
    kv_axis = dict(rules).get("kv_heads")
    kv_div = mesh.shape.get(kv_axis, 1) if kv_axis else 1
    # ceil, matching the engine's batch padding to a dp multiple — floor would
    # undercount (batch 12 on dp=8 decodes 2 rows/device, not 1).
    rows_per_device = -(-batch // dp)
    slots = rows_per_device * max_len * (model_config.num_kv_heads // kv_div)
    if model_config.kv_cache_quant:
        per_slot = model_config.head_dim * 1 + 4  # int8 values + f32 scale
    else:
        per_slot = model_config.head_dim * (2 if model_config.dtype == "bfloat16" else 4)
    return 2 * model_config.num_layers * slots * per_slot
