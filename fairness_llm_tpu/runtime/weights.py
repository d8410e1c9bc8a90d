"""HF safetensors checkpoints -> framework param trees, sharded at load time.

The reference never loads weights (its models live behind the OpenAI API,
SURVEY.md §0). For in-framework decode we map HuggingFace checkpoint layouts
onto our ``models/transformer.py`` tree:

- llama/mistral layout: ``model.layers.{i}.self_attn.q_proj.weight`` etc.,
  weights stored [out, in] -> transposed into our [in, out] kernels.
- gemma layout: llama-like, tied embeddings, and RMSNorm stored as
  ``weight`` with output ``x * (1 + weight)`` -> our ``scale = 1 + weight``.
- gpt2 layout: ``h.{i}.attn.c_attn`` Conv1D (already [in, out], no transpose)
  holding fused QKV -> split three ways; learned ``wpe`` positions.

Memory discipline for 70B-class checkpoints: tensors are streamed one at a
time via ``safetensors.safe_open`` and, when a mesh is given, each tensor is
``jax.device_put`` onto its NamedSharding immediately — the host never holds
more than one full tensor, and each device only materializes its shard.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from fairness_llm_tpu.models.configs import ModelConfig
from fairness_llm_tpu.parallel import sharding as shd

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Name mapping: our param path -> (hf name, transform)
# ---------------------------------------------------------------------------

Transform = Callable[[jnp.ndarray], jnp.ndarray]


def _t(x: jnp.ndarray) -> jnp.ndarray:
    return x.T


def _ident(x: jnp.ndarray) -> jnp.ndarray:
    return x


def _llama_map(cfg: ModelConfig) -> Dict[str, Tuple[str, Transform]]:
    """Also covers mistral (identical naming) and, with tweaks below, gemma."""
    m: Dict[str, Tuple[str, Transform]] = {
        "embedding": ("model.embed_tokens.weight", _ident),
        "final_norm/scale": ("model.norm.weight", _ident),
    }
    if not cfg.tie_embeddings:
        m["lm_head"] = ("lm_head.weight", _t)
    for i in range(cfg.num_layers):
        p = f"layer_{i}"
        h = f"model.layers.{i}"
        m[f"{p}/attn_norm/scale"] = (f"{h}.input_layernorm.weight", _ident)
        m[f"{p}/mlp_norm/scale"] = (f"{h}.post_attention_layernorm.weight", _ident)
        for ours, theirs in (("q_proj", "q_proj"), ("k_proj", "k_proj"),
                             ("v_proj", "v_proj"), ("o_proj", "o_proj")):
            m[f"{p}/attn/{ours}/kernel"] = (f"{h}.self_attn.{theirs}.weight", _t)
        for ours, theirs in (("gate_proj", "gate_proj"), ("up_proj", "up_proj"),
                             ("down_proj", "down_proj")):
            m[f"{p}/mlp/{ours}/kernel"] = (f"{h}.mlp.{theirs}.weight", _t)
    return m


def _gemma_map(cfg: ModelConfig) -> Dict[str, Tuple[str, Transform]]:
    plus_one: Transform = lambda x: x + 1.0  # noqa: E731 — gemma RMSNorm convention
    m = _llama_map(cfg)
    for key, (name, tf) in list(m.items()):
        if key.endswith("norm/scale"):
            m[key] = (name, plus_one)
    return m


def _qwen2_map(cfg: ModelConfig) -> Dict[str, Tuple[str, Transform]]:
    """Qwen2: llama naming plus biases on the q/k/v projections only."""
    m = _llama_map(cfg)
    for i in range(cfg.num_layers):
        p, h = f"layer_{i}", f"model.layers.{i}"
        for proj in ("q_proj", "k_proj", "v_proj"):
            m[f"{p}/attn/{proj}/bias"] = (f"{h}.self_attn.{proj}.bias", _ident)
    return m


def _gpt2_map(cfg: ModelConfig) -> Dict[str, Tuple[str, Transform]]:
    """GPT-2 Conv1D stores [in, out]; c_attn fuses qkv along the out axis."""
    d = cfg.d_model

    def _qkv(part: int) -> Transform:
        return lambda x: x[..., part * d:(part + 1) * d]

    m: Dict[str, Tuple[str, Transform]] = {
        "embedding": ("wte.weight", _ident),
        "pos_embedding": ("wpe.weight", _ident),
        "final_norm/scale": ("ln_f.weight", _ident),
        "final_norm/bias": ("ln_f.bias", _ident),
    }
    for i in range(cfg.num_layers):
        p = f"layer_{i}"
        h = f"h.{i}"
        m[f"{p}/attn_norm/scale"] = (f"{h}.ln_1.weight", _ident)
        m[f"{p}/attn_norm/bias"] = (f"{h}.ln_1.bias", _ident)
        m[f"{p}/mlp_norm/scale"] = (f"{h}.ln_2.weight", _ident)
        m[f"{p}/mlp_norm/bias"] = (f"{h}.ln_2.bias", _ident)
        for j, proj in enumerate(("q_proj", "k_proj", "v_proj")):
            m[f"{p}/attn/{proj}/kernel"] = (f"{h}.attn.c_attn.weight", _qkv(j))
            m[f"{p}/attn/{proj}/bias"] = (f"{h}.attn.c_attn.bias", _qkv(j))
        m[f"{p}/attn/o_proj/kernel"] = (f"{h}.attn.c_proj.weight", _ident)
        m[f"{p}/attn/o_proj/bias"] = (f"{h}.attn.c_proj.bias", _ident)
        m[f"{p}/mlp/up_proj/kernel"] = (f"{h}.mlp.c_fc.weight", _ident)
        m[f"{p}/mlp/up_proj/bias"] = (f"{h}.mlp.c_fc.bias", _ident)
        m[f"{p}/mlp/down_proj/kernel"] = (f"{h}.mlp.c_proj.weight", _ident)
        m[f"{p}/mlp/down_proj/bias"] = (f"{h}.mlp.c_proj.bias", _ident)
    return m


_FAMILY_MAPS = {
    "llama": _llama_map,
    "mistral": _llama_map,
    "gemma": _gemma_map,
    "gpt2": _gpt2_map,
    "qwen": _qwen2_map,
}


def family_of(cfg: ModelConfig) -> str:
    name = cfg.name.lower()
    for fam in ("llama", "mistral", "gemma", "gpt2", "qwen"):
        if fam in name.replace("-", ""):
            return fam
    # tiny test configs: pick by flags
    if cfg.qkv_bias:
        return "qwen"
    return "gpt2" if cfg.pos_emb == "learned" else "llama"


def hf_name_map(cfg: ModelConfig, family: Optional[str] = None) -> Dict[str, Tuple[str, Transform]]:
    if cfg.attention != "gqa" or "moe" in cfg.layer_kinds:
        # Latent attention's low-rank pairs, the delta rule's gates and
        # convolutions and stacked expert kernels have leaves of their own
        # (models/transformer.py: q_a_proj .. kv_b_proj, conv_*, A_log,
        # router, shared, experts_*); no published layout is mapped to them
        # yet, and guessing one would load a wrong model without complaint.
        raise NotImplementedError(
            f"no checkpoint loader for {cfg.name}'s kind of layer "
            f"({sorted(set(cfg.mixer_kinds))} mixers, "
            f"{sorted(set(cfg.layer_kinds))})")
    family = family or family_of(cfg)
    return _FAMILY_MAPS[family](cfg)


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------


def _strip_prefix(name: str, tensors: Dict[str, str]) -> str:
    """HF checkpoints sometimes prefix everything with 'transformer.' (gpt2)
    or 'model.' is already in our map; resolve against what's present."""
    if name in tensors:
        return name
    for prefix in ("transformer.", "model."):
        cand = prefix + name
        if cand in tensors:
            return cand
    raise KeyError(f"tensor '{name}' not found in checkpoint (have {len(tensors)} tensors)")


def _checkpoint_index(path: str) -> Dict[str, str]:
    """Map tensor name -> shard file for a safetensors checkpoint directory."""
    index_file = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index_file):
        with open(index_file) as f:
            return json.load(f)["weight_map"]
    single = [f for f in os.listdir(path) if f.endswith(".safetensors")]
    if not single:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    out: Dict[str, str] = {}
    from safetensors import safe_open

    for fname in single:
        with safe_open(os.path.join(path, fname), framework="flax") as f:
            for k in f.keys():
                out[k] = fname
    return out


def load_checkpoint(
    cfg: ModelConfig,
    path: str,
    mesh: Optional[jax.sharding.Mesh] = None,
    family: Optional[str] = None,
    dtype: Optional[Any] = None,
    verify: bool = True,
) -> Any:
    """Load an HF safetensors checkpoint dir into our param tree.

    With ``mesh``, each tensor is placed onto its tensor-parallel NamedSharding
    as it streams off disk; without, tensors land on the default device.

    ``verify`` (default on) checks the directory's sha256 ``manifest.json``
    (``integrity/manifest.py``) before any tensor is read: a bit-flipped or
    truncated shard raises ``IntegrityError`` naming the file, instead of
    loading garbage weights that decode plausible-looking garbage text.
    Directories without a manifest load unverified (pre-manifest
    checkpoints), with a debug note.
    """
    from safetensors import safe_open

    if verify:
        from fairness_llm_tpu.integrity.manifest import maybe_verify_manifest

        maybe_verify_manifest(path, kind="weights")
    dtype = dtype or (jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    name_map = hf_name_map(cfg, family)
    weight_map = _checkpoint_index(path)
    shardings = shd.param_shardings(cfg, mesh) if mesh is not None else None
    quant = cfg.weight_quant == "int8"

    handles: Dict[str, Any] = {}

    def get_tensor(hf_name: str) -> jnp.ndarray:
        hf_name = _strip_prefix(hf_name, weight_map)
        fname = weight_map[hf_name]
        if fname not in handles:
            handles[fname] = safe_open(os.path.join(path, fname), framework="flax")
        return handles[fname].get_tensor(hf_name)

    def place(our_path: str, x: Any) -> None:
        leaf_sharding = _tree_get(shardings, our_path) if shardings is not None else None
        x = jax.device_put(x, leaf_sharding) if leaf_sharding is not None else jnp.asarray(x)
        _tree_set(params, our_path, x)

    params: Dict[str, Any] = {}
    try:
        for our_path, (hf_name, transform) in name_map.items():
            x = transform(get_tensor(hf_name))
            if quant and _quant_base(our_path) is not None:
                # int8 serving: quantize each matmul kernel AS IT STREAMS off
                # disk — the float tensor exists one at a time; HBM (and for
                # 70B-class checkpoints, host RAM) never holds a float tree.
                from fairness_llm_tpu.ops.quant_matmul import quantize_weight

                base = _quant_base(our_path)
                q, s = quantize_weight(jnp.asarray(x))
                place(f"{base}/kernel_q", q)
                place(f"{base}/kernel_scale", s)
                logger.debug("loaded %s <- %s %s (int8)", base, hf_name, q.shape)
                continue
            place(our_path, x.astype(dtype))
            logger.debug("loaded %s <- %s %s", our_path, hf_name, x.shape)
    finally:
        # Shard handles hold open fds + mmaps; a multi-shard 70B checkpoint
        # must not keep them alive until interpreter GC.
        for h in handles.values():
            h.__exit__(None, None, None)
    return params


def _quant_base(our_path: str) -> Optional[str]:
    """For a float-tree kernel path, the QuantDense module base path that
    replaces it under ``weight_quant='int8'`` — else None. Quantizable =
    every 2D matmul kernel: DenseGeneral ``.../kernel`` and the untied
    ``lm_head``; embeddings (gathered, not streamed whole), norms, and
    biases stay float."""
    if our_path.endswith("/kernel"):
        return our_path[: -len("/kernel")]
    if our_path == "lm_head":
        return "lm_head"
    return None


def quantize_params(params: Any) -> Any:
    """Float param tree -> the ``weight_quant='int8'`` tree layout.

    For tests and for quantizing in-memory weights (e.g. after fine-tuning);
    ``load_checkpoint`` quantizes tensor-at-a-time off disk instead.
    """
    from fairness_llm_tpu.ops.quant_matmul import quantize_weight

    out = _copy_tree(params)
    for path in list(_walk_paths(out)):
        base = _quant_base(path)
        if base is None:
            continue
        q, s = quantize_weight(jnp.asarray(_tree_get(out, path)))
        node = out
        parts = path.split("/")
        for part in parts[:-1]:
            node = node[part]
        del node[parts[-1]]
        _tree_set(out, f"{base}/kernel_q", q)
        _tree_set(out, f"{base}/kernel_scale", s)
    return out


def dequantize_params(params: Any, dtype=jnp.float32) -> Any:
    """Inverse of ``quantize_params`` (up to quantization rounding)."""
    from fairness_llm_tpu.ops.quant_matmul import dequantize_weight

    out = _copy_tree(params)
    for path in list(_walk_paths(out)):
        if not path.endswith("/kernel_q"):
            continue
        base = path[: -len("/kernel_q")]
        module = _tree_get(out, base)  # the QuantDense param dict
        w = dequantize_weight(
            jnp.asarray(module["kernel_q"]), jnp.asarray(module["kernel_scale"]), dtype
        )
        # Remove only the quant leaves — siblings (qwen2/gpt2 biases) stay.
        del module["kernel_q"], module["kernel_scale"]
        if base == "lm_head" and not module:
            # lm_head is a bare param leaf in the float tree, not a module
            parts = base.split("/")
            node = out
            for part in parts[:-1]:
                node = node[part]
            node[parts[-1]] = w
        else:
            _tree_set(out, f"{base}/kernel", w)
    return out


def _copy_tree(tree: Any) -> Any:
    """Structure-copy of a nested dict (leaves shared, dicts fresh)."""
    return {
        k: _copy_tree(v) if isinstance(v, dict) else v for k, v in tree.items()
    }


def _walk_paths(tree: Any, prefix: str = "") -> Any:
    for key, val in tree.items():
        path = f"{prefix}/{key}" if prefix else key
        if isinstance(val, dict):
            yield from _walk_paths(val, path)
        else:
            yield path


def save_checkpoint_hf(cfg: ModelConfig, params: Any, path: str, family: Optional[str] = None) -> None:
    """Inverse mapping: write our params as an HF-layout safetensors file.

    Used by tests (fabricate a checkpoint, round-trip it) and for exporting.
    Fused tensors (gpt2 c_attn) are reassembled from their parts; int8 trees
    export dequantized (HF layouts have no per-channel-int8 convention we
    target).
    """
    from safetensors.flax import save_file

    if cfg.weight_quant == "int8":
        params = dequantize_params(params)
    name_map = hf_name_map(cfg, family)
    family = family or family_of(cfg)
    out: Dict[str, jnp.ndarray] = {}
    fused: Dict[str, list] = {}
    for our_path, (hf_name, _tf) in name_map.items():
        x = _tree_get(params, our_path)
        if x is None:
            continue
        x = jnp.asarray(x)
        if family == "gpt2":
            if ".c_attn." in hf_name:
                fused.setdefault(hf_name, [None, None, None])
                part = {"q_proj": 0, "k_proj": 1, "v_proj": 2}[our_path.split("/")[-2]]
                fused[hf_name][part] = x
                continue
            out[hf_name] = x  # Conv1D: already [in, out]
        elif hf_name.endswith("norm.weight") and family == "gemma":
            out[hf_name] = x - 1.0
        elif x.ndim == 2 and not hf_name.endswith(("embed_tokens.weight", "wte.weight", "wpe.weight")):
            out[hf_name] = x.T
        else:
            out[hf_name] = x
    for hf_name, parts in fused.items():
        out[hf_name] = jnp.concatenate(parts, axis=-1)
    os.makedirs(path, exist_ok=True)
    save_file(out, os.path.join(path, "model.safetensors"))
    # Verified-artifact manifest (integrity/manifest.py): per-file sha256 +
    # tensor shape/dtype summary, checked by load_checkpoint. Covers every
    # file present at save time; files added later (tokenizer, provenance)
    # simply go unlisted and unverified.
    from fairness_llm_tpu.integrity.manifest import write_manifest

    write_manifest(path)


def _tree_get(tree: Any, path: str) -> Any:
    node = tree
    for part in path.split("/"):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def _tree_set(tree: Dict, path: str, value: Any) -> None:
    parts = path.split("/")
    node = tree
    for part in parts[:-1]:
        node = node.setdefault(part, {})
    node[parts[-1]] = value
