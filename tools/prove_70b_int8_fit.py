"""AOT proof: llama3-70b int8 weights, tp=8, fits one v5e-8 slice.

Compiles the REAL prefill+decode program for the ``llama3-70b-int8`` config
against an eight-chip v5e topology DESCRIPTOR (``jax.experimental.topologies``
— the actual v5e TPU compiler, no 8-chip hardware needed) and reads the
compiled program's own memory analysis. This is the check that flips round
2/3's honest negative: bf16 70B at tp=8 is ~17.6 GB/chip (over a v5e's HBM),
and the naive int8-dequant-at-use program hoists a 35 GB bf16 tree
(ops/quant_matmul.py). With dequant-in-tile (ops/quant_matmul.py)
the int8 tree IS the resident form.

Run: python tools/prove_70b_int8_fit.py            (~several minutes: 80
     unrolled layers x 7 Pallas matmuls each through the Mosaic pipeline)
Prints one JSON line; also used by bench.py when BENCH_70B_PROOF=1.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V5E_HBM_GB = 15.75  # usable HBM the TPU compiler enforces on a 16 GB v5e


def prove(model_name: str = "llama3-70b-int8", batch: int = 8,
          prompt_len: int = 128, new_tokens: int = 4,
          num_layers: int | None = None) -> dict:
    """``num_layers`` override: a 2-layer variant exercises the identical
    per-layer lowering (kernels, shard_map, collectives) in ~1/40th the
    compile time — bench.py uses it for the in-run lowering check while the
    committed artifact holds the full-model memory analysis."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.models.transformer import Transformer, init_cache
    from fairness_llm_tpu.ops.quant_matmul import force_pallas
    from fairness_llm_tpu.parallel import sharding as shd

    cfg = get_model_config(model_name)
    if num_layers is not None:
        cfg = dataclasses.replace(
            cfg, name=f"{cfg.name}-{num_layers}l", num_layers=num_layers
        )
    td = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x4")
    mesh = Mesh(np.array(td.devices).reshape(1, 8, 1), ("dp", "tp", "sp"))
    rules = shd.make_axis_rules(cfg, mesh)
    shardings = shd.param_shardings(cfg, mesh, rules)

    model = Transformer(cfg)
    abstract = nn.meta.unbox(
        jax.eval_shape(
            model.init, jax.random.key(0),
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.int32),
        )["params"]
    )
    flat, treedef = jtu.tree_flatten_with_path(abstract)
    aleaves = []
    for (path, leaf), s in zip(flat, jtu.tree_leaves(shardings)):
        name = getattr(path[-1], "key", "")
        # Engine storage policy for a big bf16 model: float leaves in bf16,
        # quant scales kept f32, int8 kernels stay int8.
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            dt = leaf.dtype
        else:
            dt = jnp.float32 if name == "kernel_scale" else jnp.bfloat16
        aleaves.append(jax.ShapeDtypeStruct(leaf.shape, dt, sharding=s))
    aparams = jtu.tree_unflatten(treedef, aleaves)

    B, S, NEW = batch, prompt_len, new_tokens

    def prefill_and_decode(params, tokens, positions, valid):
        # The engine's program shape (runtime/engine.py): batch prefill
        # writes the cache, then cached single-token steps extend it.
        cache = init_cache(cfg, B, S + NEW)
        logits, cache = model.apply(
            {"params": params}, tokens, positions, valid, cache,
            left_padded=True, last_only=True,
        )

        def step(_, carry):
            logits, cache = carry
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            pos = cache.lengths[:, None]
            logits, cache = model.apply(
                {"params": params}, tok[:, None], pos,
                jnp.ones((B, 1), jnp.bool_), cache,
            )
            return logits, cache

        logits, _ = jax.lax.fori_loop(0, NEW, step, (logits, cache))
        return logits

    bs = shd.batch_sharding(mesh)
    atoks = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bs)
    apos = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bs)
    avalid = jax.ShapeDtypeStruct((B, S), jnp.bool_, sharding=bs)
    t0 = time.time()
    with mesh, nn.logical_axis_rules(rules), force_pallas():
        compiled = (
            jax.jit(prefill_and_decode).lower(aparams, atoks, apos, avalid).compile()
        )
    ma = compiled.memory_analysis()
    total_gb = (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
    ) / 1e9
    return {
        "model": cfg.name,
        "topology": "v5e:2x4 (tp=8)",
        "batch": B,
        "prompt_len": S,
        "decode_steps": NEW,
        "compile_s": round(time.time() - t0, 1),
        "args_gb_per_chip": round(ma.argument_size_in_bytes / 1e9, 2),
        "temps_gb_per_chip": round(ma.temp_size_in_bytes / 1e9, 2),
        "output_gb_per_chip": round(ma.output_size_in_bytes / 1e9, 3),
        "total_gb_per_chip": round(total_gb, 2),
        "hbm_limit_gb": V5E_HBM_GB,
        "fits": bool(total_gb < V5E_HBM_GB),
        "analytic_param_gb_per_chip": round(
            shd.per_device_param_bytes(cfg, mesh, rules) / 1e9, 2
        ),
    }


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    print(json.dumps(prove()))
