#!/usr/bin/env python
"""AOT pre-flight: compile the serving programs for a TPU from a machine
that has none — the cheap check before spending chip time.

With libtpu installed, ``jax.experimental.topologies`` describes a v5e slice
without one being attached. This tool lowers ``serve_prefill`` and
``serve_step`` (the programs ``ContinuousScheduler`` builds, from the same
``stepbuilder`` compositions, guard on, both donated the pool and the
carried logits as the scheduler donates them) for a model at full width
against that descriptor under ``force_pallas()``, and reports per program:
compile wall, the Mosaic custom calls and all-reduces in the compiled
module, the copies of a whole pool leaf it holds (a donation that did not
take), and ``memory_analysis()`` against the chip's HBM. The defaults are
``chip_smoke.py``'s configuration.

It proves the compiler accepts the programs and that they fit. It does not
run them: execution, run-time HBM, init and host code are only seen on the
chip. Prints one JSON object per program and exits non-zero if one does not
fit or lost a kernel.

    python tools/aot_preflight.py                      # one chip
    python tools/aot_preflight.py --tp 4 --layers 2    # tp=4 mesh, depth cut
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def preflight(model_name: str, slots: int, prompt_budget: int, max_new: int,
              tp: int, layers: int | None, nb: int,
              bench_config: str | None = None,
              hlo_out: str | None = None) -> list:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from prove_70b_int8_fit import V5E_HBM_GB

    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.models.tokenizer import tokenizer_for
    from fairness_llm_tpu.models.transformer import (
        Transformer,
        init_cache,
        init_params_lowmem,
    )
    from fairness_llm_tpu.ops import pool_attention
    from fairness_llm_tpu.ops.quant_matmul import force_pallas
    from fairness_llm_tpu.parallel import sharding as shd
    from fairness_llm_tpu.runtime.sampling import SamplerSettings
    from fairness_llm_tpu.runtime.stepbuilder import (
        build_serve_prefill,
        build_serve_step,
        cache_rings,
        extend_window,
        long_bucket,
    )
    from fairness_llm_tpu.telemetry.memory import (
        count_hlo_ops,
        count_pool_copies,
        count_rolled_rows,
    )

    if bench_config:
        # A benchmark configuration, as its family hands it to the program.
        from perfbench import families
        from perfbench.lib import modelcfg

        bench = modelcfg.load_config(os.path.join(
            ROOT, "perfbench", "configs", bench_config + ".json"))
        cfg = families.of(bench).model_config(bench, bench_config)
    else:
        cfg = dataclasses.replace(get_model_config(model_name),
                                  weight_quant="int8")
    if layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp, 1),
                ("dp", "tp", "sp"))
    rules = shd.make_axis_rules(cfg, mesh)
    model = Transformer(cfg)
    tok = tokenizer_for(cfg)
    cache_len = prompt_budget + max_new
    replicated = NamedSharding(mesh, P())

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    params = jax.eval_shape(
        lambda: init_params_lowmem(cfg, jax.random.key(0), jnp.bfloat16))
    params = placed(params, shd.param_shardings(cfg, mesh, rules))
    # The slot pool's form: a window layer's leaf is its ring.
    cache = jax.eval_shape(lambda: init_cache(cfg, slots, cache_len,
                                              ring=True))
    lengths = sorted({x.shape[1] for layer in cache.layers
                      for x in jax.tree.leaves(layer) if x.ndim > 2}
                     | {cache_len})
    cache = placed(cache, shd.kv_tree_shardings(cfg, mesh, cache))
    logits = jax.ShapeDtypeStruct((slots, cfg.vocab_size), jnp.float32,
                                  sharding=shd.logits_sharding(cfg, mesh))

    def host(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=replicated)

    with force_pallas():
        # The scheduler's choice on the chip (``ContinuousScheduler``).
        pool_spans = pool_attention.available(cfg, tp)
    step = build_serve_step(
        cfg, model, SamplerSettings(temperature=0.7, top_k=0, top_p=1.0),
        tok.pad_id, tok.eos_id, num_slots=slots, chunk=8, guard=True,
        fuse=1, pool_spans=pool_spans)
    row = lambda dt: host((slots,), dt)  # noqa: E731
    rings = cache_rings(cfg, cache)

    def prefill(rows, copies):
        """``serve_prefill`` of ``rows`` rows at the budget's bucket, of a
        batch that copies or of one that does not (its wide window)."""
        window = extend_window(cfg, prompt_budget, copies, rings)
        run = build_serve_prefill(cfg, model, nb=rows, P=prompt_budget,
                                  guard=True, num_slots=slots, copies=copies,
                                  rings=rings)
        return ("serve_prefill", dict(nb=rows, window=window, copies=copies),
                jax.jit(run, donate_argnums=(1, 2)),
                (params, cache, logits, host((rows, window), jnp.int32),
                 host((rows, window), jnp.bool_))
                + (host((rows,), jnp.int32),) * 5 + (host((), jnp.int32),))

    programs = [prefill(nb, True)]
    if long_bucket(cfg, prompt_budget):
        # The scheduler's other program of such a bucket: one row that
        # copies nothing, sent ahead of the rows that copy from it.
        programs.append(prefill(1, False))
    programs.append(
        ("serve_step", {}, jax.jit(step, donate_argnums=(1, 2)),
         (params, cache, logits, row(jnp.uint32), row(jnp.int32),
          row(jnp.int32), row(jnp.int32), row(jnp.bool_), row(jnp.bool_))
         + (row(jnp.int32),) * (2 * pool_spans)))
    out = []
    for name, shape, fn, args in programs:
        t0 = time.perf_counter()
        with force_pallas(), mesh, nn.logical_axis_rules(rules):
            compiled = fn.lower(*args).compile()
        wall = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        if hlo_out:
            os.makedirs(hlo_out, exist_ok=True)
            tag = "".join(f"_{k}{v}" for k, v in shape.items())
            with open(os.path.join(hlo_out, f"{name}{tag}.hlo.txt"),
                      "w") as f:
                f.write(hlo)
        live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        rec = {
            "program": name, **shape, "model": cfg.name,
            "layers": cfg.num_layers,
            "tp": tp, "slots": slots, "cache_len": cache_len,
            "device_kind": topo.devices[0].device_kind,
            "compile_s": round(wall, 1),
            **count_hlo_ops(hlo),
            "leaf_lengths": lengths,
            "pool_copies": count_pool_copies(hlo, slots, lengths),
            "rolled_rows": (count_rolled_rows(
                hlo, shape["nb"], prompt_budget, cache, lengths)
                if name == "serve_prefill" else 0),
            "argument_gb": round(ma.argument_size_in_bytes / 1e9, 2),
            "output_gb": round(ma.output_size_in_bytes / 1e9, 2),
            "alias_gb": round(ma.alias_size_in_bytes / 1e9, 2),
            "temp_gb": round(ma.temp_size_in_bytes / 1e9, 2),
            "live_gb": round(live / 1e9, 2),
            "hbm_limit_gb": V5E_HBM_GB,
            "fits": bool(live / 1e9 < V5E_HBM_GB),
            "wants_grouped": "moe" in cfg.layer_kinds,
        }
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="mistral-7b")
    ap.add_argument("--bench-config", default=None,
                    help="a configuration of perfbench/configs by name, "
                         "built as the benchmark builds it (overrides --model)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-budget", type=int, default=1152)
    ap.add_argument("--max-new", type=int, default=128)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--layers", type=int, default=None,
                    help="cut depth (compile time only; memory then "
                         "understates the full model)")
    ap.add_argument("--nb", type=int, default=8,
                    help="prefill batch rows (the scheduler's prefill_group)")
    ap.add_argument("--hlo-out", default=None,
                    help="a directory to write each compiled program's text "
                         "to (compare two trees' programs with it)")
    a = ap.parse_args()
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    recs = preflight(a.model, a.slots, a.prompt_budget, a.max_new, a.tp,
                     a.layers, a.nb, a.bench_config, a.hlo_out)
    bad = [r["program"] for r in recs
           if not r["fits"] or not r["int8_matmul"]
           or (r["wants_grouped"] and not r["grouped_int8_matmul"])
           or (a.tp > 1 and not r["all-reduce"])]
    sys.exit(f"pre-flight failed for {bad}" if bad else 0)
