"""Serve BASELINE configs[1] — Llama-3-8B — WHOLE on the one real v5e chip.

The first end-to-end >=7B full-model measurement in the project: every
earlier >=7B data point was an AOT topology compile (tests/test_70b_readiness)
or a 1/8 tp-shard (tools/measure_70b_shard.py). This runs the actual
flagship config the hardware can serve — llama3-8b-int8, ~8.6 GB of
dequant-in-tile int8 weights (ops/quant_matmul.py) on a 15.75 GB chip,
leaving ~7 GB for KV — through BOTH study workloads:

- the phase-1 45-profile counterfactual sweep (the decode-bound hot loop the
  reference runs as sequential API calls, phase1_bias_detection.py:325-340),
  with the decode-step MARGINAL measured by diffing two decode lengths so
  prefill can't smear the step time;
- a phase-2 60-item listwise ranking batch (the prefill-bound workload,
  phase2_cross_model_eval.py:319-432), flash prefill.

Weights are randomly initialized: values change neither FLOPs nor bytes
streamed, so throughput/bandwidth are representative (project convention
since round 1); real Llama weights are a --weights-dir flag away.

    python tools/serve_8b_live.py            # full (also writes the proof)
    python tools/serve_8b_live.py --no-save  # measure only
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V5E_HBM_GB = 15.75  # the v5e compiler's own HBM figure


def run(max_new: int = 128, include_probe: bool = True,
        kv_quant: bool = False, skip_listwise: bool = False) -> dict:
    """``kv_quant=True`` serves the sweep with the int8 KV cache on top of
    the int8 weights — the KV/prefix reads are ~2.3 GB of the 10 GB step,
    so halving them probes whether the 8B operating point is KV-bound the
    way the gpt2 batch-360 curve is."""
    import dataclasses

    import jax

    from bench import (
        build_listwise_prompts,
        build_sweep_prompts,
        decode_step_bytes,
        measure_achievable_gbps,
    )
    from fairness_llm_tpu.config import ModelSettings
    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    if max_new < 16:
        raise ValueError(
            "max_new must be >= 16: the marginal-step measurement diffs "
            "decode lengths max_new and max(8, max_new//4)"
        )
    config = get_model_config("llama3-8b-int8")
    if kv_quant:
        config = dataclasses.replace(config, kv_cache_quant=True)
    t0 = time.time()
    eng = DecodeEngine(config, seed=0)
    jax.block_until_ready(jax.tree.leaves(eng.params)[0])
    init_s = time.time() - t0
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))

    prompts = build_sweep_prompts()  # the 45-profile grid

    def timed(new):
        settings = ModelSettings(temperature=0.7, top_k=0, top_p=1.0, max_tokens=new)
        t0 = time.time()
        eng.generate(prompts, settings, seed=0)  # compile + warmup
        compile_s = time.time() - t0
        best, out = None, None
        for rep in range(2):
            t0 = time.perf_counter()
            out = eng.generate(prompts, settings, seed=rep + 1)
            jax.block_until_ready(out.tokens)
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        return best, compile_s, out

    short = max(8, max_new // 4)
    wall_short, compile_a, _ = timed(short)
    wall_long, compile_b, out = timed(max_new)
    ms_step = (wall_long - wall_short) / (max_new - short) * 1e3

    step_bytes = decode_step_bytes(config, out.stats)
    achievable = measure_achievable_gbps() if include_probe else None

    # HBM occupancy at the sweep operating point: exact param-tree bytes +
    # the analytic KV/prefix accounting the roofline model uses.
    per_head_slot = (config.head_dim + 4) if config.kv_cache_quant else (
        config.head_dim * 2
    )
    per_slot = config.num_kv_heads * per_head_slot * 2 * config.num_layers
    kv_bytes = out.stats["batch"] * out.stats["cache_slots"] * per_slot
    prefix_bytes = out.stats["prefix_len"] * per_slot
    used_gb = (param_bytes + kv_bytes + prefix_bytes) / 1e9

    result = {
        "model": config.name + ("+int8kv" if kv_quant else ""),
        "baseline_config": "BASELINE.json configs[1]: Llama-3-8B, TP=1, one chip",
        "init_s": round(init_s, 1),
        "param_tree_gb": round(param_bytes / 1e9, 2),
        "phase1_sweep": {
            "profiles": len(prompts),
            "max_new_tokens": max_new,
            "compile_plus_warmup_s": round(compile_a + compile_b, 1),
            "walls_s": [round(wall_short, 3), round(wall_long, 3)],
            "profiles_per_sec": round(len(prompts) / wall_long, 2),
            "ms_per_decode_step_marginal": round(ms_step, 2),
            # Live-row rate: tokens a CLIENT receives per second. The
            # bucketed batch (48) decodes 3 pad rows whose tokens nobody
            # reads — the padded rate stays as the device-throughput view.
            "steady_tokens_per_sec": round(len(prompts) / (ms_step / 1e3), 1),
            "steady_tokens_per_sec_padded": round(
                out.stats["batch"] / (ms_step / 1e3), 1
            ),
            "decode_shape": out.stats,
            "decode_step_bytes_mb": round(step_bytes / 1e6, 1),
            "achieved_hbm_gbps": round(step_bytes / (ms_step / 1e3) / 1e9, 1),
            "achievable_hbm_gbps_probe": (
                round(achievable, 1) if achievable else None
            ),
            "achieved_over_achievable": (
                round(step_bytes / (ms_step / 1e3) / 1e9 / achievable, 3)
                if achievable
                else None
            ),
            "hbm_used_gb": round(used_gb, 2),
            "hbm_limit_gb": V5E_HBM_GB,
            "hbm_headroom_gb": round(V5E_HBM_GB - used_gb, 2),
        },
    }

    if skip_listwise:
        del eng
        return result

    # Phase-2 listwise on the SAME live engine (flash prefill; head_dim 128).
    # share_prefix=False so the flash kernel actually runs (the auto-detected
    # ~64-token shared prefix would route prefill through the dense joint
    # path — round-4 finding).
    try:
        lw_prompts, lw_items, _ = build_listwise_prompts()
        settings = ModelSettings(temperature=0.7, top_k=0, top_p=1.0, max_tokens=32)
        t0 = time.time()
        eng.generate(lw_prompts, settings, seed=0, share_prefix=False)
        lw_compile = time.time() - t0
        best = None
        for rep in range(2):
            t0 = time.perf_counter()
            res = eng.generate(lw_prompts, settings, seed=rep + 1, share_prefix=False)
            jax.block_until_ready(res.tokens)
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        result["phase2_listwise"] = {
            "num_items": len(lw_items),
            "num_queries": len(lw_prompts),
            "compile_s": round(lw_compile, 1),
            "wall_s": round(best, 3),
            "queries_per_sec": round(len(lw_prompts) / best, 3),
            "decode_shape": res.stats,
        }
    except Exception as e:  # noqa: BLE001 — auxiliary measurement only
        print(f"8B phase-2 listwise skipped: {type(e).__name__}: {e}", file=sys.stderr)
        result["phase2_listwise"] = {"error": f"{type(e).__name__}: {e}"}

    del eng
    return result


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    res = run()
    print(json.dumps(res))
    if "--no-save" not in sys.argv:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(root, "results", "proofs", "llama3_8b_live.json")
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        print(f"wrote {path}", file=sys.stderr)
