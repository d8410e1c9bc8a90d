"""Live single-chip A/B: int8 weight kernel vs bf16 on the phase-1 sweep.

Proves the dequant-in-tile kernel is not a throughput regression on a model
that fits one chip both ways (llama3.2-1B by default; the 70B fit itself is
proven AOT in tools/prove_70b_int8_fit.py). Run on the TPU chip:

    python tools/ab_int8_weights.py [model] [reps]
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(model_name: str = "llama32-1b", reps: int = 3) -> dict:
    import jax

    from bench import MAX_NEW_TOKENS, build_sweep_prompts, decode_step_bytes
    from fairness_llm_tpu.config import ModelSettings
    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    prompts = build_sweep_prompts()
    settings = ModelSettings(
        temperature=0.7, top_k=0, top_p=1.0, max_tokens=MAX_NEW_TOKENS
    )
    out = {"model": model_name, "profiles": len(prompts)}
    for label in ("bf16", "int8"):
        cfg = get_model_config(model_name)
        if label == "int8":
            cfg = dataclasses.replace(cfg, weight_quant="int8")
        eng = DecodeEngine(cfg, seed=0)
        eng.generate(prompts, settings, seed=0)  # warmup/compile
        best = None
        for rep in range(reps):
            t0 = time.perf_counter()
            res = eng.generate(prompts, settings, seed=rep + 1)
            jax.block_until_ready(res.tokens)
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        out[label] = {
            "best_wall_s": round(best, 3),
            "profiles_per_sec": round(len(prompts) / best, 2),
            "decode_shape": res.stats,
        }
        del eng
    out["int8_speedup"] = round(
        out["bf16"]["best_wall_s"] / out["int8"]["best_wall_s"], 3
    )
    return out


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    name = sys.argv[1] if len(sys.argv) > 1 else "llama32-1b"
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    print(json.dumps(run(name, reps)))
