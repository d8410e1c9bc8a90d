"""Live A/B: fused int8-KV decode-attention kernel vs XLA, large-batch sweep.

The round-3 bf16 kernel lost to XLA's fusions (~8% at batch 48); the int8
variant is the one kernel target with a byte-reduction story — at batch
192/360 decode is KV-bound and int8-KV already wins +24% through plain XLA
despite its dequant cost (ROADMAP.md S1). This measures whether
dequant-in-tile beats XLA's fused dequant at the shapes that matter.

    python tools/ab_int8kv_kernel.py [model] [mults...]   # default gpt2-small 4 8
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(model_name: str = "gpt2-small", mults=(4, 8)) -> dict:
    import jax

    from bench import MAX_NEW_TOKENS, build_sweep_prompts
    from fairness_llm_tpu.config import ModelSettings
    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    base_prompts = build_sweep_prompts()
    settings = ModelSettings(
        temperature=0.7, top_k=0, top_p=1.0, max_tokens=MAX_NEW_TOKENS
    )
    out = {"model": model_name}
    for mult in mults:
        prompts = list(base_prompts) * mult
        row = {}
        for label, kernel in (("xla", False), ("kernel", True)):
            cfg = dataclasses.replace(
                get_model_config(model_name),
                kv_cache_quant=True,
                use_decode_attention_kernel=kernel,
            )
            eng = DecodeEngine(cfg, seed=0)
            eng.generate(prompts, settings, seed=0)  # warmup/compile
            best = None
            for rep in range(3):
                t0 = time.perf_counter()
                res = eng.generate(prompts, settings, seed=rep + 1)
                jax.block_until_ready(res.tokens)
                wall = time.perf_counter() - t0
                best = wall if best is None else min(best, wall)
            row[label] = {
                "best_wall_s": round(best, 3),
                "profiles_per_sec": round(len(prompts) / best, 2),
                "decode_shape": res.stats,
            }
            del eng
        row["kernel_speedup"] = round(
            row["xla"]["best_wall_s"] / row["kernel"]["best_wall_s"], 3
        )
        out[f"x{mult}"] = row
    return out


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    name = sys.argv[1] if len(sys.argv) > 1 else "gpt2-small"
    mults = [int(a) for a in sys.argv[2:]] or [4, 8]
    print(json.dumps(run(name, mults)))
