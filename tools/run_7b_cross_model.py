"""Live cross-model phase 2 at 7B scale: the BASELINE configs[2] set served
serially on the one real v5e chip.

The reference's cross-model ranking comparison
(phase2_cross_model_eval.py:319-432) evaluates each model over the same
corpus with listwise AND pairwise prompts and compares fairness. Here that
comparison runs over REAL 7B-class architectures — mistral-7b-int8,
qwen2-7b-int8, gemma-7b-int8 — each fitting the single chip via int8
dequant-in-tile weights (ops/quant_matmul.py), with random weights (bytes
and FLOPs representative; real checkpoints are a --weights-dir away).

Per-model serving notes (the chip is 15.75 GB):
- mistral/qwen2: params 7.4 / 8.2 GB; the 200-comparison pairwise batch's
  bf16 KV (~12.6 GB at batch 200 for mistral's 8 kv-heads) does NOT fit
  beside the params, so pairwise decodes in chunks (ChunkedEngineBackend).
- gemma: params 9.3 GB, but its MHA cache (16 kv heads x head_dim 256 =
  459 KB/slot bf16) is 4-8x the GQA models' — the listwise batch alone
  would need ~10.9 GB of bf16 KV. It runs with the int8 KV cache
  (kv_cache_quant, the capacity lever built for exactly this) and smaller
  pairwise chunks. "If it fits with cache" resolves to: bf16 NO, int8 YES.

    python tools/run_7b_cross_model.py [--quick]
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import sys
import time
from typing import List, Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (chunk, kv_cache_quant): pairwise decode chunk size and cache mode chosen
# from the per-slot KV arithmetic above.
MODELS = {
    "mistral-7b-int8": {"chunk": 96, "kv_cache_quant": False},
    "qwen2-7b-int8": {"chunk": 128, "kv_cache_quant": False},
    "gemma-7b-int8": {"chunk": 32, "kv_cache_quant": True},
}


def _chunked(backend_cls):
    class ChunkedEngineBackend(backend_cls):
        """EngineBackend that splits generate() into <=chunk-row decodes.

        Exists because a 200-row pairwise batch's KV cache does not fit
        beside 7-9 GB of 7B params on one chip. Chunking changes default
        row seeds (they're positional), so outputs are deterministic PER
        CHUNK SIZE — the chunk size is pinned in the record's metadata.
        """

        def __init__(self, engine, chunk: int, name=None):
            super().__init__(engine, name=name)
            self.chunk = chunk

        def generate(self, prompts, settings=None, seed=0, keys=None,
                     prefix_ids=None) -> List[str]:
            out: List[str] = []
            for i in range(0, len(prompts), self.chunk):
                out.extend(
                    super().generate(
                        prompts[i : i + self.chunk], settings, seed=seed + i,
                        keys=None if keys is None else keys[i : i + self.chunk],
                        prefix_ids=prefix_ids,
                    )
                )
            return out

    return ChunkedEngineBackend


def run(num_items: int = 60, num_queries: int = 4, num_comparisons: int = 200,
        max_tokens: int = 128, models: Optional[Sequence[str]] = None) -> dict:
    import jax

    from fairness_llm_tpu.config import ModelSettings, default_config
    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.pipeline.backends import EngineBackend
    from fairness_llm_tpu.pipeline.phase2 import (
        build_corpus,
        compare_models_and_methods,
        evaluate_model,
    )
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    config = default_config()
    items, prov = build_corpus(config, "movielens", num_items, with_provenance=True)
    settings = ModelSettings(temperature=0.7, top_k=0, top_p=1.0, max_tokens=max_tokens)
    Chunked = _chunked(EngineBackend)

    t_run = time.time()
    model_results = {}
    per_model_perf = {}
    for name in models or MODELS:
        opts = MODELS[name]
        cfg = get_model_config(name)
        if opts["kv_cache_quant"]:
            cfg = dataclasses.replace(cfg, kv_cache_quant=True)
        t0 = time.time()
        eng = DecodeEngine(cfg, seed=0)
        jax.block_until_ready(jax.tree.leaves(eng.params)[0])
        init_s = time.time() - t0
        param_gb = sum(x.nbytes for x in jax.tree.leaves(eng.params)) / 1e9
        backend = Chunked(eng, chunk=opts["chunk"], name=name)
        t0 = time.time()
        model_results[name] = evaluate_model(
            backend, items, num_comparisons, settings,
            seed=config.random_seed, num_queries=num_queries,
        )
        eval_s = time.time() - t0
        per_model_perf[name] = {
            "init_s": round(init_s, 1),
            "param_tree_gb": round(param_gb, 2),
            "kv_cache_quant": opts["kv_cache_quant"],
            "pairwise_chunk": opts["chunk"],
            "eval_wall_s": round(eval_s, 1),
            # one listwise batch + chunked pairwise + scored + perplexity,
            # compiles included — the end-to-end number a study run pays
            "eval_calls_per_sec": round(
                (num_queries + num_comparisons) / eval_s, 2
            ),
        }
        print(f"{name}: init {init_s:.0f}s eval {eval_s:.0f}s", file=sys.stderr)
        del backend, eng

    results = {
        "metadata": {
            "phase": 2,
            "variant": "7b-cross-model-live",
            "device": str(jax.devices()[0]),
            "models": list(models or MODELS),
            "corpus": "movielens",
            "corpus_provenance": prov,
            "num_items": len(items),
            "num_queries": num_queries,
            "num_comparisons": num_comparisons,
            "max_tokens": max_tokens,
            "weights": "random-init (bytes/FLOPs representative; see tool docstring)",
            "timestamp": time.time(),
            "elapsed_seconds": round(time.time() - t_run, 1),
        },
        "items": [vars(it) for it in items],
        "per_model_perf": per_model_perf,
        "model_results": model_results,
        "comparison": compare_models_and_methods(model_results),
    }
    return results


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    logging.basicConfig(level=logging.INFO)
    quick = "--quick" in sys.argv
    res = run(
        num_items=12 if quick else 60,
        num_queries=2 if quick else 4,
        num_comparisons=8 if quick else 200,
        max_tokens=16 if quick else 128,
        models=["mistral-7b-int8"] if quick else None,
    )
    out_path = os.path.join(ROOT, "results", "phase2", "phase2_7b_results.json")
    if quick:
        out_path = "/tmp/phase2_7b_quick.json"
    from fairness_llm_tpu.pipeline import results as R

    R.save_results(res, out_path)
    print(json.dumps({
        "wrote": out_path,
        "per_model_perf": res["per_model_perf"],
        "model_fairness": res["comparison"]["model_fairness"],
    }))
