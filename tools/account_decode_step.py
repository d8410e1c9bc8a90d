"""Component accounting for the decode step (VERDICT r3 item 6).

Traces the 45-profile sweep on the live chip, aggregates EVERY device op in
the capture, classifies ops into decode-step components, and prints a table
whose rows sum to the measured device time — so the "remaining gap to the
streaming ceiling is work the step must do" claim rests on a full
accounting, not one attention-only harness.

    python tools/account_decode_step.py [model] [out.json]
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Classification: the SHARED component taxonomy (telemetry/costmodel.py) —
# the same first-match-wins table the live jaxpr cost ledger publishes
# under, so an offline xplane capture and the live cost_ledger_bytes gauges
# bucket work identically. The patterns/order are the ones this tool owned
# through round 11 (regression-pinned in tests/test_costmodel.py).
from fairness_llm_tpu.telemetry.costmodel import (  # noqa: E402
    COMPONENT_TITLES,
    COMPONENTS,
    classify,
)


def run(model_name: str = "gpt2-small") -> dict:
    import jax

    from bench import MAX_NEW_TOKENS, build_sweep_prompts
    from fairness_llm_tpu.config import ModelSettings
    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.runtime.engine import DecodeEngine
    from fairness_llm_tpu.utils.profiling import summarize_trace

    prompts = build_sweep_prompts()
    settings = ModelSettings(
        temperature=0.7, top_k=0, top_p=1.0, max_tokens=MAX_NEW_TOKENS
    )
    eng = DecodeEngine(get_model_config(model_name), seed=0)
    out = eng.generate(prompts, settings, seed=0)  # warmup/compile

    trace_dir = tempfile.mkdtemp(prefix="decode_trace_")
    with jax.profiler.trace(trace_dir):
        out = eng.generate(prompts, settings, seed=1)
        jax.block_until_ready(out.tokens)

    summaries = summarize_trace(trace_dir, top_k=100000, device_filter="TPU")
    # one capture, one TPU plane expected on the single chip
    s = summaries[0]
    buckets: dict = {}
    for name, ms, cnt in s.top_ops:
        label = classify(name)
        b = buckets.setdefault(label, {"ms": 0.0, "events": 0, "top": []})
        b["ms"] += ms
        b["events"] += cnt
        b["top"].append((round(ms, 2), cnt, name[:90]))
    for b in buckets.values():
        b["top"] = sorted(b["top"], reverse=True)[:5]
        b["ms"] = round(b["ms"], 2)

    steps = MAX_NEW_TOKENS  # random weights never EOS: full trip count
    all_ops = sorted(
        ((round(ms, 3), cnt, name[:160]) for name, ms, cnt in s.top_ops),
        reverse=True,
    )[:150]
    table = sorted(buckets.items(), key=lambda kv: -kv[1]["ms"])
    result = {
        "model": model_name,
        "device_total_ms": round(s.total_ms, 1),
        "num_events": s.num_events,
        "decode_steps": steps,
        "decode_shape": out.stats,
        "top_ops": all_ops,
        "components": {
            label: {
                "ms": b["ms"],
                "ms_per_step": round(b["ms"] / steps, 4),
                "pct": round(100 * b["ms"] / s.total_ms, 1),
                "events": b["events"],
                "top_ops": b["top"],
            }
            for label, b in table
        },
    }
    return result


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    model = sys.argv[1] if len(sys.argv) > 1 else "gpt2-small"
    res = run(model)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            json.dump(res, f, indent=1)
    comps = res.pop("components")
    print(json.dumps(res))
    for label, c in comps.items():
        title = COMPONENT_TITLES.get(label, label)
        print(f"{c['ms']:9.1f} ms ({c['pct']:4.1f}%)  x{c['events']:7d}  {title}")
        for ms, cnt, name in c["top_ops"][:3]:
            print(f"    {ms:8.2f} ms x{cnt:6d}  {name}")
