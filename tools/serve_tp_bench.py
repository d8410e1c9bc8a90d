#!/usr/bin/env python
"""Real-mesh tensor-parallel serving benchmark (the bench's ``serve_tp``
entry).

Measures what the emulated 70B shard (``tools/measure_70b_shard.py``, the
``llama70b_shard`` entry) deliberately omits: decode over an ACTUAL tp mesh
with the collectives executed — every step program lowered as one SPMD
computation, params + slot KV cache + carried logits sharded, XLA-inserted
all-reduces on the wire. This worker is the CPU-mesh harness of that path
and nothing else: it pins itself to the CPU platform with ``--tp`` forced
host devices (``--xla_force_host_platform_device_count``), so it runs in CI,
never touches a chip its parent may hold, and its record names the platform
it ran on. The same path on real chips is ``chip_smoke.py --tp N``.

Must be a subprocess of bench.py / CI, never imported into a live jax
process: the forced host device count only takes effect when set BEFORE
jax initializes, which is why the env mutation sits above the imports.

Contract (asserted, not just reported):
  * token-for-token parity: tp=N serving — fuse 1 AND 4 — decodes
    exactly the single-device engine's greedy stream;
  * collectives executed: the compiled tp step program's HLO contains
    all-reduce (plus the cost ledger's nonzero ``collectives`` row under
    the ``@tp<N>`` program label).

Emits one JSON object on the last stdout line (bench.py parses it):
wall-clock tokens/sec per variant plus the exact token checksum the perf
sentinel compares byte-for-byte.
"""

import argparse
import hashlib
import json
import os
import sys
import time

ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
ap.add_argument("--tp", type=int, default=2)
ap.add_argument("--model", default="tiny-test")
ap.add_argument("--reps", type=int, default=3)
args = ap.parse_args()

if len(jax_flags := os.environ.get("XLA_FLAGS", "")) == 0 or \
        "host_platform_device_count" not in jax_flags:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.tp} " + jax_flags)
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from fairness_llm_tpu.config import (  # noqa: E402
    MeshConfig,
    ModelSettings,
    ServingConfig,
)
from fairness_llm_tpu.models.configs import get_model_config  # noqa: E402
from fairness_llm_tpu.parallel import make_mesh  # noqa: E402
from fairness_llm_tpu.runtime.engine import DecodeEngine  # noqa: E402
from fairness_llm_tpu.serving import ContinuousScheduler, Request  # noqa: E402
from fairness_llm_tpu.telemetry import use_registry  # noqa: E402

M = 16  # decode budget per request
NUM_SLOTS = 4
GREEDY = ModelSettings(temperature=0.0, top_k=0, top_p=1.0, max_tokens=M)
PROMPTS = [
    "the cat sat on the mat",
    "a b c d e f g h",
    "one two three four five six seven",
    "to be or not to be that is the question",
    "pack my box with five dozen",
    "colorless green ideas sleep furiously now",
    "the quick brown fox jumps over",
    "never gonna give you up never",
]


def run(tp: int, model_name: str, reps: int) -> dict:
    if jax.device_count() % tp != 0:
        raise SystemExit(
            f"device count {jax.device_count()} not divisible by tp={tp}")
    cfg = get_model_config(model_name)
    out: dict = {"tp": tp, "model": model_name,
                 "platform": jax.devices()[0].platform,
                 "device_kind": jax.devices()[0].device_kind,
                 "devices": jax.device_count()}

    # Single-device greedy reference: the parity oracle AND the speed
    # baseline the tp variants are compared against.
    ref_engine = DecodeEngine(cfg, seed=0)
    ref = [ref_engine.generate([p], settings=GREEDY, max_new_tokens=M)
           for p in PROMPTS]
    ref_tokens = [tuple(int(t) for t in r.tokens[0]) for r in ref]
    out["token_checksum"] = hashlib.sha256(
        repr(ref_tokens).encode()).hexdigest()[:16]
    out["useful_tokens"] = sum(len(t) for t in ref_tokens)

    mesh = make_mesh(MeshConfig(tp=tp))
    collective_rows = {}
    for fuse in (1, 4):
        tag = f"contig_k{fuse}"
        engine = DecodeEngine(cfg, seed=0, mesh=mesh)
        with use_registry() as reg:
            sched = ContinuousScheduler(
                engine,
                ServingConfig(
                    enabled=True, num_slots=NUM_SLOTS, decode_chunk=4,
                    fuse_steps=fuse, max_new_tokens=M, tp=tp,
                ),
                settings=GREEDY,
            )

            def serve(rep):
                reqs = [Request(prompt=p, id=f"{tag}_{rep}_{i}",
                                settings=GREEDY)
                        for i, p in enumerate(PROMPTS)]
                t0 = time.perf_counter()
                results = sched.serve(reqs)
                wall = time.perf_counter() - t0
                toks = [tuple(int(t) for t in r.tokens)
                        for r in results]
                assert all(r.ok for r in results), (tag, results)
                return wall, toks

            serve("warm")  # compile outside the timed reps
            best = None
            for rep in range(reps):
                wall, toks = serve(rep)
                assert toks == ref_tokens, (
                    f"{tag}: tp={tp} token stream diverged from the "
                    f"single-device engine")
                if best is None or wall < best:
                    best = wall
            # Collectives executed, not omitted: the ledger published
            # a nonzero collectives row under this tp program label.
            coll = sum(
                inst.value for inst in reg.instruments()
                if inst.name == "cost_ledger_bytes"
                and inst.labels.get("component") == "collectives"
                and f"@tp{tp}" in inst.labels.get("program", "")
            )
            assert coll > 0, f"{tag}: no collectives attributed"
            collective_rows[tag] = coll
        out[tag] = {
            "wall_s": round(best, 3),
            "tokens_per_sec": round(out["useful_tokens"] / best, 1),
        }

    # HLO witness: the sharded contiguous step program really contains
    # all-reduce ops (GSPMD inserted them post-partitioning, so the jaxpr
    # can't show them — the compiled module can).
    import flax.linen as nn

    from fairness_llm_tpu.parallel.sharding import make_axis_rules
    from fairness_llm_tpu.runtime.stepbuilder import build_serve_step
    from fairness_llm_tpu.runtime.sampling import SamplerSettings

    engine = DecodeEngine(cfg, seed=0, mesh=mesh)
    sched = ContinuousScheduler(
        engine, ServingConfig(enabled=True, num_slots=NUM_SLOTS,
                              decode_chunk=4, max_new_tokens=M, tp=tp),
        settings=GREEDY)
    step = build_serve_step(
        engine.config, engine.model, SamplerSettings(),
        engine.tokenizer.pad_id, engine.tokenizer.eos_id,
        num_slots=NUM_SLOTS, chunk=4, guard=False, fuse=1,
    )
    import jax.numpy as jnp

    zeros = lambda *s, dt=jnp.int32: jnp.zeros(s, dt)  # noqa: E731
    with mesh, nn.logical_axis_rules(make_axis_rules(cfg, mesh)):
        lowered = jax.jit(step).lower(
            engine.params, sched._cache, sched._prev_logits,
            zeros(NUM_SLOTS), zeros(NUM_SLOTS), zeros(NUM_SLOTS),
            zeros(NUM_SLOTS), zeros(NUM_SLOTS, dt=jnp.bool_),
            zeros(NUM_SLOTS, dt=jnp.bool_),
        )
        hlo = lowered.compile().as_text()
    out["all_reduce_in_hlo"] = hlo.count("all-reduce")
    assert out["all_reduce_in_hlo"] > 0, \
        "tp step program compiled without any all-reduce"
    out["collective_ledger_bytes"] = collective_rows
    # The single-device reference walls are batch-1 static calls, not a
    # load-parity A/B, so only the serving-loop rates are reported;
    # cross-variant ratios are meaningful within this record.
    return out


if __name__ == "__main__":
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    rec = run(args.tp, args.model, args.reps)
    print(json.dumps(rec))
