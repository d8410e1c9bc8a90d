#!/usr/bin/env python3
"""How far the timed path lies from the plain float32 reference at a cell's
own sizes, beside two lower-precision references: the fp8 control
(``perfbench/lib/reference.py``) and, for a family whose reference keeps a
recurrent state (``delta_rule``), that state rounded to bfloat16 at every
token. One process builds the cell as ``perfbench/run.py`` does and warms
it; then for each seed it remakes the weights, serves one pass of the
cell's closed traffic (prefill, then decode through the slot pool), and
prints one JSON line with two readings:

- ``widest_gap``, ``control_fp8``, ``control_bf16_state``: the cell's own
  comparison, as ``perfbench/tools/calibrate.py`` makes it (the served
  sample's widest gap under the reference, and the widest gap of the tokens
  each lower-precision reference puts first at the same positions);
- ``carried``: the logits each slot carries after its request's last token
  against the reference's full forward over prompt and answer at that
  position, per request the largest absolute difference over the
  vocabulary, as the largest, the median and the smallest over
  ``--requests`` requests, for the program and for each lower-precision
  reference.

The line also says which path each kernel's gate took when the programs
were traced.

With ``--float32 SLOTS`` the program is built at float32 activations and run
under ``highest`` matmul precision, in a pool of SLOTS rows that one pass of
SLOTS requests fills: the same programs, kernels and pool at the
reference's own precision, so that its carried logits differ from the
reference's by float32 rounding alone, where a bfloat16 state moves them
by the state's rounding alone. At the served bfloat16 activations the
program's own rounding moves the logits as far as a bfloat16 state does,
and no tolerance on them parts the two. The line then also holds
``tolerance`` (``FLOAT32_TOLERANCE``), whether the program's largest carried
gap lies within it, and whether the bfloat16 state's smallest lies above it.

    python tools/logit_gap.py --workload <cell> --seeds N1,N2 [--requests 16]
        [--float32 SLOTS]
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

# The float32 run's tolerance on carried logits (absolute, over the whole
# vocabulary). What it leaves to the program is float32 rounding: sums over
# 3840 and 11008 products, a state summed over some 320 tokens in another
# order than the reference's, 32 layers deep. A bfloat16 state rounds every
# one of its elements at every token, far above that. On a TPU v5e at
# olmo-hybrid-7b-int8.sweep's widths (five seeds, 40 requests) the program
# read at most 2.0e-5 and a bfloat16 state above 1e-2 on every request, at
# least 0.098 where its smallest was printed (two seeds): this lies 50 times
# above the one and at least 10 times below the other.
FLOAT32_TOLERANCE = 1e-3


def _bf16_state_rule(q, k, v, a, b):
    """``delta_rule`` with the state rounded to bfloat16 after every token."""
    import jax
    import jax.numpy as jnp

    def token(state, t):
        q_t, k_t, v_t, a_t, b_t = t
        state = a_t[..., None] * state
        u = jnp.sum(state * k_t[..., None], axis=-2)
        state = state + (b_t[..., None] * k_t)[..., None] \
            * (v_t - u)[..., None, :]
        # reduce_precision: the TPU compiler drops a round trip of casts
        state = jax.lax.reduce_precision(state, exponent_bits=8,
                                         mantissa_bits=7)
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    n, _, h, dk = q.shape
    _, o = jax.lax.scan(token, jnp.zeros((n, h, dk, v.shape[-1]),
                                         jnp.float32),
                        tuple(t.swapaxes(0, 1) for t in (q, k, v, a, b)))
    return o.swapaxes(0, 1)


def _bf16_state(family):
    """A context in which the family's reference keeps a bfloat16 state.
    Its blocks are handed out as new function objects, made once: the
    reference's jit is keyed by the block, so these are traced under the
    bfloat16 rule, and the plain blocks keep their float32 programs."""
    plain_block, plain_rule = family.block, family.delta_rule
    made = {}

    def block(cfg, kind):
        fn, spec = plain_block(cfg, kind)
        if kind not in made:
            made[kind] = lambda x, layer, s, ops, fn=fn: fn(x, layer, s, ops)
        return made[kind], spec

    @contextlib.contextmanager
    def context():
        family.block, family.delta_rule = block, _bf16_state_rule
        try:
            yield
        finally:
            family.block, family.delta_rule = plain_block, plain_rule

    return context


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--float32", type=int, default=0, metavar="SLOTS",
                    help="float32 activations, highest matmul precision, "
                         "one pass of SLOTS requests in SLOTS rows")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    import run as bench  # perfbench/run.py

    import jax

    c, _ = bench.prepare(a.workload, a.rehearse)
    cfg, mix, pool = c["cfg"], c["mix"], c["pool"]
    if mix["kind"] != "closed":
        raise SystemExit("a closed mix only: one pass, one slot a request")
    precision = contextlib.nullcontext()
    if a.float32:
        cfg = dict(cfg, served=dict(cfg.get("served", {}),
                                    activation_dtype="float32"))
        pool = dict(pool, num_slots=a.float32)
        mix = dict(mix, pass_size=a.float32)
        precision = jax.default_matmul_precision("highest")
    with precision:
        _serve(a, bench, c, cfg, mix, pool)
    return 0


def _serve(a, bench, c, cfg, mix, pool) -> None:
    import numpy as np

    from fairness_llm_tpu.ops.dispatch import COUNTER
    from fairness_llm_tpu.telemetry.registry import get_registry
    from perfbench import families
    from perfbench.lib import correct, drivers, reference
    from perfbench.lib.sut import ServedModel

    seeds = [int(s) for s in a.seeds.split(",")]
    family = families.of(cfg)
    bf16_state = _bf16_state(family) if hasattr(family, "delta_rule") \
        else None
    sut = ServedModel(cfg, c["cell"]["config"], mix, pool, seeds[0])
    warm, drive = drivers.DRIVERS[mix["kind"]]
    warm(sut, mix, seeds[0])
    count = max(3, -(-bench.SAMPLE_TOKENS // max(int(mix["output_tokens"]),
                                                  1)))
    for seed in seeds:
        sut.set_seed_weights(seed)
        window = drive(sut, mix, seed, 1.0, drivers.NoTrace(), 0.0)
        # the last pass: the tenants whose logits the slots carry
        served = window.served[-(window.attempted
                                 // window.marks["passes"]):]
        if len(served) > sut.scheduler.num_slots:
            raise SystemExit("a pass larger than the pool recycles slots")
        carried = np.asarray(sut.scheduler._prev_logits)
        # which path each kernel's gate chose when the programs were traced
        paths = {f"{m.labels.get('kernel')}/{m.labels.get('path')}": m.value
                 for m in get_registry().instruments() if m.name == COUNTER}

        sample = correct.draw_sample(window.served, seed, count)
        row = {"workload": a.workload, "seed": seed,
               "activations": "float32" if a.float32 else "served",
               "attempted": window.attempted, "failed": window.failed,
               "length_faults": correct.length_faults(
                   sample, cfg["eos_token_id"]),
               "kernel_paths": paths}
        row.update(correct.gaps(cfg, seed, sample, control_formats=["fp8"]))
        if bf16_state:
            # the tokens the bfloat16 state puts first, under the reference
            tokens, take, counts = correct._teacher_forced(sample)
            ref = reference.logits_at(cfg, seed, tokens, take)
            with bf16_state():
                first = reference.logits_at(cfg, seed, tokens,
                                            take).argmax(-1)
            mask = np.arange(take.shape[1])[None, :] < counts[:, None]
            got = np.take_along_axis(ref, first[:, :, None], -1)[:, :, 0]
            row["control_bf16_state"] = float(np.max(np.where(
                mask, ref.max(-1) - got, 0.0)))

        rows = [s for s in served if s.ok][: a.requests]
        seqs = [np.concatenate([s.spec.ids, s.tokens]) for s in rows]
        width = -(-max(map(len, seqs)) // 128) * 128
        tokens = np.zeros((len(seqs), width), np.int32)
        for r, seq in enumerate(seqs):
            tokens[r, :len(seq)] = seq
        last = np.asarray([[len(seq) - 1] for seq in seqs], np.int32)

        def logits(**kw):
            return reference.logits_at(cfg, seed, tokens, last, **kw)[:, 0]

        ref = logits()
        # each request's own slot: the one whose carried logits lie nearest
        gaps = {"program": np.abs(carried[None] - ref[:, None]).max(
            axis=-1).min(axis=1),
                "fp8_control": np.abs(logits(act_format="fp8") - ref).max(-1)}
        if bf16_state:
            with bf16_state():
                low = logits()
            gaps["bf16_state"] = np.abs(low - ref).max(-1)
            row["bf16_state_void"] = bool(np.array_equal(low, ref))
        row["carried"] = {
            "requests": len(seqs),
            "positions": [int(x) for x in last[:, 0] + 1],
            "reference_logit_std": float(ref.std()),
            **{name: {"largest": float(g.max()),
                      "median": float(np.median(g)),
                      "smallest": float(g.min())}
               for name, g in gaps.items()}}
        if a.float32 and bf16_state:
            row["carried"].update(
                tolerance=FLOAT32_TOLERANCE,
                program_within=bool(gaps["program"].max()
                                    <= FLOAT32_TOLERANCE),
                bf16_state_fails_each=bool(gaps["bf16_state"].min()
                                           > FLOAT32_TOLERANCE))
        print("LOGIT_GAP " + json.dumps(row), flush=True)
    sut.free()


if __name__ == "__main__":
    sys.exit(main())
