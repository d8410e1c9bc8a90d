#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the three-phase study once through the path a user calls
(``cli.main.build_parser`` -> ``config_from_args`` -> ``backend_for`` ->
``ServingBackend`` -> ``ContinuousScheduler`` -> ``stepbuilder`` programs,
phases run by ``cli.main.run_study_phase``) on full-width Mistral-7B with int8
weights made from the seed, at ``--quick`` sizes, then checks what came out:

- every request of every sweep completed (no failed, requeued, expired, shed
  or truncated request, no numerics fault, tokens for every row);
- the Pallas int8 matmul and flash attention agree with a float64 reference
  on a small input at the model's width, every kernel gate chose the kernel,
  and the compiled ``serve_prefill``/``serve_step`` hold the Mosaic custom
  calls;
- a repeated phase-1 sweep compiles nothing, and the device reports its
  memory limit and peak.

With no argument it is the one-chip run and refuses anything but a TPU: it
sets no platform, never falls back, and exits non-zero (printing no result)
on any failed check. ``--tp N`` and ``--mesh dp=N`` are the four-chip passes
(tensor-parallel serving; the dp-sharded static sweep with the on-device
metric reduction). ``--rehearse`` runs the same script on whatever JAX finds
(the CPU, here) with the model cut to a tiny width, and says so first.

Last line of stdout on success: one JSON object naming the device. Walls are
reported as set-up and steady time; nothing about speed is asserted.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import sys
import time
from typing import Dict, List

MODEL = "mistral-7b"
MAX_NEW_TOKENS = 128
# The study's own prompts run to 1128 byte-tokens (phase 3's fairness-aware
# prompt); the serving default of 512 would truncate every one. The budget
# and the slot pool are set through Config. serve_prefill is donated the
# pool, so admission holds it once: tools/aot_preflight.py puts 8 slots x
# (1152 + 128) positions at 11.07 GB live, 2.35 of them the call's
# temporaries, beside the 7.4 GB of int8 weights (16 slots: 12.4 of 15.75 GB).
PROMPT_BUDGET = 1152
NUM_SLOTS = 8
# --rehearse: the same model family flags at a width a CPU decodes in seconds.
REHEARSAL_CUT = dict(num_layers=2, d_model=256, num_heads=16, num_kv_heads=4,
                     d_ff=512)


class _Warnings(logging.Handler):
    """Keeps every WARNING+ record of the run: the truncation and cost-ledger
    checks read them, and a failed run prints their tail as the reason."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages: List[str] = []

    def emit(self, record):
        self.messages.append(f"{record.name}: {record.getMessage()}")


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever JAX finds, model cut to a tiny "
                         "width (the CPU rehearsal of this same script)")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--tp", type=int, default=None, metavar="N",
                      help="serve tensor-parallel over N chips")
    mode.add_argument("--mesh", default=None, metavar="dp=N",
                      help="run the static phase-1 sweep dp-sharded over N "
                           "chips instead of serving")
    return ap.parse_args(argv)


def _study_argv(a, results_dir: str) -> List[str]:
    argv = ["--quick", "--model", MODEL, "--weight-quant", "int8",
            "--numerics-guards", "--max-new-tokens", str(MAX_NEW_TOKENS),
            "--results-dir", results_dir]
    if a.mesh:
        return argv + ["--phase", "1", "--mesh", a.mesh]
    argv += ["--all", "--continuous"]
    if a.tp:
        argv += ["--tp", str(a.tp)]
    return argv


def _kernel_oracles(cfg, interpret: bool) -> List[str]:
    """Both main-path kernels against a float64 numpy reference on a small
    input at the model's width (``interpret`` runs them off the TPU)."""
    import jax.numpy as jnp
    import numpy as np

    from fairness_llm_tpu.ops import flash_attention
    from fairness_llm_tpu.ops.quant_matmul import quant_matmul

    problems = []
    rng = np.random.default_rng(0)
    # One MLP up-projection and the lm_head: the widest and the oddest N.
    for k, n in ((cfg.d_model, cfg.d_ff), (cfg.d_model, cfg.vocab_size)):
        x = jnp.asarray(rng.normal(size=(16, k)), jnp.bfloat16)
        wq = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int8)
        scale = jnp.asarray(rng.uniform(2e-4, 8e-4, size=(n,)), jnp.float32)
        got = np.asarray(quant_matmul(x, wq, scale, interpret=interpret),
                         np.float64)
        want = (np.asarray(x, np.float64) @ np.asarray(wq, np.float64)) \
            * np.asarray(scale, np.float64)
        err = np.abs(got - want).max() / np.abs(want).max()
        print(f"oracle int8_matmul [{k}x{n}]: max error {err:.2e} of max |y|")
        if not err < 1e-2:  # bf16 output rounding is 4e-3; also catches NaN
            problems.append(f"int8_matmul [{k}x{n}] disagrees with the "
                            f"reference (error {err:.2e})")
    B, S, D = 2, 256, cfg.head_dim
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    q, k, v = (jnp.asarray(rng.normal(size=(B, h, S, D)), jnp.bfloat16)
               for h in (H, Hkv, Hkv))
    lengths = np.array([S, S - 37], np.int32)  # left-padded rows
    got = np.asarray(flash_attention(
        q, k, v, jnp.asarray(lengths), causal=True,
        window=cfg.sliding_window, interpret=interpret), np.float64)
    qf, kf, vf = (np.asarray(t, np.float64) for t in (q, k, v))
    kf, vf = (np.repeat(t, H // Hkv, axis=1) for t in (kf, vf))
    s = np.einsum("bhqd,bhkd->bhqk", qf, kf) / np.sqrt(D)
    ii, jj = np.arange(S)[:, None], np.arange(S)[None, :]
    mask = (jj >= (S - lengths)[:, None, None, None]) & (jj <= ii)
    if cfg.sliding_window is not None:
        mask &= (ii - jj) < cfg.sliding_window
    s = np.where(mask, s, -np.inf)
    with np.errstate(invalid="ignore"):  # all-masked pad query rows
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), vf)
    real_q = (np.arange(S)[None, :] >= (S - lengths)[:, None])[:, None, :, None]
    err = np.abs(np.where(real_q, got - want, 0.0)).max()
    print(f"oracle flash_attention [B{B} H{H}/{Hkv} S{S} D{D}]: "
          f"max abs error {err:.2e}")
    if not err < 2e-2:
        problems.append(f"flash_attention disagrees with the reference "
                        f"(error {err:.2e})")
    return problems


def _expected_xla_shapes(cfg, tp: int) -> set:
    """Local int8 weight shapes the tile gate is RIGHT to refuse under a
    tp-way mesh (e.g. Mistral's 32000/4 = 8000-wide lm_head shard)."""
    from fairness_llm_tpu.ops.quant_matmul import quant_tileable

    def cut(size):
        return size // tp if size % tp == 0 else size

    d = cfg.d_model
    local = [(d, cut(cfg.q_dim)), (d, cut(cfg.kv_dim)), (cut(cfg.q_dim), d),
             (d, cut(cfg.d_ff)), (cut(cfg.d_ff), d), (d, cut(cfg.vocab_size))]
    return {f"{k}x{n}" for k, n in local if not quant_tileable(k, n)}


def _check_kernels(reg, cfg, tp: int, on_tpu: bool, static: bool) -> List[str]:
    """The kernels ran and were not replaced: oracles at width, no gate on the
    XLA branch for a shape the kernel takes, Mosaic custom calls (and, under
    tp, all-reduces) in the compiled programs the run actually built."""
    from fairness_llm_tpu.ops.dispatch import xla_paths

    problems = _kernel_oracles(cfg, interpret=not on_tpu)
    allowed = _expected_xla_shapes(cfg, tp)
    for w in xla_paths():
        expected = (w["reason"] == "untileable" and w["shape"] in allowed
                    and w["kernel"] == "int8_matmul") \
            or (w["reason"] == "not_tpu" and not on_tpu)
        print(f"kernel gate: {w['kernel']} {w['shape']} took the XLA path "
              f"({w['reason']}){'' if expected else '  <-- NOT expected'}")
        if not expected:
            problems.append(f"{w['kernel']} {w['shape']} took the XLA path "
                            f"({w['reason']}) on a shape the configuration "
                            "says the kernel takes")
    suffix = f"@tp{tp}" if tp > 1 else ""
    # The static sweep shares its prompt prefix: the prefix program takes the
    # flash kernel, the decode program attends over prefix + own cache in XLA.
    # The served prefill extends the slot pool a window at a time and attends
    # over the cache in XLA too.
    wanted = ([("prefix", "int8_matmul"), ("prefix", "flash_attention"),
               ("decode", "int8_matmul")]
              if static else
              [(f"serve_prefill{suffix}", "int8_matmul"),
               (f"serve_step{suffix}", "int8_matmul")])
    if tp > 1:
        wanted.append((f"serve_step{suffix}", "all-reduce"))
    for program, op in wanted:
        n = reg.read_value("program_hlo_ops", None, component="memory",
                           program=program, op=op)
        mem = {k: reg.read_value("program_memory_bytes", None,
                                 component="memory", program=program, kind=k)
               for k in ("argument", "output", "temp")}
        print(f"compiled {program}: {op} x{n}; memory_analysis {mem}")
        if on_tpu and not n:
            problems.append(f"compiled {program} holds no {op}")
    return problems


def _check_devices(params, n_mesh: int, on_tpu: bool) -> List[str]:
    """Every device of the mesh holds its share of the params and reports
    its memory limit and peak."""
    import jax

    problems = []
    shard_bytes = None
    if n_mesh > 1:
        leaves = jax.tree.leaves(params)
        kernel = next(x for x in leaves
                      if x.ndim == 2 and x.dtype == jax.numpy.int8)
        homes = {s.device for s in kernel.addressable_shards}
        shard_bytes = sum(x.addressable_shards[0].data.nbytes for x in leaves)
        print(f"params: {shard_bytes / 1e9:.2f} GB per device on "
              f"{len(homes)} device(s); first int8 kernel {kernel.shape} -> "
              f"shard {kernel.addressable_shards[0].data.shape}")
        if len(homes) != n_mesh:
            problems.append(f"params live on {len(homes)} device(s), the "
                            f"mesh has {n_mesh}")
    for d in jax.devices()[:n_mesh]:
        ms = d.memory_stats()
        if not ms:
            print(f"memory_stats {d}: none reported on {d.platform}")
            if on_tpu:
                problems.append(f"{d} reports no memory_stats")
            continue
        print(f"memory_stats {d}: bytes_limit={ms.get('bytes_limit')} "
              f"peak_bytes_in_use={ms.get('peak_bytes_in_use')} "
              f"bytes_in_use={ms.get('bytes_in_use')}")
        if not ms.get("bytes_limit") or not ms.get("peak_bytes_in_use"):
            problems.append(f"{d} reports no bytes_limit/peak_bytes_in_use")
        elif shard_bytes and ms["bytes_in_use"] < shard_bytes:
            problems.append(f"{d} holds {ms['bytes_in_use']} bytes, less "
                            f"than its {shard_bytes}-byte share of the params")
    return problems


def main(argv=None) -> int:
    a = _parse(sys.argv[1:] if argv is None else argv)
    tp = a.tp or 1
    mode = f"tp={a.tp}" if a.tp else (f"mesh {a.mesh}" if a.mesh else "one chip")
    if a.rehearse:
        print(f"REHEARSAL ({mode}): {MODEL} cut to {REHEARSAL_CUT} on whatever "
              "JAX finds; not a chip run")
    else:
        print(f"chip_smoke ({mode}): full-width {MODEL} int8, TPU only")
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    warnings = _Warnings()
    logging.getLogger().addHandler(warnings)

    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    print(f"jax={jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind} devices={len(devices)} "
          f"compile_cache={cache_dir}")
    if not on_tpu and not a.rehearse:
        print(f"chip_smoke: platform={dev.platform} is not a TPU; refusing to "
              "run (--rehearse is the only way onto another platform)")
        return 1

    cache_events: Dict[str, int] = {}

    def count_event(event, **_):
        if "/compilation_cache/" in event:
            cache_events[event] = cache_events.get(event, 0) + 1

    def count_duration(event, _secs, **_kw):
        if event.endswith("backend_compile_duration"):
            cache_events["compiles"] = cache_events.get("compiles", 0) + 1

    jax.monitoring.register_event_listener(count_event)
    jax.monitoring.register_event_duration_secs_listener(count_duration)

    from fairness_llm_tpu import telemetry as T
    from fairness_llm_tpu.cli.main import (
        build_parser,
        config_from_args,
        run_study_phase,
    )
    from fairness_llm_tpu.config import create_directories
    from fairness_llm_tpu.models import configs as model_configs
    from fairness_llm_tpu.pipeline.backends import backend_for

    if a.rehearse:
        model_configs.MODEL_CONFIGS[MODEL] = dataclasses.replace(
            model_configs.MODEL_CONFIGS[MODEL], **REHEARSAL_CUT)

    results_dir = "chiprun_out/chip_smoke" + (
        f"_tp{a.tp}" if a.tp else "_dp" if a.mesh else "")
    args = build_parser().parse_args(_study_argv(a, results_dir))
    config = config_from_args(args)
    if config.serving.enabled:
        config = dataclasses.replace(config, serving=dataclasses.replace(
            config.serving, num_slots=NUM_SLOTS, max_prompt_len=PROMPT_BUDGET))
    create_directories(config)
    telemetry_dir = f"{results_dir}/telemetry"
    sink = T.configure(telemetry_dir)  # also arms the AOT program capture
    reg = T.get_registry()
    problems: List[str] = []
    walls: Dict[str, float] = {}

    def timed(label, fn, *fargs, **fkw):
        t0 = time.perf_counter()
        out = fn(*fargs, **fkw)
        walls[label] = time.perf_counter() - t0
        return out

    # -- the run -------------------------------------------------------------
    backend = timed("build", backend_for, args.model, config,
                    allow_random=True)
    cfg = backend.engine.config
    served = config.serving.enabled
    if backend.engine.seq_bucket != (128 if on_tpu else 64):
        problems.append(f"seq_bucket {backend.engine.seq_bucket}: the "
                        "flash-eligible 128 bucket did not engage on the TPU")

    rows_seen = {"rows": 0, "no_tokens": 0, "empty_text": 0}
    inner_generate = backend.generate

    def watched_generate(prompts, *gargs, **gkw):
        texts = inner_generate(prompts, *gargs, **gkw)
        rows_seen["rows"] += len(texts)
        rows_seen["empty_text"] += sum(1 for t in texts if not t)
        out = getattr(backend, "last_output", None)
        if out is not None:
            if out.stats["decode_kernel"]:
                problems.append("use_decode_attention_kernel is on")
            pad = backend.engine.tokenizer.pad_id
            rows_seen["no_tokens"] += int(
                (~(out.tokens != pad).any(axis=1)).sum())
        return texts

    backend.generate = watched_generate

    def check_serving(label):
        st = backend.serve_totals
        if st is None:
            problems.append(f"{label}: no serving stats (nothing was served)")
            return
        print(f"{label} serving: admitted={st.admitted} "
              f"completed={st.completed} failed={st.failed} "
              f"requeued={st.requeued} expired={st.expired} shed={st.shed} "
              f"rejected={st.rejected} preempted={st.preempted} "
              f"prefill_batches={st.prefill_batches} "
              f"decode_steps={st.decode_steps} "
              f"decoded_tokens={st.decoded_tokens}")
        lost = (st.failed + st.requeued + st.expired + st.shed + st.rejected
                + st.preempted)
        if st.completed != st.admitted or lost or not st.completed:
            problems.append(f"{label}: serving lost or retried requests "
                            f"({st.as_dict()})")

    def check_rows(label, rows):
        bad = [k for k, r in rows.items()
               if "error" in r or not isinstance(r.get("raw_response"), str)]
        if bad or not rows:
            problems.append(f"{label}: {len(bad)} of {len(rows)} rows failed "
                            f"to decode ({bad[:3]})")

    phases = [1] if a.mesh else [1, 2, 3]
    p1 = None
    try:
        for phase in phases:
            backend.serve_totals = None
            res = timed(f"phase{phase}", run_study_phase, phase, args, config,
                        True, p1, backend=backend)
            if phase == 1:
                p1 = res
                check_rows("phase1", res["recommendations"])
                if a.mesh and res["metadata"]["metric_reduction"] != "dp-psum":
                    problems.append("phase1: the metric reduction ran on the "
                                    "host, not as a psum over the dp mesh")
            if served:
                check_serving(f"phase{phase}")
        # The same sweep again: every shape is warm, so nothing may compile.
        def compiles():
            return sum(m.value for m in reg.instruments()
                       if m.name == "compiles_total")

        before, jax_before = compiles(), cache_events.get("compiles", 0)
        backend.serve_totals = None
        res = timed("phase1_repeat", run_study_phase, 1, args, config, True,
                    None, backend=backend)
        check_rows("phase1 repeat", res["recommendations"])
        if served:
            check_serving("phase1 repeat")
        print(f"repeat sweep: compiles_total {before} -> {compiles()}; XLA "
              f"compile requests {cache_events.get('compiles', 0) - jax_before}")
        if compiles() != before:
            problems.append("the repeated phase-1 sweep compiled a program")
    finally:
        if warnings.messages:
            print(f"-- {len(warnings.messages)} warning(s) logged; last 20:")
            for m in warnings.messages[-20:]:
                print("   " + m[:400])

    # -- what came out ---------------------------------------------------------
    print(f"rows decoded: {rows_seen['rows']}, without tokens: "
          f"{rows_seen['no_tokens']}, with empty text: "
          f"{rows_seen['empty_text']} (the byte tokenizer gives text only to "
          "ids 3..258, and random weights sample the whole vocabulary)")
    if rows_seen["no_tokens"] or not rows_seen["rows"]:
        problems.append(f"{rows_seen['no_tokens']} row(s) came back without "
                        "a single token")
    for needle, what in (
            ("exceeds the serving budget", "a prompt was truncated"),
            ("cost ledger for", "a program's cost ledger was unavailable"),
            ("AOT memory analysis for", "a program's AOT capture failed")):
        hits = [m for m in warnings.messages if needle in m]
        if hits:
            problems.append(f"{what}: {hits[0][:300]}")
    faults = {tuple(sorted(m.labels.items())): m.value
              for m in reg.instruments()
              if m.name in ("faults_total", "numerics_faults_total",
                            "contained_chunk_failures_total") and m.value}
    if faults:
        problems.append(f"faults were contained during the run: {faults}")

    problems += _check_kernels(reg, cfg, tp, on_tpu, static=bool(a.mesh))
    problems += _check_devices(backend.engine.params, config.mesh.num_devices,
                               on_tpu)

    T.write_snapshot(reg, telemetry_dir)
    T.install_event_sink(None)
    sink.close()
    hits = cache_events.get("/jax/compilation_cache/cache_hits", 0)
    print(f"compile cache {cache_dir}: {cache_events.get('compiles', 0)} XLA "
          f"compile requests, {hits} persistent-cache hits, "
          f"{cache_events.get('/jax/compilation_cache/cache_misses', 0)} "
          "entries written")
    setup = sum(v for k, v in walls.items() if k != "phase1_repeat")
    print("walls: " + " ".join(f"{k}={v:.1f}s" for k, v in walls.items()))
    print(f"set-up (build + first pass of every phase, compiles included): "
          f"{setup:.1f}s; steady (repeated phase-1 sweep, "
          f"{len(res['recommendations']) + len(res['neutral_recommendations'])}"
          f" requests x {MAX_NEW_TOKENS} tokens): "
          f"{walls['phase1_repeat']:.1f}s")
    if problems:
        print(f"chip_smoke FAILED ({len(problems)} problem(s)):")
        for p in problems:
            print("  - " + p)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
