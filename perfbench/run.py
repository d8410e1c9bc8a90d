#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: builds the cell's configuration on the chip
with weights made from the seed, warms the cell's own shapes (that is set-up),
drives the cell's traffic for ``--seconds``, reads the device's memory peak,
frees the program, checks a sample of what the window served against the plain
reference, and prints one JSON line. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.

Everything a cell is lives in data: ``BENCHMARK.json`` names the cell, its
configuration (``configs/<config>.json``) and traffic (``traffic/<mix>.json``);
``workloads/<cell>.json`` holds what belongs to the pair (pool, rate, limits,
``serving`` overrides); ``layer_metrics/<metric>.json`` names each per-layer
metric's reader. The kind of model is the configuration's ``family``, one file
under ``families/``: leaves, the program's names, the reference's block, FLOPs.

With no ``--rehearse`` it refuses anything but a TPU with the chips the cell
asks for. ``--rehearse`` is the CPU rehearsal: a toy model, the same control
flow and last line, and no device metric's name on any number.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

TRACE_SECONDS = 6.0  # the traced stretch of a --trace 1 run (closed: whole passes)
SAMPLE_TOKENS = 384  # served tokens the reference re-reads, at least


def _load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def load_cell(name: str):
    bench = _load("BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    base = bench["paths"][0]
    own = _load(f"{base}/workloads/{name}.json")
    from perfbench.lib import modelcfg, traffic

    cfg = modelcfg.load_config(os.path.join(ROOT, conf["file"]))
    mix = traffic.load_mix(
        os.path.join(ROOT, base, "traffic", cell["traffic"] + ".json"),
        own.get("traffic_overrides"))

    def has(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return dict(bench=bench, cell=cell, cfg=cfg, mix=mix, own=own, base=base,
                end_to_end=[m for m in bench["end_to_end"] if has(m)],
                per_layer=[m for m in bench["per_layer"] if has(m)])


def prepare(workload: str, rehearse: bool):
    """The cell's data, the compile cache placed, and the chip looked for:
    what every entry point (this one, ``tools/calibrate.py``,
    ``tools/knee.py``) does before it builds the system. Returns the cell's
    dict with ``cfg``, ``mix`` and ``pool`` as they will be run, and the
    devices; raises SystemExit(1), printing no result, without the chips."""
    c = load_cell(workload)
    from fairness_llm_tpu.utils.compile_cache import place_compile_cache

    c["cache_dir"] = place_compile_cache()
    import jax

    from perfbench.lib import modelcfg

    devices = jax.devices()
    chips = c["cell"]["chips"]
    c["pool"] = dict(c["own"]["pool"])
    if "serving" in c["own"]:  # ServingConfig overrides, applied after the pool
        c["pool"]["serving"] = dict(c["own"]["serving"])
    if rehearse:
        c["cfg"] = modelcfg.rehearsal_cut(c["cfg"])
        c["mix"].update(c["own"].get("rehearsal_overrides", {}))
        c["pool"].update(c["own"].get("rehearsal_pool", {}))
    elif devices[0].platform != "tpu" or len(devices) < chips:
        print(f"perfbench: needs {chips} TPU chip(s), JAX found "
              f"{len(devices)} x {devices[0].platform}; refusing to run",
              file=sys.stderr)
        raise SystemExit(1)
    return c, devices


def _reader(base: str, metric: str):
    spec = _load(f"{base}/layer_metrics/{metric}.json")
    path = os.path.join(ROOT, base, spec["reader"])
    mod = importlib.util.spec_from_file_location(
        "reader_" + os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(mod)
    mod.loader.exec_module(module)
    return module.read, spec.get("args", {})


class Tracer:
    """Starts and stops the profiler round the traced stretch."""

    enabled = True

    def __init__(self, sut, out_dir: str):
        self.sut, self.dir = sut, out_dir
        self.t0 = self.t1 = None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.sut.calls.clear()
        self.sut.annotate = True
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax

        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - self.t1
        self.sut.annotate = False
        self.calls = list(self.sut.calls)


class Context:
    """What a layer reader may look at."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: toy model, no device metric named")
    a = ap.parse_args(argv)
    if a.rehearse:
        print("REHEARSAL: toy model on whatever JAX finds; not a chip run, "
              "no number below is a device metric")
    c, devices = prepare(a.workload, a.rehearse)
    cfg, mix, own, cell, pool = (c["cfg"], c["mix"], c["own"], c["cell"],
                                 c["pool"])
    cache_dir, dev = c["cache_dir"], devices[0]
    from perfbench.lib import correct, drivers, peaks
    from perfbench.lib.sut import ServedModel

    chip_peaks = None if a.rehearse else peaks.peaks_for(dev.device_kind)

    def peak_gb():
        return max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices[: cell["chips"]]), default=0) / 1e9

    marks = {"imports_s": time.monotonic() - _T_PROCESS}
    sut = ServedModel(cfg, cell["config"], mix, pool, a.seed)
    marks.update(sut.marks, peak_built_gb=peak_gb())
    warm, drive = drivers.DRIVERS[mix["kind"]]
    t0 = time.monotonic()
    warm(sut, mix, a.seed)
    marks.update(warm_s=time.monotonic() - t0, peak_warm_gb=peak_gb())

    out_dir = os.path.join(ROOT, "perfbench_out", a.workload)
    tracer = Tracer(sut, os.path.join(out_dir, "trace")) \
        if a.trace and not a.rehearse else drivers.NoTrace()
    t_drive = time.monotonic()
    window = drive(sut, mix, a.seed, a.seconds, tracer, TRACE_SECONDS)
    lead = float(mix.get("lead_in_s", 0.0)) if mix["kind"] == "open" else 0.0
    setup_s = (t_drive + lead) - _T_PROCESS
    marks.update(window.marks)

    stats = [d.memory_stats() or {} for d in devices[: cell["chips"]]]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                      default=0)
    eos_id = cfg["eos_token_id"]
    calls = getattr(tracer, "calls", [])
    sut.free()

    # -- correct: the served sample against the plain reference -------------
    t0 = time.monotonic()
    per_request = max(int(mix["output_tokens"]), 1)
    sample = correct.draw_sample(
        window.served, a.seed, max(3, -(-SAMPLE_TOKENS // per_request)))
    readings = {"unanswered": float(window.failed),
                "length_faults": float(correct.length_faults(sample, eos_id)),
                "widest_gap": float("inf")}
    if sample:
        readings.update(correct.gaps(cfg, a.seed, sample))
    limits = dict(own["limits"], length_faults=0, unanswered=0)
    if a.rehearse:
        limits.update(own.get("rehearsal_limits", {}))
    ok, rows = correct.judge(readings, limits)
    marks["reference_s"] = time.monotonic() - t0

    # -- metrics ---------------------------------------------------------------
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"] if not a.rehearse else len(devices),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": ok, "attempted": window.attempted,
              "failed": window.failed}
    metrics, breakdown = {}, None
    if a.trace == 0:
        values = dict(window.end_to_end, setup_s=setup_s)
        for m in c["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        trace = None
        if not a.rehearse:
            from perfbench.lib.trace import Trace, find_xplane

            t0 = time.monotonic()
            trace = Trace(find_xplane(tracer.dir),
                          {label for label, _, _ in calls})
            marks.update(trace_stop_s=tracer.stop_s,
                         trace_read_s=time.monotonic() - t0)
            device["busy_s"] = trace.busy_seconds()
            device["window_s"] = tracer.t1 - tracer.t0
            breakdown = {"device_ops": trace.top_ops(10),
                         "idle_gaps": trace.idle_gaps(drivers.PHASES, 10)}
            shutil.rmtree(tracer.dir, ignore_errors=True)
        ctx = Context(window=window, trace=trace, peaks=chip_peaks, cfg=cfg,
                      pool=pool, mix=mix, chips=cell["chips"])
        for m in c["per_layer"]:
            if a.rehearse and m["source"] == "device_trace":
                continue  # a CPU run never writes a device metric's name
            read, args = _reader(c["base"], m["name"])
            value = read(ctx, **args)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if a.rehearse:  # a CPU number never stands under a metric's own name
        metrics = {"rehearsal." + k: v for k, v in metrics.items()}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["marks"] = {k: round(v, 3) for k, v in marks.items()}
    if window.pass_s:
        result["pass_s"] = [round(x, 3) for x in window.pass_s]
    result["compile_cache"] = os.path.relpath(cache_dir, ROOT)
    result["compared"] = {n: {"value": v, "limit": l} for n, v, l in rows}
    print("marks: " + " ".join(f"{k}={v:.2f}" for k, v in marks.items()),
          file=sys.stderr)
    correct.report(rows)
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
