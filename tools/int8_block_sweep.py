"""Device time of ``quant_matmul`` under candidate blocks and under the rule.

For each M,K,N[,f32] (default: the benchmark cells' decode and prefill shapes)
every candidate runs ``--reps`` times inside one profiler trace; the table
gives the median device time of the ``int8_matmul`` custom call beside its
floor (``perfbench/lib/flops.int8_matmul_cost`` over ``peaks``). TPU only:
    python tools/int8_block_sweep.py [M,K,N[,f32] ...] [--reps 20]
"""

import argparse
import os
import statistics
import sys
import tempfile
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELLS = ("48,4096,14336 48,14336,4096 48,4096,4096 48,4096,1024 "
         "48,4096,32000,f32 48,3584,18944 48,18944,3584 48,3584,3584 "
         "48,3584,512 48,3584,152064,f32 96,3584,18944 96,18944,3584 "
         "192,7168,2048 192,2048,7168 192,1536,12288 192,8192,7168 "
         "512,4096,14336 512,14336,4096 512,3584,18944 512,18944,3584 "
         "512,7168,2048").split()


def candidates(qm, m, k, n, x_item, out_item):
    """The rule, the 512-divisor blocks it replaced, and K-deep ``[K, bn]``
    and whole-row ``[bk, N]`` blocks at fixed byte budgets; duplicates go."""
    rule = qm.matmul_blocks(m, k, n, x_item, out_item)
    out = {"rule": rule, "512x512": qm.MatmulBlocks(
        min(rule.bm, 256), qm._pick_block(k), qm._pick_block(n))}
    for kib in (512, 1024, 2048, 4096, 8192):
        out[f"kdeep{kib}K"] = rule._replace(
            bk=k, bn=qm._widest_divisor(n, (kib << 10) // k))
        out[f"rows{kib}K"] = rule._replace(
            bk=qm._widest_divisor(k, (kib << 10) // n), bn=n)
    first = {b: tag for tag, b in reversed(out.items())
             if b.vmem_bytes(x_item, out_item) <= qm._VMEM_LIMIT}
    return {tag: b for tag, b in out.items() if first.get(b) == tag}


def device_times(trace_dir):
    """Durations of the ``int8_matmul`` custom calls, in the order they ran."""
    from jax.profiler import ProfileData
    from perfbench.lib.trace import find_xplane

    plane = next(p for p in ProfileData.from_file(find_xplane(trace_dir)).planes
                 if p.name.startswith("/device:TPU:0"))
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    return [d for _, d in sorted(
        (ev.start_ns, ev.duration_ns) for ev in line.events
        if ev.name.lstrip("%").startswith("int8_matmul"))]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("shapes", nargs="*", default=CELLS)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from fairness_llm_tpu.ops import quant_matmul as qm
    from perfbench.lib import flops, peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}: no device time here")
    peak = peaks.peaks_for(dev.device_kind)
    print(f"device {dev.device_kind}; median of {args.reps} runs a candidate")
    for spec in args.shapes:
        (m, k, n), f32 = map(int, spec.split(",")[:3]), spec.endswith("f32")
        odt = jnp.float32 if f32 else jnp.bfloat16
        x, scale = jnp.ones((m, k), jnp.bfloat16), jnp.full((n,), 0.01)
        ws = [jax.random.randint(jax.random.key(s), (k, n), -127, 128, jnp.int8)
              for s in (1, 2)]  # in turn, as consecutive layers' weights
        cands = candidates(qm, m, k, n, 2, jnp.dtype(odt).itemsize)
        fns = [jax.jit(lambda x, w, s, b=b: qm._quant_matmul_pallas(
            x, w, s, False, odt, b)) for b in cands.values()]
        outs = [f(x, ws[0], scale).astype(jnp.float32) for f in fns]  # compile
        assert all(jnp.allclose(o, outs[0], rtol=2e-2, atol=2e-2) for o in outs)
        with tempfile.TemporaryDirectory() as tdir:
            jax.profiler.start_trace(tdir)
            for f in fns:
                for r in range(args.reps):
                    y = f(x, ws[r % 2], scale)
                y.block_until_ready()
            jax.profiler.stop_trace()
            durs = device_times(tdir)
        assert len(durs) == args.reps * len(fns), (len(durs), len(fns))
        floor_us = 1e6 * flops.min_time_s(*flops.int8_matmul_cost(
            m, k, n, "f32" if f32 else "bf16"), peak)
        print(f"\n{spec}: floor {floor_us:.1f} us")
        for i, (tag, b) in enumerate(cands.items()):
            us = statistics.median(durs[i * args.reps:(i + 1) * args.reps]) / 1e3
            print(f"  {tag:10s} {str(b):18s} {us:8.1f} us  "
                  f"{100 * floor_us / us:5.1f}% of the roofline")


if __name__ == "__main__":
    main()
