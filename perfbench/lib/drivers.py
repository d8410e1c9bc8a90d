"""The two load drivers. Closed: passes through ``ServingBackend.generate``
back to back until the window has passed and the last pass finishes. Open: one
thread that submits what is due, steps the scheduler, and collects results, as
``serving/replay.py``'s driver does (its clock and accounting are copied here:
times on ``time.monotonic``, latency counted from the due time, lateness of
the generator reported).

A driver takes a ``tracer`` with ``start()``/``stop()``; the traced stretch is
whole passes (closed) or a stretch of the post-roll, at the window's load
(open), entered and left only when the device is quiescent.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import families

from . import traffic
from .correct import Served


@dataclasses.dataclass
class Window:
    attempted: int = 0
    failed: int = 0
    seconds: float = 0.0
    served: List[Served] = dataclasses.field(default_factory=list)
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    # host-side samples for the layer readers
    gen_late_s: List[float] = dataclasses.field(default_factory=list)
    queue_wait_s: List[float] = dataclasses.field(default_factory=list)
    tpot_s: List[float] = dataclasses.field(default_factory=list)
    ttft_by_due: List[tuple] = dataclasses.field(default_factory=list)
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    turn_s: List[float] = dataclasses.field(default_factory=list)
    pass_s: List[float] = dataclasses.field(default_factory=list)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    traced: Optional[dict] = None  # counters + seconds of the traced stretch
    marks: Dict[str, float] = dataclasses.field(default_factory=dict)


class NoTrace:
    enabled = False

    def start(self):
        return None

    def stop(self):
        return None


PHASES = ("generate", "submit", "step")
_COUNTERS = ("prefill_tokens", "prefill_batches", "admitted", "decode_steps",
             "decoded_tokens", "occupancy_sum", "completed")


def _delta(sut, after: dict, before: dict) -> dict:
    """The traced stretch's counters: the harness's own, and those the
    configuration's family names for its ``model_flops`` (which the program's
    stats must then hold)."""
    out = {k: after.get(k, 0) - before.get(k, 0) for k in _COUNTERS}
    for k in getattr(families.of(sut.cfg), "STRETCH_COUNTERS", ()):
        out[k] = after[k] - before[k]
    return out


def _context_sums(specs, outs) -> dict:
    """Σ prompt tokens, Σ rows, Σ decoded tokens and the attention contexts
    they saw, from the requests themselves."""
    p = sum(len(s.ids) for s in specs)
    n = sum(len(t) for ok, t in outs if ok)
    pc = sum(len(s.ids) ** 2 / 2 for s in specs)
    dc = sum(len(t) * (len(s.ids) + len(t) / 2)
             for s, (ok, t) in zip(specs, outs) if ok)
    return {"prompt_tokens": p, "rows": len(specs), "decoded": n,
            "prefill_context_sum": pc, "decode_context_sum": dc}


def warm_closed(sut, mix: dict, seed: int) -> None:
    """One pass of the cell's own shapes with a short answer: every prefill
    bucket and the step program, nothing else."""
    specs = traffic.closed_pass(mix, seed, 10**6, sut.cfg["vocab_size"],
                                output_tokens=int(mix.get("warm_tokens", 9)))
    sut.generate(specs, call_seed=0)


def run_closed(sut, mix: dict, seed: int, seconds: float, tracer,
               trace_seconds: float) -> Window:
    w = Window()
    vocab = sut.cfg["vocab_size"]
    t_start = time.monotonic()
    index, tracing, traced = 0, False, None

    def close_trace():
        t1 = time.monotonic()
        tracer.stop()
        return dict(_delta(sut, sut.totals(), tr_c0), **tr_sums,
                    seconds=t1 - tr_t0)

    while True:
        now = time.monotonic()
        if now - t_start >= seconds:
            break
        if traced is None and not tracing:
            tracer.start()
            tracing, tr_t0, tr_c0 = True, time.monotonic(), sut.totals()
            tr_sums: Dict[str, float] = {}
        specs = traffic.closed_pass(mix, seed, index, vocab)
        t_pass = time.monotonic()
        with sut.span("generate"):
            outs = sut.generate(specs, call_seed=index)
        w.pass_s.append(time.monotonic() - t_pass)
        index += 1
        w.attempted += len(specs)
        for s, (ok, toks) in zip(specs, outs):
            w.failed += 0 if ok else 1
            w.served.append(Served(s, toks, ok))
        if tracing:
            for k, v in _context_sums(specs, outs).items():
                tr_sums[k] = tr_sums.get(k, 0) + v
            if time.monotonic() - tr_t0 >= trace_seconds:
                tracing, traced = False, close_trace()
    if tracing:
        traced = close_trace()
    t_end = time.monotonic()
    w.seconds = t_end - t_start
    w.traced = traced
    w.counters = sut.totals()
    done = w.attempted - w.failed
    w.end_to_end["req_per_s"] = done / w.seconds
    w.marks["passes"] = index
    return w


def warm_open(sut, mix: dict, seed: int) -> None:
    """Every admission-batch bucket the open loop can hit (1, 2, 4, ... up to
    the prefill group) at the cell's prompt bucket, and the step program."""
    vocab = sut.cfg["vocab_size"]
    group = int(sut.scheduler.serving.prefill_group)
    warm_mix = dict(mix, pass_size=group)
    specs = traffic.closed_pass(warm_mix, seed, 10**6, vocab,
                                output_tokens=int(mix.get("warm_tokens", 9)))
    stats = sut.new_stats()
    n = 1
    while n <= group:
        batch = specs[:n]
        for i, s in enumerate(batch):
            s2 = dataclasses.replace(s, id=f"warm{n}-{i}")
            sut.scheduler.submit(sut.request(s2))
        while sut.scheduler.has_work:
            sut.scheduler.step(stats)
        for i in range(len(batch)):
            sut.scheduler.take_result(f"warm{n}-{i}")
        n *= 2


def run_open(sut, mix: dict, seed: int, seconds: float, tracer,
             trace_seconds: float) -> Window:
    w = Window()
    sched = sut.scheduler
    specs = traffic.open_schedule(mix, seed, seconds, sut.cfg["vocab_size"])
    lead = float(mix["lead_in_s"])
    lo, hi = lead, lead + seconds
    in_window = {s.id for s in specs if lo <= s.due_s < hi}
    w.attempted = len(in_window)
    stats = sut.new_stats()
    outstanding: Dict[str, tuple] = {}  # id -> (spec, request, submit time)
    rows: Dict[str, dict] = {}
    nxt, backlog = 0, []
    # The traced stretch comes AFTER the window's last request has finished,
    # while the post-roll keeps the same load on: stopping the profiler
    # blocks this one thread for tens of seconds, and inside the window that
    # would make every later request late.
    want_trace = getattr(tracer, "enabled", False)
    tracing, traced = False, None
    t0 = time.monotonic()
    turn_at = 0.0
    window_left = len(in_window)
    while window_left > 0 or (want_trace and traced is None):
        now = time.monotonic() - t0
        if lo <= now < hi:  # a turn that ends in the window; a freeze is one
            w.turn_s.append(now - turn_at)
        turn_at = now
        while nxt < len(specs) and specs[nxt].due_s <= now:
            backlog.append(specs[nxt])
            nxt += 1
        still = []
        for s in backlog:
            req = sut.request(s)
            with sut.span("submit"):
                accepted = sched.submit(req)
            if accepted:
                t_sub = time.monotonic()
                outstanding[s.id] = (s, req, t_sub)
                if s.id in in_window:
                    w.gen_late_s.append(t_sub - t0 - s.due_s)
            elif sched.take_result(s.id) is not None:
                rows[s.id] = {"ok": False}  # refused for good (shed)
                window_left -= s.id in in_window
            else:
                still.append(s)  # backpressure: try again next turn
        backlog = still
        if want_trace and traced is None and not tracing \
                and window_left == 0:
            w.marks["drained_s"] = time.monotonic() - t0 - hi
            tracer.start()
            tracing, tr_t0 = True, time.monotonic()
            tr_c0 = dataclasses.asdict(stats)
        if sched.has_work:
            with sut.span("step"):
                sched.step(stats)
        else:
            wait = (specs[nxt].due_s - now) if nxt < len(specs) else 0.001
            time.sleep(min(max(wait, 0.0), 0.002))
        if tracing and time.monotonic() - tr_t0 >= trace_seconds:
            t1 = time.monotonic()
            tracer.stop()
            tracing = False
            traced = dict(_delta(sut, dataclasses.asdict(stats), tr_c0),
                          seconds=t1 - tr_t0)
        for rid in list(outstanding):
            res = sched.take_result(rid)
            if res is None:
                continue
            s, req, t_sub = outstanding.pop(rid)
            sut.tokenizer.forget([rid])
            if rid not in in_window:
                continue
            window_left -= 1
            row = {"ok": bool(res.ok), "spec": s,
                   "tokens": np.asarray(res.tokens, np.int64)}
            if res.ok and res.ttft_s is not None:
                first = req.submitted_at + res.ttft_s
                last = req.submitted_at + res.latency_s
                row.update(first=first - t0, last=last - t0,
                           queue_wait=res.queue_wait_s)
            rows[rid] = row
    t_end = time.monotonic() - t0
    w.seconds = seconds
    w.marks.setdefault("drained_s", t_end - hi)
    ttft, span, ntok = [], 0.0, 0
    worst = t_end  # a request that never answered waited the whole run
    by_id = {s.id: s for s in specs}
    for rid in in_window:
        row = rows.get(rid, {"ok": False})
        due = by_id[rid].due_s
        if not row.get("ok") or "first" not in row:
            w.failed += 1
            ttft.append(worst - due)
            w.served.append(Served(by_id[rid], np.zeros((0,)), False))
            continue
        ttft.append(row["first"] - due)
        w.ttft_by_due.append((due - lo, row["first"] - due))
        n = len(row["tokens"])
        if n > 1:
            span += row["last"] - row["first"]
            ntok += n - 1
            w.tpot_s.append((row["last"] - row["first"]) / (n - 1))
        if row.get("queue_wait") is not None:
            w.queue_wait_s.append(row["queue_wait"])
        w.served.append(Served(row["spec"], row["tokens"], True))
    w.ttft_s = ttft
    w.end_to_end["ttft_p95_ms"] = 1e3 * float(np.percentile(ttft, 95))
    w.end_to_end["tpot_ms"] = 1e3 * span / max(ntok, 1)
    w.counters = dataclasses.asdict(stats)
    if traced is not None:
        # Attention contexts of the traced stretch, from the mix's means.
        mean_p = float(np.mean([len(s.ids) for s in specs]))
        out = float(mix["output_tokens"])
        traced.update(
            prompt_tokens=traced["prefill_tokens"], rows=traced["admitted"],
            decoded=traced["occupancy_sum"],
            prefill_context_sum=traced["prefill_tokens"] * mean_p / 2,
            decode_context_sum=traced["occupancy_sum"] * (mean_p + out / 2))
    w.traced = traced
    w.marks["submitted"] = nxt
    w.marks["turn_max_s"] = max(w.turn_s, default=0.0)
    return w


DRIVERS = {"closed": (warm_closed, run_closed), "open": (warm_open, run_open)}
