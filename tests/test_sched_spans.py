"""The spans inside the scheduler's iteration, the compile stages booked from
JAX's own events, and the programs' names: what ``Timeline.span``,
``compilestats`` and ``stepbuilder`` promise the readers under
``perfbench/readers`` and ``tools/stall_report.py`` (toy model on the CPU)."""

from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fairness_llm_tpu.config import ModelSettings, ServingConfig
from fairness_llm_tpu.models.configs import get_model_config
from fairness_llm_tpu.telemetry import use_registry
from fairness_llm_tpu.telemetry.compilestats import program_scope
from fairness_llm_tpu.telemetry.timeline import (
    _NULL_SPAN,
    Timeline,
    set_attribution,
    use_timeline,
)

CHILDREN = ("sched.expire", "sched.admit.plan", "sched.prefill.dispatch",
            "sched.decode.prepare", "sched.decode.dispatch",
            "sched.decode.sync", "sched.decode.emit")


def _greedy(m):
    return ModelSettings(temperature=0.0, top_k=0, top_p=1.0, max_tokens=m)


@pytest.fixture(scope="module")
def engine():
    from fairness_llm_tpu.runtime.engine import DecodeEngine

    return DecodeEngine(get_model_config("tiny-test"), seed=0)


def _scheduler(engine, **kw):
    from fairness_llm_tpu.serving import ContinuousScheduler

    cfg = dict(enabled=True, num_slots=2, max_prompt_len=128,
               max_new_tokens=8, decode_chunk=2)
    cfg.update(kw)
    return ContinuousScheduler(engine, ServingConfig(**cfg),
                               settings=_greedy(8))


def _requests(n, tag="sp"):
    from fairness_llm_tpu.serving import Request

    words = ["one two three", "four five six", "seven eight nine ten",
             "eleven twelve"]
    return [Request(prompt=words[i % len(words)], id=f"{tag}{i}",
                    settings=_greedy(6)) for i in range(n)]


@pytest.fixture(scope="module", params=["dense", "latent"])
def served(request, engine):
    """One drain of three requests through two slots, its timeline and
    registry: a pool of k and v per KV head, and a pool of latents."""
    from toys import KINDS, toy_engine

    requests = _requests(3)
    if request.param == "latent":
        engine, cut = toy_engine(KINDS["latent"], seed=5)
        rng = np.random.default_rng(5)
        for r in requests:  # the toy's prompts are ids, kept by name
            engine.tokenizer.register(
                r.prompt, rng.integers(3, cut["vocab_size"], size=12))
    with use_registry() as reg, use_timeline() as tl:
        sched = _scheduler(engine)
        results = sched.serve(requests)
        assert all(r.ok for r in results)
        return tl.events(), reg


def _spans(events, prefix=""):
    return [e for e in events
            if e["type"] == "span" and e["name"].startswith(prefix)]


def test_children_lie_inside_an_iteration_and_cover_it(served):
    events, _ = served
    iterations = _spans(events, "sched.iterate")
    assert len(iterations) >= 3
    by_seq = {it["args"]["seq"]: it for it in iterations}
    assert len(by_seq) == len(iterations)  # one span per sequence number
    covered = dict.fromkeys(by_seq, 0.0)
    for s in _spans(events, "sched."):
        if s["name"] == "sched.iterate":
            continue
        assert s["name"] in CHILDREN
        assert s["args"]["parent"] == "sched.iterate"
        it = by_seq[s["args"]["seq"]]
        assert it["t0"] <= s["t0"]
        assert s["t0"] + s["dur_s"] <= it["t0"] + it["dur_s"] + 1e-9
        covered[s["args"]["seq"]] += s["dur_s"]
    total = sum(it["dur_s"] for it in iterations)
    assert sum(covered.values()) >= 0.95 * total
    # a toy iteration lasts a millisecond or two: what no child covers is
    # the spans' own bookkeeping, never a piece of work
    for seq, it in by_seq.items():
        assert it["dur_s"] - covered[seq] < 2e-3
    assert {s["name"] for s in _spans(events, "sched.")} >= set(CHILDREN)


def test_dispatch_span_names_the_requests_it_admits(served):
    events, _ = served
    dispatches = _spans(events, "sched.prefill.dispatch")
    assert not _spans(events, "prefill[")  # replaced, not doubled
    admitted_by = {}
    for d in dispatches:
        assert d["cat"] == "prefill" \
            and d["args"]["program"] == "serve_prefill"
        for rid in d["args"]["request_ids"]:
            admitted_by[rid] = d
    instants = [e for e in events if e["type"] == "instant"
                and e["cat"] == "lifecycle"]
    for rid in ("sp0", "sp1", "sp2"):
        mine = {e["name"]: e["t0"] for e in instants
                if e["args"]["request_id"] == rid}
        assert {"submitted", "admitted", "prefill_start", "first_token",
                "completed"} <= set(mine)
        d = admitted_by[rid]
        # admitted while the batch was planned, first token after its call
        assert mine["admitted"] <= d["t0"] <= mine["first_token"]
        assert mine["prefill_start"] == pytest.approx(d["t0"], abs=1e-3)


def test_backend_spans_bracket_a_generate_call(engine):
    from fairness_llm_tpu.serving import ServingBackend

    with use_registry(), use_timeline() as tl:
        backend = ServingBackend(engine, ServingConfig(
            enabled=True, num_slots=2, max_prompt_len=128,
            max_new_tokens=4, decode_chunk=2))
        backend.generate(["one two", "three four"], _greedy(4),
                         keys=["k0", "k1"])
        names = [e["name"] for e in _spans(tl.events())
                 if e["name"].startswith(("backend.", "sched.iterate"))]
    assert names[:2] == ["backend.submit", "backend.submit"]  # build, intake
    assert names[-1] == "backend.collect"
    assert "sched.iterate" in names[2:-1]


def _stage_seconds(reg, program):
    return {m.labels["stage"]: m.value for m in reg.instruments()
            if m.name == "compile_stage_seconds_total"
            and m.labels["program"] == program}


def test_fresh_program_books_its_stages_once():
    def fresh(x):
        return jnp.tanh(x) * 3.0 + jnp.sum(x)

    fresh.__name__ = "booked_once"
    fn = jax.jit(fresh)
    x = jnp.arange(8.0)
    with use_registry() as reg, use_timeline() as tl:
        with program_scope("probe_program"):
            fn(x).block_until_ready()
        first = _stage_seconds(reg, "probe_program")
        assert {"trace", "lower", "backend_compile"} <= set(first)
        assert all(v > 0 for v in first.values())
        assert "cache_load" not in first
        spans = _spans(tl.events(), "compile.")
        assert spans[0]["name"] == "compile.probe_program"
        assert spans[0]["args"]["stage_seconds"] == pytest.approx(
            sum(first.values()), abs=1e-5)
        assert "compile.backend" in [e["name"] for e in spans]
        n_events = len(tl.events())
        with program_scope("probe_program"):
            fn(x).block_until_ready()
        assert _stage_seconds(reg, "probe_program") == first
        assert len(tl.events()) == n_events


def test_stage_outside_every_scope_is_booked_unscoped():
    fn = jax.jit(lambda x: jnp.cos(x) - 2.0)
    with use_registry() as reg, use_timeline() as tl:
        fn(jnp.arange(5.0)).block_until_ready()
        assert _stage_seconds(reg, "unscoped")["backend_compile"] > 0
        assert {e["name"] for e in _spans(tl.events(), "compile.")} == {
            "compile.unscoped"}


def test_nested_traces_are_booked_once():
    inner = jax.jit(lambda x: jnp.sin(x) + 1.0)

    def outer(x):
        return inner(x) * inner(x + 1.0)

    with use_registry() as reg, use_timeline() as tl:
        with program_scope("nested_probe"):
            jax.jit(outer)(jnp.arange(6.0)).block_until_ready()
        booked = _stage_seconds(reg, "nested_probe")["trace"]
        parent = next(e for e in tl.events()
                      if e["name"] == "compile.nested_probe")
    # the inner traces ran inside the outer one: their seconds are part of
    # its interval, which itself lies inside the call
    assert 0 < booked <= parent["dur_s"]


@pytest.fixture
def persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    cc.reset_cache()
    yield tmp_path
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_second_build_loads_from_the_persistent_cache(persistent_cache):
    def build():
        def cached(x):
            return jnp.exp(x) / (1.0 + jnp.sum(x * x))

        cached.__name__ = "persisted_probe"
        return jax.jit(cached)

    x = jnp.arange(16.0)
    with use_registry() as reg, use_timeline():
        with program_scope("persisted"):
            build()(x).block_until_ready()
        cold = _stage_seconds(reg, "persisted")
        assert "backend_compile" in cold and "cache_load" not in cold
        assert reg.read_value("compile_cache_persistent_total",
                              component="compile", program="persisted",
                              outcome="miss") == 1
        assert os.listdir(persistent_cache)
        jax.clear_caches()  # what a second process would start with
        with program_scope("persisted"):
            build()(x).block_until_ready()
        warm = _stage_seconds(reg, "persisted")
        assert warm["cache_load"] > 0
        assert warm["backend_compile"] == cold["backend_compile"]
        assert reg.read_value("compile_cache_persistent_total",
                              component="compile", program="persisted",
                              outcome="hit") == 1


def test_ledger_trace_is_a_stage_of_its_own(served):
    _, reg = served
    stages = _stage_seconds(reg, "serve_step")
    assert stages, "the step program booked no stage"
    assert stages["ledger_trace"] > 0
    assert {"trace", "lower", "backend_compile"} <= set(stages)


@pytest.mark.parametrize("builder, kwargs, name", [
    ("build_serve_step", dict(num_slots=2, chunk=2, guard=False),
     "serve_step"),
    ("build_serve_step", dict(num_slots=2, chunk=2, guard=False, fuse=2),
     "serve_step_fused"),
    ("build_serve_prefill", dict(nb=8, P=256, guard=False, num_slots=2),
     "serve_prefill_8x256"),
])
def test_step_programs_are_named_before_jit(engine, builder, kwargs, name):
    from fairness_llm_tpu.runtime import stepbuilder
    from fairness_llm_tpu.runtime.sampling import SamplerSettings

    args = [engine.config, engine.model]
    if builder == "build_serve_step":
        args += [SamplerSettings(temperature=0.0, top_k=0, top_p=1.0), 0, 1]
    run = getattr(stepbuilder, builder)(*args, **kwargs)
    assert run.__name__ == name


def test_served_programs_lower_under_their_names(engine):
    sched = _scheduler(engine)
    fn = sched._step_fn()
    assert fn._jit.__name__ == "serve_step"
    assert sched._prefill_fn(2, 64, False)._jit.__name__ == \
        "serve_prefill_2x64"


def test_spans_land_in_the_profilers_host_plane(engine, tmp_path):
    from jax.profiler import ProfileData

    sched = _scheduler(engine)
    sched.serve(_requests(2, "warm"))  # compile outside the trace
    with use_registry(), use_timeline() as tl:
        jax.profiler.start_trace(str(tmp_path))
        try:
            sched.serve(_requests(2, "tr"))
        finally:
            jax.profiler.stop_trace()
        ring = [e["name"] for e in _spans(tl.events(), "sched.")]
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[-1]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    seen = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith(("sched.", "backend.")):
                seen.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert len(seen["sched.iterate"]) == ring.count("sched.iterate") >= 2
    for name in ("sched.expire", "sched.admit.plan",
                 "sched.prefill.dispatch", "sched.decode.prepare",
                 "sched.decode.dispatch", "sched.decode.sync",
                 "sched.decode.emit", "backend.submit"):
        assert len(seen[name]) == ring.count(name) or name.startswith("b")
        if name.startswith("sched."):
            for t0, t1 in seen[name]:  # on the profiler's clock, nested
                assert any(a <= t0 and t1 <= b
                           for a, b in seen["sched.iterate"])


def test_attribution_off_records_none_of_it(engine):
    with use_registry() as reg, use_timeline() as tl:
        prev = set_attribution(False)
        try:
            assert tl.span("sched.iterate", "sched", "serving") is _NULL_SPAN
            sched = _scheduler(engine, decode_chunk=4)  # a fresh step program
            assert all(r.ok for r in sched.serve(_requests(3, "off")))
            with program_scope("off_probe"):
                jax.jit(lambda x: x * 5.0 - 1.0)(jnp.arange(3.0))
        finally:
            set_attribution(prev)
        assert tl.events() == []
        assert [m.name for m in reg.instruments()
                if m.name.startswith(("compile_stage", "compile_trace_fn",
                                      "compile_cache_persistent",
                                      "startup_seconds", "hbm_peak"))] == []


def test_span_primitive_parent_seq_and_late_args():
    tl = Timeline()
    with tl.span("sched.iterate", "sched", "serving", seq=7):
        with tl.span("sched.admit.plan", "sched", "serving") as plan:
            plan.set(rows=3)
            assert tl.open_span() is plan
    inner, outer = tl.events()
    assert inner["args"] == {"parent": "sched.iterate", "seq": 7, "rows": 3}
    assert outer["args"] == {"parent": None, "seq": 7}
    assert outer["t0"] <= inner["t0"]
    assert inner["dur_s"] <= outer["dur_s"]
    assert tl.open_span() is None


def test_busy_interval_starts_at_the_earliest_dispatch():
    """A prefill queued ahead of a chunk keeps the device busy from its own
    dispatch: the idle gap the cost ledger counts ends there, which the
    scheduler knows once the chunk's sync has returned."""
    with use_registry() as reg, use_timeline() as tl:
        tl.decode_chunk("serving", 1.0, 0.3, steps=8, program="serve_step")
        tl.decode_chunk("serving", 2.0, 0.3, steps=8, program="serve_step",
                        busy_from=1.5)
        assert reg.read_value("cost_host_gap_s_total", component="costmodel",
                              program="serve_step") == pytest.approx(0.2)
        # step_gap_s keeps all the host time between chunks
        assert reg.histogram("step_gap_s",
                             component="serving").sum == pytest.approx(0.7)
        tl.decode_chunk("serving", 3.0, 0.3, steps=8, program="serve_step")
        assert reg.read_value("cost_host_gap_s_total", component="costmodel",
                              program="serve_step") == pytest.approx(0.9)
