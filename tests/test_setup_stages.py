"""Set-up accounted from inside the program (``telemetry/compilestats.py``):
a step program's tracing by the function traced, what a first call takes
beyond its compile stages, and the constructors' own seconds. Each test
checks an identity on the booked numbers, never a wall threshold (toy
programs and the tiny model on the CPU)."""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from fairness_llm_tpu.config import ModelSettings, ServingConfig
from fairness_llm_tpu.models.configs import get_model_config
from fairness_llm_tpu.telemetry import use_registry
from fairness_llm_tpu.telemetry.compilestats import startup_stage
from fairness_llm_tpu.telemetry.costmodel import instrument_jit
from fairness_llm_tpu.telemetry.timeline import set_attribution, use_timeline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = ("setup_build_s", "setup_first_call_s", "setup_trace_body_s")


def _by(reg, name, label, **where):
    """``label`` -> summed value of the instruments ``name`` whose labels
    hold ``where``."""
    out = {}
    for m in reg.instruments():
        if m.name == name and all(m.labels.get(k) == v
                                  for k, v in where.items()):
            key = m.labels[label]
            out[key] = out.get(key, 0.0) + m.value
    return out


def _module(relpath: str):
    """A file of the checkout outside the package, imported by path."""
    spec = importlib.util.spec_from_file_location(
        os.path.basename(relpath)[:-3], os.path.join(ROOT, relpath))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _layered(tag: str):
    """A program whose body calls one inner ``jit`` from three layers, in
    ``lax`` alone (a ``jnp`` call is a traced function of its own)."""

    def layer(x):
        return lax.tanh(lax.mul(x, x))

    layer.__name__ = f"layer_{tag}"
    inner = jax.jit(layer)

    def step(x):
        for _ in range(3):
            x = lax.add(inner(x), x)
        return x

    step.__name__ = f"layered_step_{tag}"
    return step


def test_inner_jit_is_booked_once_under_its_own_fn():
    x = jnp.arange(4.0)
    with use_registry() as reg, use_timeline():
        instrument_jit(_layered("a"), "layered")(x)
        fns = {(m.labels["fn"], m.labels["outer"]): m.value
               for m in reg.instruments()
               if m.name == "compile_trace_fn_seconds_total"
               and m.labels["program"] == "layered"}
    assert set(fns) == {("layer_a", "0"), ("layered_step_a", "1")}
    assert all(v > 0 for v in fns.values())


@pytest.fixture(scope="module")
def served():
    """The tiny model built and three requests served inside one registry
    and timeline: every step program's first call, the constructors."""
    from fairness_llm_tpu.runtime.engine import DecodeEngine
    from fairness_llm_tpu.serving import ContinuousScheduler, Request

    greedy = ModelSettings(temperature=0.0, top_k=0, top_p=1.0, max_tokens=6)
    with use_registry() as reg, use_timeline() as tl:
        engine = DecodeEngine(get_model_config("tiny-test"), seed=0)
        before = sum(_by(reg, "compile_stage_seconds_total", "stage",
                         program="unscoped").values())
        # an odd pool, so its zeros compile inside the constructor
        sched = ContinuousScheduler(
            engine, ServingConfig(enabled=True, num_slots=3,
                                  max_prompt_len=128, max_new_tokens=11,
                                  decode_chunk=2), settings=greedy)
        inside = sum(_by(reg, "compile_stage_seconds_total", "stage",
                         program="unscoped").values()) - before
        results = sched.serve([Request(prompt=p, id=f"ss{i}", settings=greedy)
                               for i, p in enumerate(["one two", "three",
                                                      "four five six"])])
        assert all(r.ok for r in results)
        return reg, tl.events(), inside


def test_trace_by_fn_sums_to_each_programs_tracing(served):
    reg, _, _ = served
    programs = set(_by(reg, "compile_stage_seconds_total", "program"))
    assert {"serve_step", "unscoped"} < programs
    for program in programs:
        stages = _by(reg, "compile_stage_seconds_total", "stage",
                     program=program)
        fns = _by(reg, "compile_trace_fn_seconds_total", "fn",
                  program=program)
        assert sum(fns.values()) == pytest.approx(
            stages.get("trace", 0.0) + stages.get("ledger_trace", 0.0),
            rel=1e-9, abs=1e-12)
    outer = _by(reg, "compile_trace_fn_seconds_total", "fn",
                program="serve_step", outer="1")
    assert list(outer) == ["serve_step"] and outer["serve_step"] > 0
    assert _by(reg, "compile_trace_fn_seconds_total", "fn",
               program="unscoped", outer="1") == {}


def test_first_call_is_booked_once_per_instance():
    x = jnp.arange(4.0)
    step = _layered("b")
    with use_registry() as reg, use_timeline() as tl:
        fn = instrument_jit(step, "first_probe")
        fn(x)
        first = reg.read_value("startup_seconds_total", component="startup",
                               stage="first_call", program="first_probe")
        n_events = len(tl.events())
        fn(x)
        fn(x)
        assert reg.read_value("startup_seconds_total", component="startup",
                              stage="first_call",
                              program="first_probe") == first
        assert len(tl.events()) == n_events
        instrument_jit(step, "first_probe")(x)  # a new instance: its own
        both = reg.read_value("startup_seconds_total", component="startup",
                              stage="first_call", program="first_probe")
        spans = [e for e in tl.events() if e["name"] == "compile.first_probe"]
    assert first > 0 and both > first
    assert [s["args"]["first_call_s"] for s in spans] == [
        round(first, 6), round(both - first, 6)]
    for s in spans:  # the call's wall is its stages and the rest, once
        assert s["dur_s"] == pytest.approx(
            s["args"]["stage_seconds"] + s["args"]["first_call_s"], abs=1e-5)


def test_construction_stages_leave_out_the_compiles_inside_them(served):
    reg, events, inside = served
    stages = _by(reg, "startup_seconds_total", "stage")
    assert stages["engine"] > 0 and stages["scheduler"] > 0
    assert inside > 0  # the pool's zeros compiled, booked ``unscoped``
    span = next(e for e in events if e["name"] == "startup.scheduler")
    assert stages["scheduler"] + inside == pytest.approx(span["dur_s"],
                                                         abs=1e-3)
    assert stages["scheduler"] < span["dur_s"] - inside / 2


def test_a_stage_nests_what_is_booked_inside_it():
    def fresh(x):
        return lax.cos(x) - 3.0

    fresh.__name__ = "nested_in_build"
    x = jnp.arange(7.0)

    @startup_stage("probe")
    def build():
        return jax.jit(fresh)(x).block_until_ready()

    with use_registry() as reg, use_timeline() as tl:
        build()
        unscoped = sum(_by(reg, "compile_stage_seconds_total", "stage",
                           program="unscoped").values())
        stage = reg.read_value("startup_seconds_total", component="startup",
                               stage="probe")
        span = next(e for e in tl.events() if e["name"] == "startup.probe")
    assert unscoped > 0 and stage > 0
    assert stage + unscoped == pytest.approx(span["dur_s"], abs=1e-3)


def test_attribution_off_books_no_set_up():
    x = jnp.arange(4.0)
    with use_registry() as reg, use_timeline() as tl:
        prev = set_attribution(False)
        try:
            fn = instrument_jit(_layered("c"), "off_first")
            fn(x)
            startup_stage("off")(lambda: None)()
            assert reg.instruments() == [] and tl.events() == []
        finally:
            set_attribution(prev)
        # on again: the cost ledger's walk is owed, the first call is not
        fn(x)
        assert reg.read_value("compile_stage_seconds_total",
                              component="compile", program="off_first",
                              stage="ledger_trace") > 0
        assert [m.name for m in reg.instruments()
                if m.name.startswith("startup_")] == []


@pytest.mark.parametrize("metric", METRICS)
def test_the_benchmark_reads_each_stage(served, metric):
    """Each new metric's reader, as ``perfbench/run.py`` runs it, finds a
    positive number in the served registry."""
    reg, _, _ = served
    with open(os.path.join(ROOT, "perfbench", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    reader = _module(os.path.join("perfbench", spec["reader"]))
    with use_registry(reg):
        value = reader.read(None, **spec["args"])
    assert value is not None and value > 0


def test_the_operators_split_shows_all_of_set_up(served):
    """``tools/stall_report.py``'s set-up split prints the constructors and
    the first calls beside the compile stages, and the five functions whose
    tracing took longest."""
    import io

    reg, _, _ = served
    out = io.StringIO()
    with use_registry(reg):
        _module(os.path.join("tools", "stall_report.py")).print_setup_split(
            1.0, out=out)
    lines = out.getvalue().splitlines()
    for stage in ("engine", "scheduler", "first_call"):
        assert any(line.startswith(f"  SETUP startup {stage} ")
                   for line in lines)
    traced = [line for line in lines if line.startswith("  SETUP traced ")]
    assert len(traced) == 5
    assert any(" serve_step serve_step (outer 1) " in line for line in traced)
