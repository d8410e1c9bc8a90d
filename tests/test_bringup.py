"""Bring-up contracts (ISSUE 21): nothing on the main path hides the device,
a lost sweep, or where the compile cache lives. CPU, fast, tier-1."""

import logging
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import pytest

from fairness_llm_tpu.config import ServingConfig, default_config
from fairness_llm_tpu.telemetry import use_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the compile cache can be placed from outside ------------------------------


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from fairness_llm_tpu.utils import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place_compile_cache() == str(tmp_path)
    # With the variable set the code sets no other path (JAX reads it).
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_and_in_checkout(monkeypatch):
    from fairness_llm_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.place_compile_cache()
        assert first == compile_cache.place_compile_cache()  # same every call
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        # Never a temporary, per-process or timestamped path.
        assert not first.startswith(tempfile.gettempdir())
        assert str(os.getpid()) not in first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# -- chip_smoke.py refuses anything but a TPU ----------------------------------


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    last = proc.stdout.strip().splitlines()[-1]
    assert "platform=cpu" in last and "not a TPU" in last
    assert '"ok"' not in proc.stdout  # no result line on a refusal


# -- a lost sweep fails the run; a lost row does not ---------------------------


class _StubBackend:
    """DecodeBackend double: raises for every chunk, or loses one row."""

    name = "tiny-test"

    def __init__(self, mode):
        self.mode = mode

    def generate(self, prompts, settings=None, seed=0, keys=None,
                 prefix_ids=None):
        if self.mode == "raise":
            raise RuntimeError("Mosaic refused the program")
        texts = ["1. Heat (1995)\n2. Alien (1979)"] * len(prompts)
        if self.mode == "one-row" and seed == default_config().random_seed:
            texts[0] = None  # one failed row in the first chunk
        return texts


def _run_cli(monkeypatch, tmp_path, mode, phase="1"):
    from fairness_llm_tpu.cli.main import main
    from fairness_llm_tpu.pipeline import phase1, phase2

    stub = _StubBackend(mode)
    monkeypatch.setattr(phase1, "backend_for", lambda *a, **k: stub)
    monkeypatch.setattr(phase2, "backend_for", lambda *a, **k: stub)
    with use_registry():
        return main(["--phase", phase, "--quick", "--model", "tiny-test",
                     "--no-save", "--results-dir", str(tmp_path)])


def test_cli_fails_when_every_chunk_of_a_sweep_fails(monkeypatch, tmp_path,
                                                     capsys):
    assert _run_cli(monkeypatch, tmp_path, "raise") == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert out[-1].startswith("FAILED phase 1") and "16 row(s)" in out[-1]


def test_cli_succeeds_when_one_row_of_many_fails(monkeypatch, tmp_path):
    assert _run_cli(monkeypatch, tmp_path, "one-row") == 0


def test_phase2_counts_a_failed_row_and_fails_on_a_lost_sweep():
    from fairness_llm_tpu.data import create_synthetic_ranking_data
    from fairness_llm_tpu.pipeline.phase2 import (
        listwise_evaluation_batch,
        pairwise_evaluation,
    )
    from fairness_llm_tpu.utils import SweepFailed

    items = create_synthetic_ranking_data(6, seed=0)

    class Serving:  # ServingBackend's convention: None for a failed request
        def __init__(self, texts):
            self.texts = texts

        def generate(self, prompts, settings=None, seed=0, keys=None,
                     prefix_ids=None):
            return self.texts[:len(prompts)]

    _, parsed = listwise_evaluation_batch(
        Serving(["2,1,3", None]), items, [None, "topic A"])
    assert parsed == [3, 0]  # the failed query parsed nothing; no crash
    _, comps = pairwise_evaluation(Serving(["A", None, "B"]), items, 3)
    assert [c["parsed"] for c in comps] == [True, False, True]
    with pytest.raises(SweepFailed, match="pairwise"):
        pairwise_evaluation(Serving([None, None]), items, 2)


# -- small repairs -------------------------------------------------------------


def test_settings_for_resolves_derived_int8_names():
    cfg = default_config()
    assert cfg.settings_for("mistral-7b-int8") == cfg.settings_for("mistral-7b")
    assert cfg.settings_for("llama3-70b-int8").max_tokens == 500
    with pytest.raises(KeyError):
        cfg.settings_for("no-such-model-int8")


def test_serving_cap_clamps_to_the_model_instead_of_failing_every_row():
    import dataclasses

    from fairness_llm_tpu.pipeline.backends import backend_for

    cfg = dataclasses.replace(default_config(),
                              serving=ServingConfig(enabled=True))
    assert cfg.serving.max_new_tokens >= 256  # tiny-test has 256 positions
    backend = backend_for("tiny-test", cfg, allow_random=True)
    assert backend.serving.max_new_tokens == 128
    few = dataclasses.replace(cfg.settings_for("tiny-test"), max_tokens=4)
    assert backend.generate(["hello"], few) != [None]
    capped = dataclasses.replace(cfg, max_new_tokens=32)  # --max-new-tokens
    assert backend_for("tiny-test", capped,
                       allow_random=True).serving.max_new_tokens == 32


# -- no device-rate fraction without published peaks ---------------------------


def test_peaks_table_raises_on_unknown_device_kind():
    from fairness_llm_tpu.telemetry import DEVICE_PEAKS, device_peaks

    assert device_peaks("TPU v5 lite").hbm_gbps == 819.0
    assert device_peaks("TPU v5 lite").bf16_gflops == 197_000.0
    with pytest.raises(LookupError, match="TPU v9000"):
        device_peaks("TPU v9000")
    assert jax.devices()[0].device_kind not in DEVICE_PEAKS
    with pytest.raises(LookupError):
        device_peaks()  # the CPU these tests run on has no peaks


def test_no_floor_or_fraction_published_without_peaks():
    from fairness_llm_tpu.models.configs import get_model_config
    from fairness_llm_tpu.telemetry import observe_decode, set_achievable_gbps
    from fairness_llm_tpu.telemetry.costmodel import (
        CostLedger,
        note_invocation,
        set_achievable_gflops,
    )

    cfg = get_model_config("tiny-test")
    stats = {"batch": 2, "cache_slots": 64, "prefix_len": 0}
    ledger = CostLedger(program="p")
    ledger.record("step", "matmuls", 1 << 20, 1 << 20)
    with use_registry() as reg:
        out = observe_decode(cfg, stats, steps=4, wall_s=0.1, program="p")
        note_invocation("p", 0.1, steps=4, ledger=ledger)
        assert out["gbps"] > 0 and out["fraction"] is None
        names = {m.name for m in reg.instruments()}
        assert "achieved_hbm_gbps" in names and "cost_wall_s_total" in names
        assert not names & {"achieved_over_achievable", "cost_reference_gbps",
                            "cost_reference_gflops",
                            "cost_component_min_s_total"}
    with use_registry() as reg:  # measured references installed: published
        set_achievable_gbps(50.0)
        set_achievable_gflops(200.0)
        try:
            note_invocation("p", 0.1, steps=4, ledger=ledger)
        finally:
            set_achievable_gbps(None)
            set_achievable_gflops(None)
        assert reg.read_value("cost_reference_gbps",
                              component="costmodel") == 50.0
        assert reg.read_value("cost_component_min_s_total", program="p",
                              component="matmuls") > 0


# -- a kernel gate that takes the XLA branch says so ---------------------------


def test_kernel_gates_witness_the_xla_branch(caplog):
    from fairness_llm_tpu.ops.dispatch import xla_paths
    from fairness_llm_tpu.ops.quant_matmul import force_pallas, quant_matmul

    x = jnp.ones((8, 256), jnp.bfloat16)
    scale = jnp.ones((200,), jnp.float32)
    with use_registry():
        assert xla_paths() == []
        quant_matmul(x, jnp.ones((256, 256), jnp.int8), jnp.ones((256,)))
        assert [(w["shape"], w["reason"]) for w in xla_paths()] == [
            ("256x256", "not_tpu")]
        # Headed for a TPU, a weight that misses the tile gate is the bf16
        # hoist the module docstring warns about: witnessed, not silent.
        with force_pallas(), caplog.at_level(logging.DEBUG):
            jax.eval_shape(quant_matmul, x, jnp.ones((256, 200), jnp.int8),
                           scale)
        assert ("256x200", "untileable") in [
            (w["shape"], w["reason"]) for w in xla_paths()]
    assert "int8_matmul 256x200 takes the XLA path (untileable)" in caplog.text
