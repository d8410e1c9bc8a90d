"""The benchmark's side of the Gated DeltaNet hybrid, in tier-1. (a) The cell
``olmo-hybrid-7b-int8.sweep``: its CPU rehearsal reaches ``correct`` true,
and false when the family's reference is given another linear layer (the
decay made per key channel, the output gate made a sigmoid). (b) The
configuration file ``perfbench/configs/olmo-hybrid-7b-int8.json`` against
the published config it was taken from, what the family ``gated_delta_hybrid``
refuses, and its FLOPs. (c) The cost rule of ``gdn_step``, its layer-metric
file, and the cell's entries in ``BENCHMARK.json``. The family's reference
is held against the program in ``test_serving_gated_delta.py``.

The rehearsal's limit is 0.5: six seeds of the sound toy read 0.032-0.043
and once 0.316 (a near-tie that bfloat16 resolves the other way), the toy
with a per-channel decay 0.76-1.08 and with a sigmoid gate 2.15-2.66 over
three seeds each."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench.families import gated_delta_hybrid as family  # noqa: E402
from perfbench.lib import modelcfg  # noqa: E402

CONFIG = os.path.join(BENCH, "configs", "olmo-hybrid-7b-int8.json")
CELL = "olmo-hybrid-7b-int8.sweep"
EARLIER = ("mistral-7b-int8", "qwen2-7b-int8", "ax-k1-int8",
           "solar-open2-int8", "trinity-mini-int8")
# The model's published config.json, every key the configuration file copies.
PUBLISHED_SOURCE = ("https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/"
                    "config.json")
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}


# -- (a) the cell's rehearsal ------------------------------------------------------


@pytest.fixture
def scratch(tmp_path):
    """A copy of the benchmark, for the tests that change the family."""
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path


def _rehearse(top, trace="0"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    run = subprocess.run(
        [sys.executable, os.path.join(top, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", trace, "--rehearse"],
        cwd=top, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


def test_the_cells_traced_rehearsal_is_correct(scratch):
    line = _rehearse(str(scratch), trace="1")
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] >= 6
    m = line["metrics"]
    assert "rehearsal.slot_occupancy_pct.req" in m
    assert "rehearsal.setup_build_s" in m
    assert not any("roofline" in k or "mfu" in k for k in m)
    # a row of the toy keeps 105 KiB in its three linear layers (4 heads x
    # 32 x 64 x 4 B of state, 3 x 512 x 2 B of inputs) and 76 KiB in its
    # full layer (64 + 12 positions x 1 KiB); the step streams all 8 slots'
    # for the 6 rows of a pass, fewer while a pass drains
    for metric, row_kib in (
            ("row_state_kb_per_token.req",
             3 * (4 * 32 * 64 * 4 + 3 * 512 * 2) / 1024),
            ("kv_read_kb_per_token.req", 76 * 4 * 64 * 2 * 2 / 1024)):
        assert row_kib * 8 / 6 - 1e-6 \
            <= m["rehearsal." + metric]["value"] < row_kib * 2


@pytest.mark.parametrize("broken,whole,instead", [
    ("decay_per_channel",
     "a = jnp.broadcast_to(_decay(x, layer, ops)[..., None], (n, s, h, dk))",
     "a = _decay(x, layer, ops)[..., None] ** jnp.linspace(0.5, 1.5, dk)"),
    ("sigmoid_gate",
     'return jax.nn.silu(ops.dense(x, layer["g_proj"]))',
     'return jax.nn.sigmoid(ops.dense(x, layer["g_proj"]))'),
], ids=lambda x: x if x in ("decay_per_channel", "sigmoid_gate") else "")
def test_another_linear_layer_is_not_correct(scratch, broken, whole,
                                             instead):
    """The comparison has teeth at the toy's size: with the reference's
    decay spread over the key channels (a^0.5 to a^1.5 of the head's), or
    its output gate a sigmoid, the same served tokens read above the
    limit."""
    path = scratch / "perfbench" / "families" / "gated_delta_hybrid.py"
    text = path.read_text()
    assert text.count(whole) == 1
    path.write_text(text.replace(whole, instead))
    line = _rehearse(str(scratch))
    assert line["correct"] is False and line["failed"] == 0
    gap = line["compared"]["widest_gap"]
    assert gap["value"] > 1.4 * gap["limit"]


# -- (b) the configuration and the family -------------------------------------------


def _raw():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_configuration_holds_every_published_width():
    config = _raw()
    assert config["source"] == PUBLISHED_SOURCE
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["reduced"] == []
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_hidden_layers"], config["vocab_size"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"]) \
        == (3840, 11008, 32, 100352, 96, 192, 4)
    assert config["deployment"]["chips"] == 1
    assert {"assumed", "served", "family"} <= set(config)
    assert config["family"] == "gated_delta_hybrid"
    assert config["assumed"]["head_dim"] == 128


def test_the_family_derives_the_layers_and_the_program_config():
    cfg = modelcfg.load_config(CONFIG)
    assert family.layer_kinds(cfg) == (["linear_layer"] * 3
                                       + ["full_layer"]) * 8
    toy = modelcfg.rehearsal_cut(cfg)
    assert family.layer_kinds(toy) == ["linear_layer"] * 3 + ["full_layer"]
    mc = family.model_config(cfg, "whole")
    assert (mc.num_heads, mc.num_kv_heads, mc.linear_heads, mc.head_dim,
            mc.linear_head_dim, mc.value_head_dim) == (30, 30, 30, 128, 96,
                                                      192)
    assert mc.softmax_layers == (3, 7, 11, 15, 19, 23, 27, 31)
    assert (mc.linear_mixer, mc.pre_norms, mc.post_norms, mc.qk_norm_whole,
            mc.attention_gate) == ("gdn", False, True, True, False)
    assert set(mc.layer_kinds) == {"dense"} and mc.pos_emb == "none"
    assert mc.row_state_shape == (15, 96, 384)


@pytest.mark.parametrize("change", [
    {"linear_allow_neg_eigval": False}, {"attention_bias": True},
    {"linear_num_key_heads": 15}, {"num_key_value_heads": 6},
    {"rope_parameters": {"rope_theta": 10000.0}},
    {"layer_types": ["sliding_attention"] * 32},
    {"assumed": {"eos_token_id": 100352, "head_dim": 128}},
], ids=lambda c: next(iter(c)))
def test_the_family_refuses_what_its_reference_does_not_compute(change):
    with pytest.raises(ValueError):
        family.load(dict(_raw(), **change))


def test_model_flops_counts_the_recurrence_the_taps_and_attention():
    cfg = modelcfg.load_config(CONFIG)
    stretch = dict(prompt_tokens=0, rows=0, decoded=1, prefill_context_sum=0,
                   decode_context_sum=0.0, seconds=1.0, decode_steps=1)
    none = family.model_flops(cfg, **stretch)
    mlp = 3 * 3840 * 11008
    linear = mlp + 3840 * 11520 + 2 * 3840 * 30 + 2 * 3840 * 5760
    full = mlp + 4 * 3840 * 3840
    per_token = (2 * (24 * linear + 8 * full)
                 + 24 * (7 * 30 * 96 * 192 + 2 * 11520 * 4)
                 + 2 * 3840 * 100352)
    assert none == per_token
    ctx = family.model_flops(cfg, **dict(stretch, decode_context_sum=10.0))
    assert ctx - none == 10 * 4 * 30 * 128 * 8  # the eight full layers


# -- (c) what this configuration adds to the yardstick ------------------------------


def test_gdn_step_cost_rule_counts_the_state_twice_and_the_vectors_once():
    rule = importlib.import_module("perfbench.costs.gdn_step").cost
    # the kernel's operands for 48 rows of 30 heads, two side by side, in
    # blocks of 20: the state, q, k and b k as columns, decay and v as rows
    n, dk, lanes, groups = 48 * 15, 96, 384, 36
    operands = [("f32", (n, dk, lanes))] + [("f32", (groups, dk, 40))] * 3 \
        + [("f32", (groups, 20, lanes))] * 2
    flops, nbytes = rule(None, operands, {})
    state = n * dk * lanes
    assert flops == 7 * state
    assert nbytes == 4 * (2 * state + 3 * 48 * 30 * 96 + 2 * n * lanes
                          + n * lanes)
    from perfbench.lib import flops as F
    from perfbench.lib.peaks import DEVICE_PEAKS

    least = F.min_time_s(flops, nbytes, DEVICE_PEAKS["TPU v5 lite"])
    assert 0.26e-3 < least < 0.27e-3  # 217 MB a call at 819 GB/s
    # the same work whatever the state's layout
    flat = [("f32", (48, 15, dk, lanes))] + operands[1:]
    assert rule(None, flat, {}) == (flops, nbytes)


def test_the_layer_metric_names_its_reader():
    with open(os.path.join(BENCH, "layer_metrics",
                           "gdn_step_roofline.req.json")) as f:
        roofline = json.load(f)
    assert (roofline["reader"], roofline["args"]) == (
        "readers/kernel_roofline.py",
        {"kernel": "gdn_step", "cost": "gdn_step"})


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_entries_name_the_cell_in_the_benchmark():
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    conf = next(c for c in bench["configs"]
                if c["name"] == "olmo-hybrid-7b-int8")
    # appended after every cell and configuration that was there before
    names = [w["name"] for w in bench["workloads"]]
    assert all(names.index(CELL) > i for i, w in enumerate(
        bench["workloads"]) if w["config"] in EARLIER)
    configs = [c["name"] for c in bench["configs"]]
    assert all(configs.index(conf["name"]) > configs.index(e)
               for e in EARLIER)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            listed = metric["workloads"]
            assert listed.count(CELL) == 1
            assert all(listed.index(CELL) > i for i, w in enumerate(listed)
                       if w.rsplit(".", 1)[0] in EARLIER)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("olmo-hybrid-7b-int8", "sweep", 1) and len(cell["why"]) <= 200
    config = _raw()
    assert conf["source"] == config["source"] and conf["reduced"] == []
    assert conf["file"] == "perfbench/configs/olmo-hybrid-7b-int8.json"
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        own = json.load(f)
    assert (own["pool"]["num_slots"], own["pool"]["prompt_budget"]) \
        == (48, 256)
    assert "serving" not in own and "traffic_overrides" not in own
    assert own["control"] == "fp8" and set(own["limits"]) == {"widest_gap"}
    named = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert named == {
        "req_per_s", "slot_occupancy_pct.req", "decode_step_ms.req",
        "prefill_ms_per_ktok.req", "step_mfu_pct.req",
        "int8_matmul_roofline.req", "sched_host_ms_per_iter.req",
        "dispatch_ms_per_call.req", "hbm_peak_pct.req", "setup_trace_lower_s",
        "setup_compile_load_s", "setup_build_s", "setup_first_call_s",
        "setup_trace_body_s", "gdn_step_roofline.req",
        "row_state_kb_per_token.req", "kv_read_kb_per_token.req"}
    own_metric = next(m for m in bench["per_layer"]
                      if m["name"] == "gdn_step_roofline.req")
    assert own_metric == {
        "name": "gdn_step_roofline.req", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "req_per_s",
        "workloads": [CELL]}
