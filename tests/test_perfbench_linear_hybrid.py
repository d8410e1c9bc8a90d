"""The benchmark's side of the delta-rule hybrid, in tier-1. (a) The cell
``solar-open2-int8.sweep180``: its CPU rehearsal reaches ``correct`` true,
traced and untraced, and false when the family's recurrence is broken (its
decay dropped). (b) The configuration file
``perfbench/configs/solar-open2-int8.json`` against the catalog row it was cut
from, what the family ``linear_hybrid_moe`` refuses to take, and its FLOPs.
(c) The cost rule of ``kda_step``, the two layer-metric files, and the cell's
entries as ISSUE 33 names them. The family's reference is held against the
program in ``test_serving_linear_hybrid.py``."""

import importlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench.families import linear_hybrid_moe as family  # noqa: E402
from perfbench.lib import modelcfg  # noqa: E402

CONFIG = os.path.join(BENCH, "configs", "solar-open2-int8.json")
CELL = "solar-open2-int8.sweep180"


# -- (a) the cell's rehearsal ------------------------------------------------------


@pytest.fixture
def scratch(tmp_path):
    """A copy of the benchmark, for the test that breaks the family's file."""
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


def test_the_cells_rehearsal_is_correct(scratch):
    line = _rehearse(str(scratch))
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] >= 6
    assert set(line["metrics"]) == {"rehearsal.setup_s", "rehearsal.req_per_s"}


def test_a_traced_rehearsal_reports_the_state_and_expert_counters(scratch):
    line = _rehearse(str(scratch), trace="1")
    assert line["correct"] is True
    m = line["metrics"]
    # the toy's three linear layers keep 54.75 KiB a row; the step streams
    # all 8 slots' for the 6 rows of a pass, fewer while a pass drains
    row_kib = 3 * (4 * 32 * 32 * 4 + 3 * 384 * 2) / 1024
    assert row_kib * 8 / 6 - 1e-6 \
        <= m["rehearsal.row_state_kb_per_token.req"]["value"] < row_kib * 2
    # the toy holds 4 of 16 experts and chooses 4: one local pair a token
    assert 0.5 < m["rehearsal.moe_local_pairs_per_token.req"]["value"] < 1.5
    assert "rehearsal.slot_occupancy_pct.req" in m
    assert "rehearsal.prefill_reuse_share.req" not in m  # nothing is copied
    assert not any("roofline" in k or "mfu" in k for k in m)


def test_a_broken_recurrence_is_not_correct(scratch):
    """The comparison has teeth at the toy's size: with the decay dropped
    from the reference's recurrence, the same served tokens read several
    times the rehearsal's limit."""
    path = scratch / "perfbench" / "families" / "linear_hybrid_moe.py"
    text = path.read_text()
    whole = "state = a_t[..., None] * state"
    assert text.count(whole) == 1
    path.write_text(text.replace(whole, "state = 1.0 * state"))
    line = _rehearse(str(scratch))
    assert line["correct"] is False
    gap = line["compared"]["widest_gap"]
    assert gap["value"] > 2 * gap["limit"]
    assert line["failed"] == 0


# -- (b) the configuration and the family -------------------------------------------


def _raw():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_configuration_holds_every_published_width():
    config = _raw()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_attention_heads", "num_key_value_heads", "linear_attn_config"])
    # inside the one nested group only the number of heads held differs
    published, held = row["config"]["linear_attn_config"], \
        config["linear_attn_config"]
    assert {k for k in published if published[k] != held[k]} == {"num_heads"}
    # every width as published
    assert (config["hidden_size"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            held["head_dim"], held["short_conv_kernel_size"]) \
        == (4096, 128, 1280, 8, 128, 4)
    assert config["published"] == dict(
        config["published"], num_hidden_layers=48, n_routed_experts=320,
        vocab_size=196608, num_attention_heads=64, num_key_value_heads=8,
        linear_attn_num_heads=64)
    share = config["deployment"]
    assert (share["chips"], share["pipeline_stages"],
            share["chips_sharing_a_layer"], share["rank"]) == (32, 4, 8, 0)
    assert {"assumed", "served", "family"} <= set(config)
    assert config["family"] == "linear_hybrid_moe"


def test_the_family_derives_the_share_from_the_deployment():
    cfg = modelcfg.load_config(CONFIG)
    assert (cfg["router_experts"], cfg["expert_shards"], cfg["expert_rank"],
            cfg["head_shards"], cfg["head_rank"]) == (320, 8, 0, 8, 0)
    assert (cfg["linear_heads"], cfg["linear_head_dim"], cfg["conv_width"],
            cfg["gate_rank"]) == (8, 128, 4, 128)
    assert family.layer_kinds(cfg) == (
        ["softmax_layer"] + ["linear_layer"] * 3) * 3
    toy = modelcfg.rehearsal_cut(cfg)
    assert family.layer_kinds(toy) == ["softmax_layer"] + ["linear_layer"] * 3
    mc = family.model_config(cfg, "whole")
    # the program is told the published heads and the share it holds of them
    assert (mc.num_heads, mc.num_kv_heads, mc.linear_heads, mc.head_shards,
            mc.num_experts, mc.expert_shards) == (64, 8, 64, 8, 320, 8)
    assert mc.softmax_layers == (0, 4, 8) and mc.pos_emb == "none"


@pytest.mark.parametrize("change", [
    {"use_rope": True}, {"use_gqa_gate": False}, {"kda_use_full_proj": True},
    {"kda_allow_neg_eigval": False}, {"first_k_dense_replace": 1},
    {"routed_scaling_factor": 2.5}, {"norm_topk_prob": False},
    {"n_routed_experts": 32}, {"num_attention_heads": 16},
    {"num_key_value_heads": 2},
    {"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                            "num_heads": 16, "num_kv_heads": None}},
    {"assumed": {"eos_token_id": 24576, "gate_low_rank": 128}},
], ids=lambda c: next(iter(c)))
def test_the_family_refuses_what_its_reference_does_not_compute(change):
    """A flag the reference's equations do not cover, a share that does not
    multiply out to the published count, an end-of-sequence id outside the
    vocabulary slice: refused when the file is read, not served wrongly."""
    with pytest.raises(ValueError):
        family.load(dict(_raw(), **change))


def test_model_flops_counts_the_recurrence_and_the_local_pairs():
    cfg = modelcfg.load_config(CONFIG)
    stretch = dict(prompt_tokens=0, rows=0, decoded=1, prefill_context_sum=0,
                   decode_context_sum=0.0, seconds=1.0, decode_steps=1)
    with pytest.raises(TypeError):
        family.model_flops(cfg, **stretch)  # the counter is required
    none = family.model_flops(cfg, moe_local_pairs=0, **stretch)
    one = family.model_flops(cfg, moe_local_pairs=1, **stretch)
    assert one - none == 2 * 3 * 4096 * 1280
    linear = 4 * 4096 * 1024 + 2 * (4096 + 1024) * 128 + 4096 * 8
    softmax = 4096 * 128 * (3 * 8 + 2)
    per_token = (2 * (9 * linear + 3 * softmax
                      + 12 * (4096 * 320 + 3 * 4096 * 1280))
                 + 9 * 8 * (7 * 128 * 128 + 2 * 3 * 128 * 4)
                 + 2 * 4096 * 24576)
    assert none == per_token
    ctx = family.model_flops(cfg, moe_local_pairs=0, **dict(
        stretch, decode_context_sum=10.0))
    assert ctx - none == 10 * 4 * 8 * 128 * 3  # the three softmax layers
    assert family.STRETCH_COUNTERS == ("moe_local_pairs",)


# -- (c) what this configuration adds to the yardstick ------------------------------


def test_kda_step_cost_rule_counts_the_state_twice_and_the_vectors_once():
    rule = importlib.import_module("perfbench.costs.kda_step").cost
    n, dk, dv = 192 * 8, 128, 128
    operands = [("f32", (n, dk))] * 4 + [("f32", (n, dv)),
                                         ("f32", (n, dk, dv))]
    flops, nbytes = rule(("f32", (n, dv)), operands, {})
    assert flops == 7 * n * dk * dv
    assert nbytes == 4 * (2 * n * dk * dv + 4 * n * dk + 2 * n * dv)
    from perfbench.lib import flops as F
    from perfbench.lib.peaks import DEVICE_PEAKS

    least = F.min_time_s(flops, nbytes, DEVICE_PEAKS["TPU v5 lite"])
    assert 0.25e-3 < least < 0.26e-3  # 206 MB a call at 819 GB/s


def test_the_two_layer_metrics_name_their_readers():
    with open(os.path.join(BENCH, "layer_metrics",
                           "kda_step_roofline.req.json")) as f:
        roofline = json.load(f)
    assert (roofline["reader"], roofline["args"]) == (
        "readers/kernel_roofline.py",
        {"kernel": "kda_step", "cost": "kda_step"})
    with open(os.path.join(BENCH, "layer_metrics",
                           "row_state_kb_per_token.req.json")) as f:
        ratio = json.load(f)
    assert ratio["reader"] == "readers/counter_ratio.py"
    spec = importlib.util.spec_from_file_location(
        "counter_ratio", os.path.join(BENCH, ratio["reader"]))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ctx = types.SimpleNamespace(
        window=types.SimpleNamespace(counters={"decoded_tokens": 180}),
        cfg={})
    # a program without the counter (the parent): nothing, and no error
    assert reader.read(ctx, **ratio["args"]) is None
    ctx.window.counters["row_state_bytes_stepped"] = 192 * 4884480
    assert reader.read(ctx, **ratio["args"]) == 192 * 4770 / 180


def test_the_entries_name_the_cell_as_the_issue_does():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended, each after every entry of its list that was there before;
    # later PRs append after it, so each is found by name
    earlier = ("mistral-7b-int8", "qwen2-7b-int8", "ax-k1-int8")

    def after_earlier(names, item):
        return names.count(item) == 1 and all(
            names.index(item) > i for i, n in enumerate(names)
            if n.startswith(earlier))

    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    conf = next(c for c in bench["configs"]
                if c["name"] == "solar-open2-int8")
    assert after_earlier([w["name"] for w in bench["workloads"]], CELL)
    assert after_earlier([c["name"] for c in bench["configs"]], conf["name"])
    # the cell's own metrics list it first; later cells may append
    own_metrics = [m for m in bench["per_layer"]
                   if m.get("workloads", [None])[0] == CELL]
    assert [m["name"] for m in own_metrics] == [
        "kda_step_roofline.req", "row_state_kb_per_token.req"]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert after_earlier(metric["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("solar-open2-int8", "sweep180", 1) and len(cell["why"]) <= 200
    config = _raw()
    assert conf["source"] == config["source"]
    assert sorted(conf["reduced"]) == sorted(config["reduced"])
    assert conf["file"] == "perfbench/configs/solar-open2-int8.json"
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        own = json.load(f)
    assert (own["pool"]["num_slots"], own["pool"]["prompt_budget"],
            own["pool"]["queue_capacity"]) == (192, 256, 256)
    assert "serving" not in own and "traffic_overrides" not in own
    # the limit of the other routed-experts cell: the fp8 control's median
    # reading (2.58) fails it, the program's largest (2.21) passes
    assert own["limits"] == {"widest_gap": 2.5} and own["control"] == "fp8"
    named = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert named == {
        "req_per_s", "slot_occupancy_pct.req", "decode_step_ms.req",
        "prefill_ms_per_ktok.req", "step_mfu_pct.req",
        "int8_matmul_roofline.req", "grouped_int8_matmul_roofline.req",
        "moe_local_pairs_per_token.req",
        "moe_expert_load_max_over_mean.req", "sched_host_ms_per_iter.req",
        "dispatch_ms_per_call.req", "hbm_peak_pct.req", "setup_trace_lower_s",
        "setup_compile_load_s", "kda_step_roofline.req",
        "row_state_kb_per_token.req", "setup_build_s", "setup_first_call_s",
        "setup_trace_body_s"}
    assert "prefill_reuse_share.req" not in named  # nothing is copied
    for metric in own_metrics:
        assert metric["workloads"][0] == CELL and metric["moves"] == "req_per_s"
