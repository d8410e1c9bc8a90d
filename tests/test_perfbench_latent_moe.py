"""The benchmark's seam, in tier-1. (a) The cell this PR entered
(``ax-k1-int8.sweep180``): its CPU rehearsal reaches ``correct`` true, traced
and untraced, and false when the family's reference block is broken (its
attention output's sign turned). (b) The tier-1 home of ``perfbench/tests/test_families.py``
(PERF.md section 7 (5) of PR 27): the dense family's goldens and the
scratch-copy family run from here too. (c) The new cost rule, reader and
stretch counter, and the cell's entries as ISSUE 28 names them."""

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

CELL = "ax-k1-int8.sweep180"


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


def test_a_traced_rehearsal_reports_the_expert_counters(scratch):
    line = _rehearse(str(scratch), trace="1")
    assert line["correct"] is True
    m = line["metrics"]
    # the toy holds 4 of 16 experts and chooses 4: one local pair a token
    assert 0.5 < m["rehearsal.moe_local_pairs_per_token.req"]["value"] < 1.5
    assert m["rehearsal.moe_expert_load_max_over_mean.req"]["value"] >= 1.0
    assert "rehearsal.slot_occupancy_pct.req" in m
    assert not any("roofline" in k or "mfu" in k for k in m)


def test_a_broken_reference_block_is_not_correct(scratch):
    """The comparison has teeth at the toy's size: with the attention
    output's sign turned in the reference, the same served tokens read three
    times the rehearsal's limit, whichever requests the one-second window
    happened to finish."""
    family = scratch / "perfbench" / "families" / "latent_moe.py"
    text = family.read_text()
    whole = 'return x + ops.dense(a, layer["o_proj"])'
    assert whole in text
    family.write_text(text.replace(whole, whole.replace("+", "-")))
    line = _rehearse(str(scratch))
    assert line["correct"] is False
    gap = line["compared"]["widest_gap"]
    assert gap["value"] > 2 * gap["limit"]
    assert line["failed"] == 0


# -- (b) perfbench/tests/test_families.py, collected here ---------------------

_spec = importlib.util.spec_from_file_location(
    "perfbench_test_families", os.path.join(BENCH, "tests",
                                            "test_families.py"))
_families = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_families)
for _name in dir(_families):
    if _name.startswith("test_") or _name in ("made", "scratch_benchmark"):
        globals()[_name] = getattr(_families, _name)


# -- (c) what this configuration adds to the yardstick --------------------------


def test_grouped_cost_rule_counts_the_held_experts_bytes_once():
    rule = importlib.import_module("perfbench.costs.grouped_int8_matmul").cost
    operands = [("s32", (18,)), ("s32", (18,)), ("s32", (1,)),
                ("bf16", (576, 7168)), ("s8", (12, 7168, 2048)),
                ("f32", (12, 1, 2048))]
    flops, nbytes = rule(("bf16", (576, 2048)), operands, {})
    assert flops == 0
    assert nbytes == 12 * 7168 * 2048 + 12 * 2048 * 4
    from perfbench.lib import flops as F
    from perfbench.lib.peaks import DEVICE_PEAKS

    least = F.min_time_s(flops, nbytes, DEVICE_PEAKS["TPU v5 lite"])
    assert 0.21e-3 < least < 0.22e-3  # a third of a layer's 0.65 ms


def test_counter_ratio_reads_nothing_where_the_program_has_no_counter():
    spec = importlib.util.spec_from_file_location(
        "counter_ratio", os.path.join(BENCH, "readers", "counter_ratio.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ctx = types.SimpleNamespace(
        window=types.SimpleNamespace(counters={"decode_steps": 9}),
        cfg={"n_routed_experts": 12})
    assert reader.read(ctx, "moe_local_pairs", "moe_tokens") is None
    ctx.window.counters.update(moe_tokens=1000, moe_local_pairs=500,
                               moe_fullest_expert_pairs=75)
    assert reader.read(ctx, "moe_local_pairs", "moe_tokens") == 0.5
    assert reader.read(ctx, "moe_fullest_expert_pairs", "moe_local_pairs",
                       scale_by_cfg="n_routed_experts") == 1.8


def test_model_flops_counts_local_pairs_from_the_programs_counter():
    from perfbench.families import latent_moe
    from perfbench.lib import modelcfg

    cfg = modelcfg.load_config(
        os.path.join(BENCH, "configs", "ax-k1-int8.json"))
    stretch = dict(prompt_tokens=0, rows=0, decoded=1, prefill_context_sum=0,
                   decode_context_sum=0.0, seconds=1.0, decode_steps=1)
    with pytest.raises(TypeError):
        latent_moe.model_flops(cfg, **stretch)  # the counter is required
    none = latent_moe.model_flops(cfg, moe_local_pairs=0, **stretch)
    one = latent_moe.model_flops(cfg, moe_local_pairs=1, **stretch)
    assert one - none == 2 * 3 * 7168 * 2048
    per_token = 2 * (15 * latent_moe.attention_params(cfg)
                     + 3 * 7168 * 18432
                     + 14 * (7168 * 192 + 3 * 7168 * 2048)
                     + 7168 * 20480)
    assert none == per_token
    assert latent_moe.STRETCH_COUNTERS == ("moe_local_pairs",)


def test_the_entries_name_the_cell_as_the_issue_does():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended, each after every entry of its list that was there before;
    # later PRs append after it, so each is found by name
    earlier = ("mistral-7b-int8", "qwen2-7b-int8")

    def after_earlier(names, item):
        return names.count(item) == 1 and all(
            names.index(item) > i for i, n in enumerate(names)
            if n.startswith(earlier))

    assert after_earlier([w["name"] for w in bench["workloads"]], CELL)
    assert after_earlier([c["name"] for c in bench["configs"]],
                         "ax-k1-int8")
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert after_earlier(metric["workloads"], CELL)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("ax-k1-int8", "sweep180", 1) and len(cell["why"]) <= 200
    conf = next(c for c in bench["configs"] if c["name"] == "ax-k1-int8")
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
    assert conf["source"] == config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differs == sorted(conf["reduced"])
    assert config["published"]["n_routed_experts"] == 192
    with open(os.path.join(BENCH, "traffic", "sweep180.json")) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["pass_size"], mix["prompt_tokens"],
            mix["shared_prefix_share"], mix["output_tokens"],
            mix["temperature"]) == ("closed", 180, {"min": 175, "max": 195},
                                    0.905, 128, 0.0)
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        own = json.load(f)
    assert (own["pool"]["num_slots"], own["pool"]["prompt_budget"],
            own["pool"]["queue_capacity"]) == (192, 256, 256)
    assert "serving" not in own  # ServingConfig defaults otherwise
    named = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert {"req_per_s", "step_mfu_pct.req", "int8_matmul_roofline.req",
            "grouped_int8_matmul_roofline.req", "moe_local_pairs_per_token.req",
            "moe_expert_load_max_over_mean.req"} <= named
    assert "flash_attention_roofline.req" not in named
    for metric in bench["per_layer"][-3:]:
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", metric["name"] + ".json"))
