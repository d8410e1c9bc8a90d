"""The benchmark's side of the window/full hybrid, in tier-1. (a) The cell
``trinity-mini-int8.catalog8k``: its CPU rehearsal reaches ``correct`` true,
traced and untraced, and false when the family's reference ignores the
window. (b) The configuration file
``perfbench/configs/trinity-mini-int8.json`` against the catalog row it was
cut from, what the family ``window_hybrid_moe`` derives and refuses, and its
FLOPs: work DONE (reused positions out, window layers capped, never over the
dense count). (c) The metric file, the traffic mix and the entries as
ISSUE 35 names them. The family's reference is held against the program in
``test_serving_window_hybrid.py``."""

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

from perfbench.families import window_hybrid_moe as family  # noqa: E402
from perfbench.lib import modelcfg, traffic  # noqa: E402

CONFIG = os.path.join(BENCH, "configs", "trinity-mini-int8.json")
CELL = "trinity-mini-int8.catalog8k"


# -- (a) the cell's rehearsal ------------------------------------------------------


@pytest.fixture
def scratch(tmp_path):
    """A copy of the benchmark (for the test that breaks the family's
    file)."""
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _rehearse(top, trace="0"):
    """One pass: a window of 0.01 s ends before a second pass can start, so
    the served sample does not depend on how fast the machine is (a window
    of 1 s held four passes alone and fewer beside other tests, and the
    sample with them)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    run = subprocess.run(
        [sys.executable, os.path.join(top, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "0.01",
         "--trace", trace, "--rehearse"],
        cwd=top, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


def test_the_cells_rehearsal_is_correct(scratch):
    line = _rehearse(str(scratch))
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] >= 6
    assert set(line["metrics"]) == {"rehearsal.setup_s", "rehearsal.req_per_s"}


def test_a_traced_rehearsal_reports_the_ring_the_reuse_and_the_experts(
        scratch):
    line = _rehearse(str(scratch), trace="1")
    assert line["correct"] is True
    m = line["metrics"]
    # the toy's pool: 8 slots x 332 positions, six window layers keep a ring
    # of 128 and two full layers the row, 256 B a position and layer in
    # bfloat16: 358 KiB a row; the step reads all 8 slots' for the 6 rows of
    # a pass, fewer while a pass drains
    row_kib = (6 * 128 + 2 * 332) * 256 / 1024
    assert row_kib * 8 / 6 - 1e-6 \
        <= m["rehearsal.kv_read_kb_per_token.req"]["value"] < row_kib * 2
    # prompts of 281-301 ids share 280: with two rows a batch, four of a
    # pass's six copy from a resident row all but their last window or two
    assert 35 < m["rehearsal.prefill_reuse_share.req"]["value"] < 75
    # the toy holds 4 of 16 experts and chooses 4: one local pair a token
    assert 0.5 < m["rehearsal.moe_local_pairs_per_token.req"]["value"] < 1.5
    assert "rehearsal.slot_occupancy_pct.req" in m
    assert not any("roofline" in k or "mfu" in k for k in m)


def test_a_reference_that_ignores_the_window_is_not_correct(scratch):
    """The comparison has teeth at the toy's size: with the window dropped
    from the reference's window layers (every earlier key seen), the same
    served tokens read 1.55 against the rehearsal's limit of 0.9 (1.5 to 3.0
    over three seeds and samples, against the toy's own 0.12 to 0.44 in
    bfloat16)."""
    path = scratch / "perfbench" / "families" / "window_hybrid_moe.py"
    text = path.read_text()
    whole = "o = attend(q, k, v, window).reshape(n, s, h * hd)"
    assert text.count(whole) == 1
    path.write_text(text.replace(whole, whole.replace("window", "0")))
    line = _rehearse(str(scratch))
    assert line["correct"] is False
    gap = line["compared"]["widest_gap"]
    assert gap["value"] > 1.5 * gap["limit"]
    assert line["failed"] == 0


# -- (b) the configuration and the family -------------------------------------------


def _raw():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_configuration_holds_every_published_width():
    config = _raw()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    assert config["source"] == row["source_url"]
    assert set(row["config"]) <= set(config)  # key by key
    differs = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == sorted([
        "num_experts", "num_attention_heads", "num_key_value_heads",
        "vocab_size"])
    # every width as published, and the depth uncut
    assert (config["hidden_size"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"],
            config["num_hidden_layers"], config["num_dense_layers"]) \
        == (2048, 128, 6144, 1024, 8, 2048, 32, 2)
    assert config["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 8
    assert config["published"] == dict(
        config["published"], num_experts=128, num_attention_heads=32,
        num_key_value_heads=4, vocab_size=200192, num_hidden_layers=32)
    share = config["deployment"]
    assert (share["chips"], share["pipeline_stages"],
            share["chips_sharing_a_layer"], share["rank"]) == (4, 1, 4, 0)
    assert {"assumed", "served", "family"} <= set(config)
    assert config["family"] == "window_hybrid_moe"
    assert {"weights_on_outputs", "mup_enabled", "selection_bias",
            "dense_mlps", "pad_token_id", "eos_token_id"} \
        <= set(config["assumed"])
    # the guide's floors: 8 experts, an eighth of the vocabulary, 4 layers
    assert config["num_experts"] >= 8
    assert 8 * config["vocab_size"] >= 200192
    assert not {"hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "sliding_window"} & set(config["reduced"])


def test_the_family_derives_the_share_from_the_deployment():
    cfg = modelcfg.load_config(CONFIG)
    assert (cfg["router_experts"], cfg["expert_shards"], cfg["expert_rank"],
            cfg["head_shards"], cfg["head_rank"]) == (128, 4, 0, 4, 0)
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 32
    kinds = family.layer_kinds(cfg)
    assert kinds[:4] == ["dense_window_first", "dense_window",
                         "expert_window", "expert_full"]
    assert kinds[4:] == (["expert_window"] * 3 + ["expert_full"]) * 7
    toy = modelcfg.rehearsal_cut(cfg)
    assert len(family.layer_kinds(toy)) == 8 and toy["sliding_window"] == 16
    assert set(family.layer_kinds(toy)) == set(kinds)
    mc = family.model_config(cfg, "whole")
    # the program is told the published heads and the share it holds of them
    assert (mc.num_heads, mc.num_kv_heads, mc.head_shards, mc.num_experts,
            mc.expert_shards, mc.first_dense_layers) == (32, 4, 4, 128, 4, 2)
    assert mc.sliding_window == 2048 and mc.embed_scale
    # layer 0's block scales the embedding, and no other
    assert family.block(cfg, "dense_window_first")[1][10] == 2048 ** 0.5
    assert family.block(cfg, "dense_window")[1][10] == 1.0
    assert family.block(cfg, "expert_full")[1][1:3] == (0, False)
    assert family.block(cfg, "expert_window")[1][1:3] == (2048, True)
    # every leaf the program's model looks up is made, and no other
    names = {n for n, _, _ in family.layer_leaves(cfg, "expert_full")}
    assert {"q_norm", "k_norm", "attn_gate", "attn_post_norm",
            "mlp_post_norm", "router_bias"} <= names


@pytest.mark.parametrize("change", [
    {"score_func": "softmax"}, {"route_norm": False}, {"n_group": 2},
    {"topk_group": 2}, {"rope_scaling": {"type": "yarn"}},
    {"mup_enabled": False}, {"num_shared_experts": 2}, {"num_experts": 16},
    {"num_attention_heads": 16}, {"num_key_value_heads": 2},
    {"layer_types": ["full_attention"] * 4},
    {"assumed": {"eos_token_id": 50048}},
], ids=lambda c: next(iter(c)))
def test_the_family_refuses_what_its_reference_does_not_compute(change):
    """A flag the reference's equations do not cover, a share that does not
    multiply out to the published count, an end-of-sequence id outside the
    vocabulary slice: refused when the file is read, not served wrongly."""
    with pytest.raises(ValueError):
        family.load(dict(_raw(), **change))


def test_model_flops_count_work_done():
    cfg = modelcfg.load_config(CONFIG)
    assert family.STRETCH_COUNTERS == ("moe_local_pairs",
                                       "prefill_tokens_reused")
    stretch = dict(prompt_tokens=0, rows=0, decoded=1, prefill_context_sum=0,
                   decode_context_sum=0.0, seconds=1.0, decode_steps=1)
    with pytest.raises(TypeError):
        family.model_flops(cfg, **stretch)  # the pair counter is required
    none = family.model_flops(cfg, moe_local_pairs=0, **stretch)
    one = family.model_flops(cfg, moe_local_pairs=1, **stretch)
    assert one - none == 2 * 3 * 2048 * 1024
    mixer = 2048 * 128 * (3 * 8 + 2)
    per_token = 2 * (32 * mixer + 2 * 3 * 2048 * 6144
                     + 30 * (2048 * 128 + 3 * 2048 * 1024))
    assert none == per_token + 2 * 2048 * 50048
    pair = 4 * 8 * 128
    # a decoded token's pairs: whole in the 8 full layers, capped at the
    # window in the 24 window layers
    near = family.model_flops(cfg, moe_local_pairs=0, **dict(
        stretch, decode_context_sum=10.0))
    assert near - none == 10 * pair * 32
    far = family.model_flops(cfg, moe_local_pairs=0, **dict(
        stretch, decode_context_sum=8100.0))
    assert far - none == pair * (8 * 8100 + 24 * 2048)
    # a pass of the cell: 60 rows of 8031, of which 52 copy all but a window
    rows, n = 60, 8031
    reused = 52 * (n - 64)
    a_pass = dict(prompt_tokens=rows * n, rows=rows, decoded=rows * 128,
                  prefill_context_sum=rows * n * n / 2,
                  decode_context_sum=rows * 128 * (n + 64.0),
                  moe_local_pairs=0)
    done = family.model_flops(cfg, prefill_tokens_reused=reused, **a_pass)
    all_of_it = family.model_flops(cfg, prefill_tokens_reused=0, **a_pass)
    computed = rows * n - reused
    assert computed == 8 * n + 52 * 64
    seen = a_pass["prefill_context_sum"] * computed / (rows * n)
    assert done == pytest.approx(
        (computed + rows * 128) * per_token
        + (rows + rows * 128) * 2 * 2048 * 50048
        + pair * (8 * (seen + a_pass["decode_context_sum"])
                  + 24 * (computed * 2048 + rows * 128 * 2048)))
    # work done is a sixth of the prompts' FLOPs counted whole (0.65 PFLOP
    # a pass: over the chip's peak in a pass of four seconds)
    assert 0.14 < done / all_of_it < 0.18 and 0.6e15 < all_of_it < 0.7e15
    # never over the dense count: every layer seeing every key, nothing reused
    dense = ((rows * n + rows * 128) * per_token
             + (rows + rows * 128) * 2 * 2048 * 50048
             + pair * 32 * (a_pass["prefill_context_sum"]
                            + a_pass["decode_context_sum"]))
    assert done < all_of_it < dense
    # and the true pairs of the prompts are not undercut by more than the
    # copied rows' own: 8 rows whole and 52 last windows, in the full layers
    true_full = 8 * n * n / 2 + 52 * (n * n - (n - 64) ** 2) / 2
    assert seen <= true_full < 1.2 * seen


# -- (c) what this configuration adds to the yardstick ------------------------------


def test_the_mix_is_a_catalogue_in_the_prompt():
    mix = traffic.load_mix(os.path.join(BENCH, "traffic", "catalog8k.json"))
    assert (mix["kind"], mix["pass_size"], mix["output_tokens"],
            mix["temperature"], mix["warm_tokens"]) == ("closed", 60, 128,
                                                        0.0, 9)
    assert mix["prompt_tokens"] == {"min": 8021, "max": 8041}
    assert mix["shared_prefix_share"] == 0.9976
    specs = traffic.closed_pass(mix, 3400000999, 0, 50048)
    lengths = sorted(len(s.ids) for s in specs)
    assert len(specs) == 60 and (lengths[0], lengths[-1]) == (8021, 8041)
    shared = min(int((a.ids[:8021] != specs[0].ids[:8021]).argmax())
                 for a in specs[1:])
    assert shared == 8002  # tails of 19 to 39 ids
    assert all(s.ids.min() >= 3 and s.ids.max() < 50048 for s in specs)
    again = traffic.closed_pass(mix, 3400000999, 1, 50048)
    assert (again[0].ids[:100] != specs[0].ids[:100]).any()  # fresh ids


def test_the_metric_file_names_its_reader():
    with open(os.path.join(BENCH, "layer_metrics",
                           "kv_read_kb_per_token.req.json")) as f:
        ratio = json.load(f)
    assert ratio["reader"] == "readers/counter_ratio.py"
    spec = importlib.util.spec_from_file_location(
        "counter_ratio", os.path.join(BENCH, ratio["reader"]))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ctx = types.SimpleNamespace(
        window=types.SimpleNamespace(counters={"decoded_tokens": 60}),
        cfg={})
    # a program without the counter (the parent): nothing, and no error
    assert reader.read(ctx, **ratio["args"]) is None
    # 64 slots x (24 rings of 3072 + 8 rows of 8192) x 512 B, for 60 rows:
    # 74.3e3 KiB (76.1 MB) a token
    row = 512 * (24 * 3072 + 8 * 8192)
    ctx.window.counters["kv_bytes_stepped"] = 64 * row
    assert reader.read(ctx, **ratio["args"]) == 64 * row / 60 / 1024
    assert 74.0e3 < 64 * row / 60 / 1024 < 74.5e3


def test_the_cell_is_in_the_benchmark_once_and_its_file_holds_no_entries():
    """The benchmark check refuses a ``model_config`` PR whose configuration
    no cell runs, so the cell is in ``BENCHMARK.json`` itself, once, and its
    own file carries no second copy of the entries; what its one limit can
    and cannot tell is written beside the readings."""
    bench = _bench()
    assert [c["name"] for c in bench["configs"]].count(
        "trinity-mini-int8") == 1
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert [m["name"] for m in bench["per_layer"]].count(
        "kv_read_kb_per_token.req") == 1
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert metric.get("workloads", []).count(CELL) <= 1
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        own = json.load(f)
    assert "held" not in own and "entries" not in own
    assert "fp8" in own["readings"] and "NONE" in own["readings"]


def test_the_entries_name_the_cell_as_the_issue_does():
    bench = _bench()
    # appended, each after every entry of its list that was there before;
    # later PRs append after it, so each is found by name
    earlier = ("mistral-7b-int8", "qwen2-7b-int8", "ax-k1-int8",
               "solar-open2-int8")

    def after_earlier(names, item):
        return names.count(item) == 1 and all(
            names.index(item) > i for i, n in enumerate(names)
            if n.startswith(earlier))

    assert after_earlier([w["name"] for w in bench["workloads"]], CELL)
    assert after_earlier([c["name"] for c in bench["configs"]],
                         "trinity-mini-int8")
    # the cell's own metrics list it first; later cells may append
    assert [m["name"] for m in bench["per_layer"]
            if m.get("workloads", [None])[0] == CELL][-1] \
        == "kv_read_kb_per_token.req"
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert after_earlier(metric["workloads"], CELL)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("trinity-mini-int8", "catalog8k", 1) and len(cell["why"]) <= 200
    conf = next(c for c in bench["configs"]
                if c["name"] == "trinity-mini-int8")
    config = _raw()
    assert conf["source"] == config["source"] and len(conf["why"]) <= 200
    assert sorted(conf["reduced"]) == sorted(config["reduced"])
    assert conf["file"] == "perfbench/configs/trinity-mini-int8.json"
    assert sum(c["name"].startswith("trinity") for c in bench["configs"]) == 1
    assert sum(w["config"] == "trinity-mini-int8"
               for w in bench["workloads"]) == 1
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        own = json.load(f)
    assert (own["pool"]["num_slots"], own["pool"]["prompt_budget"],
            own["pool"]["queue_capacity"]) == (64, 8064, 256)
    assert "traffic_overrides" not in own and own["control"] == "fp8"
    assert set(own["limits"]) == {"widest_gap"}
    assert 0.44 < own["rehearsal_limits"]["widest_gap"] < 2.0
    named = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    # trap (2) of ISSUE 35 was met: the grouped kernel's share read 121.9%
    # in the cell's first traced run, so the cell is not on that list
    assert named == {
        "req_per_s", "slot_occupancy_pct.req", "decode_step_ms.req",
        "prefill_ms_per_ktok.req", "step_mfu_pct.req",
        "int8_matmul_roofline.req",
        "sched_host_ms_per_iter.req", "dispatch_ms_per_call.req",
        "setup_trace_lower_s", "setup_compile_load_s", "hbm_peak_pct.req",
        "moe_local_pairs_per_token.req", "moe_expert_load_max_over_mean.req",
        "prefill_reuse_share.req", "kv_read_kb_per_token.req",
        "setup_build_s", "setup_first_call_s", "setup_trace_body_s",
        "decode_lead_read_share.req"}
    assert "flash_attention_roofline.req" not in named  # silent since PR 29
    metric = next(m for m in bench["per_layer"]
                  if m["name"] == "kv_read_kb_per_token.req")
    assert metric["workloads"][0] == CELL
    assert dict(metric, workloads=[CELL]) == {
        "name": "kv_read_kb_per_token.req", "unit": "KiB/token",
        "better": "lower", "source": "program_counter",
        "layer": "step programs", "moves": "req_per_s", "workloads": [CELL]}
