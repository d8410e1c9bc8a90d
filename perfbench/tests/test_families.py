"""The family seam. (a) ``dense_gqa`` is the code that ``lib/`` held before
families, moved: every leaf of both configurations' rehearsal cuts, the served
tree, the reference's logits (plain and under the control), the model's FLOPs
and both kernel cost rules equal what the parent commit (888a4c3) gave,
exactly. The digests are of the CPU's ``rbg`` stream under the installed JAX.
(b) A second family that lives only in a scratch copy of the benchmark, with
its configuration and cell, runs the rehearsal to ``correct`` true with no
file of ``perfbench/`` touched. (c) An unknown family raises, naming the ones
there are; so does an unknown ``serving`` override."""

import dataclasses
import hashlib
import importlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from perfbench import families
from perfbench.lib import modelcfg, reference, sut
from perfbench.lib import weights as W

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CONFIGS = ["mistral-7b-int8", "qwen2-7b-int8"]

with open(os.path.join(HERE, "data", "golden_dense_gqa.json")) as f:
    GOLDEN = json.load(f)


def _digest(a) -> str:
    a = np.asarray(a)
    return (hashlib.sha256(a.tobytes()).hexdigest()[:32]
            + f":{a.dtype}:{'x'.join(map(str, a.shape))}")


def _full(name):
    return modelcfg.load_config(os.path.join(BENCH, "configs", name + ".json"))


@pytest.fixture(scope="module", params=CONFIGS)
def made(request):
    """(name, golden, full cfg, cut cfg, globals, layers) at the golden seed."""
    name = request.param
    full = _full(name)
    cut = modelcfg.rehearsal_cut(full)
    make_layer, make_globals = W.jitted_makers(cut)
    words = W.seed_words(GOLDEN["seed"])
    layers = [make_layer(words, i) for i in range(cut["num_hidden_layers"])]
    return name, GOLDEN[name], full, cut, make_globals(words), layers


def test_derived_keys_and_cut_are_the_parents(made):
    _, gold, full, cut, _, _ = made
    assert {k: full[k] for k in gold["loaded"]} == gold["loaded"]
    assert {k: cut[k] for k in gold["cut"]} == gold["cut"]


def test_every_leaf_is_the_parents(made):
    _, gold, _, _, glob, layers = made
    got = {}
    for where, leaves in [("global", glob)] + [
            (f"layer_{i}", lw) for i, lw in enumerate(layers)]:
        for k, v in leaves.items():
            for j, part in enumerate(v if isinstance(v, tuple) else (v,)):
                got[f"{where}/{k}/{j}"] = _digest(part)
    assert got == gold["leaves"]
    assert list(got) == list(gold["leaves"])  # and in the parent's key order


def test_served_tree_and_model_config_are_the_parents(made):
    name, gold, full, cut, glob, layers = made
    family = families.of(cut)
    tree = family.program_tree(cut, glob, layers)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    assert {jax.tree_util.keystr(p): _digest(v)
            for p, v in flat} == gold["served_tree"]
    assert dataclasses.asdict(
        family.model_config(full, name)) == gold["model_config"]


def test_reference_logits_are_the_parents(made):
    _, gold, _, cut, _, _ = made
    rng = np.random.default_rng(11)
    toks = rng.integers(3, cut["vocab_size"], size=(2, 128)).astype(np.int32)
    take = np.stack([np.arange(20, 26), np.arange(90, 96)]).astype(np.int32)
    logits = reference.logits_at(cut, GOLDEN["seed"], toks, take)
    assert [float(x) for x in logits[0, 0, :4]] == gold["logits_head"]
    assert _digest(logits) == gold["logits"]
    low = reference.logits_at(cut, GOLDEN["seed"], toks, take,
                              act_format="fp8")
    assert _digest(low) == gold["logits_fp8"] != gold["logits"]


def test_flops_and_kernel_costs_are_the_parents(made):
    _, gold, full, _, _, _ = made
    family = families.of(full)
    # the traced stretch as the drivers hand it over: more keys than are read
    stretch = dict(gold["traced"], seconds=6.1, decode_steps=256,
                   occupancy_sum=5760)
    assert family.model_flops(full, **stretch) == gold["model_flops"]
    assert family.matmul_params(full) == gold["matmul_params"]
    d, ff, v = (full["hidden_size"], full["intermediate_size"],
                full["vocab_size"])
    h, hkv, hd = (full["num_attention_heads"], full["num_key_value_heads"],
                  full["head_dim"])
    mm = importlib.import_module("perfbench.costs.int8_matmul").cost
    assert list(mm(("bf16", (48, ff)), [("bf16", (48, d)), ("s8", (d, ff)),
                                        ("f32", (ff,))], full)) \
        == gold["int8_matmul_cost"]["up_proj_48"]
    assert list(mm(("f32", (48, v)), [("bf16", (48, d)), ("s8", (d, v)),
                                      ("f32", (v,))], full)) \
        == gold["int8_matmul_cost"]["lm_head_48"]
    at = importlib.import_module("perfbench.costs.causal_attention").cost
    for key, (b, s) in {"prefill_8x256": (8, 256),
                        "prefill_1x8192": (1, 8192)}.items():
        q, kv = ("bf16", (b, h, s, hd)), ("bf16", (b, hkv, s, hd))
        assert list(at(q, [q, kv, kv], full)) \
            == gold["causal_attention_cost"][key]


def test_unknown_family_names_the_known_ones():
    with pytest.raises(ValueError, match="dense_gqa"):
        families.of({"family": "no_such_family"})
    assert families.of({}).__name__.endswith("dense_gqa")


def test_serving_overrides_are_checked_against_the_dataclass():
    mix, pool = {"output_tokens": 8}, {"num_slots": 4, "prompt_budget": 32}
    assert sut.serving_config(mix, pool).decode_chunk == 8
    over = sut.serving_config(mix, dict(pool, serving={"decode_chunk": 4}))
    assert (over.decode_chunk, over.num_slots, over.max_prompt_len) \
        == (4, 4, 32)
    with pytest.raises(ValueError, match="no_such_field"):
        sut.serving_config(mix, dict(pool, serving={"no_such_field": 1}))


def test_a_familys_stretch_counters_reach_the_traced_stretch(monkeypatch):
    import types

    from perfbench.families import dense_gqa
    from perfbench.lib import drivers

    fake = types.SimpleNamespace(cfg={})
    before, after = {"decode_steps": 3, "routed": 10}, {"decode_steps": 8,
                                                        "routed": 25}
    assert "routed" not in drivers._delta(fake, after, before)
    monkeypatch.setattr(dense_gqa, "STRETCH_COUNTERS", ("routed",),
                        raising=False)
    got = drivers._delta(fake, after, before)
    assert (got["routed"], got["decode_steps"]) == (15, 5)
    with pytest.raises(KeyError, match="routed"):
        drivers._delta(fake, {"decode_steps": 8}, before)


# -- (b) a family that is added, not edited in -------------------------------


def _tree_state(top):
    state = {}
    for folder, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                state[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return state


@pytest.fixture
def scratch_benchmark(tmp_path):
    """A copy of the benchmark with a family, a configuration and a cell
    added to it, and not one of its files changed."""
    bench = tmp_path / "perfbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = _tree_state(bench)
    shutil.copy(os.path.join(HERE, "data", "two_kinds.py"),
                bench / "families" / "two_kinds.py")
    with open(bench / "configs" / "qwen2-7b-int8.json") as f:
        config = dict(json.load(f), name="toy-two-kinds", family="two_kinds")
    (bench / "configs" / "toy-two-kinds.json").write_text(json.dumps(config))
    with open(bench / "workloads" / "qwen2-7b-int8.sweep.json") as f:
        own = dict(json.load(f), name="toy-two-kinds.sweep",
                   serving={"decode_chunk": 4},
                   rehearsal_overrides={"pass_size": 6, "output_tokens": 12,
                                        "prompt_tokens": {"min": 20,
                                                          "max": 40},
                                        "warm_tokens": 3})
    (bench / "workloads" / "toy-two-kinds.sweep.json").write_text(
        json.dumps(own))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(dict(
        manifest["configs"][1], name="toy-two-kinds",
        file="perfbench/configs/toy-two-kinds.json"))
    manifest["workloads"].append(dict(
        manifest["workloads"][2], name="toy-two-kinds.sweep",
        config="toy-two-kinds"))
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "qwen2-7b-int8.sweep" in metric.get("workloads", ()):
            metric["workloads"].append("toy-two-kinds.sweep")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    added = {"families/two_kinds.py", "configs/toy-two-kinds.json",
             "workloads/toy-two-kinds.sweep.json"}
    yield tmp_path, bench
    after = _tree_state(bench)
    assert {k: v for k, v in after.items() if k not in added} == before


@pytest.mark.parametrize("trace", ["0", "1"])
def test_an_added_family_rehearses_correct(scratch_benchmark, trace):
    top, bench = scratch_benchmark
    repo_before = _tree_state(BENCH)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    run = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload",
         "toy-two-kinds.sweep", "--seed", "2147483999", "--seconds", "1",
         "--trace", trace, "--rehearse"],
        cwd=top, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] >= 6
    assert line["compared"]["widest_gap"]["value"] \
        <= line["compared"]["widest_gap"]["limit"]
    assert _tree_state(BENCH) == repo_before
