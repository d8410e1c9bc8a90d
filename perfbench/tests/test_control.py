"""The control at a size a test run can hold: the reference with its linear
layers' inputs in the next precision below bfloat16, put in the program's
place, must come out as not correct, while the program's own tokens pass, at
the rehearsal's limit. (The cells' own limits and readings were taken on the
chip at the cells' sizes: PERF.md.)"""

import numpy as np
import pytest

import run as bench
from perfbench.lib import correct, drivers, modelcfg
from perfbench.lib.sut import ServedModel

SEEDS = [3, 2147483747, 4000000001]


@pytest.fixture(scope="module")
def cell():
    c = bench.load_cell("qwen2-7b-int8.sweep")
    cfg = modelcfg.rehearsal_cut(c["cfg"])
    mix = dict(c["mix"], **c["own"]["rehearsal_overrides"])
    pool = dict(c["own"]["pool"], **c["own"]["rehearsal_pool"])
    sut = ServedModel(cfg, "toy", mix, pool, SEEDS[0])
    drivers.warm_closed(sut, mix, SEEDS[0])
    return c, cfg, mix, sut


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_and_program_passes(cell, seed):
    c, cfg, mix, sut = cell
    sut.set_seed_weights(seed)
    window = drivers.run_closed(sut, mix, seed, 0.2, drivers.NoTrace(), 0.0)
    sample = correct.draw_sample(window.served, seed, 32)
    fmt = c["own"]["control"]
    g = correct.gaps(cfg, seed, sample, control_formats=[fmt])
    limit = c["own"]["rehearsal_limits"]["widest_gap"]
    assert g["served_tokens"] >= 300
    assert g["widest_gap"] <= limit < g["control_" + fmt]


def test_reference_ignores_right_padding():
    from perfbench.lib import reference

    cfg = modelcfg.rehearsal_cut(bench.load_cell(
        "mistral-7b-int8.sweep")["cfg"])
    rng = np.random.default_rng(0)
    toks = rng.integers(3, cfg["vocab_size"], size=(1, 128)).astype(np.int32)
    take = np.arange(40, 50, dtype=np.int32)[None]
    a = reference.logits_at(cfg, 9, toks, take)
    toks2 = toks.copy()
    toks2[0, 60:] = 5
    b = reference.logits_at(cfg, 9, toks2, take)
    assert np.array_equal(a, b)
