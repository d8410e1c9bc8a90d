"""The FLOP/byte functions against numbers worked out by hand."""

import pytest

from perfbench.families import dense_gqa
from perfbench.lib import flops, peaks


def test_int8_matmul_104x3584x18944():
    f, b = flops.int8_matmul_cost(104, 3584, 18944)
    assert f == 2 * 104 * 3584 * 18944 == 14_122_221_568
    # weights 67,895,296 + x 745,472 + y 3,940,352 + scales 75,776
    assert b == 67_895_296 + 745_472 + 3_940_352 + 75_776 == 72_656_896
    p = peaks.peaks_for("TPU v5 lite")
    # memory-bound: 72.66 MB / 819 GB/s = 88.7 us against 71.7 us of FLOPs
    assert flops.min_time_s(f, b, p) == pytest.approx(b / 819e9)
    assert b / 819e9 > f / 197e12


def test_causal_attention_2x28x256x128():
    f, b = flops.causal_attention_cost(2, 28, 4, 256, 128)
    # visible pairs 256*257/2 = 32,896; 4 FLOPs per pair and head-dim element
    assert f == 4 * 2 * 28 * 32_896 * 128 == 943_194_112
    # q and o: 2*28*256*128 each; k and v: 2*4*256*128 each; 2 bytes
    assert b == 2 * (2 * 2 * 28 * 256 * 128 + 2 * 2 * 4 * 256 * 128)


def test_window_cuts_the_triangle():
    full, _ = flops.causal_attention_cost(1, 1, 1, 8, 1)
    cut, _ = flops.causal_attention_cost(1, 1, 1, 8, 1, window=4)
    assert full == 4 * 36 and cut == 4 * (10 + 4 * 4)


def test_unlisted_device_is_an_error():
    with pytest.raises(KeyError, match="not in"):
        peaks.peaks_for("TPU v9000")


def test_model_flops_counts_real_tokens_only():
    cfg = dict(hidden_size=4, head_dim=2, num_attention_heads=2,
               num_key_value_heads=1, intermediate_size=8,
               num_hidden_layers=3, vocab_size=10)
    body = 3 * (4 * 4 + 2 * 4 * 2 + 4 * 4 + 3 * 4 * 8)
    assert dense_gqa.matmul_params(cfg) == body
    got = dense_gqa.model_flops(cfg, prompt_tokens=5, rows=1, decoded=2,
                                prefill_context_sum=0, decode_context_sum=0)
    assert got == 5 * 2 * body + 2 * 40 + 2 * (2 * body + 2 * 40)
