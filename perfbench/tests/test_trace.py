"""The trace reduction on a small trace recorded on a v5e (three runs each of
two executables, one holding an ``int8_matmul`` [104,3584]x[3584,18944], the
other a ``flash_attention`` [2,28/4,256,128]; spans ``serve_step`` and
``serve_prefill`` round the calls)."""

import os
import types

import pytest

from perfbench.lib import peaks
from perfbench.lib.trace import Trace, parse_instruction, short_name

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "recorded.xplane.pb")
LABELS = {"serve_step", "serve_prefill"}


@pytest.fixture(scope="module")
def trace():
    return Trace(FIXTURE, LABELS)


def test_busy_union(trace):
    # ops of the six runs, nested and async ones counted once
    assert trace.busy_seconds() == pytest.approx(0.00086971, rel=1e-6)


def test_module_attribution_by_order(trace):
    assert sorted(trace.module_label.values()) == ["serve_prefill",
                                                   "serve_step"]
    step_s, step_n = trace.program_seconds("serve_step")
    pre_s, pre_n = trace.program_seconds("serve_prefill")
    assert (step_n, pre_n) == (3, 3)
    assert step_s == pytest.approx(0.000617423, rel=1e-6)
    assert pre_s == pytest.approx(0.000255202, rel=1e-6)


def test_per_kernel_sums(trace):
    mm = list(trace.kernel_events("int8_matmul"))
    fa = list(trace.kernel_events("flash_attention"))
    assert [e[3] for e in mm] == [3] and [e[3] for e in fa] == [3]
    result, operands, seconds, _ = mm[0]
    assert result == ("bf16", (104, 18944))
    assert ("s8", (3584, 18944)) in operands
    assert seconds == pytest.approx(0.000578553, rel=1e-6)
    top = dict(trace.top_ops(10))
    assert top["int8_matmul_bf16_104_18944_"] == pytest.approx(0.000578553,
                                                               rel=1e-6)


def test_mismatched_spans_raise():
    with pytest.raises(ValueError, match="module runs"):
        Trace(FIXTURE, {"serve_step"})


def test_parse_instruction_tuple_result():
    text = ("%copy-start = (bf16[2,4]{1,0}, bf16[2,4]{1,0}, u32[]{:S(2)}) "
            "copy-start(bf16[2,4]{1,0} %constant.4)")
    name, opcode, result, operands = parse_instruction(text)
    assert (name, opcode, result) == ("copy-start", "copy-start", None)
    assert operands == [("bf16", (2, 4))]
    assert short_name("%fusion.12 = f32[8,128]{1,0} fusion(f32[8]{0} %a), "
                      "kind=kLoop") == "fusion_f32_8_128_"


def _ctx(trace, scale=1.0):
    p = dict(peaks.peaks_for("TPU v5 lite"))
    p["bytes_per_s"] *= scale
    p["flops_per_s"] *= scale
    return types.SimpleNamespace(trace=trace, peaks=p, cfg={"window": None})


def _reader(name):
    import importlib.util

    path = os.path.join(os.path.dirname(HERE), "readers", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_roofline_share_and_over_100_raises(trace):
    read = _reader("kernel_roofline")
    share = read(_ctx(trace), kernel="int8_matmul", cost="int8_matmul")
    # 72.66 MB at 819 GB/s = 88.7 us of the 192.9 us the call took
    assert share == pytest.approx(46.00, abs=0.05)
    assert read(_ctx(trace), kernel="no_such_kernel",
                cost="int8_matmul") is None
    with pytest.raises(ValueError, match="roofline"):
        read(_ctx(trace, scale=0.25), kernel="int8_matmul",
             cost="int8_matmul")
