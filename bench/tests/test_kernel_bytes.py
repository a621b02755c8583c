"""The bytes a kernel call must move are read off the shapes in its
instruction, and the kernel's roofline share from them and its time."""

import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import hlo_bytes  # noqa: E402
import run  # noqa: E402
from trace_reduce import OpStat, TraceSummary  # noqa: E402

# As a TPU v5e trace names a call at the PPR cell's size.
CALL = ('%segment_combine.1 = f32[1,16777216]{1,0:T(1,128)S(1)} custom-call('
        's32[40960]{0:T(1024)S(1)} %bitcast.131, s32[40960]{0:T(1024)S(1)} '
        '%copy-done.38, s32[131072,128]{1,0:T(8,128)} %fusion.46, '
        'f32[1,131072,128]{2,1,0:T(8,128)} %bitcast.119), custom_call_target='
        '"tpu_custom_call", operand_layout_constraints={s32[40960]{0}, '
        's32[40960]{0}, s32[131072,128]{1,0}, f32[1,131072,128]{2,1,0}}')
WANT = 4 * (16777216 + 2 * 40960 + 2 * 131072 * 128)


def test_operands_and_result_counted_once_each():
    assert hlo_bytes.call_bytes(CALL) == WANT


def test_an_instruction_without_operand_shapes_is_refused():
    with pytest.raises(ValueError, match="no shapes"):
        hlo_bytes.call_bytes("%k.1 = f32[8]{0} custom-call(%a, %b), x=1")


def _ctx(ops):
    trace = TraceSummary(window_s=1.0, busy_s=0.5, busy_by_device=[0.5],
                         ops=ops)
    return SimpleNamespace(trace=trace, peaks={"hbm_bytes_per_s": 819e9})


def test_the_kernel_roofline_is_bytes_over_peak_over_kernel_time():
    spec = run.load_spec(run.ROOT)
    parts = run.resolve(run.ROOT, spec, "ppr-rowtable.graph500-20")
    reader = parts.readers["segment_combine_roofline"]
    ops = {CALL: OpStat(seconds=0.044, calls=3),
           "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %a)": OpStat(0.4, 9)}
    want = 100 * 3 * WANT / 819e9 / 0.044
    assert reader.read(_ctx(ops)) == pytest.approx(want)
    # no kernel call in the window: nothing to read, not 0
    assert reader.read(_ctx({"%fusion.7 = f fusion(x)": OpStat(0.4, 9)})) \
        is None
