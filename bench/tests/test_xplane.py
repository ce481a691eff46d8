"""The trace reduction, on synthetic events and on a short trace recorded
on a TPU v5e (``bench/data/``: 0.12 s of the dense StableLM-3B cell)."""
import gzip
from pathlib import Path

import pytest

from bench import xplane

DATA = Path(__file__).resolve().parents[1] / "data"


def test_hlo_op_names_and_opcodes():
    assert xplane.hlo_op(
        "%enec_decode.2 = u16[7860,16384]{1,0:T(8,128)(2,1)} "
        "custom-call(u8[7860,128]{1,0} %bitcast.240)") == \
        ("enec_decode", "custom-call")
    assert xplane.hlo_op(
        "%while.1 = (s32[]{:T(128)}, bf16[32,1,2560]{2,0,1}) "
        "while((s32[]{:T(128)}) %tuple.33), condition=%c") == \
        ("while", "while")
    assert xplane.hlo_op(
        "%constant_dynamic-slice_fusion.4 = bf16[1,32]{1,0} "
        "fusion(bf16[8,32] %g), kind=kLoop") == \
        ("constant_dynamic-slice_fusion", "fusion")
    assert xplane.hlo_op("fusion.12") == ("fusion", "")


def test_operands_of_a_kernel_call():
    text = ("%tiled_matmul.31 = f32[32,2560]{1,0:T(8,128)S(1)} custom-call("
            "bf16[32,2560]{1,0:T(8,128)(2,1)S(1)} %fusion.56, "
            "bf16[2560,2560]{1,0:T(8,128)(2,1)S(1)} "
            "%dynamic-slice_bitcast_fusion.15), custom_call_target=\"t\"")
    assert xplane._operands(text) == ["fusion.56",
                                      "dynamic-slice_bitcast_fusion.15"]


def test_merge_joins_overlapping_intervals():
    assert xplane._merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_staging_slices_count_with_their_kernel_once():
    def ev(lo, hi, text):
        name, opcode = xplane.hlo_op(text)
        return (lo, hi, name, opcode, text)

    slice_ = "%dynamic-slice_bitcast_fusion.15 = bf16[8,8] fusion(%p)"
    norm = "%fusion.56 = bf16[2,8] fusion(%x)"
    kernel = ("%tiled_matmul.31 = f32[2,8] custom-call(bf16[2,8] %fusion.56,"
              " bf16[8,8] %dynamic-slice_bitcast_fusion.15), c=\"t\"")
    # two executions of one program; the slice runs in both
    events = [ev(0, 4, slice_), ev(4, 5, norm), ev(5, 6, kernel),
              ev(100, 103, slice_), ev(103, 104, kernel)]
    assert xplane._staging(events, modules=[0, 100]) == \
        {"tiled_matmul": 7}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "stablelm-3b.dense.xplane.pb.gz").read_bytes()))
    return xplane.summarize(path)


def test_recorded_trace_busy_and_kernels(recorded):
    s = recorded
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert s.window_s == pytest.approx(RECORDED["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(RECORDED["busy_s"], rel=1e-9)
    assert "while" not in s.op_seconds
    assert s.kernel_seconds("tiled_matmul") == pytest.approx(
        RECORDED["tiled_matmul_s"], rel=1e-9)
    assert s.staged_seconds["tiled_matmul"] > 0
    assert s.kernel_seconds("enec_decode") == 0.0
    # the harness's own spans, for metrics that read host time
    assert s.span_counts["bench.step"] == 3
    assert 0 < s.span_seconds["bench.step"] <= s.window_s


def test_recorded_trace_breakdown(recorded):
    ops = recorded.device_ops()
    assert 0 < len(ops) <= 10
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    idle = recorded.idle_gaps
    assert 0 < len(idle) <= 10
    assert idle == sorted(idle, key=lambda kv: -kv[1])
    # the ten largest activities hold all but a trace of the idle time
    assert sum(v for _, v in idle) == pytest.approx(
        recorded.window_s - recorded.busy_s, rel=1e-6)


# the recorded trace's numbers as this reduction first read them
RECORDED = {"window_s": 0.158946996, "busy_s": 0.151024709,
            "tiled_matmul_s": 0.015737132}
