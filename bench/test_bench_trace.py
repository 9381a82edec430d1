"""The reduction from trace events to device numbers."""
import json
import pathlib

import pytest

import tracefold

DEV = "/device:TPU:0"
HOST = "/host:CPU"
OPS = tracefold.OP_LINE


XOR = ('%_unknown_.1 = (u32[128,256], s32[1,128]) custom-call(u32[128,256] '
       '%parent.1, u32[128,256] %child.1), custom_call_target="tpu_custom_call"')


def ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur))


SYNTH = [
    ev(HOST, "python", "bench.window", 1000, 10000),
    ev(HOST, "python", "bench.form", 1000, 1000),
    ev(HOST, "python", "bench.serve", 2000, 5000),
    ev(HOST, "python", "bench.snapshot", 2000, 1000),
    ev(HOST, "python", "bench.wait", 7000, 4000),
    ev(HOST, "python", "unrelated", 0, 20000),
    ev(DEV, OPS, "%fusion.2 = u32[9,8] fusion(u32[64,8] %t.1)", 3000, 1000),
    ev(DEV, OPS, XOR, 3500, 1000),               # overlaps the gather
    ev(DEV, OPS, XOR, 6000, 500),
    ev(DEV, OPS, "%before = f32[] add()", 0, 500),  # outside the window
    ev(DEV, tracefold.MODULE_LINE, "jit__lambda(123)", 3000, 400),
    ev(DEV, tracefold.MODULE_LINE, "jit__unknown(456)", 3400, 3600),
]


def test_busy_is_the_union_of_ops_inside_the_window():
    r = tracefold.reduce(SYNTH)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(2e-6)       # 3000-4500, 6000-6500
    ops = dict(map(tuple, r["device_ops"]))
    assert r["device_ops"][0][0] == "jit__unknown:_unknown_"
    assert ops["jit__unknown:_unknown_"] == pytest.approx(1.5e-6)
    assert ops["jit__lambda:fusion"] == pytest.approx(1e-6)


def test_idle_gaps_go_to_the_innermost_host_span():
    idle = dict(map(tuple, tracefold.reduce(SYNTH)["idle_gaps"]))
    assert idle["bench.form"] == pytest.approx(1e-6)       # 1000-2000
    assert idle["bench.snapshot"] == pytest.approx(1e-6)   # 2000-3000
    assert idle["bench.serve"] == pytest.approx(2e-6)      # 4500-6000, 6500-7000
    assert idle["bench.wait"] == pytest.approx(4e-6)       # 7000-11000
    assert sum(idle.values()) == pytest.approx(8e-6)


def test_kernel_time_sums_its_op_events():
    assert tracefold.kernel_seconds(SYNTH, "xor_delta") == pytest.approx(
        1.5e-6)
    assert tracefold.kernel_seconds(SYNTH[:8], "xor_delta") == pytest.approx(
        1e-6)


def test_op_labels_drop_operands_and_numbering():
    assert tracefold.op_label("%fusion.12 = u32[3] fusion(%a)") == "fusion"
    assert tracefold.op_label("%copy-done = u32[3] copy-done()") == \
        "copy-done"


def test_host_segments_nest():
    segs = tracefold.host_segments(SYNTH)
    assert [s[2] for s in segs] == ["bench.form", "bench.snapshot",
                                    "bench.serve", "bench.wait"]
    assert segs[1][:2] == (2000, 3000) and segs[2][:2] == (3000, 7000)


def test_a_trace_needs_one_window():
    with pytest.raises(RuntimeError):
        tracefold.reduce(SYNTH[1:])


def test_a_recorded_tpu_trace():
    """Events kept from a traced window of a tiny k = 3 store on a TPU v5
    lite: five waves, the gather, the XOR-delta and bitmap-VM kernels."""
    path = pathlib.Path(__file__).parent / "testdata" / "tpu_trace_k3_tiny.json"
    events = [tuple(e) for e in json.loads(path.read_text())]
    r = tracefold.reduce(events)
    assert r["window_s"] == pytest.approx(0.917847571)
    assert r["busy_s"] == pytest.approx(0.000800994)
    ops = dict(map(tuple, r["device_ops"]))
    assert ops["jit__unknown:_unknown_"] == pytest.approx(0.000254982)
    assert "jit__lambda:fusion" in ops
    idle = dict(map(tuple, r["idle_gaps"]))
    assert idle["bench.wait"] == pytest.approx(0.486385643)
    assert idle["bench.serve"] == pytest.approx(0.430265883)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert tracefold.kernel_seconds(events, "xor_delta") == pytest.approx(
        0.00025119)
