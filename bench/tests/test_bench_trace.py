"""The reduction from a profiler trace to busy time, per-op device time,
launches and idle gaps: on hand-made planes, and on a small trace recorded
on a TPU v5e (one fused SGD step at batch 256, then one 2,048-voxel int8
request served in two tiles)."""

import pathlib

import pytest

from bench import trace

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / "v5e_train_serve.xplane.pb"


def planes():
    host = {"python3": [("window", 0, 1000), ("train", 100, 200),
                        ("drain", 500, 400), ("other", 0, 1000)]}
    dev = {"XLA Ops": [("%fused_train_call.1 = (f32[7]) custom-call()", 150, 100),
                       ("%fusion.3 = f32[8] fusion()", 200, 100),
                       ("%copy-done.2 = f32[2]", 600, 50),
                       ("%while.1 = (s32[])", 1100, 10)],
           "XLA Modules": [("jit_a(1)", 140, 200), ("jit_b(2)", 590, 70),
                           ("jit_c(3)", 1100, 10)]}
    return [("/host:CPU", host), ("/device:TPU:0", dev)]


@pytest.mark.parametrize("raw,name", [
    ("%fused_train_call.1 = (f32[7,128,128]) custom-call(...)", "fused_train_call"),
    ("%fused_forward_call = f32[1024,128] custom-call()", "fused_forward_call"),
    ("%copy-start.13 = (f32[16])", "copy-start"),
    ("%broadcast_in_dim.73.clone = f32[2]", "broadcast_in_dim"),
    ("%while", "while"),
    ("%dynamic-update-slice.3 = f32[4]", "dynamic-update-slice"),
])
def test_op_name(raw, name):
    assert trace.op_name(raw) == name


def test_reduce_hand_made_planes():
    r = trace.reduce_planes(planes())
    assert r.window_s == pytest.approx(1000e-9)
    # union of [150, 300) and [600, 650); the while ran after the window
    assert r.busy_s == pytest.approx(200e-9)
    assert r.op_seconds == pytest.approx({"fused_train_call": 100e-9,
                                          "fusion": 100e-9,
                                          "copy-done": 50e-9})
    assert r.kernel_seconds("fused_train") == pytest.approx(100e-9)
    assert r.kernel_launches("fused_train") == 1
    assert r.launches == 2
    # [0,150) lies under train; [300,600) and [650,1000) mostly under drain
    assert r.idle_by_span == pytest.approx({"train": 150e-9, "drain": 650e-9})
    assert r.top_ops(1) == [["fused_train_call", pytest.approx(100e-9)]]


def test_reduce_needs_window_and_device():
    with pytest.raises(ValueError):
        trace.reduce_planes(planes(), window="nope")
    with pytest.raises(ValueError):
        trace.reduce_planes(planes()[:1])


def test_reduce_recorded_v5e_trace():
    r = trace.reduce_xplane(FIXTURE)
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    assert r.kernel_launches("fused_train_call") == 1
    # 2,048 voxels are two 1024-voxel tiles: one kernel launch each
    assert r.kernel_launches("fused_forward_call") == 2
    assert r.kernel_seconds("fused_train_call") > 0
    assert r.kernel_seconds("fused_forward_call") > 0
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert set(r.idle_by_span) <= {"train", "enqueue", "drain", "host"}
