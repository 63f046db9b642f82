"""The trace reduction against a small trace recorded on the chip
(``data/trace_v5e.json.gz``: the first events of every line of one
``--trace 1`` run, written by ``trace_reduce.write_recorded``)."""

from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import trace_reduce  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data" / "trace_v5e.json.gz"


def test_union():
    total, merged = trace_reduce.union_ns([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert total == 35 and merged == [(0, 20), (30, 45)]


def test_synthetic_planes():
    planes = {
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 100, 50), ("convolution.2", 160, 90), ("fusion.1", 400, 50)],
            "XLA Modules": [("jit_mdl_m_1(123)", 100, 150), ("jit_mdl_m_1(123)", 400, 50)],
        },
        "/host:CPU": {"thread": [("stage", 250, 150), ("long", 0, 1000)]},  # not read
    }
    out = trace_reduce.reduce(planes, 1)
    assert out["busy_s"] == 190e-9
    assert out["window_s"] == 350e-9  # first to last device op, whatever the host plane spans
    assert out["launches"]["jit_mdl_m_1"] == {"count": 2, "device_s": 200e-9}
    assert out["breakdown"]["device_ops"][0] == ["fusion.1", 100e-9]
    assert out["breakdown"]["idle_gaps"] == [
        ["between launches, gap under 1 ms", 150e-9], ["inside jit_mdl_m_1", 10e-9]]


def test_head_of_a_trace_is_left_out():
    head = int(trace_reduce.HEAD_LEFT_OUT_S * 1e9)
    planes = {"/device:TPU:0": {
        "XLA Ops": [("fusion.1", 0, 50), ("fusion.1", head + 100, 50), ("fusion.1", head + 300, 50)],
        "XLA Modules": [("jit_mdl_m_1(1)", 0, 50), ("jit_mdl_m_1(1)", head + 100, 50), ("jit_mdl_m_1(1)", head + 300, 50)],
    }}
    out = trace_reduce.reduce(planes, 1)
    assert out["busy_s"] == 100e-9 and out["window_s"] == 250e-9
    assert out["launches"]["jit_mdl_m_1"]["count"] == 2


def test_recorded_chip_trace():
    planes = trace_reduce.read_recorded(DATA)
    assert any(trace_reduce.DEVICE_PLANE.match(p) for p in planes)
    out = trace_reduce.reduce(planes, 1)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert any("mdl_" in name for name in out["launches"])
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
