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
            "XLA Ops": [("fusion.0", 10, 20), ("fusion.1", 100, 50), ("convolution.2", 160, 90), ("fusion.1", 400, 50),
                        ("fusion.3", 600, 30)],
            "XLA Modules": [("jit_mdl_m_1(123)", 10, 20), ("jit_mdl_m_1(123)", 100, 150), ("jit_mdl_m_1(123)", 400, 50),
                            ("jit_mdl_m_1(123)", 600, 30)],
        },
        "/host:CPU": {"thread": [("stage", 250, 150), ("long", 0, 1000)]},  # not read
    }
    out = trace_reduce.reduce(planes, 1)
    assert out["busy_s"] == 240e-9
    assert out["window_s"] == 620e-9  # first to last device op, whatever the host plane spans
    # the first and the last module touch the trace's edges: neither counted nor timed
    assert out["launches"]["jit_mdl_m_1"] == {"count": 2, "device_s": 200e-9}
    assert out["breakdown"]["device_ops"][0] == ["fusion.1", 100e-9]
    assert out["breakdown"]["idle_gaps"] == [
        ["between launches, gap under 1 ms", 370e-9], ["inside jit_mdl_m_1", 10e-9]]


def test_head_of_a_trace_is_left_out():
    head = int(trace_reduce.HEAD_LEFT_OUT_S * 1e9)
    starts = (0, head - 20, head + 100, head + 300, head + 500)
    planes = {"/device:TPU:0": {
        "XLA Ops": [("fusion.1", s, 50) for s in starts],
        "XLA Modules": [("jit_mdl_m_1(1)", s, 50) for s in starts],
    }}
    out = trace_reduce.reduce(planes, 1)
    assert out["busy_s"] == 150e-9 and out["window_s"] == 450e-9
    # begun before the head ended (one of them across it), or ending with the trace: left out
    assert out["launches"]["jit_mdl_m_1"] == {"count": 2, "device_s": 100e-9}


def test_a_module_the_trace_end_cuts_is_no_launch():
    """The profiler stops inside a launch: its module event ends with
    the trace, short of a launch's time. Counted as a launch it pulled
    the mean down (PERF.md section 6: 197.6 ms where 208.5 is true)."""
    full, cut = 208_000_000, 23_000_000
    starts = [k * 210_000_000 for k in range(1, 25)]
    modules = [("jit__arrival_marker(7)", s - 5_000, 2_000) for s in starts]
    modules += [("jit_mdl_m_1(1)", s, full) for s in starts[:-1]] + [("jit_mdl_m_1(1)", starts[-1], cut)]
    ops = [("fusion.1", s, d) for _, s, d in modules]
    out = trace_reduce.reduce({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}}, 1)
    row = out["launches"]["jit_mdl_m_1"]
    assert row["count"] == 15  # 24 less the 8 begun in the head left out (1.5 s) and the one cut
    assert row["device_s"] == 15 * full / 1e9


def test_recorded_chip_trace():
    planes = trace_reduce.read_recorded(DATA)
    assert any(trace_reduce.DEVICE_PLANE.match(p) for p in planes)
    out = trace_reduce.reduce(planes, 1)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert any("mdl_" in name for name in out["launches"])
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


def test_recorded_chip_trace_leaves_out_the_modules_at_its_edges():
    """The fixture is shorter than the head left out, so it is read
    whole: of its 12 launcher modules the first (begun with the trace)
    and the last (nothing ends after it) are neither counted nor timed."""
    planes = trace_reduce.read_recorded(DATA)
    events = sorted(planes["/device:TPU:0"]["XLA Modules"], key=lambda e: e[1])
    assert len(events) == 12 and all(e[0].startswith("jit_mdl_") for e in events)
    (row,) = trace_reduce.reduce(planes, 1)["launches"].values()
    assert row["count"] == 10
    assert row["device_s"] == sum(d for _, _, d in events[1:-1]) / 1e9
