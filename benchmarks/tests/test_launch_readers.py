"""The four launch readers of PR 26 (``h2d_ms``, ``client_write_ms``,
``host_gap_ms``, ``device_busy_est_ms``) on a hand-made ``/traces``
document and ``Window``: each gives the value computed by hand, and
nothing where the program stamps no ``launch_id``."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import loadgen  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    _launches, _spans, client_write_ms, device_busy_est_ms, h2d_ms, host_gap_ms,
)

BASE = 5000.0  # the perf_counter value the export's zero stands for
BYTES = 600_000_000


def event(tid, name, t0_s, t1_s, **args):
    return {"ph": "X", "name": name, "tid": tid, "pid": 1, "ts": t0_s * 1e6, "dur": (t1_s - t0_s) * 1e6,
            **({"args": args} if args else {})}


def launch(tid, launch_id, start, parse_end, slot_end, h2d_end, launch_end, ready, stamped=True):
    """One request's events, seconds from the export's zero."""
    ids = {"launch_id": launch_id} if stamped else {}
    return [
        event(tid, "request", start, ready + 0.01, status="ok"),
        event(tid, "parse", start, parse_end),
        event(tid, "batch_queue", parse_end, parse_end + 0.01),
        event(tid, "slot_wait", parse_end + 0.01, slot_end, **ids),
        event(tid, "stage", parse_end + 0.01, slot_end + 0.004, **ids),
        event(tid, "h2d", slot_end, h2d_end, bytes=BYTES, rows=768, **ids),
        event(tid, "launch", h2d_end, launch_end, **ids),
        event(tid, "device_execute", launch_end, ready, **ids),
        event(tid, "readback", ready, ready + 0.005, **ids),
    ]


def traces(stamped=True, clock=True):
    """Four launches. By hand, with ready_1 = 0.70:
    launch 2: frames there at 1.10, enqueued 1.11, ready 1.30: gap 0.41 (slot_wait 0.10, h2d 0.30, launch 0.01), busy 0.19;
              its h2d (0.80-1.10) misses launch 1's busy interval (0.51-0.70)
    launch 3: frames there at 1.25 while 2 computes, ready 1.50: gap 0, busy 0.20; h2d 0.85-1.25 overlaps 1.11-1.30 by 0.14
    launch 4: its request arrives at 1.60, frames there 2.00, enqueued 2.02, ready 2.20: gap 0.52
              (no_request 0.10, parse 0.02, batch_queue 0.01, h2d 0.37, launch 0.02), busy 0.18; no overlap
    launch 9 follows nothing (a hole in the ring) and gives no row."""
    events = [
        *launch(11, 1, 0.00, 0.01, 0.02, 0.50, 0.51, 0.70, stamped),
        *launch(12, 2, 0.05, 0.06, 0.80, 1.10, 1.11, 1.30, stamped),
        *launch(13, 3, 0.10, 0.11, 0.85, 1.25, 1.26, 1.50, stamped),
        *launch(14, 4, 1.60, 1.62, 1.63, 2.00, 2.02, 2.20, stamped),
        *launch(15, 9, 2.30, 2.31, 2.32, 2.60, 2.61, 2.80, stamped),
    ]
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    if clock:
        doc["clock"] = {"base_perf_counter_s": BASE, "anchor_perf_counter_s": BASE - 100.0, "anchor_time_ns": 0}
    return doc


def window(t_start=BASE, t_end=BASE + 10.0):
    win = loadgen.Window()
    win.t_start, win.t_end = t_start, t_end
    return win


def ctx(**over):
    return {"traces": traces(), "window": window(), **over}


def test_records_group_by_launch_and_keep_the_window():
    recs = _launches.records(ctx())
    assert [r["launch_id"] for r in recs] == [1, 2, 3, 4, 9]
    assert recs[0]["h2d"] == pytest.approx((BASE + 0.02, BASE + 0.50)) and recs[0]["bytes"] == BYTES
    assert recs[3]["request_start"] == pytest.approx(BASE + 1.60)
    # only the launches ready inside the window; without a clock, all of them on the export's own zero
    late = _launches.records(ctx(window=window(BASE + 1.0, BASE + 2.5)))
    assert [r["launch_id"] for r in late] == [2, 3, 4]
    unclocked = _launches.records(ctx(traces=traces(clock=False), window=window(BASE + 1.0, BASE + 2.5)))
    assert len(unclocked) == 5 and unclocked[0]["h2d"] == pytest.approx((0.02, 0.50))


def test_gaps_by_hand():
    rows = {r["launch_id"]: r for r in _launches.gaps(ctx())}
    assert sorted(rows) == [2, 3, 4]
    want = {
        2: {"gap_s": 0.41, "busy_s": 0.19, "h2d_overlap_s": 0.0, "slot_wait": 0.10, "h2d": 0.30, "launch": 0.01},
        3: {"gap_s": 0.0, "busy_s": 0.20, "h2d_overlap_s": 0.14},
        4: {"gap_s": 0.52, "busy_s": 0.18, "h2d_overlap_s": 0.0, "no_request": 0.10, "parse": 0.02,
            "batch_queue": 0.01, "h2d": 0.37, "launch": 0.02},
    }
    for launch_id, row in rows.items():
        for key in ("gap_s", "busy_s", "h2d_overlap_s"):
            assert row[key] == pytest.approx(want[launch_id][key], abs=1e-9), (launch_id, key)
        for state, value in row["by_state"].items():
            assert value == pytest.approx(want[launch_id].get(state, 0.0), abs=1e-9), (launch_id, state)


def test_h2d_ms(capsys):
    # h2d of the five launches: 0.48, 0.30, 0.40, 0.37, 0.28 s
    assert h2d_ms.read(ctx()) == pytest.approx(370.0)
    logged = json.loads(capsys.readouterr().out)
    assert logged["launches"] == 5 and logged["h2d_gb_per_s"] == pytest.approx(BYTES / 0.37 / 1e9)


def test_host_gap_ms(capsys):
    assert host_gap_ms.read(ctx()) == pytest.approx(310.0)  # the mean of 0.41, 0, 0.52
    logged = json.loads(capsys.readouterr().out)
    assert logged["launches"] == 3 and logged["host_gap_median_ms"] == pytest.approx(410.0)
    assert logged["h2d_overlap"] == pytest.approx(0.14 / (0.30 + 0.40 + 0.37))
    assert logged["idle_share_host_clock"] == pytest.approx(0.93 / (0.93 + 0.57))
    assert logged["host_gap_by_state_s"] == pytest.approx(
        {"no_request": 0.10, "parse": 0.02, "batch_queue": 0.01, "batch_merge": 0.0, "slot_wait": 0.10,
         "h2d": 0.67, "launch": 0.03, "other": 0.0}, abs=1e-9)


def test_device_busy_est_ms():
    assert device_busy_est_ms.read(ctx()) == pytest.approx(190.0)  # the median of 0.19, 0.20, 0.18


def test_client_write_ms(monkeypatch):
    from triton_client_tpu.runtime import shared_memory

    log = [(BASE - 2.0, BASE - 1.0, 8), (BASE + 0.1, BASE + 0.3, 8), (BASE + 1.0, BASE + 1.1, 8),
           (BASE + 2.0, BASE + 2.4, 8), (BASE + 9.9, BASE + 10.5, 8)]
    monkeypatch.setattr(shared_memory, "write_log", lambda: list(log))
    assert client_write_ms.read(ctx()) == pytest.approx(200.0)  # 200, 100, 400 ms ended inside the window
    assert client_write_ms.read(ctx(window=window(BASE + 20.0, BASE + 30.0))) is None
    monkeypatch.delattr(shared_memory, "write_log")  # the parent of PR 26 keeps no such log
    assert client_write_ms.read(ctx()) is None


@pytest.mark.parametrize("reader", [h2d_ms, host_gap_ms, device_busy_est_ms])
def test_nothing_where_no_launch_carries_an_id(reader, capsys):
    assert reader.read(ctx(traces=traces(stamped=False))) is None
    assert reader.read(ctx(traces=None)) is None
    assert capsys.readouterr().out == ""


def test_a_trace_pinned_to_the_window_has_its_spans_read_before_the_profiler_starts():
    """``run.py`` states ``spans_until`` for a mix with ``trace_at_s``:
    requests and launches that ended after it (while the profiler ran,
    and the seconds after it stopped) are left out."""
    pinned = ctx(spans_until=BASE + 1.40)
    assert [r["launch_id"] for r in _launches.records(pinned)] == [1, 2]  # ready at 0.70 and 1.30; 3 at 1.50
    # requests 11 (0.00-0.71) and 12 (0.05-1.31) ended in time: parse 10 ms each
    assert sorted(_spans.per_request_ms(pinned, ("parse",)).round(6)) == [10.0, 10.0]
    assert len(_spans.per_request_ms(ctx(), ("parse",))) == 5  # nothing pinned: every traced request
    assert h2d_ms.read(pinned) == pytest.approx((480.0 + 300.0) / 2)
    late_window = ctx(spans_until=BASE + 1.40, window=window(t_start=BASE + 0.03))
    assert len(_spans.per_request_ms(late_window, ("parse",))) == 1  # request 11 began before the window
    no_clock = {"traces": traces(clock=False), "window": window(), "spans_until": BASE + 1.40}
    assert len(_spans.per_request_ms(no_clock, ("parse",))) == 5  # an export that does not say its clock
