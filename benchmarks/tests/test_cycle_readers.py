"""The five cycle readers (``cycle_handback_ms`` .. ``cycle_restage_ms``,
PR 42) on a hand-made ``/traces`` document: each gives the mean computed
by hand, the five add up to the counted gaps' mean, the log line carries
the medians and the counts, and a program that stamps no ``session`` or
no ``front`` (the parent of the PR that brought them) yields nothing."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import loadgen  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    _cycle, cycle_answer_ms, cycle_away_ms, cycle_handback_ms, cycle_intake_ms, cycle_restage_ms, host_gap_ms,
)

BASE = 7000.0
READERS = {"handback": cycle_handback_ms, "answer": cycle_answer_ms, "away": cycle_away_ms,
           "intake": cycle_intake_ms, "restage": cycle_restage_ms}


def event(tid, name, t0_s, t1_s, **args):
    return {"ph": "X", "name": name, "tid": tid, "pid": 1, "ts": t0_s * 1e6, "dur": (t1_s - t0_s) * 1e6,
            **({"args": args} if args else {})}


def member(tid, session, front, start, queue, dispatch, end, respond=None, stamped=True):
    """A session's request: its own events, seconds from the export's zero."""
    return [
        event(tid, "request", start, end, status="ok", **({"session": session} if stamped else {})),
        *([event(tid, "front", front, start)] if stamped else []),
        event(tid, "parse", start, start + 0.0002),
        event(tid, "batch_queue", queue, dispatch),
        *([event(tid, "batch_respond", *respond)] if respond else []),
    ]


def launch(tids, launch_id, stage0, h2d_end, launch_end, ready, readback_end, window):
    """A launch's events, on every member's row."""
    ids = {"launch_id": launch_id}
    return [ev for tid in tids for ev in (
        event(tid, "slot_wait", stage0, stage0 + 0.0001, **ids),
        event(tid, "stage", stage0, stage0 + 0.001, **ids),
        event(tid, "h2d", stage0 + 0.0001, h2d_end, bytes=64, rows=len(tids), **ids),
        event(tid, "launch", stage0 + 0.001, launch_end, **ids),
        event(tid, "device_execute", launch_end, ready, **ids),
        event(tid, window, launch_end, ready, tokens=8, sessions=len(tids), **ids),
        event(tid, "readback", ready, readback_end, **ids),
    )]


def traces(stamped=True):
    """Five launches. By hand (a = the previous launch's ``device_execute`` end, b = this launch's start):
    launch 1: the PROMPT of session a, alone; ready 0.100, readback ends 0.1015, accounted 0.104
    launch 2: the first BLOCK of a and of e (e came in while the prompt ran): a 0.100, b 0.114; e2 0.1015 (no
              batcher answered the prompt: its readback's end), e3 0.104, e4 0.089 -> 0.104, e5 0.109:
              handback 1.5, answer 2.5, away 0, intake 5, restage 5 ms; not closed (e was not in launch 1)
    launch 3: a and e again: a 0.150, b 0.171; futures by 0.153, accounted by 0.159, first front 0.162, last
              in the batcher 0.167: 3, 6, 3, 5, 4 ms; closed
    launch 4: c alone, which came in before launch 3 was ready: a 0.220, b 0.225; futures by 0.222:
              handback 2, restage 3 ms; not closed
    launch 5: another PROMPT: no row
    means over the three counted gaps: 6.5/3, 8.5/3, 1.0, 10/3, 4.0; the gaps' mean 40/3 ms."""
    events = [
        *member(1, "a", 0.0495, 0.050, 0.051, 0.052, 0.104, stamped=stamped),
        *launch([1], 1, 0.052, 0.054, 0.055, 0.100, 0.1015, "lm_prefill"),
        *member(2, "a", 0.1080, 0.1085, 0.1090, 0.110, 0.1550, respond=(0.1515, 0.1520), stamped=stamped),
        *member(3, "e", 0.0890, 0.0895, 0.0910, 0.110, 0.1590, respond=(0.1515, 0.1530), stamped=stamped),
        *launch([2, 3], 2, 0.110, 0.113, 0.114, 0.150, 0.1515, "lm_block"),
        *member(4, "a", 0.1620, 0.1625, 0.1630, 0.168, 0.2300, respond=(0.2210, 0.2215), stamped=stamped),
        *member(5, "e", 0.1650, 0.1655, 0.1670, 0.168, 0.2310, respond=(0.2210, 0.2220), stamped=stamped),
        *launch([4, 5], 3, 0.168, 0.170, 0.171, 0.220, 0.2210, "lm_block"),
        *member(6, "c", 0.1700, 0.1705, 0.1710, 0.2225, 0.2600, stamped=stamped),
        *launch([6], 4, 0.2225, 0.2240, 0.2250, 0.250, 0.2510, "lm_block"),
        *member(7, "f", 0.2000, 0.2005, 0.2010, 0.2520, 0.3100, stamped=stamped),
        *launch([7], 5, 0.2520, 0.2540, 0.2550, 0.300, 0.3010, "lm_prefill"),
    ]
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "clock": {"base_perf_counter_s": BASE, "anchor_perf_counter_s": BASE - 50.0, "anchor_time_ns": 0}}


def ctx(**over):
    win = loadgen.Window()
    win.t_start, win.t_end = BASE, BASE + 10.0
    return {"traces": traces(), "window": win, **over}


BY_HAND_MS = {
    2: {"handback": 1.5, "answer": 2.5, "away": 0.0, "intake": 5.0, "restage": 5.0},
    3: {"handback": 3.0, "answer": 6.0, "away": 3.0, "intake": 5.0, "restage": 4.0},
    4: {"handback": 2.0, "answer": 0.0, "away": 0.0, "intake": 0.0, "restage": 3.0},
}


def test_cycles_by_hand():
    rows = {r["launch_id"]: r for r in _cycle.cycles(ctx())}
    assert sorted(rows) == [2, 3, 4]  # launch 5 is a prompt's
    assert [rows[k]["closed"] for k in (2, 3, 4)] == [False, True, False]
    for launch_id, want in BY_HAND_MS.items():
        row = rows[launch_id]
        for phase in _cycle.PHASES:
            assert row["by_phase"][phase] * 1e3 == pytest.approx(want[phase], abs=1e-6), (launch_id, phase)
        assert sum(row["by_phase"].values()) == pytest.approx(row["gap_s"])
        assert row["gap_s"] * 1e3 == pytest.approx(sum(want.values()), abs=1e-6)


@pytest.mark.parametrize("phase", sorted(READERS))
def test_a_reader_gives_the_mean_and_logs_the_medians_and_counts(phase, capsys):
    value = READERS[phase].read(ctx())
    by_launch = [BY_HAND_MS[k][phase] for k in (2, 3, 4)]
    assert value == pytest.approx(sum(by_launch) / 3, abs=1e-6)
    logged = json.loads(capsys.readouterr().out)[f"cycle_{phase}_ms"]
    assert logged["gaps"] == 3 and logged["closed"] == 1
    assert logged["median_ms"] == pytest.approx(sorted(by_launch)[1], abs=1e-6)
    assert logged["median_closed_ms"] == logged["mean_closed_ms"] == pytest.approx(BY_HAND_MS[3][phase], abs=1e-6)
    assert logged["gap_mean_ms"] == pytest.approx(40.0 / 3, abs=1e-6)
    assert logged["gap_mean_closed_ms"] == pytest.approx(21.0, abs=1e-6)


def test_the_five_add_up_to_the_counted_gaps_mean_and_to_host_gap_ms_rows(capsys):
    total = sum(reader.read(ctx()) for reader in READERS.values())
    assert total == pytest.approx(40.0 / 3, abs=1e-6)
    # host_gap_ms counts the gap before launch 5 (a prompt's) too: 14, 21, 5 and 5 ms
    assert host_gap_ms.read(ctx()) == pytest.approx(45.0 / 4, abs=1e-6)
    capsys.readouterr()


def test_nothing_on_a_program_without_session_or_front(capsys):
    old = ctx(traces=traces(stamped=False))
    assert _cycle.cycles(old) is None
    assert all(reader.read(old) is None for reader in READERS.values())
    # a session without a front span (a stream's request) is not enough either
    doc = traces()
    doc["traceEvents"] = [e for e in doc["traceEvents"] if e["name"] != "front"]
    assert _cycle.cycles(ctx(traces=doc)) is None
    # nor an export without launches, nor none at all
    assert cycle_answer_ms.read(ctx(traces={"traceEvents": []})) is None
    assert cycle_answer_ms.read(ctx(traces=None)) is None
    assert capsys.readouterr().out == ""


def test_only_the_measured_windows_launches_count():
    win = loadgen.Window()
    win.t_start, win.t_end = BASE + 0.16, BASE + 10.0
    rows = _cycle.cycles(ctx(window=win))
    assert [r["launch_id"] for r in rows] == [4]  # launch 3 is the first record: it follows nothing
    rows = _cycle.cycles(ctx(spans_until=BASE + 0.24))
    assert [r["launch_id"] for r in rows] == [2, 3]
