"""``loadgen``'s ``sessions`` kind against a fake channel: order within
a stream, the sequence flags, one request of a stream in flight at a
time, items summed per request, a failed request ending its stream,
and the window's end at the last answer."""

from __future__ import annotations

import pathlib
import sys
import threading
import time
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import loadgen  # noqa: E402

TRAFFIC = {"loop": "sessions", "clients": 3, "items_per_request": 5}


def streams(n=6, length=4, items_first=None):
    """Stream s, request k carries ``tag = [s, k]``; the first request
    of a stream may state its own item count."""
    out = []
    for s in range(n):
        stream = [{"tag": np.asarray([s, k])} for k in range(length)]
        if items_first is not None:
            stream[0]["items"] = items_first
        out.append(stream)
    return out


class FakeChannel:
    """Records every call; answers after ``delay_s``; fails where
    ``fail(tag)`` says so. One log for all the channels of a test."""

    def __init__(self, log, delay_s=0.002, fail=lambda tag: False):
        self.log, self.delay_s, self.fail = log, delay_s, fail

    def do_inference(self, request):
        tag = tuple(int(v) for v in request.inputs["tag"])
        t0 = time.perf_counter()
        time.sleep(self.delay_s)
        failed = self.fail(tag)
        with self.log["lock"]:
            self.log["calls"].append({
                "tag": tag, "sequence_id": request.sequence_id, "start": request.sequence_start,
                "end": request.sequence_end, "t0": t0, "t1": time.perf_counter(), "failed": failed})
        if failed:
            raise RuntimeError(f"refused {tag}")
        return types.SimpleNamespace(outputs={"tag": np.asarray(tag)})

    def close(self):
        self.log["closed"] += 1


def new_log():
    return {"lock": threading.Lock(), "calls": [], "closed": 0}


def by_sequence(calls):
    out = {}
    for c in sorted(calls, key=lambda c: c["t0"]):
        out.setdefault(c["sequence_id"], []).append(c)
    return out


def run_loop(pool, seconds=0.25, **channel_kw):
    log = new_log()
    make = lambda: FakeChannel(log, **channel_kw)
    requests = loadgen.sessions_requests("m", pool)
    win = loadgen.sessions_loop(make, make(), requests, TRAFFIC, seconds, np.random.default_rng([7, 2]))
    return win, log


def test_requests_keep_the_generator_s_streams_and_item_counts():
    pool = loadgen.sessions_requests("m", streams(2, 3, items_first=1000))
    assert [len(s) for s in pool] == [3, 3]
    assert [items for _, items in pool[0]] == [1000, None, None]
    first = pool[0][0][0]
    assert first.model_name == "m" and set(first.inputs) == {"tag"} and first.sequence_id == ""
    flat = loadgen.closed_requests("m", [{"x": np.zeros(1)}, {"x": np.ones(1)}])
    assert [r.model_name for r in flat] == ["m", "m"] and loadgen.open_requests is loadgen.closed_requests
    assert loadgen.first_request(streams(2, 3, items_first=9)).keys() == {"tag"}
    assert loadgen.first_request([{"x": 1}]) == {"x": 1}


def test_each_stream_goes_in_order_under_a_fresh_sequence_id():
    win, log = run_loop(streams())
    sequences = by_sequence(log["calls"])
    assert len(sequences) >= 2 * TRAFFIC["clients"]  # every caller opened stream after stream
    assert all(s.startswith("bench-") for s in sequences)
    for calls in sequences.values():
        assert len({c["tag"][0] for c in calls}) == 1  # one stream of the pool a sequence id
        assert [c["tag"][1] for c in calls] == list(range(len(calls)))  # in order, from the first
        assert [c["start"] for c in calls] == [True] + [False] * (len(calls) - 1)
        assert all(c["end"] == (c["tag"][1] == 3) for c in calls)
        for a, b in zip(calls, calls[1:]):  # never two of one stream in flight together
            assert a["t1"] <= b["t0"]
    whole = [calls for calls in sequences.values() if len(calls) == 4]
    assert len(whole) >= len(sequences) - TRAFFIC["clients"]  # only the deadline cuts a stream, one a caller
    assert log["closed"] == TRAFFIC["clients"]  # the callers' own channels, not the one handed in


def test_callers_run_side_by_side():
    _, log = run_loop(streams(), delay_s=0.01)
    calls = sorted(log["calls"], key=lambda c: c["t0"])
    overlapping = sum(1 for a, b in zip(calls, calls[1:]) if b["t0"] < a["t1"])
    assert overlapping > len(calls) // 4


def test_items_are_summed_per_request_and_the_window_ends_at_the_last_answer():
    win, log = run_loop(streams(items_first=1000), seconds=0.2, delay_s=0.12)
    calls = log["calls"]
    assert win.attempted == len(calls) == len(win.latencies_ms) and win.failed == 0
    firsts = sum(1 for c in calls if c["tag"][1] == 0)
    assert win.items_done == 1000 * firsts + TRAFFIC["items_per_request"] * (len(calls) - firsts)
    last_answer = max(c["t1"] for c in calls)
    assert 0 <= win.t_end - last_answer < 0.1  # recorded just after the channel returned
    assert win.span_s() > 0.22  # the answer to a request sent at 0.12 s, not the 0.2 s asked for
    assert max(c["t0"] for c in calls) < win.t_start + 0.2  # and none was sent late
    assert win.end_to_end() == {"throughput": win.items_done / win.span_s()}


def test_a_failed_request_ends_its_stream():
    win, log = run_loop(streams(), fail=lambda tag: tag == (2, 1))
    failed = [c for c in log["calls"] if c["failed"]]
    assert failed and win.failed == len(failed)
    assert win.attempted == len(log["calls"]) and len(win.latencies_ms) == win.attempted - win.failed
    for calls in by_sequence(log["calls"]).values():
        if calls[0]["tag"][0] == 2:
            assert [c["tag"][1] for c in calls] in ([0, 1], [0])  # nothing after the failure ([0]: cut by the deadline)
    after = [c for c in log["calls"] if c["t0"] > failed[0]["t1"]]
    assert after  # the caller opened the next stream
    assert any("refused" in e for e in win.errors)


def test_window_check_sees_every_response():
    log = new_log()
    make = lambda: FakeChannel(log)
    seen = []
    check = lambda response: seen.append(tuple(response.outputs["tag"])) or ("odd" if response.outputs["tag"][1] == 3 else None)
    win = loadgen.sessions_loop(make, make(), loadgen.sessions_requests("m", streams()), TRAFFIC, 0.15,
                                np.random.default_rng(1), check)
    assert len(seen) == len(win.latencies_ms)
    assert win.malformed == sum(1 for tag in seen if tag[1] == 3) and win.errors[:1] == ["odd"]


def test_sample_returns_every_stream_s_responses_in_order():
    log = new_log()
    pool = loadgen.sessions_requests("m", streams(5, 3))
    responses = loadgen.sessions_sample(lambda: FakeChannel(log), FakeChannel(log), pool, TRAFFIC)
    assert [[tuple(r.outputs["tag"]) for r in stream] for stream in responses] == [
        [(s, k) for k in range(3)] for s in range(5)]
    sequences = by_sequence(log["calls"])
    assert len(sequences) == 5 and len(log["calls"]) == 15  # each stream once, whole
    for calls in sequences.values():
        assert [c["start"] for c in calls] == [True, False, False] and [c["end"] for c in calls] == [False, False, True]


@pytest.mark.parametrize("kind", ["open", "closed", "sessions"])
def test_every_kind_has_the_three_entry_points(kind):
    for part in ("requests", "loop", "sample"):
        assert callable(getattr(loadgen, f"{kind}_{part}"))
