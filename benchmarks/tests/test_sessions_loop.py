"""``loadgen``'s ``sessions`` kind against a fake channel: order within
a stream, the sequence flags, one request of a stream in flight at a
time, items summed per request, a failed request ending its stream, the
window's end at the last answer; and the ROUNDS: a window is a whole
number of balanced rounds, the same work on every seed, in an order the
seed draws; a call under half a round is one round cut by the deadline;
a window three times over its nominal length stops."""

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

from benchmarks import loadgen, server_child as sc  # noqa: E402

# 3 callers over the 3 streams of ``streams()``: a round holds the pool once
TRAFFIC = {"loop": "sessions", "clients": 3, "items_per_request": 5, "round_s": 0.025}
# 6 callers over 3 streams of different lengths and sizes: a round holds the pool twice, as a cell's does
BALANCED = {"loop": "sessions", "clients": 6, "items_per_request": 1, "round_s": 0.1}


def streams(n=3, length=4, items_first=None):
    """Stream s, request k carries ``tag = [s, k]``; the first request
    of a stream may state its own item count."""
    out = []
    for s in range(n):
        stream = [{"tag": np.asarray([s, k])} for k in range(length)]
        if items_first is not None:
            stream[0]["items"] = items_first
        out.append(stream)
    return out


def ladder():
    """Three streams that differ as a ladder's do: stream s opens with
    a request of 100 x (s + 1) items, then s + 2 requests of one."""
    return [[{"tag": np.asarray([s, 0]), "items": 100 * (s + 1)}]
            + [{"tag": np.asarray([s, k]), "items": 1} for k in range(1, s + 3)] for s in range(3)]


LADDER_ITEMS = sum(100 * (s + 1) + s + 2 for s in range(3))  # a pool's worth: 609
LADDER_REQUESTS = sum(s + 3 for s in range(3))  # 12


class FakeChannel:
    """Records every call; answers after ``delay_s``; fails where
    ``fail(tag)`` says so. One log for all the channels of a test; a
    channel knows the how-manieth of its log it is (the loops make one
    a caller, in the callers' order)."""

    def __init__(self, log, delay_s=0.002, fail=lambda tag: False):
        self.log, self.delay_s, self.fail = log, delay_s, fail
        with log["lock"]:
            self.index = log["made"]
            log["made"] += 1

    def do_inference(self, request):
        tag = tuple(int(v) for v in request.inputs["tag"])
        t0 = time.perf_counter()
        time.sleep(self.delay_s)
        failed = self.fail(tag)
        with self.log["lock"]:
            self.log["calls"].append({
                "tag": tag, "sequence_id": request.sequence_id, "start": request.sequence_start,
                "end": request.sequence_end, "t0": t0, "t1": time.perf_counter(), "failed": failed,
                "channel": self.index})
        if failed:
            raise RuntimeError(f"refused {tag}")
        return types.SimpleNamespace(outputs={"tag": np.asarray(tag)})

    def close(self):
        self.log["closed"] += 1


def new_log():
    return {"lock": threading.Lock(), "calls": [], "closed": 0, "made": 0}


def by_sequence(calls):
    out = {}
    for c in sorted(calls, key=lambda c: c["t0"]):
        out.setdefault(c["sequence_id"], []).append(c)
    return out


def run_loop(pool, seconds=0.25, traffic=TRAFFIC, rng=None, **channel_kw):
    log = new_log()
    make = lambda: FakeChannel(log, **channel_kw)
    requests = loadgen.sessions_requests("m", pool)
    rng = np.random.default_rng([7, 3]) if rng is None else rng
    win = loadgen.sessions_loop(make, make(), requests, traffic, seconds, rng)
    return win, log


def sent_by_round(log):
    """``rounds[r][c]``: the pool stream the ``c``-th caller opened in
    round ``r`` (its ``r``-th sequence). Channel 0 is the one handed in."""
    per_caller = {}
    for calls in by_sequence(log["calls"]).values():
        per_caller.setdefault(calls[0]["channel"], []).append(calls[0]["tag"][0])
    callers = [per_caller[c] for c in sorted(per_caller)]
    return [list(r) for r in zip(*callers)]


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
    win, log = run_loop(streams())  # 0.25 s over rounds of 0.025 s: ten rounds
    sequences = by_sequence(log["calls"])
    assert len(sequences) == 10 * TRAFFIC["clients"]  # every caller opened a stream a round
    assert all(s.startswith("bench-") for s in sequences)
    for calls in sequences.values():
        assert len({c["tag"][0] for c in calls}) == 1  # one stream of the pool a sequence id
        assert [c["tag"][1] for c in calls] == [0, 1, 2, 3]  # whole and in order: no deadline cuts a round
        assert [c["start"] for c in calls] == [True, False, False, False]
        assert [c["end"] for c in calls] == [False, False, False, True]
        for a, b in zip(calls, calls[1:]):  # never two of one stream in flight together
            assert a["t1"] <= b["t0"]
    assert log["closed"] == TRAFFIC["clients"]  # the callers' own channels, not the one handed in


def test_callers_run_side_by_side():
    _, log = run_loop(streams(), delay_s=0.01, seconds=0.05)
    calls = sorted(log["calls"], key=lambda c: c["t0"])
    overlapping = sum(1 for a, b in zip(calls, calls[1:]) if b["t0"] < a["t1"])
    assert overlapping > len(calls) // 4


def test_items_are_summed_per_request_and_the_window_ends_at_the_last_answer():
    traffic = {**TRAFFIC, "round_s": 0.2}
    win, log = run_loop(streams(items_first=1000), seconds=0.4, traffic=traffic, delay_s=0.03)
    calls = log["calls"]
    assert win.attempted == len(calls) == len(win.latencies_ms) == 2 * 3 * 4 and win.failed == 0
    assert win.items_done == 2 * 3 * (1000 + 3 * TRAFFIC["items_per_request"])  # two rounds, whole
    last_answer = max(c["t1"] for c in calls)
    assert 0 <= win.t_end - last_answer < 0.1  # recorded just after the channel returned
    assert 0.23 < win.span_s() < 0.4  # eight answers of 0.03 s a caller: the work's length, not the 0.4 s asked for
    assert win.end_to_end() == {"throughput": win.items_done / win.span_s()}


def test_under_half_a_round_the_deadline_cuts_one_round():
    traffic = {**TRAFFIC, "round_s": 1.0}
    win, log = run_loop(streams(items_first=1000), seconds=0.2, traffic=traffic, delay_s=0.12)
    calls = log["calls"]
    assert win.attempted == len(calls) == len(win.latencies_ms) <= 2 * TRAFFIC["clients"]  # sent at 0 and 0.12 s
    firsts = sum(1 for c in calls if c["tag"][1] == 0)
    assert firsts == TRAFFIC["clients"] < len(calls)
    assert win.items_done == 1000 * firsts + TRAFFIC["items_per_request"] * (len(calls) - firsts)
    assert not any(c["end"] for c in calls)  # cut streams are left open: the server reclaims them
    assert win.span_s() > 0.22  # the answer to a request sent at 0.12 s, not the 0.2 s asked for
    assert max(c["t0"] for c in calls) < win.t_start + 0.2  # and none was sent late
    assert len(by_sequence(calls)) == TRAFFIC["clients"]  # one round: no caller opened a second stream


def test_a_failed_request_ends_its_stream():
    win, log = run_loop(streams(), fail=lambda tag: tag == (2, 1))
    failed = [c for c in log["calls"] if c["failed"]]
    assert failed and win.failed == len(failed)
    assert win.attempted == len(log["calls"]) and len(win.latencies_ms) == win.attempted - win.failed
    for calls in by_sequence(log["calls"]).values():
        if calls[0]["tag"][0] == 2:
            assert [c["tag"][1] for c in calls] == [0, 1]  # nothing after the failure
    after = [c for c in log["calls"] if c["t0"] > failed[0]["t1"] and c["channel"] == failed[0]["channel"]]
    assert after  # the caller went on to its next round
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


@pytest.mark.parametrize("seed", range(20))
def test_a_window_of_k_rounds_is_the_same_work_on_every_seed(seed):
    k = 2 + seed % 2
    win, log = run_loop(ladder(), seconds=k * BALANCED["round_s"], traffic=BALANCED, rng=sc.seeded(seed, 3),
                        delay_s=0.001)
    copies = BALANCED["clients"] // 3
    assert win.items_done == k * copies * LADDER_ITEMS and win.attempted == k * copies * LADDER_REQUESTS
    assert win.failed == 0 and len(win.latencies_ms) == win.attempted
    rounds = sent_by_round(log)
    assert len(rounds) == k
    for sent in rounds:  # every round sends every pool stream clients / n times
        assert sorted(sent) == sorted(list(range(3)) * copies)


@pytest.mark.parametrize("seconds,rounds", [(0.0625, 1), (0.18, 1), (0.1875, 2), (0.3125, 3), (0.4375, 4)])
def test_seconds_are_rounded_to_whole_rounds_and_a_half_goes_up(seconds, rounds):
    """``--seconds 30`` over rounds of 20 s is 2 rounds and 50 is 3
    (``round`` would say 2 and 2: half to even); from half a round up
    the window is whole, so the work does not follow the deadline."""
    win, log = run_loop(ladder(), seconds=seconds, traffic={**BALANCED, "round_s": 0.125}, delay_s=0.001)
    assert len(sent_by_round(log)) == rounds
    assert win.items_done == rounds * 2 * LADDER_ITEMS and win.attempted == rounds * 2 * LADDER_REQUESTS


def test_the_seed_draws_who_sends_what_and_each_round_anew():
    orders = [loadgen.round_orders(sc.seeded(seed, 3), 32, 16, 6) for seed in range(20)]
    assert all(len(o) == 32 and all(len(caller) == 6 for caller in o) for o in orders)
    for o in orders:
        rounds = list(zip(*o))
        assert all(sorted(r) == sorted(list(range(16)) * 2) for r in rounds)  # 8 prompts of each rung of 4 x 4
        assert len(set(rounds)) == 6  # drawn anew each round
    assert len({tuple(map(tuple, o)) for o in orders}) == 20  # two seeds, two orders
    assert orders[3] == loadgen.round_orders(sc.seeded(3, 3), 32, 16, 6)  # one seed, one order
    with pytest.raises(ValueError, match="not a multiple"):  # a round holds the WHOLE pool, or there are no rounds
        loadgen.round_orders(sc.seeded(5, 3), 3, 6, 5)


def test_a_channel_twice_as_slow_gives_the_same_items_in_about_twice_the_time():
    fast, _ = run_loop(ladder(), seconds=0.2, traffic=BALANCED, delay_s=0.01)
    slow, _ = run_loop(ladder(), seconds=0.2, traffic=BALANCED, delay_s=0.02)
    assert fast.items_done == slow.items_done == 2 * 2 * LADDER_ITEMS and fast.attempted == slow.attempted
    assert 1.5 < slow.span_s() / fast.span_s() < 2.5  # the window is the work's length, continuous in the speed
    assert 1.5 < fast.end_to_end()["throughput"] / slow.end_to_end()["throughput"] < 2.5


@pytest.mark.parametrize("warmup_rounds", [1, 3])
def test_the_window_s_order_does_not_follow_the_warm_up_s_length(warmup_rounds):
    """``run.py`` draws the warm-up from ``seeded(seed, 2)`` and the
    window from ``seeded(seed, 3)``: the loop draws from the stream it
    is handed and from nothing else."""
    warm, window = sc.seeded(11, 2), sc.seeded(11, 3)
    for _ in range(warmup_rounds):
        run_loop(ladder(), seconds=0.001, traffic=BALANCED, rng=warm, delay_s=0.001)
    _, log = run_loop(ladder(), seconds=2 * BALANCED["round_s"], traffic=BALANCED, rng=window, delay_s=0.001)
    expected = loadgen.round_orders(sc.seeded(11, 3), BALANCED["clients"], 3, 2)
    assert sent_by_round(log) == [list(r) for r in zip(*expected)]


def test_a_window_three_times_over_its_nominal_length_stops_sending():
    log = new_log()
    make = lambda: FakeChannel(log, delay_s=0.05)
    requests = loadgen.sessions_requests("m", streams())
    with pytest.raises(RuntimeError, match="1 round.*not over after 0.075 s"):
        loadgen.sessions_loop(make, make(), requests, TRAFFIC, 0.025, np.random.default_rng(1))
    assert TRAFFIC["clients"] <= len(log["calls"]) <= 2 * TRAFFIC["clients"]  # sent at 0 and 0.05 s, nothing at 0.1 s
    assert log["closed"] == TRAFFIC["clients"]


def test_a_cell_s_sessions_mix_states_round_s_and_a_multiple_of_its_pool():
    assert "round_s" in loadgen.sessions_refused({"clients": 32}, 16)
    assert "not a multiple" in loadgen.sessions_refused({"clients": 30, "round_s": 20}, 16)
    assert loadgen.sessions_refused({"clients": 32, "round_s": 20}, 16) is None
    bench = sc.load_json(ROOT / "BENCHMARK.json")
    for cell in bench["workloads"]:
        mix = sc.load_json(ROOT / f"benchmarks/traffic/{cell['traffic']}.json")
        refused = getattr(loadgen, f"{mix['loop']}_refused", None)
        if refused is None:
            continue
        cfg = sc.load_json(ROOT / next(c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
        assert refused(mix, sc.sample_size(cfg, mix, False)) is None, cell["name"]
        if mix.get("trace_at_s") is None:  # a mix need not pin its trace
            continue
        rounds = max(1, int(bench["run_seconds"] / mix["round_s"] + 0.5))
        # the traced span lies inside the window the driver runs, and holds the start of a later round
        assert mix["trace_at_s"] < mix["round_s"] * (rounds - 1) < mix["trace_at_s"] + mix["trace_s"] <= rounds * mix["round_s"]


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
