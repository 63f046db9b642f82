"""A launch written once, the two facts a traced request gained, and the
gap between two launches of token sessions cut at events of both (PR
42): ``obs.trace.LaunchRecord``, the ``front`` span and ``session`` of a
traced request, ``obs/launch_timeline.cycles`` on timelines made by
hand, its agreement with the benchmark's own copy
(``benchmarks/layer_metrics/_cycle.py``; the same export also holds
``host_gaps`` to ``_launches.gaps``), ``/profile``'s
``cycle_by_phase_s``, and when the span summary rides a response."""

import concurrent.futures
import json
import pathlib
import sys
import time
import types
import urllib.request

import numpy as np
import pytest

from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.channel.kserve import codec
from triton_client_tpu.obs import launch_timeline
from triton_client_tpu.obs.trace import (
    SUMMARY_PARAM_KEY,
    LaunchRecord,
    RequestTrace,
    TraceContext,
    Tracer,
    chrome_trace,
)

jax = pytest.importorskip("jax")

ROOT = pathlib.Path(__file__).resolve().parents[1]
LAUNCH_SPANS = ("slot_wait", "stage", "h2d", "launch", "device_execute", "readback")
X = np.arange(8, dtype=np.float32).reshape(2, 4)


def _repo():
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository

    spec = ModelSpec(
        name="double", version="1",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
        max_batch_size=64,
    )
    repo = ModelRepository()
    repo.register(spec, lambda inputs: {"y": np.asarray(inputs["x"]) * 2.0})
    return repo


# -- a launch is written once ---------------------------------------------------


@pytest.mark.parametrize("path", ["channel", "batcher"])
def test_a_merged_launch_of_16_writes_each_span_once_and_every_row_shows_it(path):
    """The old ``MultiTrace`` case, kept as a case: every member's
    exported row carries the launch's spans under the one ``launch_id``.
    New: they were written ONCE, not once a member."""
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel

    members = [RequestTrace(i + 1, model="double") for i in range(16)]
    if path == "channel":
        record = LaunchRecord(members)
        TPUChannel(_repo()).do_inference(InferRequest("double", {"x": X}, trace=record))
    else:
        chan = ContinuousBatchingChannel(TPUChannel(_repo()), max_batch=64, pipeline_depth=1)
        futures = [concurrent.futures.Future() for _ in members]
        t_in = time.perf_counter()
        try:
            chan._run_group(
                [(t_in + i * 1e-4, InferRequest("double", {"x": X}, trace=tr), fut)
                 for i, (tr, fut) in enumerate(zip(members, futures))],
            )
            for fut in futures:
                np.testing.assert_allclose(fut.result(timeout=60.0).outputs["y"], X * 2.0)
        finally:
            chan.close()
        (record,) = {id(r): r for m in members for r in m.launches}.values()
        assert [s.name for s in record.spans].count("batch_merge") == 1
        # a member's own: when its future was set (the cycle's e2 is the last of them)
        ends = [s.t1 for m in members for s in m.own if s.name == "batch_respond"]
        assert len(ends) == 16 and ends == sorted(ends)
    written = [s.name for s in record.spans]
    for name in LAUNCH_SPANS:
        assert written.count(name) == 1, name
    assert all(m.launches == [record] for m in members)
    assert not any(s.name in LAUNCH_SPANS for m in members for s in m.own)
    # the export: a row a member, each with the launch's spans and the one id
    for m in members:
        m.t_end = time.perf_counter()
    events = chrome_trace(members)["traceEvents"]
    for m in members:
        row = [e for e in events if e.get("ph") == "X" and e["tid"] == m.trace_id]
        for name in LAUNCH_SPANS:
            (ev,) = [e for e in row if e["name"] == name]
            assert ev["args"]["launch_id"] == 1, name
        assert [e for e in row if e["name"] == "h2d"][0]["args"]["bytes"] > 0
    (rec,) = launch_timeline.launches(members)
    assert rec["launch_id"] == 1


# -- front and session on a traced request, and nothing on the untraced path ------


class _Ctx:
    def __init__(self, peer="ipv4:127.0.0.1:40000"):
        self._peer = peer

    def peer(self):
        return self._peer

    def abort(self, code, details):
        raise RuntimeError(f"{code}: {details}")


def _servicer(tracer):
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.runtime.server import _Servicer

    repo = _repo()
    return _Servicer(repo, TPUChannel(repo), tracer=tracer)


def _wire_request(request_id, **parameters):
    return codec.build_infer_request("double", {"x": X}, request_id=request_id, parameters=parameters)


def test_front_and_session_are_on_a_traced_request():
    tracer = Tracer(capacity=8)
    servicer = _servicer(tracer)
    t_before = time.perf_counter()
    servicer.ModelInfer(_wire_request("s1", sequence_id="robot-7", sequence_start=True), _Ctx())
    servicer.ModelInfer(_wire_request("plain"), _Ctx())
    in_session, plain = tracer.recent()
    assert in_session.session == "robot-7" and plain.session == ""
    for tr in (in_session, plain):
        (front,) = [s for s in tr.spans if s.name == "front"]
        # from ModelInfer's entry to the trace's start: request begins where it began
        assert t_before <= front.t0 <= front.t1 == tr.t_start
    events = [e for e in tracer.chrome_trace()["traceEvents"] if e.get("name") == "request"]
    by_id = {e["args"]["request_id"]: e["args"] for e in events}
    assert by_id["s1"]["session"] == "robot-7" and "session" not in by_id["plain"]


def test_the_untraced_path_reads_no_clock_for_front(monkeypatch):
    """No ``perf_counter`` call in ``ModelInfer`` without a tracer:
    every call the untraced request makes comes from below it."""
    from triton_client_tpu.runtime import server

    servicer = _servicer(None)
    servicer.ModelInfer(_wire_request("warm"), _Ctx())
    calls = []
    real = time.perf_counter

    def counting():
        calls.append(sys._getframe(1).f_code.co_name)
        return real()

    monkeypatch.setattr(server.time, "perf_counter", counting)
    resp = servicer.ModelInfer(_wire_request("u1", sequence_id="robot-7"), _Ctx())
    monkeypatch.undo()
    assert resp.id == "u1" and calls  # the servicer's own clock reads were seen
    assert "ModelInfer" not in calls
    assert SUMMARY_PARAM_KEY not in resp.parameters


def test_the_span_summary_rides_only_a_request_that_carried_a_traceparent():
    tracer = Tracer(capacity=8)
    servicer = _servicer(tracer)
    bare = servicer.ModelInfer(_wire_request("bare"), _Ctx())
    ctx = TraceContext.new()
    asked = servicer.ModelInfer(_wire_request("asked", traceparent=ctx.encode()), _Ctx())
    assert len(tracer.recent()) == 2  # both were traced
    assert SUMMARY_PARAM_KEY not in bare.parameters
    doc = json.loads(asked.parameters[SUMMARY_PARAM_KEY].string_param)
    assert {"front", "parse", "stage", "device_execute", "encode"} <= {row[0] for row in doc["s"]}
    assert doc["ctx"].split("-")[1] == ctx.trace_id


def test_finish_feeds_the_stage_histograms_in_one_call_and_the_slo_family():
    from triton_client_tpu.obs.histogram import HistogramFamily
    from triton_client_tpu.obs.profiling import StageProfiler

    profiler, family = StageProfiler(), HistogramFamily()
    calls = []
    real_many = profiler.record_many
    profiler.record_many = lambda samples: (calls.append(len(samples)), real_many(samples))[1]
    heard = []
    profiler.add_listener(lambda stage, seconds: heard.append(stage))
    tracer = Tracer(capacity=4, profiler=profiler, histograms=family)
    tr = tracer.start(model="m")
    record = LaunchRecord([tr])
    tr.add("batch_queue", 1.0, 1.5)
    record.add("device_execute", 1.5, 2.5, {"launch_id": 1})
    record.add("readback", 2.5, 2.75, {"launch_id": 1})
    tracer.finish(tr)
    assert calls == [3]  # one call a request, the launch's spans with the request's own
    summary = profiler.summary()
    assert {k: v["count"] for k, v in summary.items()} == {
        "span_batch_queue": 1.0, "span_device_execute": 1.0, "span_readback": 1.0}
    assert summary["span_device_execute"]["mean_ms"] == pytest.approx(1000.0)
    assert sorted(heard) == sorted(summary)  # the exporter's listener still hears every sample
    assert family.count("m", "queue_delay") == 1 and family.count("m", "device_execute") == 1
    assert family.count("m", "readback") == 1 and family.count("m", "e2e") == 1


# -- the five phases on timelines made by hand -------------------------------------


def _member(session, start, end, front=None, batch_queue=None, future=None):
    return {"session": session, "request": (start, end), "front": start if front is None else front,
            "batch_queue": batch_queue, "future": future}


def _rec(launch_id, h2d, launch, ready, window=None, members=(), **more):
    rec = {"launch_id": launch_id, "request_start": min((m["request"][0] for m in members), default=0.0),
           "h2d": h2d, "launch": launch, "ready": ready, "rows": 2, "bytes": 8, **more}
    if window:
        rec["window"] = window
    if members:
        rec["members"] = list(members)
    return rec


# launch 1 (block launch of sessions a, b) is ready at 1.000; its answers' futures are set by
# 1.003 (first 1.002); a's request is accounted at 1.005, b's at 1.009
_FIRST = _rec(
    1, (0.90, 0.91), (0.91, 0.92), 1.000, "lm_block", readback_end=1.0015,
    members=[_member("a", 0.80, 1.005, future=1.002), _member("b", 0.81, 1.009, future=1.003)],
)
CYCLE_CASES = {
    # the same two sessions come back: fronts at 1.012 and 1.015, in the batcher at 1.013 and 1.017,
    # the next device window begins at 1.021 (launch's end)
    "closed_cycle": (
        _FIRST,
        _rec(2, (1.018, 1.019), (1.019, 1.021), 1.050, "lm_block", members=[
            _member("a", 1.0125, 1.06, front=1.012, batch_queue=1.013),
            _member("b", 1.0155, 1.06, front=1.015, batch_queue=1.017)]),
        {"gap_s": 0.021, "closed": True, "handback": 0.003, "answer": 0.006, "away": 0.003,
         "intake": 0.005, "restage": 0.004},
    ),
    # two cohorts take turns: launch 2 carries c and d, which came in while launch 1 ran:
    # no session in both, e4 and e5 before a: handback + restage
    "alternating_cohorts": (
        _FIRST,
        _rec(2, (1.004, 1.005), (1.005, 1.007), 1.040, "lm_step", members=[
            _member("c", 0.95, 1.05, front=0.949, batch_queue=0.951),
            _member("d", 0.96, 1.05, front=0.959, batch_queue=0.962)]),
        {"gap_s": 0.007, "closed": False, "handback": 0.003, "answer": 0.0, "away": 0.0,
         "intake": 0.0, "restage": 0.004},
    ),
    # after a PROMPT launch (it went down alone: nobody set a batcher's future, so e2 is its
    # readback's end, 1.0015); its session's first step and another session's step follow
    "after_a_prompt_launch": (
        _rec(1, (0.90, 0.91), (0.91, 0.92), 1.000, "lm_prefill", readback_end=1.0015,
             members=[_member("a", 0.80, 1.004)]),
        _rec(2, (1.012, 1.013), (1.013, 1.014), 1.030, "lm_step", members=[
            _member("a", 1.0085, 1.04, front=1.008, batch_queue=1.009),
            _member("e", 0.99, 1.04, front=0.989, batch_queue=0.991)]),
        {"gap_s": 0.014, "closed": False, "handback": 0.0015, "answer": 0.0025, "away": 0.0,
         "intake": 0.005, "restage": 0.005},
    ),
}


@pytest.mark.parametrize("case", sorted(CYCLE_CASES))
def test_cycles_on_a_synthetic_timeline(case):
    first, second, want = CYCLE_CASES[case]
    (row,) = launch_timeline.cycles([second, first])  # any order in
    assert row["launch_id"] == 2 and row["closed"] is want["closed"]
    assert row["gap_s"] == pytest.approx(want["gap_s"])
    for phase in launch_timeline.PHASES:
        assert row["by_phase"][phase] == pytest.approx(want[phase], abs=1e-12), phase
    assert sum(row["by_phase"].values()) == pytest.approx(row["gap_s"])
    # the same gap as host_gaps has it
    (gap,) = launch_timeline.host_gaps([first, second])
    assert gap["gap_s"] == pytest.approx(row["gap_s"])


def test_a_gap_before_a_launch_of_another_kind_keeps_todays_states():
    first, second, _ = CYCLE_CASES["closed_cycle"]
    prompt = {**second, "window": "lm_prefill"}
    plain = {k: v for k, v in second.items() if k not in ("window", "members")}
    for other in (prompt, plain):
        assert launch_timeline.cycles([first, other]) == []
        (gap,) = launch_timeline.host_gaps([first, other])
        assert sum(gap["by_state"].values()) == pytest.approx(gap["gap_s"])
    # and a hole in the ring gives no row
    assert launch_timeline.cycles([first, {**second, "launch_id": 3}]) == []


# -- one export, two copies of the arithmetic ---------------------------------------


def _session_traces():
    """Three launches as the server would trace them: a block launch of
    sessions a and b (merged: one record), the same two again, then a
    launch of c alone that came in meanwhile (solo: its spans on its own
    trace). Times in seconds on one clock."""
    ids = iter(range(1, 100))

    def request(session, front, start, queue, dispatch, end, respond=None):
        tr = RequestTrace(next(ids), model="m")
        tr.session, tr.t_start, tr.t_end = session, start, end
        tr.add("front", front, start)
        tr.add("parse", start, start + 0.0002)
        tr.add("batch_queue", queue, dispatch)
        if respond is not None:
            tr.add("batch_respond", *respond)
        return tr

    def launch(target, launch_id, stage0, h2d_end, launch_end, ready, readback_end, window):
        attrs = {"launch_id": launch_id}
        target.add("slot_wait", stage0, stage0 + 0.0001, attrs)
        target.add("stage", stage0, stage0 + 0.001, attrs)
        target.add("h2d", stage0 + 0.0001, h2d_end, {"bytes": 64, "rows": 2, **attrs})
        target.add("launch", stage0 + 0.001, launch_end, attrs)
        target.add("device_execute", launch_end, ready, attrs)
        target.add(window, launch_end, ready, {**attrs, "tokens": 8, "sessions": 2})
        target.add("readback", ready, readback_end, attrs)

    a1 = request("a", 0.0995, 0.100, 0.101, 0.104, 0.1550, respond=(0.1515, 0.1520))
    b1 = request("b", 0.1015, 0.102, 0.103, 0.104, 0.1590, respond=(0.1515, 0.1530))
    a2 = request("a", 0.1620, 0.1625, 0.1630, 0.168, 0.2300, respond=(0.2210, 0.2215))
    b2 = request("b", 0.1650, 0.1655, 0.1670, 0.168, 0.2310, respond=(0.2210, 0.2220))
    c1 = request("c", 0.1700, 0.1705, 0.1710, 0.2225, 0.2600)
    launch(LaunchRecord([a1, b1]), 1, 0.104, 0.106, 0.107, 0.150, 0.1515, "lm_block")
    launch(LaunchRecord([a2, b2]), 2, 0.168, 0.170, 0.171, 0.220, 0.2210, "lm_block")
    launch(c1, 3, 0.2225, 0.2240, 0.2250, 0.250, 0.2510, "lm_block")
    return [a1, b1, a2, b2, c1]


def test_the_program_and_the_benchmarks_copy_agree_on_one_export():
    sys.path.insert(0, str(ROOT))
    from benchmarks.layer_metrics import _cycle, _launches

    traces = _session_traces()
    recs = launch_timeline.launches(traces)
    mine = launch_timeline.cycles(recs)
    ctx = {"traces": chrome_trace(traces, clock_anchor=(0.0, 0))}
    theirs = _cycle.cycles(ctx)
    assert [r["launch_id"] for r in mine] == [r["launch_id"] for r in theirs] == [2, 3]
    by_hand = {
        # a = 0.150, b = 0.171: futures by 0.153, accounted by 0.159, first front 0.162, last in 0.167
        2: {"handback": 0.003, "answer": 0.006, "away": 0.003, "intake": 0.005, "restage": 0.004},
        # a = 0.220, b = 0.225: c was not in launch 2 and came in before it was ready
        3: {"handback": 0.002, "answer": 0.0, "away": 0.0, "intake": 0.0, "restage": 0.003},
    }
    for m, t in zip(mine, theirs):
        assert m["closed"] is t["closed"] is (m["launch_id"] == 2)
        assert m["gap_s"] == pytest.approx(t["gap_s"], abs=1e-9)
        for phase in launch_timeline.PHASES:
            assert m["by_phase"][phase] == pytest.approx(t["by_phase"][phase], abs=1e-9), phase
            assert m["by_phase"][phase] == pytest.approx(by_hand[m["launch_id"]][phase], abs=1e-9), phase
    # the pair that no test held together before: host_gaps and _launches.gaps
    for m, t in zip(launch_timeline.host_gaps(recs), _launches.gaps(ctx)):
        assert m["launch_id"] == t["launch_id"]
        for key in ("gap_s", "busy_s", "h2d_s", "h2d_overlap_s"):
            assert m[key] == pytest.approx(t[key], abs=1e-9), key
        for state, value in m["by_state"].items():
            assert value == pytest.approx(t["by_state"][state], abs=1e-9), state


def test_timeline_carries_cycle_by_phase_s():
    doc = launch_timeline.timeline(_session_traces())
    assert doc["cycle_by_phase_s"] == pytest.approx(
        {"handback": 0.005, "answer": 0.006, "away": 0.003, "intake": 0.005, "restage": 0.007})
    # a part of the idle time that idle_by_state_s splits
    assert sum(doc["cycle_by_phase_s"].values()) == pytest.approx(sum(doc["idle_by_state_s"].values()))
    assert launch_timeline.timeline([])["cycle_by_phase_s"] == dict.fromkeys(launch_timeline.PHASES, 0)


def _shifted(traces, by):
    """The same traces ``by`` seconds later."""
    for span in {id(s): s for tr in traces for s in tr.spans}.values():
        span.t0, span.t1 = span.t0 + by, span.t1 + by
    for tr in traces:
        tr.t_start, tr.t_end = tr.t_start + by, tr.t_end + by
    return traces


def test_profile_on_a_cpu_server_returns_cycle_by_phase_s(monkeypatch):
    from triton_client_tpu.obs import http
    from triton_client_tpu.obs.http import TelemetryServer

    tracer = Tracer(capacity=64)

    def served_meanwhile(seconds):
        """The capture's window: the three launches happen inside it."""
        tracer._ring.extend(_shifted(_session_traces(), time.perf_counter()))
        time.sleep(0.3)

    monkeypatch.setattr(
        http, "time", types.SimpleNamespace(sleep=served_meanwhile, perf_counter=time.perf_counter)
    )
    srv = TelemetryServer(port=0, tracer=tracer)
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/profile?seconds=0.05", timeout=60.0) as resp:
            doc = json.loads(resp.read().decode())
    finally:
        srv.close()
    timeline = doc["launch_timeline"]
    assert timeline["offset_s"] is None  # the CPU backend's trace has no device line
    assert timeline["cycle_by_phase_s"] == pytest.approx(
        {"handback": 0.005, "answer": 0.006, "away": 0.003, "intake": 0.005, "restage": 0.007}, abs=1e-9)
