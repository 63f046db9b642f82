"""Spans over the host's path to the device (PR 26): the ``h2d`` span
and ``launch_id`` of the staged channel, the ``/traces`` clock, the
client-side shm write log, ``obs/launch_timeline`` (grouping by launch,
host-clock gaps, the join with a device trace), the device-only capture
options, and ``/profile``'s ``launch_timeline`` key."""

import json
import pathlib
import sys
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

from triton_client_tpu.obs import launch_timeline
from triton_client_tpu.obs.trace import (
    LaunchRecord,
    RequestTrace,
    Tracer,
    chrome_trace,
)

jax = pytest.importorskip("jax")

ROOT = pathlib.Path(__file__).resolve().parents[1]
FIXTURE = ROOT / "benchmarks" / "tests" / "data" / "trace_v5e.json.gz"
LAUNCH_SPANS = ("slot_wait", "stage", "h2d", "launch", "device_execute", "readback")
X = np.arange(8, dtype=np.float32).reshape(2, 4)


def _repo(sleep_s=0.0):
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository

    spec = ModelSpec(
        name="double",
        version="1",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
    )

    def infer(inputs):
        if sleep_s:
            time.sleep(sleep_s)
        return {"y": np.asarray(inputs["x"]) * 2.0}

    repo = ModelRepository()
    repo.register(spec, infer)
    return repo


def _request(trace=None):
    from triton_client_tpu.channel.base import InferRequest

    return InferRequest("double", {"x": X}, trace=trace)


def _by_name(trace):
    return {s.name: s for s in trace.spans}


# -- the staged channel: h2d, launch_id ---------------------------------------


def test_h2d_span_carries_bytes_rows_and_launch_id():
    from triton_client_tpu.channel.tpu_channel import TPUChannel

    chan = TPUChannel(_repo())
    first, second = RequestTrace(1), RequestTrace(2)
    chan.do_inference(_request(first))
    chan.do_inference(_request(second))
    spans = _by_name(first)
    assert spans["h2d"].attrs == {"bytes": X.nbytes, "rows": 2, "launch_id": 1}
    # stage kept its meaning: it ends at the enqueue, h2d at the arrival
    assert spans["stage"].t1 <= spans["h2d"].t1
    assert spans["slot_wait"].t1 == spans["h2d"].t0
    # h2d is closed on the resolving thread, before it waits for the outputs
    assert spans["launch"].t1 <= spans["h2d"].t1 <= spans["device_execute"].t1
    for name in LAUNCH_SPANS:
        assert spans[name].attrs["launch_id"] == 1, name
        assert _by_name(second)[name].attrs["launch_id"] == 2, name
    assert chan.stats()["launched"] == 2


def test_stage_never_waits_for_the_device(monkeypatch):
    from triton_client_tpu.channel import staged
    from triton_client_tpu.channel.tpu_channel import TPUChannel

    chan = TPUChannel(_repo())
    waits, markers = [], []
    real_wait, real_marker = jax.block_until_ready, staged._arrival_marker
    monkeypatch.setattr(
        staged.jax, "block_until_ready", lambda x: (waits.append(1), real_wait(x))[1]
    )
    monkeypatch.setattr(
        staged, "_arrival_marker", lambda x: (markers.append(1), real_marker(x))[1]
    )
    st = chan.stage(_request())
    # untraced: no sync and no extra dispatch
    assert waits == [] and markers == [] and st.trace_state is None
    trace = RequestTrace(1)
    traced = chan.stage(_request(trace))
    # traced: the marker is dispatched, and still nothing waits in stage()
    assert waits == [] and markers == [1]
    assert "h2d" not in _by_name(trace) and "stage" in _by_name(trace)
    chan.launch(st).result()
    assert waits == []  # an untraced launch without a ledger never fences at all
    chan.launch(traced).result()
    assert len(waits) == 2 and "h2d" in _by_name(trace)  # the marker, then the outputs


def test_launch_record_shows_its_attrs_on_every_member():
    from triton_client_tpu.channel.tpu_channel import TPUChannel

    members = [RequestTrace(i) for i in (1, 2, 3)]
    TPUChannel(_repo()).do_inference(_request(LaunchRecord(members)))
    for m in members:
        spans = _by_name(m)
        assert {spans[n].attrs["launch_id"] for n in LAUNCH_SPANS} == {1}
        assert spans["h2d"].attrs["bytes"] == X.nbytes


@pytest.mark.parametrize("depth", [1, 2])
def test_merged_launch_has_one_launch_id_on_all_members(depth):
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel

    inner = TPUChannel(_repo(sleep_s=0.02))
    chan = ContinuousBatchingChannel(inner, max_batch=8, pipeline_depth=depth)
    tracer = Tracer(capacity=64)

    def one():
        tr = tracer.start("double")
        chan.do_inference(_request(tr))
        tracer.finish(tr)

    try:
        shared = False
        for _ in range(5):
            threads = [threading.Thread(target=one) for _ in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            by_launch = {}
            for tr in tracer.recent():
                spans = _by_name(tr)
                ids = {spans[n].attrs["launch_id"] for n in LAUNCH_SPANS}
                assert len(ids) == 1, "one launch, one id, on every span"
                by_launch.setdefault(ids.pop(), []).append(spans["h2d"])
            for members in by_launch.values():
                # the launch's spans, not a copy per member
                assert len({(s.t0, s.t1) for s in members}) == 1
            shared = any(len(m) > 1 for m in by_launch.values())
            if shared:
                break
        assert shared, "no two requests ever merged into one launch"
        recs = launch_timeline.launches(tracer.recent())
        assert [r["launch_id"] for r in recs] == sorted(by_launch)
    finally:
        chan.close()


# -- /traces: the clock --------------------------------------------------------


def test_chrome_trace_carries_a_clock_and_still_loads(tmp_path, capsys):
    from triton_client_tpu.cli.tools import trace_join

    before = (time.perf_counter(), time.time_ns())
    tracer = Tracer(capacity=8)
    tr = tracer.start("m")
    tr.add("h2d", tr.t_start + 0.25, tr.t_start + 0.5, {"launch_id": 7})
    tracer.finish(tr)
    doc = tracer.chrome_trace()
    clock = doc["clock"]
    assert clock["base_perf_counter_s"] == tr.t_start
    assert before[0] <= clock["anchor_perf_counter_s"] <= tr.t_start
    assert before[1] <= clock["anchor_time_ns"] <= time.time_ns()
    (h2d,) = [e for e in doc["traceEvents"] if e.get("name") == "h2d"]
    assert clock["base_perf_counter_s"] + h2d["ts"] / 1e6 == pytest.approx(tr.t_start + 0.25)
    assert h2d["args"] == {"launch_id": 7}
    # the function without a tracer is what it was; an empty ring still says its clock
    assert chrome_trace([]) == {"traceEvents": [], "displayTimeUnit": "ms"}
    assert Tracer(capacity=4).chrome_trace()["clock"]["base_perf_counter_s"] is None
    # an existing reader ignores the new top-level key
    path = tmp_path / "t.json"
    path.write_text(json.dumps(doc))
    out = tmp_path / "joined.json"
    trace_join([f"a={path}", "-o", str(out)])
    assert any(e.get("name") == "h2d" for e in json.loads(out.read_text())["traceEvents"])


# -- the client's copy into the shm region ------------------------------------


def test_write_log_is_bounded_and_ordered():
    from triton_client_tpu.runtime import shared_memory

    region = shared_memory.SharedMemoryRegion.create(f"tct_test_wlog_{time.time_ns()}", 64)
    try:
        t_before = time.perf_counter()
        for i in range(4200):
            region.write(np.full(1 + i % 8, i % 251, np.uint8))
        log = shared_memory.write_log()
    finally:
        region.close()
    assert len(log) == 4096
    assert all(t0 <= t1 for t0, t1, _ in log)
    assert all(a[1] <= b[0] for a, b in zip(log, log[1:]))  # oldest first
    assert log[-1][2] == 1 + 4199 % 8 and log[-1][0] >= t_before
    assert log is not shared_memory.write_log()  # a copy, not the ring


# -- launch_timeline: gaps on the host clock ----------------------------------


def _rec(launch_id, request_start, h2d, launch, ready, **more):
    return {"launch_id": launch_id, "request_start": request_start, "h2d": h2d,
            "launch": launch, "ready": ready, "rows": 8, "bytes": 80, **more}


FIRST = _rec(1, 0.0, (0.0, 0.5), (0.5, 0.51), 0.7)
GAP_CASES = {
    # launch 2's frames arrive 0.4 s after launch 1 is done: the device waits for the copy
    "wholly_in_h2d": (
        _rec(2, 0.1, (0.6, 1.1), (1.1, 1.1), 1.3),
        {"gap_s": 0.4, "busy_s": 0.2, "h2d": 0.4, "h2d_overlap_s": 0.1},
    ),
    # launch 2's request arrives 0.3 s after launch 1 is done: nobody asked
    "no_request": (
        _rec(2, 1.0, (1.0, 1.0), (1.0, 1.0), 1.2),
        {"gap_s": 0.3, "busy_s": 0.2, "no_request": 0.3, "h2d_overlap_s": 0.0},
    ),
    # launch 2 was staged and enqueued while launch 1 ran: no gap at all
    "overlapping": (
        _rec(2, 0.1, (0.5, 0.6), (0.6, 0.61), 0.9),
        {"gap_s": 0.0, "busy_s": 0.2, "h2d_overlap_s": 0.09},  # launch 1 is busy from 0.51
    ),
    # queueing, slot wait and dispatch each take their part; the rest is other
    "split_by_state": (
        _rec(2, 0.7, (1.0, 1.1), (1.15, 1.2), 1.4, parse=(0.7, 0.75),
             batch_queue=(0.75, 0.8), batch_merge=(0.8, 0.9), slot_wait=(0.9, 1.0)),
        {"gap_s": 0.5, "busy_s": 0.2, "parse": 0.05, "batch_queue": 0.05, "batch_merge": 0.1,
         "slot_wait": 0.1, "h2d": 0.1, "launch": 0.05, "other": 0.05, "h2d_overlap_s": 0.0},
    ),
    # a lone request that needed no pad rows was not copied (PR 27): its member has no
    # batch_merge span, and the gap falls to the states that are there
    "no_batch_merge_span": (
        _rec(2, 0.7, (0.9, 1.1), (1.1, 1.15), 1.35, parse=(0.7, 0.75),
             batch_queue=(0.75, 0.85), batch_merge=None, slot_wait=(0.85, 0.9)),
        {"gap_s": 0.45, "busy_s": 0.2, "parse": 0.05, "batch_queue": 0.1, "slot_wait": 0.05,
         "h2d": 0.2, "launch": 0.05, "h2d_overlap_s": 0.0},
    ),
}


@pytest.mark.parametrize("case", sorted(GAP_CASES))
def test_host_gaps_on_a_synthetic_timeline(case):
    second, want = GAP_CASES[case]
    (row,) = launch_timeline.host_gaps([second, FIRST])  # any order in
    assert row["launch_id"] == 2
    for key in ("gap_s", "busy_s", "h2d_overlap_s"):
        assert row[key] == pytest.approx(want[key]), key
    for state in (*launch_timeline.STATES, "other"):
        assert row["by_state"][state] == pytest.approx(want.get(state, 0.0)), state
    assert sum(row["by_state"].values()) == pytest.approx(row["gap_s"])


def test_host_gaps_skips_a_hole_in_the_ring():
    third = _rec(4, 2.0, (2.0, 2.1), (2.1, 2.1), 2.4)
    assert launch_timeline.host_gaps([FIRST, third]) == []


def test_launches_group_by_id_and_keep_the_earliest_member():
    early, late, alone = RequestTrace(1), RequestTrace(2), RequestTrace(3)
    early.t_start, late.t_start, alone.t_start = 10.0, 10.2, 11.0
    early.add("parse", 10.0, 10.1)
    late.add("parse", 10.2, 10.25)
    early.add("batch_queue", 10.1, 10.3)
    merged = LaunchRecord([late, early])
    merged.add("batch_merge", 10.3, 10.4)  # the batcher's copy: on every member, without an id
    ids = {"launch_id": 5}
    merged.add("slot_wait", 10.4, 10.5, ids)
    merged.add("h2d", 10.5, 10.9, {"bytes": 64, "rows": 4, **ids})
    merged.add("launch", 10.9, 10.95, ids)
    merged.add("device_execute", 10.95, 11.3, ids)
    merged.add("readback", 11.3, 11.35, ids)
    alone.add("h2d", 11.0, 11.1, {"bytes": 16, "rows": 1, "launch_id": 6})  # never launched
    (rec,) = launch_timeline.launches([late, alone, early])
    assert rec == {
        "launch_id": 5, "request_start": 10.0, "parse": (10.0, 10.1), "batch_queue": (10.1, 10.3),
        "batch_merge": (10.3, 10.4),
        "slot_wait": (10.4, 10.5), "h2d": (10.5, 10.9), "launch": (10.9, 10.95), "ready": 11.3,
        "readback_end": 11.35, "rows": 4, "bytes": 64,
    }


# -- launch_timeline: the join with a device trace ----------------------------


def _fixture_modules():
    sys.path.insert(0, str(ROOT))
    from benchmarks import trace_reduce

    planes = trace_reduce.read_recorded(FIXTURE)
    return [e for e in planes["/device:TPU:0"]["XLA Modules"] if e[0].startswith("jit_mdl_")]


def _host_records(modules, offset, lags_ms, earlier=0):
    """Launch records whose ``ready`` is each module's end at a known
    offset plus a wake-up lag; ``earlier`` more launches before them."""
    recs = []
    for k in range(earlier):
        ready = offset + modules[0][1] / 1e9 - 0.137 * (earlier - k)
        recs.append(_rec(k + 1, ready - 0.1, (ready - 0.1, ready - 0.05), (ready - 0.05, ready - 0.04), ready))
    for i, (_, start, dur) in enumerate(modules):
        ready = offset + (start + dur) / 1e9 + lags_ms[i % len(lags_ms)] / 1e3
        h2d = (ready - dur / 1e9 - 0.06, ready - dur / 1e9 - 0.01)
        recs.append(_rec(earlier + i + 1, h2d[0], h2d, (h2d[1], h2d[1] + 0.002), ready))
    return recs


LAGS_MS = (0.31, 0.12, 0.9, 0.2, 0.45, 0.12, 2.4, 0.18)  # smallest 0.12, median 0.255


@pytest.mark.parametrize("earlier", [0, 2])
def test_align_recovers_a_known_offset_on_the_recorded_trace(earlier):
    modules = _fixture_modules()
    assert len(modules) >= 10
    offset = 51234.5
    found = launch_timeline.align(modules, _host_records(modules, offset, LAGS_MS, earlier))
    assert found["shift"] == earlier and found["matched"] == len(modules)
    assert 0.0 < found["residual_ms"] < 1.0
    # within the residual of the truth (the smallest wake-up lag cannot be told from the offset)
    assert abs(found["offset_s"] - offset) * 1e3 <= found["residual_ms"]
    # a hint near the truth changes nothing; one far from it finds nothing
    assert launch_timeline.align(modules, _host_records(modules, offset, LAGS_MS, earlier),
                                 near_s=offset - 0.5) == found
    assert launch_timeline.align(modules, _host_records(modules, offset, LAGS_MS, earlier),
                                 near_s=offset - 60.0) is None


def test_align_needs_three_pairs():
    modules = _fixture_modules()
    assert launch_timeline.align(modules[:2], _host_records(modules[:2], 5.0, LAGS_MS)) is None
    assert launch_timeline.align([], _host_records(modules, 5.0, LAGS_MS)) is None


def test_timeline_splits_the_devices_own_gaps_by_host_state():
    modules = _fixture_modules()
    offset = 900.0
    recs = _host_records(modules, offset, LAGS_MS)
    traces = []
    for r in recs:
        tr = RequestTrace(r["launch_id"])
        tr.t_start = r["request_start"]
        ids = {"launch_id": r["launch_id"]}
        tr.add("h2d", *r["h2d"], {"bytes": 80, "rows": 8, **ids})
        tr.add("launch", *r["launch"], ids)
        tr.add("device_execute", r["launch"][1], r["ready"], ids)
        traces.append(tr)
    doc = launch_timeline.timeline(traces, modules, near_s=offset)
    assert doc["launches"] == len(modules)
    assert doc["offset_s"] == pytest.approx(offset, abs=1e-3) and doc["residual_ms"] < 1.0
    # busy is the device's own module time, idle the gaps between its modules
    assert doc["busy_s"] == pytest.approx(sum(d for _, _, d in modules[1:]) / 1e9)
    ordered = sorted(modules, key=lambda e: e[1])
    gaps = sum(max(0, b[1] - (a[1] + a[2])) for a, b in zip(ordered, ordered[1:])) / 1e9
    assert sum(doc["idle_by_state_s"].values()) == pytest.approx(gaps)
    assert doc["idle_by_state_s"]["h2d"] > 0 and doc["idle_by_state_s"]["no_request"] > 0
    assert 0.0 <= doc["h2d_overlap"] <= 1.0
    # without device events: the host-clock estimate, and it says so
    host_only = launch_timeline.timeline(traces)
    assert host_only["offset_s"] is None and host_only["launches"] == len(modules) - 1


# -- one capture helper --------------------------------------------------------


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_device_trace_picks_its_options_from_the_platform(monkeypatch, tmp_path, platform):
    from triton_client_tpu.obs import profiling

    started, stopped = [], []
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: started.append((a, k)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: stopped.append(1))
    with profiling.device_trace(str(tmp_path)):
        assert len(started) == 1 and not stopped
    assert stopped == [1]
    (args, kwargs), = started
    assert args == (str(tmp_path),)
    if platform == "tpu":
        options = kwargs["profiler_options"]
        assert (options.host_tracer_level, options.python_tracer_level) == (0, 0)
        assert options.enable_hlo_proto is False
    else:
        assert kwargs == {}  # the defaults: on the CPU backend the ops are host events


def test_sampler_captures_through_device_trace(monkeypatch, tmp_path):
    import contextlib

    from triton_client_tpu.obs import opstats, profiling
    from triton_client_tpu.obs.sampler import ContinuousSampler

    used = []

    @contextlib.contextmanager
    def fake(log_dir):
        used.append(log_dir)
        yield

    monkeypatch.setattr(profiling, "device_trace", fake)
    monkeypatch.setattr(opstats, "summarize_profile_dir", lambda *a, **k: {"ops": []})
    sampler = ContinuousSampler(interval_s=1.0, window_s=0.01)
    assert sampler.sample_once() == {"ops": []}
    assert len(used) == 1


# -- /profile ------------------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=60.0) as resp:
        return resp.status, json.loads(resp.read().decode())


@pytest.mark.parametrize("broken", [False, True])
def test_profile_reports_the_launch_timeline_or_its_error(monkeypatch, broken):
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.obs import http
    from triton_client_tpu.obs.http import TelemetryServer

    if broken:
        monkeypatch.setattr(
            launch_timeline, "timeline",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
        )
    chan = TPUChannel(_repo())
    tracer = Tracer(capacity=64)
    srv = TelemetryServer(port=0, tracer=tracer)
    stop = threading.Event()
    answered = threading.Semaphore(0)

    def serve():
        while not stop.is_set():
            tr = tracer.start("double")
            chan.do_inference(_request(tr))
            tracer.finish(tr)
            answered.release()
            time.sleep(0.002)

    def until_launches(seconds):
        """The capture's length in LAUNCHES, not in seconds: a machine that
        runs six test workers may answer fewer than three requests in 0.2 s.
        The first answer counted may have begun before the capture."""
        while answered.acquire(blocking=False):
            pass
        for _ in range(6):
            assert answered.acquire(timeout=60.0), "the serving thread stalled"

    monkeypatch.setattr(
        http, "time",
        types.SimpleNamespace(sleep=until_launches, perf_counter=time.perf_counter),
    )

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    try:
        status, doc = _get(f"http://127.0.0.1:{srv.port}/profile?seconds=0.2")
    finally:
        stop.set()
        t.join(timeout=10.0)
        srv.close()
    assert status == 200 and doc["log_dir"]
    if broken:
        assert "boom" in doc["launch_timeline_error"] and "launch_timeline" not in doc
        return
    timeline = doc["launch_timeline"]
    assert set(timeline) == {"offset_s", "residual_ms", "launches", "busy_s",
                             "idle_by_state_s", "cycle_by_phase_s", "h2d_overlap"}
    # the CPU backend's trace has no device line: the host-clock estimate
    assert timeline["offset_s"] is None and timeline["launches"] >= 3
    assert timeline["busy_s"] > 0 and set(timeline["idle_by_state_s"]) == {
        *launch_timeline.STATES, "other"}


def test_profile_without_a_tracer_has_no_timeline_key():
    from triton_client_tpu.obs.http import TelemetryServer

    srv = TelemetryServer(port=0)
    try:
        status, doc = _get(f"http://127.0.0.1:{srv.port}/profile?seconds=0.05")
    finally:
        srv.close()
    assert status == 200
    assert "launch_timeline" not in doc and "launch_timeline_error" not in doc
