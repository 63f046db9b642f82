"""Request-scoped serving telemetry (obs/): spans through the
overlapped pipeline, the runtime collector bridge, and trace export.

Covers the PR's acceptance contract:
  * spans cover >=95% of request wall time on the batching+TPUChannel
    serving path, with channel-side spans nested inside the handler's
    ``channel`` span;
  * the /traces export is valid Chrome-trace JSON (Perfetto-loadable
    shape: M metadata + X complete events, non-negative rebased ts);
  * every collector family in METRIC_TYPES is present and correctly
    typed on a /metrics scrape, and counter values match the channel's
    own stats() snapshot;
  * failing requests are measured too: the per-model latency sample
    lands in a finally and the error counter carries the gRPC code;
  * the trace ring buffer stays bounded under load.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from triton_client_tpu.obs.collector import METRIC_TYPES, RuntimeCollector
from triton_client_tpu.obs.trace import (
    LaunchRecord,
    RequestTrace,
    Tracer,
    chrome_trace,
    decode_span_summary,
    encode_span_summary,
    graft_span_summary,
)

jax = pytest.importorskip("jax")


# -- helpers ------------------------------------------------------------------


def _double_repo(name="double"):
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository

    spec = ModelSpec(
        name=name,
        version="1",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
    )
    repo = ModelRepository()
    repo.register(spec, lambda inputs: {"y": np.asarray(inputs["x"]) * 2.0})
    return repo, spec


def _serving_stack(repo, **server_kw):
    """batching + TPUChannel + InferenceServer on loopback with an
    ephemeral telemetry port — the full overlapped serving path."""
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
    from triton_client_tpu.runtime.server import InferenceServer

    chan = ContinuousBatchingChannel(
        TPUChannel(repo), max_batch=4
    )
    server = InferenceServer(
        repo, chan, address="127.0.0.1:0", metrics_port="auto", **server_kw
    )
    server.start()
    return chan, server


def _drive_clients(server, model="double", clients=4, rounds=3):
    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.grpc_channel import GRPCChannel

    x = np.arange(8, dtype=np.float32).reshape(2, 4)

    def one():
        c = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=30.0)
        try:
            for _ in range(rounds):
                out = c.do_inference(InferRequest(model, {"x": x}))
                np.testing.assert_allclose(out.outputs["y"], x * 2.0)
        finally:
            c.close()

    threads = [threading.Thread(target=one) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return clients * rounds


def _family_attr_name(name, typ):
    """Family ``.name`` as the collect() protocol reports it: the
    CounterMetricFamily constructor strips the _total suffix (the text
    exposition re-appends it on TYPE/sample lines)."""
    if typ == "counter" and name.endswith("_total"):
        return name[: -len("_total")]
    return name


# -- trace primitives ---------------------------------------------------------


def test_span_recording_and_context():
    tr = RequestTrace(1, model="m")
    tr.add("a", 1.0, 2.0)
    with tr.span("b"):
        pass
    assert [s.name for s in tr.spans] == ["a", "b"]
    assert tr.spans[0].duration_s == pytest.approx(1.0)


def test_begin_end_crosses_threads_and_tolerates_misuse():
    tr = RequestTrace(1)
    tr.begin("q")
    done = threading.Event()

    def closer():
        tr.end("q")
        done.set()

    threading.Thread(target=closer).start()
    assert done.wait(5.0)
    assert [s.name for s in tr.spans] == ["q"]
    tr.end("q")  # double end: no-op
    tr.end("never_began")  # end without begin: no-op
    assert len(tr.spans) == 1


def test_span_coverage_is_union_of_intervals():
    tr = RequestTrace(1)
    tr.t_start = 0.0
    tr.t_end = 10.0
    tr.add("a", 0.0, 4.0)
    tr.add("b", 2.0, 5.0)  # overlaps a: union [0,5]
    tr.add("c", 7.0, 9.0)
    assert tr.span_coverage() == pytest.approx(0.7)


def test_span_coverage_counts_a_span_for_its_part_inside_the_wall():
    tr = RequestTrace(1)
    tr.t_start, tr.t_end = 10.0, 20.0
    tr.add("front", 4.0, 10.0)  # ends where the wall begins: covers none of it
    assert tr.span_coverage() == 0.0
    tr.add("a", 9.0, 12.0)  # 2 s of it inside
    tr.add("b", 18.0, 25.0)  # and 2 s of this one
    assert tr.span_coverage() == pytest.approx(0.4)


def test_spans_is_a_new_list_every_read_with_or_without_a_launch():
    alone, merged = RequestTrace(1), RequestTrace(2)
    LaunchRecord([merged]).add("stage", 1.0, 2.0)
    for tr in (alone, merged):
        tr.add("parse", 0.0, 1.0)
        read = tr.spans
        assert read is not tr.spans and read is not tr.own
        tr.add("encode", 2.0, 3.0)  # a later write does not grow what was read
        assert len(tr.spans) == len(read) + 1


def test_a_front_span_before_the_first_trace_sorts_behind_the_metadata_and_grafts():
    tr = RequestTrace(7, model="m")
    tr.add("front", tr.t_start - 0.002, tr.t_start)
    tr.add("parse", tr.t_start, tr.t_start + 0.001)
    tr.t_end = tr.t_start + 0.004
    events = chrome_trace([tr])["traceEvents"]
    assert [e["ph"] for e in events[:2]] == ["M", "M"]
    assert events[2]["name"] == "front" and events[2]["ts"] == pytest.approx(-2000.0)
    # the far side lays a summary's negative offset BEFORE the server's start
    local = RequestTrace(8)
    graft_span_summary(local, decode_span_summary(encode_span_summary(tr)), 100.0, 100.010)
    spans = {s.name: s for s in local.spans}
    assert spans["wire_send"].t1 == pytest.approx(100.003)  # (10 - 4) / 2 ms of wire each way
    assert spans["srv.front"].t0 == pytest.approx(100.001)
    assert spans["srv.front"].t1 == pytest.approx(spans["srv.parse"].t0) == pytest.approx(100.003)


def test_launch_record_is_written_once_and_read_on_every_member():
    a, b = RequestTrace(1), RequestTrace(2)
    a.add("own", 0.5, 0.6)
    rec = LaunchRecord([a, None, b])
    rec.add("stage", 1.0, 2.0)
    rec.add("launch", 2.0, 2.5, {"launch_id": 3})
    assert [s.name for s in rec.spans] == ["stage", "launch"]  # one write a span
    assert [s.name for s in a.spans] == ["own", "stage", "launch"]
    assert [s.name for s in b.spans] == ["stage", "launch"]
    assert a.spans[1] is b.spans[0]  # the launch's span, not a copy a member
    assert a.own == [a.spans[0]] and b.own == []


def test_tracer_disabled_returns_none():
    assert Tracer(enabled=False).start(model="m") is None
    assert Tracer(capacity=0).start(model="m") is None
    Tracer().finish(None)  # disabled propagates as None: finish no-ops


def test_tracer_ring_buffer_is_bounded():
    tr = Tracer(capacity=8)
    for _ in range(50):
        t = tr.start(model="m")
        t.add("s", t.t_start, time.perf_counter())
        tr.finish(t)
    stats = tr.stats()
    assert stats == {"finished": 50, "buffered": 8, "capacity": 8}
    assert len(tr.recent()) == 8
    assert len(tr.recent(3)) == 3
    # oldest-first: the ring kept the LAST 8 trace ids
    assert [t.trace_id for t in tr.recent()] == list(range(43, 51))


def test_tracer_feeds_profiler_span_histograms():
    from triton_client_tpu.obs.profiling import StageProfiler

    p = StageProfiler()
    tr = Tracer(profiler=p)
    t = tr.start(model="m")
    t.add("device_execute", 1.0, 1.25)
    tr.finish(t)
    s = p.summary()["span_device_execute"]
    assert s["count"] == 1
    assert s["mean_ms"] == pytest.approx(250.0)


def test_chrome_trace_json_shape():
    tr = Tracer(capacity=4)
    for i in range(2):
        t = tr.start(model="m", request_id=f"r{i}")
        with t.span("stage"):
            time.sleep(0.001)
        tr.finish(t, status="ok")
    doc = json.loads(json.dumps(tr.chrome_trace()))  # round-trips
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    complete = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in meta} == {"process_name", "thread_name"}
    # one request parent per trace, plus its spans
    reqs = [e for e in complete if e["name"] == "request"]
    assert len(reqs) == 2
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in complete)
    # rebased: the earliest event sits at t=0
    assert min(e["ts"] for e in complete) == 0
    # spans land on their request's tid (row) with distinct tids
    assert len({e["tid"] for e in reqs}) == 2
    assert chrome_trace([]) == {"traceEvents": [], "displayTimeUnit": "ms"}


# -- collector ----------------------------------------------------------------


def test_collector_families_match_metric_types_and_stats():
    prometheus_client = pytest.importorskip("prometheus_client")
    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel

    repo, spec = _double_repo()
    chan = ContinuousBatchingChannel(TPUChannel(repo), max_batch=4)
    registry = prometheus_client.CollectorRegistry()
    collector = RuntimeCollector(channel=chan, registry=registry)
    try:
        x = np.ones((2, 4), np.float32)
        for _ in range(5):
            chan.do_inference(InferRequest(spec.name, {"x": x}))
        stats_chan = chan.inner.stats()
        stats_bat = chan.stats()
        fams = {f.name: f for f in collector.collect()}
        expected = {
            _family_attr_name(n, t): t for n, t in METRIC_TYPES.items()
        }
        # exactly the promised families (HBM only on devices that
        # report memory_stats, i.e. not the CPU backend this runs on)
        assert set(fams) - {"tpu_serving_device_hbm_bytes"} == set(expected)
        for name, typ in expected.items():
            assert fams[name].type == typ, name
        # counter values are the channel's own stats() numbers — the
        # scrape and the perf scripts read identical state
        def value(family_name):
            (sample,) = [
                s for f in [fams[family_name]] for s in f.samples
            ]
            return sample.value

        assert value("tpu_serving_launched_batches") == stats_chan["launched"]
        assert value("tpu_serving_staged_requests") == stats_chan["staged"]
        assert value("tpu_serving_batch_merges") == stats_bat["merges"]
        assert value("tpu_serving_batched_frames") == stats_bat["merged_frames"]
        assert (
            fams["tpu_serving_pipeline_depth"].samples[0].value
            == stats_chan["pipeline_depth"]
        )
        # the labelled occupancy family mirrors the dict counter
        occ = {
            s.labels["frames"]: s.value
            for s in fams["tpu_serving_merge_occupancy"].samples
        }
        assert occ == {
            str(k): v for k, v in stats_bat["merge_occupancy"].items()
        }
    finally:
        collector.close()
        chan.close()
    # close() unregistered the custom collector
    assert "tpu_serving" not in prometheus_client.generate_latest(
        registry
    ).decode()


def test_collector_request_plane_and_errors():
    collector = RuntimeCollector()
    collector.request_started()
    collector.request_started()
    collector.request_finished()
    collector.record_error("yolo", "NOT_FOUND")
    collector.record_error("yolo", "NOT_FOUND")
    collector.record_error("pp", "INTERNAL")
    snap = collector.snapshot()
    assert snap["inflight_requests"] == 1
    assert snap["errors"] == {"yolo|NOT_FOUND": 2, "pp|INTERNAL": 1}
    assert snap["channel"] is None and snap["batching"] is None


def test_collector_delta_diffs_recursively():
    old = {"a": 1, "b": {"c": 2.0, "d": 5}, "e": "str", "f": 7}
    new = {"a": 4, "b": {"c": 2.5, "d": 5}, "e": "str", "f": 7, "g": 2}
    d = RuntimeCollector.delta(new, old)
    # unchanged / non-numeric leaves drop out
    assert d == {"a": 3, "b": {"c": 0.5}, "g": 2}
    assert RuntimeCollector.delta(new, None) == {
        "a": 4, "b": {"c": 2.5, "d": 5}, "f": 7, "g": 2,
    }


# -- serving round trip -------------------------------------------------------


def test_server_round_trip_spans_nesting_and_coverage():
    pytest.importorskip("grpc")
    repo, spec = _double_repo()
    chan, server = _serving_stack(repo)
    try:
        served = _drive_clients(server, clients=4, rounds=3)
        traces = server.tracer.recent()
        assert len(traces) == served
        # every phase of the overlapped pipeline shows up
        names = {s.name for t in traces for s in t.spans}
        assert {
            "parse", "channel", "batch_queue", "stage", "launch",
            "device_execute", "readback", "encode",
        } <= names
        # acceptance: spans cover >=95% of request wall time
        cov = [t.span_coverage() for t in traces]
        assert sum(cov) / len(cov) >= 0.95, sorted(cov)[:3]
        assert min(cov) >= 0.80, sorted(cov)[:3]
        for t in traces:
            spans = {s.name: s for s in t.spans}
            ch = spans["channel"]
            # channel-stack spans nest inside the handler's wait; the
            # full pipeline is ordered queue -> stage -> launch ->
            # device -> readback
            for inner in ("batch_queue", "stage", "launch",
                          "device_execute", "readback"):
                assert ch.t0 <= spans[inner].t0
                assert spans[inner].t1 <= ch.t1 + 1e-6, inner
            assert spans["batch_queue"].t1 <= spans["stage"].t1
            assert spans["stage"].t0 <= spans["launch"].t0
            assert spans["launch"].t1 <= spans["device_execute"].t1
            assert spans["device_execute"].t1 <= spans["readback"].t1
            assert t.status == "ok"
            assert t.model == spec.name
    finally:
        server.stop()
        chan.close()


def test_failing_requests_are_measured_and_coded():
    """Satellite fix: the latency sample lands in a finally and the
    error counter carries the model + gRPC status code (failing
    requests used to vanish from the metrics entirely)."""
    import grpc

    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.grpc_channel import GRPCChannel

    repo, spec = _double_repo()
    chan, server = _serving_stack(repo)
    try:
        client = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=30.0)
        x = np.ones((2, 4), np.float32)
        client.do_inference(InferRequest(spec.name, {"x": x}))
        with pytest.raises(grpc.RpcError) as exc_info:
            client.do_inference(InferRequest("no_such_model", {"x": x}))
        assert exc_info.value.code() == grpc.StatusCode.NOT_FOUND
        client.close()
        snap = server.collector.snapshot()
        assert snap["errors"] == {"no_such_model|NOT_FOUND": 1}
        assert snap["inflight_requests"] == 0  # finally decremented
        summary = server.profiler.summary()
        assert summary["infer_no_such_model"]["count"] == 1
        assert summary[f"infer_{spec.name}"]["count"] == 1
        # the failed request's trace finished with the error status
        statuses = {t.status for t in server.tracer.recent()}
        assert statuses == {"ok", "NOT_FOUND"}
    finally:
        server.stop()
        chan.close()


def test_metrics_endpoint_smoke_every_family_typed():
    """Tier-1 smoke (satellite): boot the full server with an ephemeral
    telemetry port and assert every promised collector family is
    present and correctly typed on one scrape."""
    pytest.importorskip("prometheus_client")
    pytest.importorskip("grpc")
    repo, spec = _double_repo()
    chan, server = _serving_stack(repo)
    try:
        assert server.metrics_enabled
        assert server.metrics_port > 0
        _drive_clients(server, clients=2, rounds=2)
        base = f"http://127.0.0.1:{server.metrics_port}"
        body = urllib.request.urlopen(base + "/metrics", timeout=10).read()
        text = body.decode()
        for name, typ in METRIC_TYPES.items():
            # the text exposition keeps the _total suffix on counter
            # TYPE lines (the stripped name only exists on family.name)
            assert f"# TYPE {name} {typ}" in text, (name, typ)
        # the stage-histogram family carries both the per-model latency
        # and the span histograms under the same stage label
        assert (
            f'tpu_serving_stage_latency_seconds_count{{stage="infer_{spec.name}"}}'
            in text
        )
        assert 'stage="span_device_execute"' in text
        # /traces: valid Chrome-trace JSON over HTTP
        doc = json.load(urllib.request.urlopen(base + "/traces?n=2", timeout=10))
        reqs = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "request"
        ]
        assert len(reqs) == 2
        # /snapshot: the collector's structured read as JSON
        snap = json.load(urllib.request.urlopen(base + "/snapshot", timeout=10))
        assert snap["channel"]["launched"] >= 1
        assert snap["tracer"]["finished"] == 4
    finally:
        server.stop()
        chan.close()


def test_trace_dump_cli_writes_chrome_json(tmp_path, capsys):
    pytest.importorskip("grpc")
    from triton_client_tpu.cli.tools import trace_dump

    repo, spec = _double_repo()
    chan, server = _serving_stack(repo)
    try:
        _drive_clients(server, clients=2, rounds=2)
        out = tmp_path / "trace.json"
        trace_dump([
            "--url", f"http://127.0.0.1:{server.metrics_port}",
            "-o", str(out),
        ])
        doc = json.loads(out.read_text())
        reqs = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "request"
        ]
        assert len(reqs) == 4
        assert "wrote 4 request traces" in capsys.readouterr().err
    finally:
        server.stop()
        chan.close()


def test_tracing_disabled_leaves_serving_path_clean():
    """trace_capacity=0: requests carry trace=None end to end, /traces
    404s, but metrics still export."""
    pytest.importorskip("grpc")
    repo, spec = _double_repo()
    chan, server = _serving_stack(repo, trace_capacity=0)
    try:
        assert server.tracer is None
        _drive_clients(server, clients=1, rounds=2)
        base = f"http://127.0.0.1:{server.metrics_port}"
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(base + "/traces", timeout=10)
        assert err.value.code == 404
        text = urllib.request.urlopen(base + "/metrics", timeout=10).read().decode()
        assert "tpu_serving_launched_batches" in text
    finally:
        server.stop()
        chan.close()
