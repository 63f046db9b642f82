"""Fleet-wide distributed tracing + device-time attribution (ISSUE 11).

Covers the PR's acceptance contract:
  * ``TraceContext`` — W3C-traceparent-style encode/decode roundtrip,
    tolerant decode of garbage, child contexts share the trace id;
  * span summaries — encode/decode roundtrip and ``graft_span_summary``
    placing far-side spans onto the local clock with the NTP-midpoint
    wire split (``wire_send``/``wire_recv`` named spans);
  * ``DeviceTimeLedger`` — per-model×tenant device-seconds, rolling
    utilization, MFU from analytic flops vs the policy peak;
  * router tracing — the FrontDoorRouter originates (or forwards) a
    context, every attempt ships a distinct child context, attempts
    land as sibling spans tagged {attempt, endpoint, kind}, hedge
    losers are marked cancelled, and the winner's server summary is
    grafted exactly once (no device-time double-count);
  * the LIVE joined timeline — one request through a 2-replica fleet
    with a hedge produces a single trace whose spans cover >=95% of
    the client-observed wall, with wire/queue/device_execute
    separately attributed;
  * ledger-vs-histogram reconciliation within 5%, and nonzero
    ``tpu_serving_device_seconds_total`` / ``tpu_serving_mfu`` on a
    live scrape;
  * merged-batch members each get their own per-member spans sharing
    one device_execute window;
  * the ``/profile`` capture guard (409 on overlap) and the
    ``trace-join`` CLI;
  * trace propagation stays ~free (sub-2ms per request against the
    untraced router on the same fake fleet).
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from triton_client_tpu.channel.base import InferRequest, InferResponse
from triton_client_tpu.obs.device_time import (
    DeviceTimeLedger,
)
from triton_client_tpu.obs.roofline import V5E, peak_flops
from triton_client_tpu.obs.trace import (
    SUMMARY_PARAM_KEY,
    RequestTrace,
    TraceContext,
    Tracer,
    decode_span_summary,
    encode_span_summary,
    graft_span_summary,
)
from triton_client_tpu.runtime.router import FrontDoorRouter

jax = pytest.importorskip("jax")

X = np.arange(8, dtype=np.float32).reshape(2, 4)

#: analytic flops-per-call stamped on the test model so live MFU reports
FLOPS_PER_CALL = 2.5e9


# -- helpers (mirroring test_router's live rig) -------------------------------


def _repo(name="double", sleep_s=0.0, flops=FLOPS_PER_CALL):
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository

    spec = ModelSpec(
        name=name,
        version="1",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
        extra={"flops_per_call": flops, "precision": "bf16"},
    )

    def infer(inputs):
        if sleep_s:
            time.sleep(sleep_s)
        return {"y": np.asarray(inputs["x"]) * 2.0}

    repo = ModelRepository()
    repo.register(spec, infer)
    return repo, spec


def _stack(repo, **server_kw):
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


def _ok_response(request):
    return InferResponse(
        model_name=request.model_name,
        model_version="1",
        outputs={"y": np.asarray(request.inputs["x"]) * 2.0},
        request_id=request.request_id,
    )


class _FakeChannel:
    def __init__(self, endpoint, script):
        self.endpoint = endpoint
        self.script = script

    def do_inference_async(self, request):
        from triton_client_tpu.channel.base import InferFuture

        return InferFuture(lambda: self.script(self.endpoint, request))

    def server_ready(self, timeout_s=None):
        return True

    def model_ready(self, model_name, model_version="", timeout_s=None):
        return True

    def close(self):
        pass


def _router(endpoints, script, **kw):
    kw.setdefault("probe_interval_s", 0.0)
    return FrontDoorRouter(
        list(endpoints),
        channel_factory=lambda ep: _FakeChannel(ep, script),
        **kw,
    )


def _spans(trace, name):
    return [s for s in trace.spans if s.name == name]


# -- TraceContext -------------------------------------------------------------


class TestTraceContext:
    def test_roundtrip(self):
        ctx = TraceContext.new()
        back = TraceContext.decode(ctx.encode())
        assert back.trace_id == ctx.trace_id
        assert back.parent_span_id == ctx.parent_span_id
        assert back.sampled is True
        off = TraceContext("a" * 32, "b" * 16, sampled=False)
        assert TraceContext.decode(off.encode()).sampled is False

    def test_tolerant_decode(self):
        for garbage in ("", "nope", "00-only-two", "00---01", None, 42):
            assert TraceContext.decode(garbage) is None

    def test_child_shares_trace_id_fresh_span(self):
        ctx = TraceContext.new()
        kids = {ctx.child().parent_span_id for _ in range(8)}
        assert len(kids) == 8  # every attempt distinguishable
        assert all(
            ctx.child().trace_id == ctx.trace_id for _ in range(3)
        )


# -- span summaries + grafting ------------------------------------------------


class TestSpanSummary:
    def test_encode_decode_roundtrip(self):
        tr = RequestTrace(1, model="m", context=TraceContext.new())
        t0 = tr.t_start
        tr.add("queue", t0 + 0.001, t0 + 0.004)
        tr.add("device_execute", t0 + 0.004, t0 + 0.014)
        doc = decode_span_summary(encode_span_summary(tr))
        assert doc["st"] == "ok"
        names = [row[0] for row in doc["s"]]
        assert names == ["queue", "device_execute"]
        # μs-relative with μs durations
        assert doc["s"][1][2] == pytest.approx(10000, abs=500)
        assert doc["ctx"] == tr.context.encode()

    def test_decode_rejects_garbage(self):
        assert decode_span_summary("") is None
        assert decode_span_summary("{not json") is None
        assert decode_span_summary('{"x": 1}') is None

    def test_graft_places_spans_and_wire_residue(self):
        local = RequestTrace(2, model="m")
        # server: 100 ms of wall, one 40 ms device span 20 ms in;
        # observed locally as a 160 ms RPC -> 60 ms residue, 30 ms
        # one-way (the NTP midpoint split)
        summary = {
            "w": 100_000, "st": "ok",
            "s": [["device_execute", 20_000, 40_000]],
        }
        t_sent = local.t_start + 0.01
        t_recv = t_sent + 0.16
        graft_span_summary(
            local, summary, t_sent, t_recv, attrs={"attempt": 0}
        )
        (send,) = _spans(local, "wire_send")
        (recv,) = _spans(local, "wire_recv")
        (dev,) = _spans(local, "srv.device_execute")
        assert send.duration_s == pytest.approx(0.03, abs=1e-6)
        assert recv.duration_s == pytest.approx(0.03, abs=1e-6)
        assert dev.t0 == pytest.approx(t_sent + 0.03 + 0.02, abs=1e-6)
        assert dev.duration_s == pytest.approx(0.04, abs=1e-6)
        assert dev.attrs == {"attempt": 0}
        # everything lands inside the observed RPC window
        for s in local.spans:
            assert t_sent - 1e-9 <= s.t0 and s.t1 <= t_recv + 1e-9


# -- DeviceTimeLedger ---------------------------------------------------------


class TestDeviceTimeLedger:
    def test_accounts_device_seconds_by_model_and_tenant(self):
        class Tenants:
            def tenant_of(self, model):
                return {"a": "team1"}.get(model)

        led = DeviceTimeLedger(tenants=Tenants(), devices=2)
        led.record("a", 0.05)
        led.record("a", 0.07)
        led.record("b", 0.10)
        secs = led.device_seconds()
        assert secs["a|team1"] == pytest.approx(0.12)
        assert secs["b|default"] == pytest.approx(0.10)
        snap = led.snapshot()
        assert snap["devices"] == 2
        assert snap["launches"] == {"a": 2, "b": 1}
        assert snap["total_device_seconds"] == pytest.approx(0.22)
        assert 0.0 < snap["window"]["utilization"] <= 1.0

    def test_mfu_from_flops_metadata(self):
        led = DeviceTimeLedger(window_s=60.0, device_kind=V5E)
        extra = {"flops_per_call": 1e12, "precision": "int8"}
        for _ in range(4):
            led.record("m", 0.01, extra)
        mfu = led.mfu()
        assert "m" in mfu and mfu["m"] > 0.0
        # flops/elapsed vs the int8 policy peak: doubling the recorded
        # flops (same wall) ~doubles the reported MFU
        before = mfu["m"]
        for _ in range(4):
            led.record("m", 0.01, extra)
        assert led.mfu()["m"] > before
        assert peak_flops("int8", V5E) == 2 * peak_flops("bf16", V5E)
        # a device with no listed peak accounts seconds, reports no MFU
        unlisted = DeviceTimeLedger(window_s=60.0, device_kind="cpu")
        unlisted.record("m", 0.01, extra)
        assert unlisted.mfu() == {}
        assert unlisted.snapshot()["window"]["mfu"] == {}
        # models without metadata still account seconds, no MFU row
        led.record("bare", 0.01)
        assert "bare" not in led.mfu()
        assert led.device_seconds()["bare|default"] == pytest.approx(0.01)

    def test_negative_duration_clamped(self):
        led = DeviceTimeLedger()
        led.record("m", -1.0)
        assert led.device_seconds()["m|default"] == 0.0


# -- router tracing (deterministic fake fleet) --------------------------------


class TestRouterTracing:
    def test_originates_context_and_attempt_span(self):
        tracer = Tracer(capacity=8)
        seen = []

        def script(ep, request):
            seen.append(request.trace.context.encode())
            return _ok_response(request)

        r = _router(["r0", "r1"], script, tracer=tracer)
        try:
            r.do_inference(InferRequest("m", {"x": X}, request_id="q1"))
        finally:
            r.close()
        (tr,) = tracer.recent()
        assert tr.status == "ok" and tr.request_id == "q1"
        assert tr.context is not None
        # the attempt shipped a CHILD of the router's context
        shipped = TraceContext.decode(seen[0])
        assert shipped.trace_id == tr.context.trace_id
        assert shipped.parent_span_id != tr.context.parent_span_id
        (att,) = _spans(tr, "attempt")
        assert att.attrs["attempt"] == 0
        assert att.attrs["kind"] == "primary"
        assert att.attrs["endpoint"] in ("r0", "r1")
        assert _spans(tr, "route")  # the routing wall itself is a span

    def test_forwards_inbound_context(self):
        tracer = Tracer(capacity=8)
        inbound = TraceContext.new()
        r = _router(["r0"], lambda ep, req: _ok_response(req), tracer=tracer)
        try:
            carrier = RequestTrace(1, context=inbound)
            r.do_inference(InferRequest("m", {"x": X}, trace=carrier))
        finally:
            r.close()
        (tr,) = tracer.recent()
        assert tr.context.trace_id == inbound.trace_id
        assert tr.context.parent_span_id != inbound.parent_span_id

    def test_grafts_server_summary_once(self):
        tracer = Tracer(capacity=8)
        summary = json.dumps(
            {"w": 30000, "st": "ok", "s": [["device_execute", 10000, 10000]]}
        )

        def script(ep, request):
            resp = _ok_response(request)
            resp.parameters = {SUMMARY_PARAM_KEY: summary}
            return resp

        r = _router(["r0", "r1"], script, tracer=tracer)
        try:
            r.do_inference(InferRequest("m", {"x": X}))
        finally:
            r.close()
        (tr,) = tracer.recent()
        (dev,) = _spans(tr, "srv.device_execute")  # grafted exactly once
        assert dev.duration_s == pytest.approx(0.01, abs=1e-6)
        assert dev.attrs["kind"] == "primary"

    def test_retry_attempts_are_sibling_spans(self):
        from tests.test_router import _FakeRpcError

        tracer = Tracer(capacity=8)
        shipped = []

        def script(ep, request):
            # whichever replica the primary lands on fails once; the
            # failover retry (either endpoint) succeeds
            shipped.append(request.trace.context.encode())
            if len(shipped) == 1:
                raise _FakeRpcError("UNAVAILABLE")
            return _ok_response(request)

        r = _router(["r0", "r1"], script, tracer=tracer)
        try:
            r.do_inference(InferRequest("m", {"x": X}))
        finally:
            r.close()
        (tr,) = tracer.recent()
        atts = sorted(_spans(tr, "attempt"), key=lambda s: s.attrs["attempt"])
        assert [a.attrs["kind"] for a in atts] == ["primary", "retry"]
        assert atts[0].attrs["error"] == "UNAVAILABLE"
        assert "error" not in atts[1].attrs
        # both attempts shipped distinct child contexts of ONE trace
        a, b = (TraceContext.decode(s) for s in shipped)
        assert a.trace_id == b.trace_id == tr.context.trace_id
        assert a.parent_span_id != b.parent_span_id

    def test_error_finishes_trace_with_status(self):
        from tests.test_router import _FakeRpcError

        tracer = Tracer(capacity=8)

        def script(ep, request):
            raise _FakeRpcError("RESOURCE_EXHAUSTED", "shed")

        r = _router(["r0", "r1"], script, tracer=tracer)
        try:
            with pytest.raises(Exception):
                r.do_inference(InferRequest("m", {"x": X}))
        finally:
            r.close()
        (tr,) = tracer.recent()
        assert tr.status == "RESOURCE_EXHAUSTED"

    def test_propagation_is_effectively_free(self):
        """Acceptance: trace propagation adds ~0% measurable cost. On a
        fake fleet whose RPC is microseconds, the traced router must
        stay within 2 ms/request of the untraced one — at ~100 ms e2e
        latencies that bounds the tax at <2%,
        and the real tax (a uuid, a dict, a few spans) is microseconds."""
        script = lambda ep, req: _ok_response(req)  # noqa: E731
        n = 50

        def drive(router):
            t0 = time.perf_counter()
            for _ in range(n):
                router.do_inference(InferRequest("m", {"x": X}))
            return time.perf_counter() - t0

        # the smallest of several alternated repeats of each loop: what
        # the loop costs, not what five other xdist workers took from it
        plain = _router(["r0", "r1"], script)
        traced = _router(["r0", "r1"], script, tracer=Tracer(capacity=256))
        try:
            pairs = [(drive(plain), drive(traced)) for _ in range(5)]
        finally:
            plain.close()
            traced.close()
        t_plain = min(p for p, _ in pairs)
        t_traced = min(t for _, t in pairs)
        assert (t_traced - t_plain) / n < 0.002


# -- live acceptance: joined timeline over a 2-replica fleet ------------------


@pytest.mark.slow
class TestLiveJoinedTrace:
    def test_hedged_request_produces_one_joined_timeline(self):
        repo, _ = _repo(sleep_s=0.15)
        stacks = [_stack(repo) for _ in range(2)]
        endpoints = [f"127.0.0.1:{s.port}" for _c, s in stacks]
        tracer = Tracer(capacity=16)
        router = FrontDoorRouter(
            endpoints, probe_interval_s=0.0, hedge_min_samples=10,
            hedge_budget_fraction=1.0, tracer=tracer,
        )
        try:
            for _ in range(20):  # prime the hedge trigger far below
                router._latency.observe(0.01)  # the 0.15 s service time
            t0 = time.perf_counter()
            resp = router.do_inference(
                InferRequest("double", {"x": X}, request_id="joined-1")
            )
            wall = time.perf_counter() - t0
            np.testing.assert_allclose(resp.outputs["y"], X * 2.0)
            assert router.stats()["hedges_launched"] == 1

            (tr,) = tracer.recent()
            names = {s.name for s in tr.spans}
            # one joined timeline: local routing + wire + the replica's
            # queue/device phases, all on the router's clock
            assert "route" in names
            assert "wire_send" in names and "wire_recv" in names
            assert "srv.device_execute" in names
            assert any(n.startswith("srv.batch") for n in names)
            # the winner's summary grafted ONCE: device time is not
            # double-counted even though two replicas ran the request
            assert len(_spans(tr, "srv.device_execute")) == 1
            # hedged duplicates are sibling spans; the loser is marked
            atts = sorted(
                _spans(tr, "attempt"), key=lambda s: s.attrs["attempt"]
            )
            assert [a.attrs["kind"] for a in atts] == ["primary", "hedge"]
            assert len({a.attrs["endpoint"] for a in atts}) == 2
            cancelled = [a for a in atts if a.attrs.get("cancelled")]
            assert len(cancelled) == 1
            # spans cover >=95% of the client-observed wall
            assert tr.span_coverage() >= 0.95
            assert tr.wall_s() >= 0.95 * wall - 0.01
            # the Chrome export carries the fleet context + attempt tags
            doc = tracer.chrome_trace()
            req_ev = [
                e for e in doc["traceEvents"]
                if e.get("ph") == "X" and e.get("name") == "request"
            ]
            assert req_ev and "traceparent" in req_ev[0]["args"]
        finally:
            router.close()
            for _c, server in stacks:
                server.stop()

    def test_ledger_reconciles_and_metrics_scrape_nonzero(self, monkeypatch):
        import jax

        from triton_client_tpu.channel.grpc_channel import GRPCChannel
        from triton_client_tpu.obs.roofline import DEVICE_PEAKS

        # the server's ledger reads the LIVE device's peak, and this
        # CPU has none (no MFU gauge): list it with v5e's numbers
        monkeypatch.setitem(
            DEVICE_PEAKS, jax.devices()[0].device_kind, DEVICE_PEAKS[V5E]
        )

        repo, _ = _repo(sleep_s=0.0)
        chan, server = _stack(repo)
        try:
            client = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=30.0)
            try:
                for i in range(8):  # sequential: every launch is solo,
                    client.do_inference(  # ledger/histogram stay 1:1
                        InferRequest("double", {"x": X}, request_id=f"r{i}")
                    )
            finally:
                client.close()

            # ledger totals vs the device_execute span histogram: the
            # SAME (t_launched, t_ready) window feeds both, so they
            # reconcile well inside the 5% acceptance tolerance
            snap = server.device_time.snapshot()
            assert snap["launches"].get("double", 0) >= 8
            ledger_s = snap["total_device_seconds"]
            prof = server.profiler.summary()["span_device_execute"]
            hist_s = prof["count"] * prof["mean_ms"] / 1e3
            assert ledger_s > 0
            assert abs(ledger_s - hist_s) / hist_s <= 0.05

            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.metrics_port}/metrics",
                timeout=10.0,
            ).read().decode()
            line = next(
                ln for ln in body.splitlines()
                if ln.startswith("tpu_serving_device_seconds_total{")
            )
            assert 'model="double"' in line and 'tenant="default"' in line
            assert float(line.rsplit(" ", 1)[1]) > 0.0
            mfu_line = next(
                ln for ln in body.splitlines()
                if ln.startswith("tpu_serving_mfu{")
            )
            assert float(mfu_line.rsplit(" ", 1)[1]) > 0.0

            dt = json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.metrics_port}/snapshot",
                    timeout=10.0,
                ).read()
            )["device_time"]
            assert dt["total_device_seconds"] > 0
        finally:
            server.stop()


# -- merged-batch member spans ------------------------------------------------


def test_merged_batch_members_get_per_member_spans():
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel

    repo, _ = _repo()
    import concurrent.futures

    chan = ContinuousBatchingChannel(
        TPUChannel(repo), max_batch=4, pipeline_depth=1
    )
    ledger = DeviceTimeLedger()
    chan.inner.attach_device_time(ledger)
    traces = [RequestTrace(i + 1, model="double") for i in range(2)]
    futures = [concurrent.futures.Future() for _ in traces]
    try:
        # the group the dispatcher forms of two staged members, handed
        # over directly: which arrivals share a slot is timing, what a
        # formed group records is not
        t_staged = time.perf_counter()
        chan._run_group(
            [
                (t_staged, InferRequest("double", {"x": X}, trace=tr), fut)
                for tr, fut in zip(traces, futures)
            ]
        )
        outs = [fut.result(timeout=60.0) for fut in futures]
    finally:
        chan.close()
    for i in range(2):
        np.testing.assert_allclose(outs[i].outputs["y"], X * 2.0)
    devs = [_spans(tr, "device_execute") for tr in traces]
    assert all(len(d) == 1 for d in devs)
    # the members rode ONE device call: identical shared window...
    assert devs[0][0].t0 == devs[1][0].t0
    assert devs[0][0].t1 == devs[1][0].t1
    # ...but each member keeps its OWN queue-side spans
    for tr in traces:
        assert len(_spans(tr, "merge_wait")) == 1
        assert len(_spans(tr, "batch_merge")) == 1
    # and the ledger accounted the shared window ONCE, not per member
    assert ledger.snapshot()["launches"]["double"] == 1


# -- /profile capture guard ---------------------------------------------------


@pytest.mark.slow
def test_profile_endpoint_guards_concurrent_capture():
    repo, _ = _repo()
    _chan, server = _stack(repo)
    base = f"http://127.0.0.1:{server.metrics_port}/profile"
    try:
        results = {}

        def long_capture():
            try:
                with urllib.request.urlopen(
                    f"{base}?seconds=0.8", timeout=30.0
                ) as resp:
                    results["first"] = (resp.status, json.load(resp))
            except urllib.error.HTTPError as e:
                results["first"] = (e.code, None)

        t = threading.Thread(target=long_capture)
        t.start()
        time.sleep(0.25)  # the first capture is mid-window
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}?seconds=0.05", timeout=10.0)
        assert exc.value.code == 409
        t.join()
        status, doc = results["first"]
        assert status == 200
        assert doc["log_dir"] and doc["seconds"] == pytest.approx(0.8)
        # malformed window -> 400, not a capture
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}?seconds=nope", timeout=10.0)
        assert exc.value.code == 400
    finally:
        server.stop()


# -- trace-join CLI -----------------------------------------------------------


def test_trace_join_merges_files_onto_one_timeline(tmp_path, capsys):
    from triton_client_tpu.cli.tools import trace_join

    def dump(path, label, ts):
        doc = {
            "traceEvents": [
                {
                    "ph": "M", "name": "process_name", "pid": 1, "tid": 0,
                    "args": {"name": "tpu_serving"},
                },
                {
                    "ph": "X", "name": "request", "pid": 1, "tid": 7,
                    "ts": ts, "dur": 50.0, "args": {"label": label},
                },
            ],
            "displayTimeUnit": "ms",
        }
        path.write_text(json.dumps(doc))

    a, b = tmp_path / "router.json", tmp_path / "replica.json"
    dump(a, "router", 0.0)
    dump(b, "replica", 10.0)
    out = tmp_path / "joined.json"
    trace_join(
        [str(a), f"replica={b}", "--offset", "replica=1500", "-o", str(out)]
    )
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    pids = {e["pid"] for e in events}
    assert pids == {1, 2}  # one process row per source
    names = {
        e["pid"]: e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e["name"] == "process_name"
    }
    assert names == {1: "router", 2: "replica"}
    reqs = {
        e["args"]["label"]: e for e in events if e.get("name") == "request"
    }
    assert reqs["router"]["ts"] == 0.0
    assert reqs["replica"]["ts"] == pytest.approx(1510.0)  # 10 + offset
