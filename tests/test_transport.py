"""Host transport: negotiation, UDS, region pool, stream groups.

The tentpole behind these tests: a
same-host client must not pay the protobuf serialize/frame/parse tax
per 786 KB frame. The pieces under test:

  * endpoint classification (channel/transport.py) — the one decision
    point shared by GRPCChannel, the router, loadgen, and `route`;
  * auto-negotiated shm with a generation-tagged region pool sized to
    pipeline_depth, so do_inference_async and infer_stream ride shm
    concurrently (the old single-region + lock serialized them);
  * the UDS listener (serve alongside TCP) and unix: dialing;
  * multi-frame stream groups: one ModelStreamInfer message carries G
    packed frames, the server fans them into the batcher individually;
  * bitwise parity: wire, shm, and grouped-stream answers must be the
    SAME BYTES — a transport is not allowed to change the math;
  * restart recovery via the shm_detach fault point;
  * compressed wire payloads (runtime/wire_encoding.py) for the
    remote path that cannot ride shm.
"""

import os
import threading

import numpy as np
import pytest

from triton_client_tpu.channel import transport as transports
from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.channel.grpc_channel import GRPCChannel
from triton_client_tpu.channel.kserve import codec, pb
from triton_client_tpu.channel.tpu_channel import TPUChannel
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.runtime import faults
from triton_client_tpu.runtime.repository import ModelRepository
from triton_client_tpu.runtime.server import InferenceServer


def _repo():
    """Two models: a 2D detector stand-in and a 3D pointcloud one, so
    parity covers both tensor ranks the paper's pipelines serve."""
    repo = ModelRepository()
    repo.register(
        ModelSpec(
            name="addone",
            version="1",
            platform="jax",
            inputs=(TensorSpec("x", (-1, 4), "FP32"),),
            outputs=(TensorSpec("y", (-1, 4), "FP32"),),
            max_batch_size=16,
        ),
        lambda inputs: {"y": np.asarray(inputs["x"]) + 1.0},
    )
    repo.register(
        ModelSpec(
            name="cube",
            version="1",
            platform="jax",
            inputs=(TensorSpec("pts", (-1, 5, 3), "FP32"),),
            outputs=(TensorSpec("out", (-1, 5, 3), "FP32"),),
            max_batch_size=16,
        ),
        lambda inputs: {"out": np.asarray(inputs["pts"]) * 2.0 - 1.0},
    )
    return repo


@pytest.fixture()
def server():
    repo = _repo()
    server = InferenceServer(
        repo,
        TPUChannel(repo),
        address="127.0.0.1:0",
        uds_address="auto",
        max_workers=8,
    )
    server.start()
    yield server
    server.stop()


class TestNegotiation:
    def test_classify(self):
        assert transports.classify("unix:/tmp/s.sock") == "uds"
        assert transports.classify("unix:///tmp/s.sock") == "uds"
        assert transports.classify("127.0.0.1:8001") == "local"
        assert transports.classify("127.8.3.1:8001") == "local"
        assert transports.classify("localhost:8001") == "local"
        assert transports.classify("[::1]:8001") == "local"
        assert transports.classify("dns:///svc.cluster:443") == "remote"
        assert transports.classify("10.0.0.5:8001") == "remote"
        assert transports.classify("tpu-host-3:8001") == "remote"

    def test_uds_path(self):
        assert transports.uds_path("unix:/a/b.sock") == "/a/b.sock"
        assert transports.uds_path("unix:///a/b.sock") == "/a/b.sock"
        with pytest.raises(ValueError):
            transports.uds_path("127.0.0.1:80")

    def test_negotiated_labels(self):
        assert transports.negotiated("unix:/s", True) == "uds+shm"
        assert transports.negotiated("unix:/s", False) == "uds"
        assert transports.negotiated("127.0.0.1:80", True) == "shm"
        assert transports.negotiated("10.0.0.5:80", False) == "grpc"

    def test_remote_endpoint_never_auto_shm(self):
        # constructor must not probe the network: remote targets
        # classify without dialing
        chan = GRPCChannel("203.0.113.9:8001", timeout_s=1.0)
        try:
            assert chan.transport == "grpc"
        finally:
            chan.close()


class TestParity:
    """Same input, same bytes out — across every transport."""

    CASES = [
        ("addone", "x", "y", (6, 4)),
        ("cube", "pts", "out", (4, 5, 3)),  # 3D pointcloud shape
    ]

    @pytest.mark.parametrize("model,xin,yout,shape", CASES)
    def test_wire_shm_stream_bitwise_identical(
        self, server, model, xin, yout, shape
    ):
        addr = f"127.0.0.1:{server.port}"
        x = (
            np.random.default_rng(7)
            .standard_normal(shape)
            .astype(np.float32)
        )
        req = InferRequest(model_name=model, inputs={xin: x})
        wire = GRPCChannel(addr, timeout_s=10.0, use_shared_memory=False)
        shm = GRPCChannel(addr, timeout_s=10.0, use_shared_memory=True)
        try:
            a = wire.do_inference(req).outputs[yout]
            # twice through shm: first request learns output sizes and
            # answers over the wire; the second rides the output arena
            shm.do_inference(req)
            b = shm.do_inference(req).outputs[yout]
            (c,) = [
                r.outputs[yout]
                for r in shm.infer_stream(iter([req]), stream_timeout_s=10.0)
            ]
            (d,) = [
                r.outputs[yout]
                for r in wire.infer_stream(
                    iter([req] * 4), stream_timeout_s=10.0, group_size=4
                )
            ][:1]
            assert a.tobytes() == b.tobytes()
            assert a.tobytes() == c.tobytes()
            assert a.tobytes() == d.tobytes()
            assert a.dtype == b.dtype == c.dtype == np.float32
        finally:
            shm.close()
            wire.close()

    def test_uds_parity(self, server):
        assert server.uds_address.startswith("unix:")
        chan = GRPCChannel(server.uds_address, timeout_s=10.0)
        x = np.random.default_rng(3).random((2, 4)).astype(np.float32)
        try:
            assert chan.transport == "uds+shm"
            out = chan.do_inference(
                InferRequest(model_name="addone", inputs={"x": x})
            ).outputs["y"]
            np.testing.assert_array_equal(out, x + 1.0)
        finally:
            chan.close()


class TestRegionPool:
    def test_concurrent_async_never_aliases(self, server):
        """8 threads racing do_inference_async over a depth-4 pool:
        every response must match ITS OWN input (an aliased region
        would cross-contaminate payloads) and the pool's alias counter
        must stay 0. Overflow beyond the pool depth rides the wire."""
        addr = f"127.0.0.1:{server.port}"
        chan = GRPCChannel(
            addr, timeout_s=30.0, use_shared_memory=True, pipeline_depth=4
        )
        failures: list = []

        def worker(tid: int):
            try:
                for i in range(6):
                    x = np.full((2, 4), float(tid * 100 + i), np.float32)
                    fut = chan.do_inference_async(
                        InferRequest(model_name="addone", inputs={"x": x})
                    )
                    got = fut.result().outputs["y"]
                    if not np.array_equal(got, x + 1.0):
                        failures.append((tid, i, got[0, 0]))
            except Exception as e:  # pragma: no cover - diagnostic
                failures.append((tid, repr(e)))

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(8)
        ]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
            assert not failures
            stats = chan.stats()["shm_pool"]
            assert stats["aliased"] == 0
            assert stats["max_in_flight"] <= 4
            assert stats["acquires"] > 0
        finally:
            chan.close()

    def test_pool_lifecycle_and_segment_cleanup(self, server):
        addr = f"127.0.0.1:{server.port}"
        chan = GRPCChannel(addr, timeout_s=10.0, use_shared_memory=True)
        x = np.ones((1, 4), np.float32)
        req = InferRequest(model_name="addone", inputs={"x": x})
        chan.do_inference(req)
        chan.do_inference(req)
        stats = chan.stats()["shm_pool"]
        assert stats["in_flight"] == 0
        assert stats["regions"] >= 1
        segs = [
            f
            for f in os.listdir("/dev/shm")
            if f.startswith(f"tct_{os.getpid()}_")
        ]
        assert segs  # live regions are backed by real segments
        chan.close()
        # close unregisters server-side AND unlinks every segment
        assert server.shm_registry.status() == {}
        segs = [
            f
            for f in os.listdir("/dev/shm")
            if f.startswith(f"tct_{os.getpid()}_")
        ]
        assert not segs


class TestStreamGroups:
    def test_group_responses_keep_request_ids(self, server):
        addr = f"127.0.0.1:{server.port}"
        chan = GRPCChannel(addr, timeout_s=10.0)
        reqs = [
            InferRequest(
                model_name="addone",
                inputs={"x": np.full((1, 4), float(i), np.float32)},
                request_id=f"r{i}",
            )
            for i in range(8)
        ]
        try:
            got = {}
            for resp in chan.infer_stream(
                iter(reqs), stream_timeout_s=10.0, group_size=4
            ):
                got[resp.request_id] = resp.outputs["y"]
            assert set(got) == {f"r{i}" for i in range(8)}
            for i in range(8):
                np.testing.assert_array_equal(
                    got[f"r{i}"], np.full((1, 4), float(i) + 1.0, np.float32)
                )
        finally:
            chan.close()

    def test_indivisible_group_is_member_safe_error(self, server):
        """A malformed group (leading dim not divisible by G) must fail
        the GROUP with the 'stream group failed:' prefix — a raw client
        speaking the group protocol can tell a group-level rejection
        from a member-level one."""
        import queue

        addr = f"127.0.0.1:{server.port}"
        chan = GRPCChannel(addr, timeout_s=10.0, use_shared_memory=False)
        wire = codec.build_infer_request(
            "addone", {"x": np.zeros((3, 4), np.float32)}
        )
        codec.set_request_params(wire, {codec.STREAM_GROUP_PARAM: 2})
        try:
            q: queue.Queue = queue.Queue()
            q.put(wire)
            q.put(None)
            call = chan._stub.ModelStreamInfer(
                iter(q.get, None), timeout=10.0
            )
            resp = next(iter(call))
            assert resp.error_message.startswith("stream group failed: ")
            assert "divisible" in resp.error_message
        finally:
            chan.close()

    def test_stream_group_metrics(self):
        repo = _repo()
        server = InferenceServer(
            repo,
            TPUChannel(repo),
            address="127.0.0.1:0",
            max_workers=4,
            metrics_port="auto",
        )
        server.start()
        chan = GRPCChannel(
            f"127.0.0.1:{server.port}", timeout_s=10.0,
            use_shared_memory=True,
        )
        try:
            reqs = [
                InferRequest(
                    model_name="addone",
                    inputs={"x": np.ones((1, 4), np.float32)},
                )
                for _ in range(4)
            ]
            list(chan.infer_stream(iter(reqs), group_size=4))
            chan.do_inference(reqs[0])
            snap = server.collector.snapshot()["transport"]
            assert snap["stream_groups"].get(4) == 1
            assert sum(snap["requests"].values()) >= 2
            assert any(
                k in snap["requests"] for k in ("shm", "uds+shm")
            )
            assert snap["shm_bytes"] > 0
        finally:
            chan.close()
            server.stop()


class TestRestartRecovery:
    def _plan(self, after: int):
        return faults.FaultPlan(
            rules=[
                {
                    "point": "shm_detach",
                    "model": "addone",
                    "after": after,
                    "count": 1,
                }
            ],
            seed=11,
        )

    def test_shm_detach_unary_recovers(self, server):
        prev = faults.install_fault_plan(self._plan(after=1))
        chan = GRPCChannel(
            f"127.0.0.1:{server.port}", timeout_s=10.0,
            use_shared_memory=True,
        )
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        req = InferRequest(model_name="addone", inputs={"x": x})
        try:
            np.testing.assert_array_equal(
                chan.do_inference(req).outputs["y"], x + 1.0
            )
            # second request trips the detach: server wipes its registry
            # before parse; the client re-registers and re-issues once
            np.testing.assert_array_equal(
                chan.do_inference(req).outputs["y"], x + 1.0
            )
            assert faults.active_plan().stats()["fired"] == 1
            assert len(server.shm_registry.status()) >= 1
        finally:
            faults.install_fault_plan(prev)
            chan.close()

    def test_shm_detach_mid_stream_recovers(self, server):
        """Server 'restart' mid-stream: the faulted message fails with
        'not registered'; the channel re-registers its pool and serves
        the affected members over unary, and the stream keeps going —
        every frame answered, every answer correct."""
        prev = faults.install_fault_plan(self._plan(after=2))
        chan = GRPCChannel(
            f"127.0.0.1:{server.port}", timeout_s=10.0,
            use_shared_memory=True,
        )
        reqs = [
            InferRequest(
                model_name="addone",
                inputs={"x": np.full((1, 4), float(i), np.float32)},
                request_id=f"s{i}",
            )
            for i in range(6)
        ]
        try:
            got = {
                r.request_id: r.outputs["y"]
                for r in chan.infer_stream(iter(reqs), stream_timeout_s=30.0)
            }
            assert set(got) == {f"s{i}" for i in range(6)}
            for i in range(6):
                np.testing.assert_array_equal(
                    got[f"s{i}"], np.full((1, 4), float(i) + 1.0, np.float32)
                )
            assert faults.active_plan().stats()["fired"] == 1
        finally:
            faults.install_fault_plan(prev)
            chan.close()


class TestWireEncoding:
    def test_quantize_roundtrip_q8_q16(self):
        from triton_client_tpu.runtime import wire_encoding as we

        rng = np.random.default_rng(5)
        arr = (rng.standard_normal((3, 50)) * 4.0).astype(np.float32)
        for bits, rtol in ((8, 1 / 255.0), (16, 1 / 65535.0)):
            payload, params = we.quantize(arr, bits=bits)
            assert payload.dtype == (np.uint8 if bits == 8 else np.uint16)
            info = {
                "encoding": params[we.ENCODING_PARAM],
                "scale": float(params[we.Q_SCALE_PARAM]),
                "min": float(params[we.Q_MIN_PARAM]),
                "dtype": params[we.Q_DTYPE_PARAM],
            }
            back = np.asarray(we.decode_one(payload, info))
            assert back.dtype == np.float32
            span = float(arr.max() - arr.min())
            np.testing.assert_allclose(back, arr, atol=span * rtol + 1e-7)

    def test_jpeg_roundtrip(self):
        from triton_client_tpu.runtime import wire_encoding as we

        if we._PILImage is None:
            pytest.skip("PIL not installed")
        img = np.full((32, 32, 3), 128, np.uint8)
        payload, params = we.encode_jpeg(img, quality=95)
        assert payload.ndim == 1 and payload.dtype == np.uint8
        assert payload.nbytes < img.nbytes  # it actually compressed
        back = we.decode_one(payload, {"encoding": "jpeg"})
        assert back.shape == img.shape
        assert int(np.abs(back.astype(int) - 128).max()) <= 3

    def test_encoded_inference_end_to_end(self, server):
        """content_encoding=q8 through the real wire: the server
        dequantizes on-device and serves the model on the decoded
        tensor — the remote-client path where shm is not an option."""
        from triton_client_tpu.runtime import wire_encoding as we

        x = np.linspace(-2.0, 2.0, 24, dtype=np.float32).reshape(6, 4)
        payload, params = we.quantize(x, bits=8)
        chan = GRPCChannel(
            f"127.0.0.1:{server.port}", timeout_s=10.0,
            use_shared_memory=False,
        )
        try:
            out = chan.do_inference(
                InferRequest(
                    model_name="addone",
                    inputs={"x": payload},
                    input_params={"x": params},
                )
            ).outputs["y"]
            span = float(x.max() - x.min())
            np.testing.assert_allclose(
                out, x + 1.0, atol=span / 255.0 + 1e-6
            )
        finally:
            chan.close()

    def test_malformed_quant_params_rejected(self):
        from triton_client_tpu.runtime import wire_encoding as we

        req = pb.ModelInferRequest(model_name="m")
        t = req.inputs.add(name="x", datatype="UINT8", shape=[4])
        t.parameters[we.ENCODING_PARAM].string_param = "q8"
        # no q_scale/q_min -> must be a clear ValueError, not a KeyError
        with pytest.raises(ValueError):
            we.encodings_of(req)


class TestLoadgenTransport:
    @pytest.mark.parametrize("mode,kw", [
        ("unary", {}),
        ("stream", {"inflight": 4, "stream_group": 4}),
    ])
    def test_run_pool_auto_negotiates(self, server, mode, kw):
        from triton_client_tpu.utils.loadgen import run_pool

        res = run_pool(
            f"127.0.0.1:{server.port}",
            "addone",
            {"x": np.ones((1, 4), np.float32)},
            clients=2,
            duration_s=0.4,
            deadline_s=15.0,
            stagger_s=0.0,
            mode=mode,
            **kw,
        )
        assert not res.errors
        assert res.served_frames > 0

    def test_router_snapshot_reports_transport(self, server):
        from triton_client_tpu.runtime.router import ReplicaSet

        rs = ReplicaSet(
            [f"127.0.0.1:{server.port}"], probe_interval_s=0.0
        )
        try:
            (snap,) = rs.snapshot()
            assert snap["transport"] == "shm"
        finally:
            rs.close()


class TestSequenceParams:
    """Streaming-session sequence parameters (ISSUE 15) ride the same
    request-parameter plumbing as ``priority``/``traceparent`` — and
    must survive every transport: wire, shm, uds, and grouped streams.
    The observation point is end-to-end: a SessionManager attached to
    the serving channel only opens/advances/closes a session when the
    decoded parameters say so."""

    DET_DIM = 11

    @pytest.fixture()
    def session_server(self):
        from triton_client_tpu.ops.tracking import TrackerConfig
        from triton_client_tpu.runtime.sessions import SessionManager

        repo = ModelRepository()
        repo.register(
            ModelSpec(
                name="echo",
                version="1",
                inputs=(
                    TensorSpec("detections", (-1, self.DET_DIM), "FP32"),
                    TensorSpec("valid", (-1,), "BOOL"),
                ),
                outputs=(
                    TensorSpec("detections", (-1, self.DET_DIM), "FP32"),
                    TensorSpec("valid", (-1,), "BOOL"),
                ),
            ),
            lambda inputs: {
                "detections": inputs["detections"],
                "valid": inputs["valid"],
            },
        )
        chan = TPUChannel(repo)
        manager = SessionManager(
            max_sessions=8, tracker=TrackerConfig(max_tracks=8)
        )
        chan.attach_sessions(manager)
        server = InferenceServer(
            repo, chan, address="127.0.0.1:0", uds_address="auto"
        )
        server.start()
        yield server, manager
        server.stop()

    def _frame(self):
        det = np.zeros((4, self.DET_DIM), np.float32)
        det[0, :2] = (1.0, 2.0)
        det[0, -2] = 0.9
        valid = np.zeros((4,), bool)
        valid[0] = True
        return {"detections": det, "valid": valid}

    def _reqs(self, sid, n=3):
        return [
            InferRequest(
                model_name="echo",
                inputs=self._frame(),
                sequence_id=sid,
                sequence_start=(k == 0),
                sequence_end=(k == n - 1),
                priority=1,  # parameter plane shared with sequences
            )
            for k in range(n)
        ]

    @pytest.mark.parametrize("transport", ["wire", "shm", "uds", "stream"])
    def test_sequence_round_trip_matrix(self, session_server, transport):
        server, manager = session_server
        addr = f"127.0.0.1:{server.port}"
        if transport == "wire":
            chan = GRPCChannel(addr, timeout_s=10.0,
                               use_shared_memory=False)
        elif transport == "shm":
            chan = GRPCChannel(addr, timeout_s=10.0, use_shared_memory=True)
        elif transport == "uds":
            chan = GRPCChannel(server.uds_address, timeout_s=10.0)
        else:
            chan = GRPCChannel(addr, timeout_s=10.0)
        sid = f"seq-{transport}"
        before = manager.stats()
        try:
            reqs = self._reqs(sid)
            if transport == "stream":
                resps = list(
                    chan.infer_stream(iter(reqs), stream_timeout_s=10.0)
                )
            else:
                resps = [chan.do_inference(r) for r in reqs]
        finally:
            chan.close()
        # sequence_id decoded on every frame: the tracker ran, and the
        # same session advanced each time (one stable track id)
        tids = [int(r.outputs["det_track_ids"][0]) for r in resps]
        assert len(resps) == 3
        assert tids[0] > 0 and len(set(tids)) == 1
        after = manager.stats()
        assert after["created_total"] == before["created_total"] + 1
        assert after["frames_total"] == before["frames_total"] + 3
        # sequence_end decoded: the slot closed with the stream
        assert after["ended_total"] == before["ended_total"] + 1
        assert after["active_sessions"] == 0

    def test_stateless_alongside_traced_request(self, session_server):
        # a request with NO sequence params but a trace + priority must
        # stay stateless: parameter planes do not bleed into each other
        from triton_client_tpu.obs.trace import RequestTrace

        server, manager = session_server
        chan = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=10.0)
        try:
            resp = chan.do_inference(
                InferRequest(
                    model_name="echo",
                    inputs=self._frame(),
                    priority=2,
                    trace=RequestTrace(7, model="echo"),
                )
            )
            assert "det_track_ids" not in resp.outputs
            assert manager.stats()["active_sessions"] == 0
        finally:
            chan.close()
