"""KServe v2 gRPC façade: codec round-trips and a live loopback server.

The reference's transport is tritonclient gRPC against a remote Triton
(communicator/channel/grpc_channel.py); here the same protocol is
served in-tree (runtime/server.py) and consumed by GRPCChannel, so the
test drives a real localhost RPC round-trip over the registered model.
"""

import numpy as np
import pytest

from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.channel.grpc_channel import GRPCChannel
from triton_client_tpu.channel.kserve import codec, pb
from triton_client_tpu.channel.tpu_channel import TPUChannel
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.runtime.repository import ModelRepository
from triton_client_tpu.runtime.server import InferenceServer, message_limit


def _spec():
    return ModelSpec(
        name="addone",
        version="1",
        platform="jax",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
        max_batch_size=8,
    )


def _repo():
    repo = ModelRepository()
    repo.register(_spec(), lambda inputs: {"y": np.asarray(inputs["x"]) + 1.0})
    return repo


class TestCodec:
    def test_roundtrip_dtypes(self, rng):
        for dtype in [np.float32, np.float16, np.int32, np.int64, np.uint8]:
            arr = rng.normal(0, 10, (3, 5)).astype(dtype)
            raw = codec.serialize_tensor(arr)
            back = codec.deserialize_tensor(raw, codec.datatype_of(arr), arr.shape)
            np.testing.assert_array_equal(arr, back)

    def test_request_roundtrip(self, rng):
        inputs = {
            "images": rng.random((2, 8, 8, 3)).astype(np.float32),
            "count": np.array([7], np.int32),
        }
        req = codec.build_infer_request("m", inputs, request_id="42")
        wire = pb.ModelInferRequest.FromString(req.SerializeToString())
        parsed = codec.parse_infer_request(wire)
        assert set(parsed) == set(inputs)
        for k in inputs:
            np.testing.assert_array_equal(parsed[k], inputs[k])

    def test_zero_copy_deserialize(self):
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        back = codec.deserialize_tensor(arr.tobytes(), "FP32", (3, 4))
        assert not back.flags.writeable  # view over the wire buffer

    def test_roundtrip_matrix_every_config_dtype(self, rng):
        """Every entry in the canonical dtype table round-trips bitwise
        — including the precision-policy wire dtypes: BF16 (ml_dtypes;
        the bf16 policy's wire words) and INT8 (the int8 policy's
        quantized activations) — and the deserialize side stays a
        zero-copy view over the wire buffer."""
        import ml_dtypes

        from triton_client_tpu.config import config_dtypes

        for datatype, np_dtype in config_dtypes().items():
            dtype = (
                np.dtype(ml_dtypes.bfloat16)
                if np_dtype is None  # the BF16 entry
                else np.dtype(np_dtype)
            )
            if dtype == np.bool_:
                arr = rng.random((3, 5)) > 0.5
            elif np.issubdtype(dtype, np.floating) or np_dtype is None:
                arr = rng.normal(0, 10, (3, 5)).astype(dtype)
            else:
                info = np.iinfo(dtype)
                arr = rng.integers(
                    max(info.min, -100), min(info.max, 100) + 1, (3, 5)
                ).astype(dtype)
            assert codec.datatype_of(arr) == datatype
            raw = codec.serialize_tensor(arr)
            assert len(raw) == arr.nbytes
            back = codec.deserialize_tensor(raw, datatype, arr.shape)
            assert back.dtype == dtype
            np.testing.assert_array_equal(
                back.view(np.uint8), arr.view(np.uint8)
            )
            # np.frombuffer view over the wire bytes, never a copy:
            # read-only, backed by the buffer object itself
            assert not back.flags.writeable, datatype
            assert back.base is not None, datatype
            assert np.shares_memory(
                back, np.frombuffer(raw, np.uint8)
            ), datatype

    def test_mismatched_raw_buffers_rejected(self):
        req = pb.ModelInferRequest(model_name="m")
        req.inputs.add(name="x", datatype="FP32", shape=[1])
        with pytest.raises(ValueError):
            codec.parse_infer_request(req)


class TestLoopbackServer:
    @pytest.fixture()
    def server_and_channel(self):
        repo = _repo()
        server = InferenceServer(
            repo, TPUChannel(repo), address="127.0.0.1:0", max_workers=2
        )
        server.start()
        channel = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=10.0)
        yield server, channel
        channel.close()
        server.stop()

    def test_health_and_metadata(self, server_and_channel):
        _, channel = server_and_channel
        assert channel.server_live()
        spec = channel.get_metadata("addone")
        assert spec.name == "addone"
        assert [t.name for t in spec.inputs] == ["x"]
        assert spec.inputs[0].dtype == "FP32"
        assert spec.max_batch_size == 8

    def test_infer_roundtrip(self, server_and_channel, rng):
        _, channel = server_and_channel
        x = rng.random((2, 4)).astype(np.float32)
        resp = channel.do_inference(
            InferRequest(model_name="addone", inputs={"x": x}, request_id="7")
        )
        np.testing.assert_allclose(resp.outputs["y"], x + 1.0, rtol=1e-6)
        assert resp.request_id == "7"

    def test_infer_unknown_model_raises(self, server_and_channel):
        import grpc

        _, channel = server_and_channel
        with pytest.raises(grpc.RpcError):
            channel.do_inference(
                InferRequest(
                    model_name="nope", inputs={"x": np.zeros((1, 4), np.float32)}
                )
            )

    def test_streaming(self, server_and_channel, rng):
        _, channel = server_and_channel
        frames = [rng.random((1, 4)).astype(np.float32) for _ in range(3)]
        reqs = (
            InferRequest(model_name="addone", inputs={"x": f}, request_id=str(i))
            for i, f in enumerate(frames)
        )
        outs = list(channel.infer_stream(reqs))
        assert len(outs) == 3
        for i, (frame, out) in enumerate(zip(frames, outs)):
            np.testing.assert_allclose(out.outputs["y"], frame + 1.0, rtol=1e-6)
            assert out.request_id == str(i)


def test_message_limit_scales_with_specs():
    repo = _repo()
    assert message_limit(repo) >= 64 << 20
    big = ModelSpec(
        name="big",
        inputs=(TensorSpec("x", (3, 2048, 2048), "FP32"),),
        outputs=(TensorSpec("y", (3, 2048, 2048), "FP32"),),
        max_batch_size=4,
    )
    repo.register(big, lambda i: i)
    assert message_limit(repo) >= 2 * 2 * 4 * 3 * 2048 * 2048 * 4


# -- what a connection and a request's tensor descriptors fix, resolved once --


class _Aborted(Exception):
    def __init__(self, code, details):
        self.code = code
        super().__init__(details)


class _Ctx:
    """A servicer context that counts its ``peer()`` calls."""

    def __init__(self, peer="ipv4:127.0.0.1:40000"):
        self._peer = peer
        self.peer_calls = 0

    def peer(self):
        self.peer_calls += 1
        return self._peer

    def abort(self, code, details):
        raise _Aborted(code, details)


def _rows_repo():
    """``rows``: answers ``[4, 4]`` where the input's first element is
    100 or more, else ``[1, 4]``: one set of request descriptors whose
    answer changes shape."""
    spec = ModelSpec(
        name="rows", version="1", platform="jax",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
        max_batch_size=8,
    )
    repo = _repo()
    repo.register(
        spec,
        lambda inputs: {
            "y": np.repeat(
                np.asarray(inputs["x"]) + 1.0,
                4 if float(np.asarray(inputs["x"])[0, 0]) >= 100.0 else 1,
                axis=0,
            )
        },
    )
    return repo


class _Windows:
    """A client's shm regions beside a servicer of its own registry: an
    input window and an answer window a ``session``."""

    def __init__(self, sessions=("a", "b"), collector=None):
        import os

        from triton_client_tpu.runtime.server import _Servicer
        from triton_client_tpu.runtime.shared_memory import (
            SharedMemoryRegion,
            SystemSharedMemoryRegistry,
        )

        repo = _rows_repo()
        self.registry = SystemSharedMemoryRegistry()
        self.servicer = _Servicer(
            repo, TPUChannel(repo), shm_registry=self.registry,
            collector=collector,
        )
        self.regions = {}
        for s in sessions:
            for kind, size in (("in", 16), ("out", 64)):
                key = f"/tct_front_{os.getpid()}_{id(self)}_{s}_{kind}"
                region = SharedMemoryRegion.create(key, size)
                self.regions[s, kind] = region
                self.registry.register(f"{s}_{kind}", key, 0, size)

    def request(self, session, value, request_id, model="rows", shm=True):
        x = np.full((1, 4), value, np.float32)
        if not shm:
            return codec.build_infer_request(model, {"x": x}, request_id=request_id)
        self.regions[session, "in"].write(x)
        req = codec.build_infer_request_shm(
            model, {"x": x}, {"x": (f"{session}_in", 0, 16)}, request_id=request_id
        )
        codec.add_requested_output(req, "y", f"{session}_out", 0, 64)
        return req

    def answer(self, session, resp):
        """The answer's array, from the window it says it is in."""
        (out,) = resp.outputs
        region, offset, size = codec.shm_params(out)
        assert region == f"{session}_out"
        return np.frombuffer(
            bytes(self.regions[session, "out"].read(offset, size)), np.float32
        ).reshape(tuple(out.shape))

    def close(self):
        self.registry.unregister_all()
        for region in self.regions.values():
            region.close()


@pytest.fixture()
def windows():
    w = _Windows()
    yield w
    w.close()


class TestFrontMemo:
    @pytest.mark.parametrize("shm", [False, True], ids=["wire", "shm"])
    def test_one_peer_call_a_request(self, windows, shm):
        ctx = _Ctx()
        for i in range(5):
            resp = windows.servicer.ModelInfer(
                windows.request("a", float(i), str(i), shm=shm), ctx
            )
            assert resp.id == str(i)
            assert ctx.peer_calls == i + 1
        stats = windows.servicer.front_stats()
        assert stats["handler_requests"] == 5
        assert stats["handler_cpu_s"] > 0.0
        assert (stats["front_memo_hits"], stats["front_memo_misses"]) == (4, 1)

    @pytest.mark.parametrize("rpc", ["unary", "stream"])
    def test_remote_shm_request_refused_on_every_request(self, windows, rpc):
        """The memo holds what a peer string and a set of descriptors
        MEAN, never who may pass: the very request a local peer has
        warmed is refused from a remote one, every time, and the local
        peer is served again after it."""
        import grpc

        servicer = windows.servicer

        def send(ctx, i):
            req = windows.request("a", float(i), str(i))
            if rpc == "unary":
                return servicer.ModelInfer(req, ctx)
            (resp,) = list(servicer.ModelStreamInfer(iter([req]), ctx))
            return resp.infer_response

        local, remote = _Ctx(), _Ctx("ipv4:203.0.113.9:51000")
        for i in range(3):
            assert send(local, i).id == str(i)
        for i in range(3):
            with pytest.raises(_Aborted) as e:
                send(remote, i)
            assert e.value.code == grpc.StatusCode.PERMISSION_DENIED
            assert "203.0.113.9" in str(e.value)
        assert send(local, 9).id == "9"
        # a remote peer's WIRE request passes, as it always did
        assert servicer.ModelInfer(
            windows.request("a", 1.0, "w", shm=False), remote
        ).id == "w"

    def test_kept_answer_never_carries_another_requests_id_shape_or_window(
        self, windows
    ):
        """Two sessions interleaved, and a session whose answer changes
        rows (``[1, 4]`` then ``[4, 4]`` and back) under ONE set of
        request descriptors."""
        ctx = _Ctx()
        script = [("a", 1.0), ("b", 2.0), ("a", 100.0), ("b", 3.0),
                  ("a", 4.0), ("b", 200.0), ("a", 101.0), ("b", 5.0)]
        for turn in range(2):  # the second pass finds every message kept
            for i, (session, value) in enumerate(script):
                request_id = f"{session}-{turn}-{i}"
                resp = windows.servicer.ModelInfer(
                    windows.request(session, value, request_id), ctx
                )
                rows = 4 if value >= 100.0 else 1
                assert resp.id == request_id
                assert resp.model_name == "rows" and resp.model_version == "1"
                assert tuple(resp.outputs[0].shape) == (rows, 4)
                assert not resp.raw_output_contents
                np.testing.assert_array_equal(
                    windows.answer(session, resp),
                    np.full((rows, 4), value + 1.0, np.float32),
                )
        stats = windows.servicer.front_stats()
        assert stats["front_memo_misses"] == 2  # a session's descriptors, once
        assert stats["front_memo_hits"] == 14

    @pytest.mark.parametrize("shm", [False, True], ids=["wire", "shm"])
    def test_answers_byte_for_byte_equal_warm_and_cold(self, shm):
        """The same requests through a servicer whose memo is cold for
        each of them (a new servicer a request) and through one that has
        seen them all before."""
        script = [("a", 1.0), ("b", 2.0), ("a", 100.0), ("a", 3.0), ("b", 150.0)]

        def serve(w, i, session, value):
            resp = w.servicer.ModelInfer(
                w.request(session, value, f"id-{i}", shm=shm), _Ctx()
            )
            payload = w.answer(session, resp).tobytes() if shm else b""
            return resp.SerializeToString(deterministic=True), payload

        warm = _Windows()
        try:
            for i, (session, value) in enumerate(script):
                serve(warm, i, session, value)  # plans and messages kept
            for i, (session, value) in enumerate(script):
                cold = _Windows()
                try:
                    assert cold.servicer.front_stats()["front_memo_hits"] == 0
                    assert serve(cold, i, session, value) == serve(
                        warm, i, session, value
                    )
                finally:
                    cold.close()
            assert warm.servicer.front_stats()["front_memo_misses"] <= 2
        finally:
            warm.close()

    def test_request_without_a_plan_takes_the_standing_path(self, windows):
        """Malformed shm parameters are refused where they always were
        (by the parse, INVALID_ARGUMENT), and keep no plan."""
        import grpc

        req = windows.request("a", 1.0, "bad")
        req.inputs[0].parameters["shared_memory_byte_size"].int64_param = 0
        for _ in range(2):
            with pytest.raises(_Aborted) as e:
                windows.servicer.ModelInfer(req, _Ctx())
            assert e.value.code == grpc.StatusCode.INVALID_ARGUMENT
        stats = windows.servicer.front_stats()
        assert (stats["front_memo_hits"], stats["front_memo_misses"]) == (0, 2)

    def test_memo_is_bounded(self):
        from triton_client_tpu.runtime.server import _Memo

        memo = _Memo(4)
        for i in range(10):
            memo.put(i, str(i))
        assert [memo.get(i) for i in range(10)] == [None] * 6 + ["6", "7", "8", "9"]

    def test_transport_mix_is_counted_as_before(self):
        from triton_client_tpu.obs.collector import RuntimeCollector

        collector = RuntimeCollector()
        w = _Windows(collector=collector)
        collector.attach_front_end(w.servicer.front_stats, w.servicer.active_requests)
        try:
            for i in range(3):
                w.servicer.ModelInfer(w.request("a", 1.0, str(i)), _Ctx())
            w.servicer.ModelInfer(w.request("a", 1.0, "w", shm=False), _Ctx())
            w.servicer.ModelInfer(w.request("a", 1.0, "u"), _Ctx("unix:/tmp/s.sock"))
            snap = collector.snapshot()
            assert snap["transport"]["requests"] == {"shm": 3, "grpc": 1, "uds+shm": 1}
            assert snap["transport"]["shm_bytes"] == 4 * 16
            assert snap["transport"]["wire_bytes"] == 16
            assert snap["inflight_requests"] == 0
            assert snap["front_end"]["handler_requests"] == 5
        finally:
            w.close()
            collector.close()
