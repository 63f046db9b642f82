"""System shared-memory extension: regions, registry, codec, live RPC.

The reference's Triton deployment ships this extension (tritonclient
exposes it as tritonclient.utils.shared_memory); here the same wire
contract — SystemSharedMemory{Register,Status,Unregister} RPCs plus
shared_memory_* input/output parameters — is served in-tree, so a
same-host client can hand 786 KB camera frames to the server through
one memcpy instead of a protobuf round-trip."""

import os

import numpy as np
import pytest

from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.channel.grpc_channel import GRPCChannel
from triton_client_tpu.channel.kserve import codec, pb
from triton_client_tpu.channel.tpu_channel import TPUChannel
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.runtime.repository import ModelRepository
from triton_client_tpu.runtime.server import InferenceServer
from triton_client_tpu.runtime.shared_memory import (
    SharedMemoryRegion,
    SystemSharedMemoryRegistry,
    _shm_path,
)


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


class TestRegion:
    def test_create_write_read_unlink(self):
        key = f"/tct_test_{os.getpid()}_rw"
        arr = np.arange(24, dtype=np.float32).reshape(4, 6)
        with SharedMemoryRegion.create(key, arr.nbytes) as region:
            assert region.write(arr) == arr.nbytes
            view = region.read(0, arr.nbytes)
            back = np.frombuffer(view, np.float32).reshape(4, 6)
            np.testing.assert_array_equal(back, arr)
            assert os.path.exists(_shm_path(key))
        assert not os.path.exists(_shm_path(key))  # owner unlinks

    def test_attach_sees_writer_bytes(self):
        key = f"/tct_test_{os.getpid()}_attach"
        with SharedMemoryRegion.create(key, 64) as owner:
            owner.write(np.full(16, 3.5, np.float32))
            reader = SharedMemoryRegion.attach(key)
            got = np.frombuffer(reader.read(0, 64), np.float32)
            np.testing.assert_array_equal(got, np.full(16, 3.5, np.float32))
            reader.close()
            # non-owner close must NOT unlink
            assert os.path.exists(_shm_path(key))

    def test_bounds_and_key_validation(self):
        key = f"/tct_test_{os.getpid()}_bounds"
        with SharedMemoryRegion.create(key, 16) as region:
            with pytest.raises(ValueError):
                region.write(np.zeros(5, np.float32))  # 20 > 16
            with pytest.raises(ValueError):
                region.read(8, 16)
        for bad in ("", "/", "a/b", "/../etc", ".hidden"):
            with pytest.raises(ValueError):
                _shm_path(bad)


class TestRegistry:
    def test_register_status_unregister(self):
        key = f"/tct_test_{os.getpid()}_reg"
        with SharedMemoryRegion.create(key, 128) as region:
            region.write(np.arange(32, dtype=np.float32))
            reg = SystemSharedMemoryRegistry()
            reg.register("r0", key, 0, 128)
            with pytest.raises(ValueError):
                reg.register("r0", key, 0, 128)  # duplicate name
            assert reg.status()["r0"].byte_size == 128
            got = np.frombuffer(reg.read("r0", 0, 128), np.float32)
            np.testing.assert_array_equal(got, np.arange(32, dtype=np.float32))
            with pytest.raises(ValueError):
                reg.read("r0", 64, 128)  # beyond registered window
            reg.unregister("r0")
            with pytest.raises(ValueError):
                reg.read("r0", 0, 4)
            with pytest.raises(KeyError):
                reg.status("r0")

    def test_attach_missing_key_fails(self):
        reg = SystemSharedMemoryRegistry()
        with pytest.raises(OSError):
            reg.register("nope", f"/tct_test_{os.getpid()}_missing", 0, 8)

    def test_registered_window_respects_offset(self):
        key = f"/tct_test_{os.getpid()}_off"
        with SharedMemoryRegion.create(key, 64) as region:
            region.write(np.arange(16, dtype=np.float32))
            reg = SystemSharedMemoryRegistry()
            reg.register("w", key, offset=32, byte_size=32)
            got = np.frombuffer(reg.read("w", 0, 32), np.float32)
            np.testing.assert_array_equal(
                got, np.arange(8, 16, dtype=np.float32)
            )
            reg.unregister_all()


class TestCodecShm:
    def test_mixed_wire_and_shm_inputs(self):
        key = f"/tct_test_{os.getpid()}_codec"
        imgs = np.random.default_rng(0).random((2, 4, 4, 3)).astype(np.float32)
        count = np.array([7], np.int32)
        with SharedMemoryRegion.create(key, imgs.nbytes) as region:
            region.write(imgs)
            reg = SystemSharedMemoryRegistry()
            reg.register("imgs_r", key, 0, imgs.nbytes)
            req = codec.build_infer_request_shm(
                "m",
                {"images": imgs, "count": count},
                shm_inputs={"images": ("imgs_r", 0, imgs.nbytes)},
            )
            # only the wire input consumes a raw slot
            assert len(req.raw_input_contents) == 1
            wire = pb.ModelInferRequest.FromString(req.SerializeToString())
            parsed = codec.parse_infer_request(wire, shm=reg)
            np.testing.assert_array_equal(parsed["images"], imgs)
            np.testing.assert_array_equal(parsed["count"], count)
            reg.unregister_all()

    def test_negative_offset_rejected(self):
        """int64_param is signed: a negative offset must not reach
        python slice semantics (it would silently read from the END of
        the segment, outside the registered window)."""
        key = f"/tct_test_{os.getpid()}_neg"
        with SharedMemoryRegion.create(key, 64) as region:
            reg = SystemSharedMemoryRegistry()
            reg.register("neg", key, offset=32, byte_size=32)
            with pytest.raises(ValueError):
                reg.read("neg", -32, 32)
            with pytest.raises(ValueError):
                reg.write("neg", -32, np.zeros(4, np.float32))
            with pytest.raises(ValueError):
                region.read(-8, 8)
            req = pb.ModelInferRequest(model_name="m")
            t = req.inputs.add(name="x", datatype="FP32", shape=[8])
            codec.set_shm_params(t, "neg", 0, 32)
            t.parameters["shared_memory_offset"].int64_param = -32
            with pytest.raises(ValueError):
                codec.parse_infer_request(req, shm=reg)
            reg.unregister_all()

    def test_shm_input_without_registry_rejected(self):
        req = codec.build_infer_request_shm(
            "m",
            {"x": np.zeros((1, 4), np.float32)},
            shm_inputs={"x": ("r", 0, 16)},
        )
        with pytest.raises(ValueError):
            codec.parse_infer_request(req, shm=None)

    def test_response_through_shm(self):
        key = f"/tct_test_{os.getpid()}_out"
        y = np.arange(12, dtype=np.float32).reshape(3, 4)
        with SharedMemoryRegion.create(key, 256) as client_region:
            reg = SystemSharedMemoryRegistry()
            reg.register("out_r", key, 0, 256)
            resp = codec.build_infer_response(
                "m",
                {"y": y},
                shm_outputs={"y": ("out_r", 0, 256)},
                shm=reg,
            )
            assert not resp.raw_output_contents  # travelled via shm
            wire = pb.ModelInferResponse.FromString(resp.SerializeToString())
            parsed = codec.parse_infer_response(
                wire, regions={"out_r": client_region}
            )
            np.testing.assert_array_equal(parsed["y"], y)
            reg.unregister_all()

    def test_oversize_output_rejected(self):
        key = f"/tct_test_{os.getpid()}_small"
        with SharedMemoryRegion.create(key, 8):
            reg = SystemSharedMemoryRegistry()
            reg.register("small", key, 0, 8)
            with pytest.raises(ValueError):
                codec.build_infer_response(
                    "m",
                    {"y": np.zeros(16, np.float32)},
                    shm_outputs={"y": ("small", 0, 8)},
                    shm=reg,
                )
            reg.unregister_all()


class TestLiveShmServer:
    @pytest.fixture()
    def server(self):
        repo = _repo()
        server = InferenceServer(
            repo, TPUChannel(repo), address="127.0.0.1:0", max_workers=4
        )
        server.start()
        yield server
        server.stop()

    def test_shm_channel_matches_wire_channel(self, server):
        addr = f"127.0.0.1:{server.port}"
        # loopback auto-negotiates shm; force pure wire for the control
        wire = GRPCChannel(addr, timeout_s=10.0, use_shared_memory=False)
        shm = GRPCChannel(addr, timeout_s=10.0, use_shared_memory=True)
        x = np.random.default_rng(1).random((3, 4)).astype(np.float32)
        req = InferRequest(model_name="addone", inputs={"x": x})
        try:
            assert wire.transport == "grpc"
            assert shm.transport == "shm"
            a = wire.do_inference(req).outputs["y"]
            b = shm.do_inference(req).outputs["y"]
            np.testing.assert_array_equal(a, b)
            np.testing.assert_allclose(b, x + 1.0)
            # one input region from the shm channel's pool slot; the
            # wire control registered nothing
            assert len(server.shm_registry.status()) == 1
        finally:
            shm.close()
            wire.close()
        # channel close unregisters server-side and unlinks the segment
        assert server.shm_registry.status() == {}

    def test_region_grows_with_input(self, server):
        addr = f"127.0.0.1:{server.port}"
        shm = GRPCChannel(addr, timeout_s=10.0, use_shared_memory=True)
        try:
            for batch in (1, 4, 2):  # grow then reuse-larger
                x = np.full((batch, 4), float(batch), np.float32)
                out = shm.do_inference(
                    InferRequest(model_name="addone", inputs={"x": x})
                ).outputs["y"]
                np.testing.assert_allclose(out, x + 1.0)
            # generation-tagged growth retires the old segment: one
            # live input region plus the learned output arena
            assert len(server.shm_registry.status()) == 2
        finally:
            shm.close()

    def test_unregistered_region_is_invalid_argument(self, server):
        import grpc

        addr = f"127.0.0.1:{server.port}"
        chan = GRPCChannel(addr, timeout_s=10.0)
        req = codec.build_infer_request_shm(
            "addone",
            {"x": np.zeros((1, 4), np.float32)},
            shm_inputs={"x": ("ghost", 0, 16)},
        )
        try:
            with pytest.raises(grpc.RpcError) as exc:
                chan._stub.ModelInfer(req, timeout=10.0)
            assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        finally:
            chan.close()

    def test_status_and_unregister_rpcs(self, server):
        addr = f"127.0.0.1:{server.port}"
        chan = GRPCChannel(addr, timeout_s=10.0)
        key = f"/tct_test_{os.getpid()}_rpc"
        with SharedMemoryRegion.create(key, 64):
            chan._stub.SystemSharedMemoryRegister(
                pb.SystemSharedMemoryRegisterRequest(
                    name="rpc_r", key=key, byte_size=64
                ),
                timeout=10.0,
            )
            status = chan._stub.SystemSharedMemoryStatus(
                pb.SystemSharedMemoryStatusRequest(), timeout=10.0
            )
            assert status.regions["rpc_r"].key == key
            assert status.regions["rpc_r"].byte_size == 64
            chan._stub.SystemSharedMemoryUnregister(
                pb.SystemSharedMemoryUnregisterRequest(name="rpc_r"),
                timeout=10.0,
            )
            status = chan._stub.SystemSharedMemoryStatus(
                pb.SystemSharedMemoryStatusRequest(), timeout=10.0
            )
            assert not status.regions
        chan.close()


class TestLoadgen:
    def test_run_pool_closed_loop(self):
        """The perf_analyzer-style driver (utils/loadgen): pool runs,
        every thread drains before return, shm regions are gone."""
        from triton_client_tpu.utils.loadgen import run_pool

        repo = _repo()
        server = InferenceServer(
            repo, TPUChannel(repo), address="127.0.0.1:0", max_workers=4
        )
        server.start()
        try:
            for use_shm in (False, True):
                res = run_pool(
                    f"127.0.0.1:{server.port}",
                    "addone",
                    {"x": np.ones((1, 4), np.float32)},
                    clients=3,
                    duration_s=0.5,
                    deadline_s=10.0,
                    use_shared_memory=use_shm,
                    stagger_s=0.0,
                )
                assert not res.errors
                assert res.served_frames > 0
                # latencies include the drained final in-flight request
                # per client; served_frames counts only in-window
                assert len(res.latencies_ms) >= res.served_frames
                assert res.fps > 0
            assert server.shm_registry.status() == {}
        finally:
            server.stop()


def test_create_reclaims_stale_segment():
    """A crashed run leaves its segment behind; a same-name create
    (pid reuse after container restart) must reclaim it rather than
    fail or silently attach."""
    key = f"/tct_test_{os.getpid()}_stale"
    with open(_shm_path(key), "wb") as f:
        f.write(b"\xff" * 32)  # stale garbage
    with SharedMemoryRegion.create(key, 16) as region:
        got = np.frombuffer(region.read(0, 16), np.uint8)
        np.testing.assert_array_equal(got, np.zeros(16, np.uint8))
    assert not os.path.exists(_shm_path(key))


class TestSecurityAndRecovery:
    def test_shm_rpcs_rejected_for_remote_peers(self):
        """A remote peer must not be able to map server-host /dev/shm
        segments: the shm RPCs and shm-parameterized infer requests are
        loopback/unix-only (the servicer checks context.peer())."""
        import grpc

        from triton_client_tpu.runtime.server import _Servicer
        from triton_client_tpu.runtime.shared_memory import (
            SystemSharedMemoryRegistry,
        )

        class _RemoteCtx:
            def peer(self):
                return "ipv4:203.0.113.9:51000"

            def abort(self, code, details):
                raise _Aborted(code, details)

        class _Aborted(Exception):
            def __init__(self, code, details):
                self.code = code
                super().__init__(details)

        repo = _repo()
        servicer = _Servicer(
            repo, TPUChannel(repo), shm_registry=SystemSharedMemoryRegistry()
        )
        ctx = _RemoteCtx()
        with pytest.raises(_Aborted) as e:
            servicer.SystemSharedMemoryRegister(
                pb.SystemSharedMemoryRegisterRequest(
                    name="x", key="/victim", byte_size=8
                ),
                ctx,
            )
        assert e.value.code == grpc.StatusCode.PERMISSION_DENIED
        with pytest.raises(_Aborted):
            servicer.SystemSharedMemoryStatus(
                pb.SystemSharedMemoryStatusRequest(), ctx
            )
        with pytest.raises(_Aborted):
            servicer.SystemSharedMemoryUnregister(
                pb.SystemSharedMemoryUnregisterRequest(name="x"), ctx
            )
        # infer referencing shm params is gated the same way
        req = codec.build_infer_request_shm(
            "addone",
            {"x": np.zeros((1, 4), np.float32)},
            shm_inputs={"x": ("r", 0, 16)},
        )
        with pytest.raises(_Aborted):
            servicer.ModelInfer(req, ctx)

    def test_shm_channel_recovers_from_server_restart(self):
        """The wire path recovers from a server restart via the retry
        ladder; the shm path must too: on 'not registered' it
        re-registers its cached segments and re-issues once."""
        repo = _repo()
        server = InferenceServer(
            repo, TPUChannel(repo), address="127.0.0.1:0", max_workers=2
        )
        server.start()
        addr = f"127.0.0.1:{server.port}"
        chan = GRPCChannel(addr, timeout_s=10.0, use_shared_memory=True)
        x = np.ones((2, 4), np.float32)
        req = InferRequest(model_name="addone", inputs={"x": x})
        try:
            np.testing.assert_allclose(
                chan.do_inference(req).outputs["y"], x + 1.0
            )
            # simulate restart: the new server process has an empty
            # registry (same port is the hard part to arrange, so wipe
            # the registry in place — the failure mode is identical)
            server.shm_registry.unregister_all()
            np.testing.assert_allclose(
                chan.do_inference(req).outputs["y"], x + 1.0
            )
            # recovery re-registered the input region; the second
            # request also carries the learned output arena
            assert len(server.shm_registry.status()) == 2
        finally:
            chan.close()
            server.stop()


def test_stream_infer_shm_gated_for_remote_peers():
    """ModelStreamInfer must apply the same loopback gate as unary
    ModelInfer when a streamed request carries shm parameters."""
    import grpc as grpc_mod

    from triton_client_tpu.runtime.server import _Servicer

    class _Aborted(Exception):
        def __init__(self, code, details):
            self.code = code
            super().__init__(details)

    class _RemoteCtx:
        def peer(self):
            return "ipv4:198.51.100.7:4242"

        def abort(self, code, details):
            raise _Aborted(code, details)

    repo = _repo()
    servicer = _Servicer(
        repo, TPUChannel(repo), shm_registry=SystemSharedMemoryRegistry()
    )
    req = codec.build_infer_request_shm(
        "addone",
        {"x": np.zeros((1, 4), np.float32)},
        shm_inputs={"x": ("r", 0, 16)},
    )
    with pytest.raises(_Aborted) as e:
        list(servicer.ModelStreamInfer(iter([req]), _RemoteCtx()))
    assert e.value.code == grpc_mod.StatusCode.PERMISSION_DENIED


def test_bf16_tensor_through_shm_region():
    """BF16 is the codec's one special-cased dtype (no stock-numpy
    dtype; travels as ml_dtypes.bfloat16 words): it must survive the
    shared-memory path bit-exactly like it does the wire."""
    import ml_dtypes

    bf16 = np.dtype(ml_dtypes.bfloat16)
    arr = np.arange(16, dtype=np.float32).astype(bf16).reshape(4, 4)
    key = f"/tct_test_{os.getpid()}_bf16"
    with SharedMemoryRegion.create(key, arr.nbytes) as region:
        region.write(arr)
        reg = SystemSharedMemoryRegistry()
        reg.register("bf16_r", key, 0, arr.nbytes)
        req = codec.build_infer_request_shm(
            "m", {"x": arr}, shm_inputs={"x": ("bf16_r", 0, arr.nbytes)}
        )
        assert req.inputs[0].datatype == "BF16"
        wire = pb.ModelInferRequest.FromString(req.SerializeToString())
        parsed = codec.parse_infer_request(wire, shm=reg)
        assert parsed["x"].dtype == bf16
        np.testing.assert_array_equal(
            parsed["x"].view(np.uint16), arr.view(np.uint16)
        )
        reg.unregister_all()


def test_output_windows_stay_inside_the_arena_when_a_size_is_learned_meanwhile():
    """Several callers share one channel and the model's answers differ
    in rows (a block model: ``[1, V]`` for an extend, ``[4, V]`` for a
    block): another caller's response may teach the channel a larger
    size between the arena's sizing and the windows. Every window asked
    for lies inside the arena that was sized for it."""
    chan = GRPCChannel("127.0.0.1:1", use_shared_memory=True)
    chan._learned_out["m"] = {"logits": 1024, "aux": 100}
    arenas = []

    class _Arena:
        key = "/arena_g0"

        def __init__(self, size):
            self.size = size

    class _Slot:
        def region_for(self, name, nbytes):
            # what another caller's ``_parse_shm_response`` does meanwhile
            chan._learned_out["m"]["logits"] = 4096
            arenas.append(_Arena(nbytes))
            return arenas[-1]

    wire = pb.ModelInferRequest(model_name="m")
    chan._request_shm_outputs(wire, _Slot(), "m")
    assert len(wire.outputs) == 2
    for t in wire.outputs:
        region, offset, nbytes = codec.shm_params(t)
        assert region == "arena_g0" and offset + nbytes <= arenas[0].size
    # the next request sizes its arena for what was learned
    wire = pb.ModelInferRequest(model_name="m")
    chan._request_shm_outputs(wire, _Slot(), "m")
    assert arenas[1].size >= 4096 + 100
