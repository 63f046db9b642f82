"""Remote-channel client mode: detect CLIs against a KServe v2 server.

The reference client's entire job is remote inference (one gRPC hop per
frame, grpc_channel.py:73-78); these tests run that topology in-process:
InferenceServer on a loopback port, CLI/adapters in the test process.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from triton_client_tpu.channel.grpc_channel import GRPCChannel
from triton_client_tpu.channel.tpu_channel import TPUChannel
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.drivers.driver import channel_infer3d
from triton_client_tpu.runtime.repository import ModelRepository
from triton_client_tpu.runtime.server import InferenceServer


@pytest.fixture()
def yolo_server():
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline

    pipe, spec, _ = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=(64, 64)
    )
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn())
    server = InferenceServer(
        repo, TPUChannel(repo), address="127.0.0.1:0", max_workers=2
    )
    server.start()
    yield server, spec.name
    server.stop()


def test_detect2d_cli_remote_channel(yolo_server, tmp_path, capsys):
    server, model_name = yolo_server
    from triton_client_tpu.cli.detect2d import main

    main(
        [
            "-u", f"grpc:127.0.0.1:{server.port}",
            "-m", model_name,
            "-i", "synthetic:3:64x64",
            "--sink", "jsonl",
            "-o", str(tmp_path),
            "--limit", "3",
        ]
    )
    out = capsys.readouterr().out
    assert "frames" in out
    assert (tmp_path / "detections.jsonl").exists()


def test_detect2d_cli_remote_shm_transport(yolo_server, tmp_path, capsys):
    """--shm: same CLI run but frames travel through POSIX shared
    memory (system-shared-memory extension); regions must be gone from
    the server registry after the run."""
    server, model_name = yolo_server
    from triton_client_tpu.cli.detect2d import main

    main(
        [
            "-u", f"grpc:127.0.0.1:{server.port}",
            "-m", model_name,
            "-i", "synthetic:3:64x64",
            "--shm",
            "--sink", "jsonl",
            "-o", str(tmp_path),
            "--limit", "3",
        ]
    )
    out = capsys.readouterr().out
    assert "frames" in out
    assert (tmp_path / "detections.jsonl").exists()
    assert server.shm_registry.status() == {}


def test_detect2d_cli_remote_requires_model_name(yolo_server):
    server, _ = yolo_server
    from triton_client_tpu.cli.detect2d import main

    with pytest.raises(SystemExit, match="model-name"):
        main(["-u", f"grpc:127.0.0.1:{server.port}", "-i", "synthetic:1"])


def test_channel_infer3d_pads_and_unpacks():
    """Remote 3D adapter: bucketed padding + z offset from served
    metadata, detections/valid unpacked to the reference contract."""
    seen = {}

    def fake_infer(inputs):
        seen["points"] = np.asarray(inputs["points"])
        seen["num_points"] = int(np.asarray(inputs["num_points"]))
        dets = np.zeros((4, 9), np.float32)
        dets[0] = [1, 2, 3, 4, 5, 6, 0.5, 0.9, 2]
        valid = np.zeros(4, bool)
        valid[0] = True
        return {"detections": dets, "valid": valid}

    spec = ModelSpec(
        name="pp",
        inputs=(
            TensorSpec("points", (-1, 4), "FP32"),
            TensorSpec("num_points", (), "INT32"),
        ),
        outputs=(
            TensorSpec("detections", (4, 9), "FP32"),
            TensorSpec("valid", (4,), "BOOL"),
        ),
        extra={"point_buckets": [128, 256], "z_offset": 1.5},
    )
    repo = ModelRepository()
    repo.register(spec, fake_infer)
    channel = TPUChannel(repo, validate=False)

    infer = channel_infer3d(channel, "pp")
    pts = np.ones((100, 5), np.float32)  # extra column must be dropped
    out = infer(pts)

    assert seen["points"].shape == (128, 4)  # smallest bucket
    assert seen["num_points"] == 100
    np.testing.assert_allclose(seen["points"][:100, 2], 1.0 + 1.5)  # z offset
    np.testing.assert_allclose(out["pred_boxes"], [[1, 2, 3, 4, 5, 6, 0.5]])
    np.testing.assert_allclose(out["pred_scores"], [0.9])
    assert out["pred_labels"].tolist() == [2]


def test_channel_infer3d_over_grpc(yolo_server):
    """The same adapter through the real wire (server fixture reused for
    its port; register a stub 3D model into its repository)."""
    server, _ = yolo_server
    # fixture's repo is inside the server; use a fresh loopback instead
    seen = {}

    def fake_infer(inputs):
        seen["shape"] = tuple(np.asarray(inputs["points"]).shape)
        n = int(np.asarray(inputs["num_points"]))
        dets = np.zeros((2, 9), np.float32)
        dets[0, :] = [n, 0, 0, 1, 1, 1, 0, 0.7, 1]
        valid = np.asarray([True, False])
        return {"detections": dets, "valid": valid}

    spec = ModelSpec(
        name="pp3d",
        inputs=(
            TensorSpec("points", (-1, 4), "FP32"),
            TensorSpec("num_points", (), "INT32"),
        ),
        outputs=(
            TensorSpec("detections", (2, 9), "FP32"),
            TensorSpec("valid", (2,), "BOOL"),
        ),
        extra={"point_buckets": [64], "z_offset": 0.0},
    )
    repo = ModelRepository()
    repo.register(spec, fake_infer)
    srv = InferenceServer(repo, TPUChannel(repo, validate=False),
                          address="127.0.0.1:0", max_workers=2)
    srv.start()
    try:
        # loopback auto-negotiates shm; force pure wire for the control
        channel = GRPCChannel(
            f"127.0.0.1:{srv.port}", timeout_s=10.0, use_shared_memory=False
        )
        # extra must survive the wire (ModelConfig parameters map)
        assert channel.get_metadata("pp3d").extra["point_buckets"] == [64]
        infer = channel_infer3d(channel, "pp3d")
        out = infer(np.zeros((10, 4), np.float32))
        assert out["pred_boxes"][0, 0] == 10  # num_points made it across
        assert seen["shape"] == (64, 4)  # served bucket applied remotely

        # the same 3D adapter over the shared-memory transport: BOTH
        # request tensors (points f32 + num_points scalar i32) travel
        # as shm regions, and results bit-match the wire path
        shm_chan = GRPCChannel(
            f"127.0.0.1:{srv.port}", timeout_s=10.0, use_shared_memory=True
        )
        shm_infer = channel_infer3d(shm_chan, "pp3d")
        out2 = shm_infer(np.zeros((10, 4), np.float32))
        np.testing.assert_array_equal(out2["pred_boxes"], out["pred_boxes"])
        assert len(srv.shm_registry.status()) == 2  # one region per input
        shm_chan.close()
        assert srv.shm_registry.status() == {}
        channel.close()
    finally:
        srv.stop()


def test_detect2d_cli_streaming_mode(yolo_server, tmp_path, capsys):
    """--streaming pumps frames through one ModelStreamInfer stream."""
    server, model_name = yolo_server
    from triton_client_tpu.cli.detect2d import main

    import json

    main(
        [
            "-u", f"grpc:127.0.0.1:{server.port}",
            "-m", model_name,
            "--streaming",
            "-i", "synthetic:5:64x64",
            "--sink", "jsonl",
            "-o", str(tmp_path),
            "--limit", "5",
        ]
    )
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["streaming"] is True
    assert report["driver"]["frames"] == 5
    lines = (tmp_path / "detections.jsonl").read_text().splitlines()
    assert len(lines) == 5


def test_streaming_flag_combos_rejected(yolo_server, tmp_path):
    server, model_name = yolo_server
    from triton_client_tpu.cli.detect2d import main

    base = ["-u", f"grpc:127.0.0.1:{server.port}", "-m", model_name,
            "--streaming", "-i", "synthetic:2:64x64"]
    with pytest.raises(SystemExit, match="unary-mode"):
        main(base + ["--gt", str(tmp_path / "gt.jsonl")])
    with pytest.raises(SystemExit, match="does not combine"):
        main(base + ["--cameras", "2"])
    with pytest.raises(SystemExit, match="remote ModelStreamInfer"):
        main(["--streaming", "-i", "synthetic:2:64x64", "--input-size", "64"])


def test_serve_with_batching_channel(tmp_path):
    """Concurrent remote requests through a serve-style stack with the
    micro-batcher in front of TPUChannel."""
    import concurrent.futures

    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
    from triton_client_tpu.channel.base import InferRequest

    pipe, spec, _ = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=(64, 64)
    )
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn())
    channel = ContinuousBatchingChannel(
        TPUChannel(repo), max_batch=4
    )
    server = InferenceServer(repo, channel, address="127.0.0.1:0", max_workers=4)
    server.start()
    try:
        grpc_channel = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=60.0)

        def one(i):
            img = np.full((1, 64, 64, 3), 10.0 * i, np.float32)
            return grpc_channel.do_inference(
                InferRequest(model_name=spec.name, inputs={"images": img})
            ).outputs["detections"].shape

        with concurrent.futures.ThreadPoolExecutor(max_workers=4) as ex:
            shapes = list(ex.map(one, range(8)))
        assert all(s == (1, 300, 6) for s in shapes)
        grpc_channel.close()
    finally:
        server.stop()
        channel.close()
