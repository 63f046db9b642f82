"""``serve`` CLI: the tritonserver-process role, stood up for real.

Drives the reference deployment topology end to end in-process: scan
the examples/ model repository (the layout the reference provisions at
/opt/model_repo, docker/server/Dockerfile:131-135), build the channel
stack from parsed CLI args (mesh/batching/pipeline flags), serve
KServe v2 on a loopback port, and hit it with GRPCChannel."""

import argparse

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.channel.grpc_channel import GRPCChannel
from triton_client_tpu.cli import serve


def _args(**over):
    base = dict(
        model_repository="examples",
        address="127.0.0.1:0",
        max_workers=4,
        mesh="",
        batching=False,
        max_batch=8,
        pipeline_depth=2,
        metrics_port=0,
        warmup=False,
        verbose=False,
    )
    base.update(over)
    return argparse.Namespace(**base)


def test_parser_builds():
    import contextlib
    import io

    with contextlib.redirect_stdout(io.StringIO()):
        with pytest.raises(SystemExit):
            serve.main(["--help"])  # parser wires every flag without error


def test_serve_builds_and_answers_over_grpc(tmp_path):
    # one-entry copy of the repo: scan_disk loads models eagerly and
    # compiling all 8 examples makes the smoke take minutes
    import shutil

    shutil.copytree("examples/yolov5_crop", tmp_path / "yolov5_crop")
    server = serve.build_server(
        _args(
            model_repository=str(tmp_path), batching=True, pipeline_depth=2
        )
    )
    server.start()
    try:
        chan = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=60.0)
        assert chan.server_live()
        index = chan.repository_index()
        names = {name for name, _, _ in index}
        assert "yolov5_crop" in names
        spec = chan.get_metadata("yolov5_crop")
        hw = spec.extra.get("input_hw", [512, 512])
        frame = np.zeros((1, int(hw[0]), int(hw[1]), 3), np.uint8)
        resp = chan.do_inference(
            InferRequest(model_name="yolov5_crop", inputs={"images": frame})
        )
        assert "detections" in resp.outputs
        chan.close()
    finally:
        server.stop()


def test_serve_rejects_missing_repository(tmp_path):
    with pytest.raises(FileNotFoundError):
        serve.build_server(_args(model_repository=str(tmp_path / "nope")))


@pytest.mark.parametrize(
    "flag",
    [
        ["--batcher", "window"],
        ["--batch-timeout-us", "3000"],
        ["--merge-hold-us", "0"],
        ["--pad-buckets"],
    ],
    ids=lambda flag: flag[0],
)
def test_serve_refuses_the_removed_batcher_flags(flag, capsys):
    """The window batcher's four flags did nothing on the scheduler
    every server ran; an old command line now fails in argparse."""
    parser = serve.make_parser()
    parser.parse_args(["-r", "examples", "--batching"])  # the rest still parses
    with pytest.raises(SystemExit) as exit_info:
        parser.parse_args(["-r", "examples", "--batching", *flag])
    assert exit_info.value.code == 2
    assert f"unrecognized arguments: {flag[0]}" in capsys.readouterr().err
    assert flag[0] not in parser.format_help()


def test_serve_builds_lifecycle_from_flags(tmp_path):
    import shutil

    shutil.copytree(
        "examples/camera_preprocess", tmp_path / "camera_preprocess"
    )
    (tmp_path / "tenants.yaml").write_text(
        "tenants:\n"
        "  vision:\n"
        "    share: 4\n"
        "    models: [camera_preprocess]\n"
        "    pinned: [camera_preprocess]\n"
    )
    server = serve.build_server(
        _args(
            model_repository=str(tmp_path),
            batching=True,
            hbm_budget=512.0,
            tenants=str(tmp_path / "tenants.yaml"),
        )
    )
    try:
        assert server.lifecycle is not None
        assert server.lifecycle.stats()["budget_bytes"] == 512 << 20
        assert server.tenants is not None
        assert server.tenants.tenant_of("camera_preprocess") == "vision"
        assert server.tenants.pinned("camera_preprocess")
    finally:
        server.stop()
