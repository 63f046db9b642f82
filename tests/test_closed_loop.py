"""Closed accuracy loop plumbing: synth data -> train CLI -> export ->
detect CLI --repo (trained weights) -> mAP report.

These are SMOKE tests (few steps, tiny shapes) proving the loop's
plumbing end to end; the convergence runs with real step counts live in
perf/closed_loop.py.
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
cv2 = pytest.importorskip("cv2")


def test_2d_loop_train_export_eval(tmp_path, capsys):
    from triton_client_tpu.cli.detect2d import main as detect_main
    from triton_client_tpu.cli.train import main as train_main
    from triton_client_tpu.io.synthdata import write_detection_dataset

    images_dir, gt_path = write_detection_dataset(
        str(tmp_path / "train"), 4, hw=(64, 64), num_classes=2, seed=0
    )
    repo = tmp_path / "repo"
    train_main(
        [
            "-i", images_dir,
            "--gt", gt_path,
            "--input-size", "64",
            "-c", "2",
            "-b", "2",
            "--steps", "2",
            "--mesh", "data=2",
            "--export", str(repo),
            "-m", "loop2d",
        ]
    )
    capsys.readouterr()

    hold_dir, hold_gt = write_detection_dataset(
        str(tmp_path / "hold"), 3, hw=(64, 64), num_classes=2, seed=99
    )
    detect_main(
        [
            "-m", "loop2d",
            "--repo", str(repo),
            "-i", hold_dir,
            "--gt", hold_gt,
            "--conf", "0.01",
        ]
    )
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["model"] == "loop2d"
    assert report["eval"]["frames"] == 3
    # untrained-ish net: mAP is whatever it is, but the full pipeline
    # (decode + NMS + matching) must produce a finite score
    assert 0.0 <= report["eval"]["map50"] <= 1.0


def test_load_pipeline_overrides_and_version(tmp_path):
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline
    from triton_client_tpu.runtime import disk_repository as dr

    _, _, variables = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=(64, 64)
    )
    doc = {
        "family": "yolov5",
        "model": {"variant": "n", "input_hw": [64, 64], "num_classes": 2},
    }
    dr.export_model(tmp_path, "m", doc, variables=variables, version="1")
    dr.export_model(tmp_path, "m", doc, variables=variables, version="3")

    pipe, spec = dr.load_pipeline(
        tmp_path / "m", config_overrides={"conf_thresh": 0.123}
    )
    assert spec.version == "3"  # latest wins
    assert pipe.config.conf_thresh == 0.123
    _, spec1 = dr.load_pipeline(tmp_path / "m", version="1")
    assert spec1.version == "1"

    with pytest.raises(FileNotFoundError):
        dr.load_pipeline(tmp_path / "m", version="7")

    dr.export_model(tmp_path, "empty", doc)  # config only, no weights
    with pytest.raises(FileNotFoundError, match="no version dirs"):
        dr.load_pipeline(tmp_path / "empty")


def test_detect2d_repo_requires_model_name(tmp_path):
    from triton_client_tpu.cli.detect2d import main as detect_main

    with pytest.raises(SystemExit, match="requires -m"):
        detect_main(["--repo", str(tmp_path), "-i", "synthetic:1:64x64"])


def test_repo_guards(tmp_path):
    """--repo refuses remote mode, conflicting model-shape flags, and
    wrong-family entries — loudly, not silently."""
    from triton_client_tpu.cli.detect2d import main as d2
    from triton_client_tpu.cli.detect3d import main as d3

    with pytest.raises(SystemExit, match="SERVER loads the repository"):
        d2(["-u", "grpc:localhost:1", "-m", "m", "--repo", str(tmp_path)])
    with pytest.raises(SystemExit, match="SERVER loads the repository"):
        d3(["-u", "grpc:localhost:1", "-m", "m", "--repo", str(tmp_path)])
    with pytest.raises(SystemExit, match="--input-size.*conflict"):
        d2(["-m", "m", "--repo", str(tmp_path), "--input-size", "640"])
    with pytest.raises(SystemExit, match="--config.*conflict"):
        d3(["-m", "m", "--repo", str(tmp_path), "--config", "x.yaml"])


def test_load_pipeline_rejects_wrong_family(tmp_path):
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline
    from triton_client_tpu.runtime import disk_repository as dr

    _, _, variables = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=(64, 64)
    )
    doc = {
        "family": "yolov5",
        "model": {"variant": "n", "input_hw": [64, 64], "num_classes": 2},
    }
    dr.export_model(tmp_path, "m2", doc, variables=variables)
    with pytest.raises(ValueError, match="use the detect2d CLI"):
        dr.load_pipeline(tmp_path / "m2", kind="3d")
    pipe, _ = dr.load_pipeline(tmp_path / "m2", kind="2d")
    assert pipe is not None


TINY3D_YAML = """\
model: pointpillars
voxel:
  point_cloud_range: [0.0, -8.0, -3.0, 16.0, 8.0, 1.0]
  voxel_size: [0.5, 0.5, 4.0]
  max_voxels: 512
  max_points_per_voxel: 8
vfe_filters: 16
backbone_layers: [1, 1, 1]
backbone_filters: [16, 16, 16]
upsample_filters: [16, 16, 16]
"""


def test_3d_loop_train_export_eval(tmp_path, capsys):
    from triton_client_tpu.cli.detect3d import main as detect_main
    from triton_client_tpu.cli.train import main as train_main
    from triton_client_tpu.io.synthdata import write_scene_dataset

    cfg_path = tmp_path / "tiny3d.yaml"
    cfg_path.write_text(TINY3D_YAML)
    scene_kwargs = dict(
        pc_range=(0.0, -8.0, -3.0, 16.0, 8.0, 1.0),
        n_objects=2,
        n_clutter=500,
        min_points=10,
    )
    clouds, gt = write_scene_dataset(
        str(tmp_path / "train"), 2, seed=0, **scene_kwargs
    )
    hold_clouds, hold_gt = write_scene_dataset(
        str(tmp_path / "hold"), 2, seed=9, **scene_kwargs
    )
    repo = tmp_path / "repo"
    train_main(
        [
            "--family", "pointpillars",
            "--config", str(cfg_path),
            "-i", clouds,
            "--gt", gt,
            "-b", "1",
            "--mesh", "data=1",
            "--points", "4096",
            "--max-boxes", "8",
            "--steps", "2",
            "--export", str(repo),
            "-m", "loop3d",
        ]
    )
    capsys.readouterr()

    detect_main(
        [
            "-m", "loop3d",
            "--repo", str(repo),
            "-i", hold_clouds,
            "--gt", hold_gt,
        ]
    )
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["model"] == "loop3d"
    assert report["eval"]["frames"] == 2
    assert 0.0 <= report["eval"]["map50"] <= 1.0


TINY_SECOND_YAML = """\
model: second_iou
voxel:
  point_cloud_range: [0.0, -8.0, -2.0, 16.0, 8.0, 2.0]
  voxel_size: [0.5, 0.5, 0.5]
  max_voxels: 1024
  max_points_per_voxel: 4
middle_filters: [8, 8]
backbone_layers: [1]
backbone_strides: [1]
backbone_filters: [16]
upsample_strides: [1]
upsample_filters: [16]
"""


def test_second_loop_train_export_eval(tmp_path, capsys):
    """SECOND-IoU trains through the same loop as PointPillars (the
    anchor-head loss + the IoU-quality term) and serves from the
    exported entry."""
    from triton_client_tpu.cli.detect3d import main as detect_main
    from triton_client_tpu.cli.train import main as train_main
    from triton_client_tpu.io.synthdata import write_scene_dataset

    cfg_path = tmp_path / "tiny_second.yaml"
    cfg_path.write_text(TINY_SECOND_YAML)
    kw = dict(
        pc_range=(0.0, -8.0, -2.0, 16.0, 8.0, 2.0),
        n_objects=2,
        n_clutter=500,
        min_points=10,
    )
    clouds, gt = write_scene_dataset(str(tmp_path / "train"), 2, seed=0, **kw)
    hold_clouds, hold_gt = write_scene_dataset(
        str(tmp_path / "hold"), 2, seed=9, **kw
    )
    repo = tmp_path / "repo"
    train_main(
        [
            "--family", "second_iou",
            "--config", str(cfg_path),
            "-i", clouds,
            "--gt", gt,
            "-b", "1",
            "--mesh", "data=1",
            "--points", "4096",
            "--max-boxes", "8",
            "--steps", "2",
            "--export", str(repo),
            "-m", "loop_second",
        ]
    )
    capsys.readouterr()
    detect_main(
        [
            "-m", "loop_second",
            "--repo", str(repo),
            "-i", hold_clouds,
            "--gt", hold_gt,
        ]
    )
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["model"] == "loop_second"
    assert report["eval"]["frames"] == 2
