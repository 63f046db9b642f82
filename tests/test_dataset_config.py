"""YAML dataset config -> typed model/pipeline configs."""

import numpy as np
import pytest
import yaml

from triton_client_tpu.dataset_config import (
    client_params,
    detect3d_from_yaml,
    load_yaml,
    model_config_from_dict,
    voxel_from_dict,
)
from triton_client_tpu.ops.voxelize import VoxelConfig

REPO_KITTI = "data/kitti_pointpillars.yaml"
REPO_NUSC = "data/nusc_centerpoint.yaml"
REPO_SECOND = "data/kitti_second.yaml"


def test_voxel_from_dict_partial_override():
    v = voxel_from_dict({"max_voxels": 1234})
    assert v.max_voxels == 1234
    assert v.voxel_size == VoxelConfig().voxel_size  # untouched defaults


def test_kitti_pointpillars_yaml_matches_reference_grid():
    name, model_cfg, pipe_cfg = detect3d_from_yaml(REPO_KITTI)
    assert name == "pointpillars"
    # reference pointpillar.yaml:5,17-18
    assert model_cfg.voxel.point_cloud_range == (0.0, -39.68, -3.0, 69.12, 39.68, 1.0)
    assert model_cfg.voxel.voxel_size == (0.16, 0.16, 4.0)
    assert model_cfg.voxel.max_points_per_voxel == 32
    # 432 x 496 canvas (pointpillar.yaml grid)
    nx, ny, nz = model_cfg.voxel.grid_size
    assert (nx, ny, nz) == (432, 496, 1)
    # anchors :83-110
    names = [a.name for a in model_cfg.anchor_classes]
    assert names == ["Car", "Pedestrian", "Cyclist"]
    assert model_cfg.anchor_classes[0].size == (3.9, 1.6, 1.56)
    assert model_cfg.anchor_classes[0].bottom_z == -1.78
    assert model_cfg.anchor_classes[1].matched_thresh == 0.5
    assert pipe_cfg.class_names == ("Car", "Pedestrian", "Cyclist")


def test_nusc_centerpoint_yaml():
    name, model_cfg, pipe_cfg = detect3d_from_yaml(REPO_NUSC)
    assert name == "centerpoint"
    assert model_cfg.voxel.voxel_size == (0.2, 0.2, 8.0)
    assert model_cfg.with_velocity is True
    assert len(model_cfg.class_names) == 10
    assert pipe_cfg.iou_thresh == 0.2
    assert pipe_cfg.class_names == tuple(model_cfg.class_names)


def test_kitti_second_yaml():
    name, model_cfg, _ = detect3d_from_yaml(REPO_SECOND)
    assert name == "second_iou"
    assert model_cfg.voxel.max_voxels == 40000
    assert model_cfg.voxel.max_points_per_voxel == 5


def test_unknown_key_fails_loudly(tmp_path):
    p = tmp_path / "bad.yaml"
    p.write_text("model: pointpillars\nvfe_filterz: 64\n")
    with pytest.raises(KeyError, match="vfe_filterz"):
        detect3d_from_yaml(str(p))


def test_anchors_on_anchor_free_model_rejected():
    with pytest.raises(ValueError, match="anchor-free"):
        model_config_from_dict(
            "centerpoint",
            {"anchors": [{"name": "car", "size": [1, 1, 1], "bottom_z": 0.0}]},
        )


def test_model_override_fields():
    cfg = model_config_from_dict(
        "pointpillars", {"vfe_filters": 32, "backbone_filters": [32, 64, 128]}
    )
    assert cfg.vfe_filters == 32
    assert cfg.backbone_filters == (32, 64, 128)


def test_yaml_configs_build_pipelines():
    """The repo YAML files must actually construct models (shape sanity —
    catches grid/anchor drift against the dataclass contracts)."""
    from triton_client_tpu.models.pointpillars import generate_anchors

    _, model_cfg, _ = detect3d_from_yaml(REPO_KITTI)
    anchors = generate_anchors(model_cfg)
    h, w = model_cfg.head_hw
    assert anchors.shape == (h, w, 6, 7)
    assert np.isfinite(np.asarray(anchors)).all()


def test_client_params_defaults_and_file():
    params = client_params()
    assert params["channel"] == "tpu"
    params = client_params("data/client_parameter.yaml")
    assert "sub_topic" in params and "pub_topic" in params


def test_voxel_from_dict_unknown_key_fails():
    with pytest.raises(KeyError, match="max_voxelz"):
        voxel_from_dict({"max_voxelz": 99})


def test_anchor_class_unknown_key_fails(tmp_path):
    doc = load_yaml(REPO_KITTI)
    doc["anchors"][0]["bottomz"] = -1.0
    p = tmp_path / "bad.yaml"
    p.write_text(yaml.safe_dump(doc))
    with pytest.raises(KeyError, match="bottomz"):
        detect3d_from_yaml(str(p))


def test_kitti_pointpillars_capacity_yaml():
    """examples/pointpillar_wide serves the measured pp_capacity
    configuration (6.8x FLOPs, -18% throughput) — the yaml must
    reproduce those hyperparameters on the unchanged reference grid."""
    name, model_cfg, pipe_cfg = detect3d_from_yaml(
        "data/kitti_pointpillars_capacity.yaml"
    )
    assert name == "pointpillars"
    assert model_cfg.vfe_filters == 128
    assert model_cfg.backbone_filters == (128, 256, 512)
    assert model_cfg.upsample_filters == (256, 256, 256)
    assert model_cfg.backbone_layers == (6, 10, 10)
    # grid unchanged vs the base entry (same anchors/range)
    base_name, base_cfg, _ = detect3d_from_yaml("data/kitti_pointpillars.yaml")
    assert model_cfg.voxel == base_cfg.voxel
    assert model_cfg.anchor_classes == base_cfg.anchor_classes
