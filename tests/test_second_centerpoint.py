"""SECOND-IoU (dense middle encoder) and CenterPoint (center heatmap).

Reference parity targets: examples/second_iou/* (OpenPCDet spconv model
behind Triton) and the det3d CenterPoint path
(clients/preprocess/voxelize.py, data/nusc_centerpoint_pp_*.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_client_tpu.models.centerpoint import CenterPoint, CenterPointConfig
from triton_client_tpu.models.second import (
    SECONDConfig,
    SECONDIoU,
    init_second,
    scatter_to_volume,
)
from triton_client_tpu.ops.voxelize import VoxelConfig

TINY_SECOND = SECONDConfig(
    voxel=VoxelConfig(
        point_cloud_range=(0.0, -8.0, -3.0, 16.0, 8.0, 1.0),
        voxel_size=(0.5, 0.5, 0.5),
        max_voxels=256,
        max_points_per_voxel=5,
    ),
    middle_filters=(8, 16),
    backbone_layers=(1, 1),
    backbone_strides=(1, 2),
    backbone_filters=(16, 32),
    upsample_strides=(1, 2),
    upsample_filters=(16, 16),
)

TINY_CENTERPOINT = CenterPointConfig(
    voxel=VoxelConfig(
        point_cloud_range=(-8.0, -8.0, -5.0, 8.0, 8.0, 3.0),
        voxel_size=(0.5, 0.5, 8.0),
        max_voxels=256,
        max_points_per_voxel=8,
    ),
    vfe_filters=16,
    backbone_layers=(1, 1),
    backbone_strides=(1, 2),
    backbone_filters=(16, 32),
    upsample_strides=(1, 2),
    upsample_filters=(16, 16),
    head_width=16,
    max_objects=16,
)


def test_scatter_to_volume_places_and_dumps():
    feats = jnp.asarray([[1.0, 2.0], [3.0, 4.0], [9.0, 9.0]])
    coords = jnp.asarray([[1, 2, 3], [0, 0, 0], [-1, -1, -1]], jnp.int32)
    vol = scatter_to_volume(feats, coords, (2, 4, 5))
    assert vol.shape == (2, 4, 5, 2)
    np.testing.assert_array_equal(np.asarray(vol[1, 2, 3]), [1.0, 2.0])
    np.testing.assert_array_equal(np.asarray(vol[0, 0, 0]), [3.0, 4.0])
    # Invalid voxel must not leak anywhere.
    assert float(jnp.abs(vol).sum()) == pytest.approx(10.0)


class TestSECOND:
    @pytest.fixture(scope="class")
    def model_and_vars(self):
        return init_second(jax.random.PRNGKey(0), TINY_SECOND)

    @pytest.mark.slow
    def test_head_shapes(self, model_and_vars):
        model, variables = model_and_vars
        cfg = TINY_SECOND
        v, k = cfg.voxel.max_voxels, cfg.voxel.max_points_per_voxel
        heads = model.apply(
            variables,
            jnp.zeros((1, v, k, 4)),
            jnp.zeros((1, v), jnp.int32),
            jnp.full((1, v, 3), -1, jnp.int32),
            train=False,
        )
        h, w = cfg.head_hw
        a = cfg.anchors_per_loc
        assert heads["cls"].shape == (1, h, w, a, cfg.num_classes)
        assert heads["box"].shape == (1, h, w, a, 7)
        assert heads["iou"].shape == (1, h, w, a)

    @pytest.mark.slow
    def test_from_points_matches_grouped(self, model_and_vars, rng):
        """SECOND's scatter mean VFE keys on the full 3D cell id, so it
        must match the grouped path on this tall (nz = 8) grid while the
        voxel budgets hold."""
        from triton_client_tpu.ops.voxelize import pad_points, voxelize

        model, variables = model_and_vars
        r = TINY_SECOND.voxel.point_cloud_range
        n = 150  # sparse cells: must stay under the 256-voxel budget
        pts = np.empty((n, 4), np.float32)
        pts[:, 0] = rng.uniform(r[0], r[3], n)
        pts[:, 1] = rng.uniform(r[1], r[4], n)
        pts[:, 2] = rng.uniform(r[2], r[5], n)
        pts[:, 3] = rng.uniform(0, 1, n)
        padded, m = pad_points(pts, 512)
        pj, mj = jnp.asarray(padded), jnp.asarray(m)
        vox = voxelize(pj, mj, TINY_SECOND.voxel)
        assert int(vox["voxel_valid"].sum()) < TINY_SECOND.voxel.max_voxels
        grouped = model.apply(
            variables,
            vox["voxels"][None],
            vox["num_points_per_voxel"][None],
            vox["coords"][None],
            train=False,
        )
        scatter = model.apply(
            variables, pj, mj, train=False, method=model.from_points
        )
        for k in grouped:
            np.testing.assert_allclose(
                np.asarray(grouped[k]), np.asarray(scatter[k]), atol=1e-4,
                err_msg=f"head {k}",
            )

    def test_pipeline_routes_scatter_for_tall_grid(self):
        """Detect3DConfig.vfe='auto' must pick the scatter path for
        SECOND despite nz > 1 (scatter_any_nz)."""
        from triton_client_tpu.pipelines.detect3d import (
            Detect3DConfig,
            build_second_pipeline,
        )

        pipe, _, _ = build_second_pipeline(
            jax.random.PRNGKey(0),
            model_cfg=TINY_SECOND,
            config=Detect3DConfig(
                model_name="second_iou", point_buckets=(512,),
                max_det=8, pre_max=16,
            ),
        )
        assert pipe.model.scatter_any_nz
        out = pipe.infer(np.zeros((32, 4), np.float32))
        assert "pred_boxes" in out

    def test_decode_rectifies_scores(self, model_and_vars):
        model, _ = model_and_vars
        cfg = TINY_SECOND
        h, w = cfg.head_hw
        a = cfg.anchors_per_loc
        heads = {
            "cls": jnp.full((1, h, w, a, cfg.num_classes), 2.0),  # sigmoid=0.881
            "box": jnp.zeros((1, h, w, a, 7)),
            "dir": jnp.concatenate(
                [jnp.ones((1, h, w, a, 1)), jnp.zeros((1, h, w, a, 1))], -1
            ),
            "iou": jnp.full((1, h, w, a), 1.0),  # q = 1.0
        }
        out = model.decode(heads)
        # q=1 -> score = cls^(1-alpha).
        expect = jax.nn.sigmoid(2.0) ** (1 - cfg.iou_alpha)
        np.testing.assert_allclose(
            np.asarray(out["scores"]).max(), float(expect), rtol=1e-5
        )
        # iou=-1 -> q clipped to ~0 -> score collapses.
        heads["iou"] = jnp.full((1, h, w, a), -1.0)
        low = model.decode(heads)
        assert np.asarray(low["scores"]).max() < 1e-3

    @pytest.mark.slow
    def test_zero_deltas_decode_to_anchors(self, model_and_vars):
        from triton_client_tpu.models.pointpillars import generate_anchors

        model, _ = model_and_vars
        cfg = TINY_SECOND
        h, w = cfg.head_hw
        a = cfg.anchors_per_loc
        heads = {
            "cls": jnp.zeros((1, h, w, a, cfg.num_classes)),
            "box": jnp.zeros((1, h, w, a, 7)),
            "dir": jnp.concatenate(
                [jnp.ones((1, h, w, a, 1)), jnp.zeros((1, h, w, a, 1))], -1
            ),
            "iou": jnp.zeros((1, h, w, a)),
        }
        out = model.decode(heads)
        anchors = np.asarray(generate_anchors(cfg)).reshape(-1, 7)
        np.testing.assert_allclose(
            np.asarray(out["boxes"][0, :, :6]), anchors[:, :6], atol=1e-4
        )

    @pytest.mark.slow
    def test_pipeline_end_to_end(self):
        from triton_client_tpu.pipelines.detect3d import (
            Detect3DConfig,
            build_second_pipeline,
        )

        pipeline, spec, _ = build_second_pipeline(
            jax.random.PRNGKey(0),
            model_cfg=TINY_SECOND,
            config=Detect3DConfig(
                model_name="second_iou", point_buckets=(2048,), max_det=32, pre_max=64
            ),
        )
        assert spec.extra["iou_alpha"] == TINY_SECOND.iou_alpha
        rng = np.random.default_rng(0)
        pts = np.column_stack(
            [
                rng.uniform(0, 16, 500),
                rng.uniform(-8, 8, 500),
                rng.uniform(-3, 1, 500),
                rng.uniform(0, 1, 500),
            ]
        ).astype(np.float32)
        out = pipeline.infer(pts)
        assert out["pred_boxes"].shape[1] == 7
        assert (out["pred_labels"] >= 1).all() if len(out["pred_labels"]) else True


class TestCenterPoint:
    def test_decode_planted_peak(self):
        """Hand-crafted heads -> exact world-space box recovery."""
        cfg = TINY_CENTERPOINT
        model = CenterPoint(cfg)
        h, w = cfg.head_hw
        nc = cfg.num_classes
        heat = jnp.full((1, h, w, nc), -10.0)
        heat = heat.at[0, 5, 7, 3].set(6.0)  # strong peak, class 3
        heads = {
            "heatmap": heat,
            "offset": jnp.full((1, h, w, 2), 0.5),
            "height": jnp.full((1, h, w, 1), -1.0),
            "size": jnp.log(jnp.broadcast_to(jnp.asarray([4.0, 2.0, 1.5]), (1, h, w, 3))),
            "rot": jnp.broadcast_to(
                jnp.asarray([np.sin(0.3), np.cos(0.3)]), (1, h, w, 2)
            ),
            "vel": jnp.full((1, h, w, 2), 0.25),
        }
        out = model.decode(heads)
        boxes = np.asarray(out["boxes"])
        scores = np.asarray(out["scores"])
        # Top candidate is the planted peak.
        assert scores[0, 0, 3] == pytest.approx(float(jax.nn.sigmoid(6.0)), rel=1e-5)
        assert scores[0, 0].argmax() == 3
        vs, r = cfg.voxel.voxel_size, cfg.voxel.point_cloud_range
        s = cfg.head_stride
        np.testing.assert_allclose(
            boxes[0, 0, 0], (7 + 0.5) * s * vs[0] + r[0], rtol=1e-5
        )
        np.testing.assert_allclose(
            boxes[0, 0, 1], (5 + 0.5) * s * vs[1] + r[1], rtol=1e-5
        )
        np.testing.assert_allclose(boxes[0, 0, 2], -1.0, rtol=1e-5)
        np.testing.assert_allclose(boxes[0, 0, 3:6], [4.0, 2.0, 1.5], rtol=1e-5)
        np.testing.assert_allclose(boxes[0, 0, 6], 0.3, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out["velocity"])[0, 0], [0.25, 0.25])

    def test_peak_nms_suppresses_plateau_neighbors(self):
        cfg = TINY_CENTERPOINT
        model = CenterPoint(cfg)
        h, w = cfg.head_hw
        nc = cfg.num_classes
        heat = jnp.full((1, h, w, nc), -10.0)
        # A dominant peak and a weaker 8-neighbor: only the peak survives.
        heat = heat.at[0, 5, 7, 0].set(6.0)
        heat = heat.at[0, 5, 8, 0].set(5.0)
        heads = {
            "heatmap": heat,
            "offset": jnp.zeros((1, h, w, 2)),
            "height": jnp.zeros((1, h, w, 1)),
            "size": jnp.zeros((1, h, w, 3)),
            "rot": jnp.broadcast_to(jnp.asarray([0.0, 1.0]), (1, h, w, 2)),
            "vel": jnp.zeros((1, h, w, 2)),
        }
        out = model.decode(heads)
        scores = np.asarray(out["scores"]).max(-1)[0]
        strong = (scores > 0.9).sum()
        assert strong == 1  # the neighbor was pooled away

    @pytest.mark.slow
    def test_pipeline_end_to_end(self):
        from triton_client_tpu.pipelines.detect3d import (
            Detect3DConfig,
            build_centerpoint_pipeline,
        )

        pipeline, spec, _ = build_centerpoint_pipeline(
            jax.random.PRNGKey(0),
            model_cfg=TINY_CENTERPOINT,
            config=Detect3DConfig(
                model_name="centerpoint",
                class_names=TINY_CENTERPOINT.class_names,
                point_buckets=(2048,),
                max_det=16,
                pre_max=32,
                iou_thresh=0.2,
            ),
        )
        assert spec.extra["with_velocity"] is True
        rng = np.random.default_rng(1)
        pts = np.column_stack(
            [
                rng.uniform(-8, 8, 400),
                rng.uniform(-8, 8, 400),
                rng.uniform(-5, 3, 400),
                rng.uniform(0, 1, 400),
            ]
        ).astype(np.float32)
        out = pipeline.infer(pts)
        assert out["pred_boxes"].shape[1] == 7
        assert out["pred_scores"].shape == out["pred_labels"].shape


def test_second_decode_topk_matches_full_decode_path():
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_client_tpu.models.second import SECONDConfig, init_second
    from triton_client_tpu.ops.detect3d_postprocess import (
        extract_boxes_3d,
        nms_pack_3d,
    )
    from triton_client_tpu.ops.voxelize import VoxelConfig

    cfg = SECONDConfig(
        voxel=dataclasses.replace(
            VoxelConfig(),
            point_cloud_range=(0.0, -10.24, -3.0, 20.48, 10.24, 1.0),
            max_voxels=128,
        )
    )
    model, variables = init_second(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    v = cfg.voxel
    voxels = jnp.asarray(
        rng.standard_normal((1, v.max_voxels, v.max_points_per_voxel, 4)),
        jnp.float32,
    )
    nums = jnp.asarray(
        rng.integers(0, v.max_points_per_voxel, (1, v.max_voxels)), jnp.int32
    )
    nx, ny, _ = v.grid_size
    coords = jnp.stack(
        [
            jnp.asarray(rng.integers(0, nx, (1, v.max_voxels)), jnp.int32),
            jnp.asarray(rng.integers(0, ny, (1, v.max_voxels)), jnp.int32),
            jnp.zeros((1, v.max_voxels), jnp.int32),
        ],
        axis=-1,
    )
    heads = model.apply(variables, voxels, nums, coords, train=False)

    pred = model.decode(heads)
    ref_dets, ref_valid = extract_boxes_3d(
        pred["boxes"], pred["scores"], score_thresh=0.05, iou_thresh=0.2,
        max_det=32, pre_max=128,
    )
    cand = model.decode_topk(heads, pre_max=128, score_thresh=0.05)
    fast_dets, fast_valid = nms_pack_3d(
        cand["boxes"], cand["scores"], cand["labels"],
        iou_thresh=0.2, max_det=32,
    )
    np.testing.assert_array_equal(np.asarray(ref_valid), np.asarray(fast_valid))
    np.testing.assert_allclose(
        np.asarray(ref_dets), np.asarray(fast_dets), atol=1e-5
    )


def test_non_divisible_grid_rejected_at_build():
    """A voxel size whose BEV grid doesn't divide the composed stride
    (e.g. 0.15 m over the 70.4x80 m KITTI range -> 469x533) must fail
    loudly at init, not as a reshape error mid-trace (a grid sweep
    found the silent variant)."""
    from triton_client_tpu.models.second import SECONDConfig, init_second
    from triton_client_tpu.ops.voxelize import VoxelConfig

    bad = SECONDConfig(
        voxel=VoxelConfig(
            point_cloud_range=(0.0, -40.0, -3.0, 70.4, 40.0, 1.0),
            voxel_size=(0.15, 0.15, 0.3),
            max_voxels=512,
            max_points_per_voxel=4,
        )
    )
    with pytest.raises(ValueError, match="divisible"):
        init_second(jax.random.PRNGKey(0), bad)

    # the direct flax path (no init_* wrapper) is guarded too: setup()
    # validates, so model.init fails loudly before any trace math
    from triton_client_tpu.models.second import SECONDIoU

    with pytest.raises(ValueError, match="divisible"):
        SECONDIoU(bad).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 8, 4, 4)),
            jnp.zeros((1, 8), jnp.int32),
            jnp.full((1, 8, 3), -1, jnp.int32),
            train=False,
        )

    # 0.1 m divides -> accepted (shape-only check, no forward)
    ok = SECONDConfig(
        voxel=VoxelConfig(
            point_cloud_range=(0.0, -40.0, -3.0, 70.4, 40.0, 1.0),
            voxel_size=(0.1, 0.1, 0.2),
            max_voxels=512,
            max_points_per_voxel=4,
        )
    )
    ok.validate()
