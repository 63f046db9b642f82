"""3D detection pipeline: raw point cloud in, packed 3D boxes out.

The reference's 3D path spans three processes (client voxelizes on CPU
via OpenPCDet, ships dynamic-shaped tensors over gRPC, the server runs
the network; SURVEY.md section 3.2/3.3). Here voxelize -> VFE -> scatter
-> backbone -> head -> rotated NMS is ONE jitted program on static
budgets: the host only pads the raw cloud to the point budget
(pad_points) and reads back (max_det, 9) rows.

Bucketed padding: ``point_buckets`` trades recompiles for wasted
compute — clouds are padded up to the smallest bucket that fits, so
the jit caches one executable per bucket instead of one per frame
(the reference instead rewrites request shapes every frame,
communicator/ros_inference3d.py:131-139).
"""

from __future__ import annotations

import bisect
import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.models.pointpillars import (
    PointPillars,
    PointPillarsConfig,
    init_pointpillars,
)
from triton_client_tpu.ops.detect3d_postprocess import (
    extract_boxes_3d,
    nms_pack_3d,
)
from triton_client_tpu.ops import fused as fused_routing
from triton_client_tpu.ops.pallas_decode import fused_residual_decode
from triton_client_tpu.ops.pallas_voxel import fused_mean_volume
from triton_client_tpu.ops.voxelize import pad_points, voxelize
from triton_client_tpu.runtime.precision import (
    KEEP_F32_3D,
    PrecisionPolicy,
    realize,
    resolve_policy,
)


@dataclasses.dataclass(frozen=True)
class Detect3DConfig:
    model_name: str = "pointpillars"
    score_thresh: float = 0.1
    iou_thresh: float = 0.01
    max_det: int = 128
    # NMS candidate width (top-k on raw logits before box decode).
    # 256 measured mAP-identical to 512 on the trained closed-loop
    # model while saving ~1.7 ms/scan — the rotated-IoU matrix is
    # quadratic in this; raise it
    # for scenes with hundreds of above-threshold objects
    pre_max: int = 256
    point_buckets: tuple[int, ...] = (32768, 65536, 131072)
    # Sensor-height z correction added to incoming points before
    # voxelization (reference driver parity: ros_inference3d.py:126-128
    # adds 1.5 m for its lidar mount)
    z_offset: float = 0.0
    class_names: tuple[str, ...] = ("Car", "Pedestrian", "Cyclist")
    # Sweeps aggregated per inference by the stream layer (ops/sweeps
    # .py sweep_source): 1 = single scan (KITTI), 10 = the reference's
    # nuScenes CenterPoint config. The pipeline itself always consumes
    # ONE aggregated cloud; this field carries the dataset default to
    # the CLI/driver layer.
    nsweeps: int = 1
    # VFE routing: "auto" uses the model's sort-free from_points path
    # when it has one — pillar models on nz == 1 grids, plus models
    # that declare scatter_any_nz (SECOND's mean VFE keys on the full
    # 3D cell, so tall grids route scatter too). "grouped" forces the
    # (V, K) voxelizer contract (exact OpenPCDet budget semantics —
    # caps at max_voxels/max_points_per_voxel; the scatter path keeps
    # all points, which can only add information).
    vfe: str = "auto"
    # Fused Pallas hot-path routing (ops/fused): "auto" fuses the
    # eligible stages on a real TPU backend (subject to the
    # TPU_FUSED_KERNELS env allowlist), "on" forces them everywhere
    # (interpret mode off-TPU — the parity matrix), "off" is the
    # spec-level opt-out. Resolved per stage at build time and
    # published as spec.extra["fused_stages"].
    fused: str = "auto"


class Detect3DPipeline:
    def __init__(
        self,
        config: Detect3DConfig,
        model: PointPillars,
        variables,
        precision: PrecisionPolicy | str | None = None,
    ) -> None:
        self.config = config
        self.model = model
        self.variables = variables
        # KEEP_F32_3D contract: the raw cloud stays f32 on the wire no
        # matter the policy — voxelize derives integer cell coords from
        # point xyz, and a bf16/int8 coordinate flips cells. int8
        # activation quantization is therefore a no-op for 3D (weights
        # still quantize); bf16 narrows the model, not the points.
        policy = PrecisionPolicy.parse(precision)
        if "points" not in policy.keep_f32_inputs:
            policy = dataclasses.replace(
                policy, keep_f32_inputs=policy.keep_f32_inputs + ("points",)
            )
        self.precision = policy
        if config.vfe not in ("auto", "grouped"):
            raise ValueError(f"unknown vfe mode {config.vfe!r} (auto|grouped)")
        # pillar scatter VFE is nz == 1 only (a taller grid's z cells
        # would merge silently), so auto falls back to grouped there;
        # models whose scatter path keys on the full 3D cell (SECOND's
        # mean VFE) declare scatter_any_nz
        self.use_scatter = (
            config.vfe == "auto"
            and hasattr(model, "from_points")
            and (
                model.cfg.voxel.grid_size[2] == 1
                or getattr(model, "scatter_any_nz", False)
            )
        )
        if self.use_scatter:
            logger.info(
                "vfe=auto routes %s to the sort-free scatter VFE: all points "
                "and pillars are kept, so outputs differ from the OpenPCDet "
                "budget contract (max_voxels/max_points_per_voxel caps) "
                "whenever budgets would have been exceeded; use vfe='grouped' "
                "for exact reference budget semantics",
                config.model_name,
            )
        # fused-stage eligibility is structural (which model surfaces
        # exist), the routing decision layers env + config + backend on
        # top (ops/fused). voxelize_scatter needs the dense-middle
        # scatter VFE (fused_mean_volume is _scatter_mean_volume's
        # twin); decode_nms applies to every 3D tail.
        candidates = ("decode_nms",)
        if (
            self.use_scatter
            and getattr(model, "scatter_any_nz", False)
            and getattr(model.cfg, "middle", None) == "dense"
            and hasattr(model, "from_volume")
        ):
            candidates = ("voxelize_scatter",) + candidates
        self.fused_stages = fused_routing.resolve_fused_stages(
            config.fused, candidates
        )
        if "voxelize_scatter" in self.fused_stages:
            logger.info(
                "fused voxelize->scatter caps occupied cells at max_voxels "
                "(%d) — the grouped/OpenPCDet budget contract; the XLA "
                "scatter path it replaces keeps every occupied cell, so "
                "outputs differ once a scan exceeds the budget",
                model.cfg.voxel.max_voxels,
            )
        self._jit = jax.jit(self._pipeline)

    def _pipeline(self, points: jnp.ndarray, count: jnp.ndarray):
        cfg = self.config
        use_scatter = self.use_scatter
        # int8 kernels dequantize inside the trace (runtime/precision.py
        # realize — HBM reads stay int8); voxelize below always sees the
        # f32 cloud (KEEP_F32_3D: cell coords are precision-sensitive)
        variables = realize(self.variables)
        interpret = fused_routing.fused_interpret()
        if "voxelize_scatter" in self.fused_stages:
            # fused Pallas voxelize->scatter: sorted-segment mean via
            # MXU one-hot matmuls + unique-index set-scatter epilogue,
            # replacing the XLA scatter-add that dominates the dense
            # SECOND front (ops/pallas_voxel module docstring)
            volume = fused_mean_volume(
                points, count, self.model.cfg.voxel, interpret=interpret
            )
            heads = self.model.apply(
                variables, volume, train=False, method=self.model.from_volume
            )
        elif use_scatter:
            # sort-free path: pillar mean/max as dense-grid scatters,
            # no (V, K) grouping (see PointPillars.from_points)
            heads = self.model.apply(
                variables, points, count, train=False,
                method=self.model.from_points,
            )
        else:
            vox = voxelize(points, count, self.model.cfg.voxel)
            heads = self.model.apply(
                variables,
                vox["voxels"][None],
                vox["num_points_per_voxel"][None],
                vox["coords"][None],
                train=False,
            )
        # keep-list boundary: box decode and NMS scoring below run in
        # f32 regardless of the model compute dtype
        heads = self.precision.boundary(heads)
        fuse_tail = "decode_nms" in self.fused_stages
        if hasattr(self.model, "decode_topk"):
            # Fast path: gate + top-k on raw logits BEFORE box decode —
            # only pre_max boxes are ever decoded (see decode_topk).
            if fuse_tail and hasattr(self.model, "topk_candidates"):
                # fused tail: residual decode + rectify as ONE
                # elementwise launch, then suppression + packing as
                # another (ops/pallas_decode) — detections never leave
                # the device between stages
                tc = self.model.topk_candidates(
                    heads, pre_max=cfg.pre_max, score_thresh=cfg.score_thresh
                )
                mc = self.model.cfg
                boxes = jax.vmap(
                    lambda d, a, db: fused_residual_decode(
                        d, a, db,
                        num_dir_bins=mc.num_dir_bins,
                        dir_offset=mc.dir_offset,
                        interpret=interpret,
                    )
                )(tc["deltas"], tc["anchors"], tc["dir_bin"])
                cand = {
                    "boxes": boxes,
                    "scores": tc["scores"],
                    "labels": tc["labels"],
                }
            else:
                cand = self.model.decode_topk(
                    heads, pre_max=cfg.pre_max, score_thresh=cfg.score_thresh
                )
            dets, valid = nms_pack_3d(
                cand["boxes"],
                cand["scores"],
                cand["labels"],
                iou_thresh=cfg.iou_thresh,
                max_det=cfg.max_det,
                fused=fuse_tail,
                interpret=interpret,
            )
        else:
            pred = self.model.decode(heads)
            boxes = pred["boxes"]
            if "velocity" in pred:
                # ride-along columns: velocity survives NMS packing and
                # surfaces as pred_velocities (the det3d wire carries
                # vx/vy the same way for CenterPoint)
                boxes = jnp.concatenate([boxes, pred["velocity"]], axis=-1)
            dets, valid = extract_boxes_3d(
                boxes,
                pred["scores"],
                score_thresh=cfg.score_thresh,
                iou_thresh=cfg.iou_thresh,
                max_det=cfg.max_det,
                pre_max=cfg.pre_max,
                fused=fuse_tail,
                interpret=interpret,
            )
        return dets[0], valid[0]

    def infer(self, points: np.ndarray) -> dict[str, np.ndarray]:
        """points: (M, 4+) raw cloud [x, y, z, intensity, ...]. Returns
        the reference 3D client contract: pred_boxes (n, 7), pred_scores
        (n,), pred_labels (n,) — n = live detections."""
        return self.infer_dispatch(points).result()

    def infer_dispatch(self, points: np.ndarray):
        """Async half of infer (the driver's --async path): host prep +
        jit enqueue happen here; the returned future's result() performs
        the only blocking step (device->host read + packing), so callers
        can overlap the next scan's prep with this scan's compute."""
        from triton_client_tpu.channel.base import InferFuture

        buckets = self.config.point_buckets
        i = bisect.bisect_left(buckets, points.shape[0])
        budget = buckets[min(i, len(buckets) - 1)]
        if points.shape[0] > budget:
            logger.warning(
                "point cloud (%d pts) exceeds largest bucket (%d); tail "
                "points dropped — raise Detect3DConfig.point_buckets",
                points.shape[0],
                budget,
            )
        # astype(copy=True default) always returns a fresh array, so the
        # in-place z shift below never aliases caller memory.
        pf = self.model.cfg.voxel.point_features
        points = points[:, :pf].astype(np.float32)
        if points.shape[1] < pf:
            # narrower cloud than the model's VFE contract: zero-fill
            # the missing trailing channels — a single sweep's Δt=0,
            # exactly the reference's zero-padded time column
            # (clients/preprocess/voxelize.py:38-40)
            points = np.pad(points, ((0, 0), (0, pf - points.shape[1])))
        if self.config.z_offset:
            points[:, 2] += self.config.z_offset
        padded, m = pad_points(points, budget)
        dets, valid = self._jit(jnp.asarray(padded), jnp.asarray(m))

        def resolve() -> dict[str, np.ndarray]:
            d, v = np.asarray(dets), np.asarray(valid)
            live = d[v]
            # rows are [box7, extras..., score, label]; whether the
            # extras are CenterPoint's (vx, vy) is a model-config fact,
            # not a row-width guess
            w = live.shape[1]
            out = {
                "pred_boxes": live[:, :7],
                "pred_scores": live[:, w - 2],
                "pred_labels": live[:, w - 1].astype(np.int32),
            }
            if getattr(self.model.cfg, "with_velocity", False):
                out["pred_velocities"] = live[:, 7:9]
            return out

        return InferFuture(resolve)

    def infer_fn(self):
        """Repository-facing adapter over the padded static contract.
        CenterPoint's velocity head additionally surfaces as a NAMED
        ``velocities`` output — the packed-row slice stays a device
        view, so remote clients (and the session tracker's motion seed)
        address it without knowing the row layout."""
        wv = getattr(self.model.cfg, "with_velocity", False)

        def fn(inputs):
            dets, valid = self._jit(inputs["points"], inputs["num_points"])
            out = {"detections": dets, "valid": valid}
            if wv:
                out["velocities"] = dets[:, 7:9]
            return out

        return fn

    def device_fn(self):
        """Jit-traceable form (runtime/ensemble.py fused DAGs): same
        padded static contract as infer_fn, composed via the unjitted
        pipeline so a parent ensemble's single XLA program inlines it —
        e.g. an aggregation/compensation step chained into a 3D
        detector keeps the padded cloud in HBM between members."""

        wv = getattr(self.model.cfg, "with_velocity", False)

        def fn(inputs):
            dets, valid = self._pipeline(
                inputs["points"], inputs["num_points"]
            )
            out = {"detections": dets, "valid": valid}
            if wv:
                out["velocities"] = dets[:, 7:9]
            return out

        return fn


def _detect3d_spec(
    cfg: Detect3DConfig, model_cfg, extra: dict | None = None
) -> ModelSpec:
    """Serving spec shared by every 3D pipeline (the analogue of
    examples/pointpillar_kitti/config.pbtxt + examples/second_iou).
    Detection rows are [box7, extras..., score, label]; CenterPoint's
    velocity rides as 2 extra columns."""
    n_extra = 2 if (extra or {}).get("with_velocity") else 0
    pf = model_cfg.voxel.point_features
    return ModelSpec(
        name=cfg.model_name,
        version="1",
        platform="jax",
        inputs=(
            # donatable: the voxelizer consumes the staged scan exactly
            # once, so the serving channel may recycle the HBM buffer
            # across consecutive scans (channel/tpu_channel.py).
            TensorSpec("points", (-1, pf), "FP32", donatable=True),
            TensorSpec("num_points", (), "INT32"),
        ),
        outputs=(
            TensorSpec("detections", (cfg.max_det, 9 + n_extra), "FP32"),
            TensorSpec("valid", (cfg.max_det,), "BOOL"),
        )
        + (
            # the velocity head's named surface (a view of detection
            # columns 7:9) — present exactly when with_velocity, so the
            # spec and the infer_fn output set never disagree
            (TensorSpec("velocities", (cfg.max_det, 2), "FP32"),)
            if n_extra
            else ()
        ),
        extra={
            "score_thresh": cfg.score_thresh,
            "iou_thresh": cfg.iou_thresh,
            # every in-repo 3D spec states velocity presence explicitly
            # so remote clients never have to sniff the row width
            "with_velocity": n_extra > 0,
            "class_names": list(cfg.class_names),
            "max_voxels": model_cfg.voxel.max_voxels,
            # Remote clients self-configure host-side prep from the
            # served metadata (the reference's parse_model pattern,
            # clients/detector_3d_client.py:28-91): pad buckets + the
            # sensor z correction applied before the padded contract.
            "point_buckets": list(cfg.point_buckets),
            "z_offset": cfg.z_offset,
            **(extra or {}),
        },
    )


def build_pointpillars_pipeline(
    rng: jax.Array | None = None,
    model_cfg: PointPillarsConfig | None = None,
    config: Detect3DConfig | None = None,
    variables=None,
    dtype: jnp.dtype = jnp.float32,
    precision: PrecisionPolicy | str | None = None,
) -> tuple[Detect3DPipeline, ModelSpec, dict]:
    policy, dtype = resolve_policy(precision, dtype)
    model_cfg = model_cfg or PointPillarsConfig()
    if variables is None:
        model, variables = init_pointpillars(
            rng if rng is not None else jax.random.PRNGKey(0), model_cfg, dtype
        )
    else:
        model = PointPillars(model_cfg, dtype=dtype)
    # pipeline serves the cast tree; the UNCAST tree returns as the
    # weight-loading template (disk_repository)
    cast_vars = policy.cast_params(variables)
    cfg = config or Detect3DConfig()
    pipeline = Detect3DPipeline(cfg, model, cast_vars, precision=policy)
    spec = _detect3d_spec(cfg, model_cfg)
    spec.extra["fused_stages"] = list(pipeline.fused_stages)
    spec.extra.update(
        pipeline.precision.spec_extra(cast_vars, KEEP_F32_3D)
    )
    return pipeline, spec, variables


def build_second_pipeline(
    rng: jax.Array | None = None,
    model_cfg=None,
    config: Detect3DConfig | None = None,
    variables=None,
    dtype: jnp.dtype = jnp.float32,
    precision: PrecisionPolicy | str | None = None,
) -> tuple[Detect3DPipeline, ModelSpec, dict]:
    """SECOND-IoU over the same seam as PointPillars (the reference
    serves both from the same Triton python backend shape,
    examples/second_iou/*). Duck-typed into Detect3DPipeline: identical
    apply/decode surfaces."""
    from triton_client_tpu.models.second import SECONDConfig, SECONDIoU, init_second

    policy, dtype = resolve_policy(precision, dtype)
    model_cfg = model_cfg or SECONDConfig()
    if variables is None:
        model, variables = init_second(
            rng if rng is not None else jax.random.PRNGKey(0), model_cfg, dtype
        )
    else:
        model = SECONDIoU(model_cfg, dtype=dtype)
    cast_vars = policy.cast_params(variables)
    cfg = config or Detect3DConfig(model_name="second_iou")
    pipeline = Detect3DPipeline(cfg, model, cast_vars, precision=policy)
    spec = _detect3d_spec(cfg, model_cfg, {"iou_alpha": model_cfg.iou_alpha})
    spec.extra["fused_stages"] = list(pipeline.fused_stages)
    spec.extra.update(
        pipeline.precision.spec_extra(cast_vars, KEEP_F32_3D)
    )
    return pipeline, spec, variables


def build_centerpoint_pipeline(
    rng: jax.Array | None = None,
    model_cfg=None,
    config: Detect3DConfig | None = None,
    variables=None,
    dtype: jnp.dtype = jnp.float32,
    precision: PrecisionPolicy | str | None = None,
) -> tuple[Detect3DPipeline, ModelSpec, dict]:
    """CenterPoint-pillar, nuScenes config (the reference's det3d path,
    clients/preprocess/voxelize.py + data/nusc_centerpoint_pp...py).
    decode emits one-hot class scores so the shared rotated-NMS
    postprocess applies unchanged; with_velocity rides through the
    packed rows as 2 extra columns and surfaces as pred_velocities
    (the reference's base 3D wire carries boxes/scores/labels only,
    clients/detector_3d_client.py:29-34 — velocity is the det3d
    extension this config exists for)."""
    from triton_client_tpu.models.centerpoint import (
        CenterPointConfig,
        CenterPoint,
        init_centerpoint,
    )

    policy, dtype = resolve_policy(precision, dtype)
    model_cfg = model_cfg or CenterPointConfig()
    if variables is None:
        model, variables = init_centerpoint(
            rng if rng is not None else jax.random.PRNGKey(0), model_cfg, dtype
        )
    else:
        model = CenterPoint(model_cfg, dtype=dtype)
    cfg = config if config is not None else default_detect3d_config("centerpoint")
    # class_names derive from the MODEL config — reconcile so a caller
    # config built with the KITTI defaults can't mislabel nuScenes
    # predictions (pred_labels range over model_cfg.class_names).
    if tuple(cfg.class_names) != tuple(model_cfg.class_names):
        cfg = dataclasses.replace(cfg, class_names=tuple(model_cfg.class_names))
    cast_vars = policy.cast_params(variables)
    pipeline = Detect3DPipeline(cfg, model, cast_vars, precision=policy)
    spec = _detect3d_spec(cfg, model_cfg, {"with_velocity": model_cfg.with_velocity})
    spec.extra["fused_stages"] = list(pipeline.fused_stages)
    spec.extra.update(
        pipeline.precision.spec_extra(cast_vars, KEEP_F32_3D)
    )
    return pipeline, spec, variables


def default_detect3d_config(model_name: str) -> Detect3DConfig:
    """Single source of per-family pipeline defaults. Center-heatmap
    models pre-NMS via local peaks, so box NMS only needs to kill
    duplicate peaks (higher IoU gate)."""
    if model_name == "centerpoint":
        return Detect3DConfig(model_name=model_name, iou_thresh=0.2)
    return Detect3DConfig(model_name=model_name)


# family name -> builder; the single dispatch table shared by the CLI
# entry points and the disk model repository.
BUILDERS_3D = {
    "pointpillars": build_pointpillars_pipeline,
    "second_iou": build_second_pipeline,
    "centerpoint": build_centerpoint_pipeline,
}
