"""2D detection pipeline: raw frame(s) in, packed detections out.

Fuses the reference's five host/device hops (cv2.resize -> numpy
normalize -> gRPC -> torch NMS -> numpy rescale; SURVEY.md section 3.1)
into one XLA program per input resolution. Re-traces once per distinct
camera resolution (static shapes), then every frame is a single
dispatch.

Output contract per image: (max_det, 6) rows [x1, y1, x2, y2, conf,
class] in ORIGINAL image pixels + validity mask — the fixed-shape
analogue of the reference's variable-length list
(yolov5_postprocess.py:34 + ros_inference.py:100-115 rescale).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.models.yolov5 import YoloV5, num_predictions
from triton_client_tpu.ops.boxes import scale_boxes
from triton_client_tpu.ops.detect_postprocess import (
    extract_boxes,
    extract_boxes_scored,
)
from triton_client_tpu.ops import fused as fused_routing
from triton_client_tpu.ops.preprocess import normalize_image
from triton_client_tpu.runtime.precision import (
    KEEP_F32_2D,
    PrecisionPolicy,
    realize,
    resolve_policy,
)


@dataclasses.dataclass(frozen=True)
class Detect2DConfig:
    """Pipeline hyperparameters (reference: argparse FLAGS main.py:51-113
    + per-model thresholds ros_inference.py:148)."""

    model_name: str = "yolov5"
    input_hw: tuple[int, int] = (512, 512)
    num_classes: int = 80
    conf_thresh: float = 0.3
    iou_thresh: float = 0.45
    max_det: int = 300
    max_nms: int = 1024
    scaling: str = "yolo"
    multi_label: bool = False
    class_names: tuple[str, ...] = ()
    # "yolo": forward returns (B, N, 5+nc) obj/cls predictions.
    # "scored": forward returns ((B, N, 4) boxes, (B, N, nc) scores) —
    # the detectron family, where decode happens in the model.
    head_style: str = "yolo"
    # Fused Pallas decode+NMS routing (ops/fused): "auto" fuses on a
    # real TPU backend (subject to TPU_FUSED_KERNELS), "on" forces the
    # kernel everywhere (interpret mode off-TPU — the parity matrix),
    # "off" is the spec-level opt-out. Published as
    # spec.extra["fused_stages"].
    fused: str = "auto"


class Detect2DPipeline:
    """Wraps a detector apply-fn into the fused frame->detections jit."""

    def __init__(
        self,
        config: Detect2DConfig,
        forward: Callable[[jnp.ndarray], jnp.ndarray],
        precision: PrecisionPolicy | str | None = None,
    ) -> None:
        """``forward``: (B, H, W, 3) float input -> (B, N, 5+nc) decoded
        predictions in input-pixel units. ``precision``: the serving
        PrecisionPolicy (runtime/precision.py) — ingress frames cast to
        its compute dtype, model outputs return to f32 at ``boundary()``
        before the keep-list ops (box decode / NMS / rescale)."""
        self.config = config
        self._forward = forward
        self.precision = PrecisionPolicy.parse(precision)
        self.fused_stages = fused_routing.resolve_fused_stages(
            config.fused, ("decode_nms",)
        )
        self._jit = jax.jit(self._pipeline, static_argnames=("orig_hw",))

    def _pipeline(
        self, frames: jnp.ndarray, orig_hw: tuple[int, int]
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        return self._pipeline_counted(frames, orig_hw)[:2]

    def _pipeline_counted(self, frames: jnp.ndarray, orig_hw: tuple[int, int]):
        """``(detections, valid, steps)``: ``steps`` is the fused
        kernel's greedy steps a group of eight frames (None where the
        tail is not fused), which ``device_fn`` hands the serving
        channel for its counters."""
        cfg = self.config
        # policy compute dtype (f32 legacy; bf16 halves the resize/
        # normalize/forward HBM traffic). Wire inputs may arrive already
        # narrowed (uint8 frames, bf16 words, dequantized int8) — the
        # cast fuses into the first op either way.
        x = self.precision.cast_in(frames)
        if orig_hw != cfg.input_hw:
            b = x.shape[0]
            x = jax.image.resize(
                x, (b, cfg.input_hw[0], cfg.input_hw[1], 3), method="bilinear"
            )
        x = normalize_image(x, cfg.scaling)
        # keep-list boundary (KEEP_F32_2D, declared in the spec): box
        # decode, NMS scoring and pixel rescale below run in f32
        # regardless of policy
        pred = self.precision.boundary(self._forward(x))
        fuse_tail = "decode_nms" in self.fused_stages
        interpret = fused_routing.fused_interpret()
        if cfg.head_style == "scored":
            dets, valid, steps = extract_boxes_scored(
                *pred,
                conf_thresh=cfg.conf_thresh,
                iou_thresh=cfg.iou_thresh,
                max_det=cfg.max_det,
                max_nms=cfg.max_nms,
                multi_label=cfg.multi_label,
                fused=fuse_tail,
                interpret=interpret,
                return_steps=True,
            )
        else:
            dets, valid, steps = extract_boxes(
                pred,
                conf_thresh=cfg.conf_thresh,
                iou_thresh=cfg.iou_thresh,
                max_det=cfg.max_det,
                max_nms=cfg.max_nms,
                multi_label=cfg.multi_label,
                fused=fuse_tail,
                interpret=interpret,
                return_steps=True,
            )
        boxes = scale_boxes(dets[..., :4], cfg.input_hw, orig_hw)
        dets = jnp.concatenate([boxes, dets[..., 4:]], axis=-1)
        dets = jnp.where(valid[..., None], dets, 0.0)
        return dets, valid, steps

    def infer(self, frames) -> tuple[np.ndarray, np.ndarray]:
        """frames: (B, H, W, 3) or (H, W, 3) uint8/float RGB — numpy OR
        an already-device jax array (TPUChannel stages inputs on the
        mesh; jnp.asarray below is then a no-op, so the serving path
        pays ONE upload, not a device->host->device bounce). Returns
        ((B, max_det, 6), (B, max_det)) numpy; batch dim added if
        absent."""
        if not hasattr(frames, "ndim"):  # lists from host callers
            frames = np.asarray(frames)
        squeeze = frames.ndim == 3
        if squeeze:
            frames = frames[None]
        orig_hw = (frames.shape[1], frames.shape[2])
        dets, valid = self._jit(jnp.asarray(frames), orig_hw)
        dets, valid = np.asarray(dets), np.asarray(valid)
        return (dets[0], valid[0]) if squeeze else (dets, valid)

    def infer_fn(self):
        """Repository-facing dict->dict adapter. Emits the wire contract
        of the spec its builder registers: packed detections/valid for
        the YOLO family, the reference's detectron 4-output contract
        (boxes/scores/classes/dims, RetinaNet_detectron/config.pbtxt)
        for scored heads."""
        if self.config.head_style == "scored":

            def fn(inputs):
                # no np.asarray on the input: a device array from
                # TPUChannel must flow through without the
                # device->host->device bounce (see infer)
                dets, valid = self.infer(inputs["images"])
                return {
                    "boxes": dets[..., :4],
                    "scores": dets[..., 4],
                    "classes": dets[..., 5].astype(np.int64),
                    "dims": valid.sum(axis=-1).astype(np.int32),
                }

        else:

            def fn(inputs):
                dets, valid = self.infer(inputs["images"])
                return {"detections": dets, "valid": valid}

        return fn

    def device_fn(self):
        """Jit-traceable form of infer_fn: same tensor names, device
        arrays end to end, no host boundary — the member contract
        device-fused ensembles compose through (runtime/ensemble.py;
        intermediates stay in HBM instead of round-tripping host
        memory between steps). orig_hw comes off the traced shape, so
        per-resolution retracing matches the wire path's behavior. A
        fused tail's step count rides along under ``NMS_STEPS_KEY``
        (ops/fused), for the serving channel's counters."""
        scored = self.config.head_style == "scored"

        def fn(inputs):
            frames = inputs["images"]
            dets, valid, steps = self._pipeline_counted(
                frames, (frames.shape[1], frames.shape[2])
            )
            if scored:
                out = {
                    "boxes": dets[..., :4],
                    "scores": dets[..., 4],
                    "classes": dets[..., 5].astype(jnp.int32),
                    "dims": valid.sum(axis=-1).astype(jnp.int32),
                }
            else:
                out = {"detections": dets, "valid": valid}
            if steps is not None:
                out[fused_routing.NMS_STEPS_KEY] = steps
            return out

        return fn


def load_class_names(path: str) -> tuple[str, ...]:
    """data/*.names loader (one class per line; reference
    yolov5_postprocess.py:19-26)."""
    with open(path) as f:
        return tuple(line.strip() for line in f if line.strip())


def build_yolov5_pipeline(
    rng: jax.Array | None = None,
    variant: str = "n",
    num_classes: int = 80,
    input_hw: tuple[int, int] = (512, 512),
    variables=None,
    dtype: jnp.dtype = jnp.float32,
    config: Detect2DConfig | None = None,
    s2d: bool = False,
    ch_floor: int = 0,
    precision: PrecisionPolicy | str | None = None,
) -> tuple[Detect2DPipeline, ModelSpec, dict]:
    """Construct model + pipeline + serving spec in one call.

    The spec mirrors the reference's served contract
    (examples/YOLOv5/config.pbtxt: images in, [1, N, 5+nc] out) plus the
    packed-detections outputs unique to the fused pipeline.
    ``s2d``/``ch_floor`` are the MXU-shape options (models/yolov5.py) —
    identical detection function, faster chip layout. ``precision``
    selects the serving precision policy (runtime/precision.py): params
    are cast/quantized HERE, once, before registration.
    """
    policy, dtype = _resolve_precision(precision, dtype)
    model = YoloV5(
        num_classes=num_classes, variant=variant, dtype=dtype,
        s2d=s2d, ch_floor=ch_floor,
    )
    if variables is None:
        if rng is None:
            rng = jax.random.PRNGKey(0)
        dummy = jnp.zeros((1, input_hw[0], input_hw[1], 3), jnp.float32)
        variables = model.init(rng, dummy, train=False)
    # cast/quantize ONCE here; the UNCAST tree is still returned as the
    # weight-loading template (disk_repository restores checkpoints onto
    # the f32 structure, then rebuilds through this path)
    cast_vars = policy.cast_params(variables)

    def forward(x: jnp.ndarray) -> jnp.ndarray:
        # realize: int8 kernels dequantize inside the trace (HBM reads
        # stay int8); boundary: raw heads re-enter f32 BEFORE decode —
        # the KEEP_F32_2D contract
        raw = model.apply(realize(cast_vars), x, train=False)
        return model.decode(policy.boundary(raw))

    cfg = config or Detect2DConfig(
        model_name=f"yolov5{variant}", input_hw=input_hw, num_classes=num_classes
    )
    pipeline = Detect2DPipeline(cfg, forward, precision=policy)
    spec = _detect2d_spec(cfg, num_predictions(cfg.input_hw))
    spec.extra["fused_stages"] = list(pipeline.fused_stages)
    spec.extra.update(policy.spec_extra(cast_vars, KEEP_F32_2D))
    return pipeline, spec, variables


# builder-shared policy/compute-dtype resolution (runtime/precision.py)
_resolve_precision = resolve_policy


def build_yolov4_pipeline(
    rng: jax.Array | None = None,
    num_classes: int = 80,
    width: float = 1.0,
    input_hw: tuple[int, int] = (512, 512),
    variables=None,
    dtype: jnp.dtype = jnp.float32,
    config: Detect2DConfig | None = None,
    precision: PrecisionPolicy | str | None = None,
) -> tuple[Detect2DPipeline, ModelSpec, dict]:
    """YOLOv4 variant of the fused pipeline (reference contract:
    examples/YOLOv4/config.pbtxt confs+boxes; decode parity with
    tools/yolo_layer.py). The flat pixel-unit decode drops into the same
    Detect2DPipeline as YOLOv5."""
    from triton_client_tpu.models.yolov4 import YoloV4
    from triton_client_tpu.models.yolov4 import num_predictions as v4_num_predictions

    policy, dtype = _resolve_precision(precision, dtype)
    model = YoloV4(num_classes=num_classes, width=width, dtype=dtype)
    if variables is None:
        if rng is None:
            rng = jax.random.PRNGKey(0)
        dummy = jnp.zeros((1, input_hw[0], input_hw[1], 3), jnp.float32)
        variables = model.init(rng, dummy, train=False)
    cast_vars = policy.cast_params(variables)

    def forward(x: jnp.ndarray) -> jnp.ndarray:
        raw = model.apply(realize(cast_vars), x, train=False)
        return model.decode_flat(policy.boundary(raw))

    cfg = config or Detect2DConfig(
        model_name="yolov4",
        input_hw=input_hw,
        num_classes=num_classes,
        conf_thresh=0.4,
        iou_thresh=0.6,
    )
    pipeline = Detect2DPipeline(cfg, forward, precision=policy)
    spec = _detect2d_spec(cfg, v4_num_predictions(cfg.input_hw))
    spec.extra["fused_stages"] = list(pipeline.fused_stages)
    spec.extra.update(policy.spec_extra(cast_vars, KEEP_F32_2D))
    return pipeline, spec, variables


def _detect2d_spec(cfg: Detect2DConfig, n_predictions: int) -> ModelSpec:
    """Serving spec shared by the 2D detector pipelines (the analogue of
    examples/YOLOv5/config.pbtxt + examples/YOLOv4/config.pbtxt)."""
    return ModelSpec(
        name=cfg.model_name,
        version="1",
        platform="jax",
        # Any camera resolution is accepted; the jitted graph re-traces
        # once per distinct resolution and resizes to input_hw on-device.
        # donatable: the pipeline consumes the staged frames exactly
        # once, so the serving channel may recycle the HBM input buffer
        # across consecutive batches (channel/tpu_channel.py).
        inputs=(
            TensorSpec("images", (-1, -1, -1, 3), "FP32", "NHWC", donatable=True),
        ),
        outputs=(
            TensorSpec("detections", (-1, cfg.max_det, 6), "FP32"),
            TensorSpec("valid", (-1, cfg.max_det), "BOOL"),
        ),
        # the 2D pipelines are genuinely batched (leading dim of every
        # tensor is the frame batch) — declaring it is what lets the
        # mesh-sharded serving channel split requests over the data
        # axis (channel/sharded_channel.py; Triton's own batchable
        # convention, examples/YOLOv5/config.pbtxt max_batch_size).
        # 8 matches the examples/ repository configs.
        max_batch_size=8,
        extra={
            "conf_thresh": cfg.conf_thresh,
            "iou_thresh": cfg.iou_thresh,
            "model_input_hw": list(cfg.input_hw),
            "num_predictions": n_predictions,
            "num_classes": cfg.num_classes,
            # Remote clients label/draw from served metadata
            # (parse_model role, base_client.py:32-104).
            "class_names": list(cfg.class_names),
        },
    )


def build_retinanet_pipeline(
    rng: jax.Array | None = None,
    num_classes: int = 80,
    depth: str = "resnet50",
    input_hw: tuple[int, int] = (480, 640),
    variables=None,
    dtype: jnp.dtype = jnp.float32,
    config: Detect2DConfig | None = None,
    precision: PrecisionPolicy | str | None = None,
) -> tuple[Detect2DPipeline, ModelSpec, dict]:
    """RetinaNet (detectron family) fused pipeline.

    Contract parity: examples/RetinaNet_detectron/config.pbtxt (3x640x480
    input; boxes/classes/scores/dims outputs — served via
    detectron_infer_fn). Unlike the YOLO paths there is no /255 scaling
    (clients/preprocess/detectron_preprocess.py:12-24 feeds raw pixels).
    """
    from triton_client_tpu.models.retinanet import RetinaNet

    policy, dtype = _resolve_precision(precision, dtype)
    model = RetinaNet(
        num_classes=num_classes, depth=depth, input_hw=input_hw, dtype=dtype
    )
    if variables is None:
        if rng is None:
            rng = jax.random.PRNGKey(0)
        dummy = jnp.zeros((1, *input_hw, 3), jnp.float32)
        variables = model.init(rng, dummy, train=False)
    cast_vars = policy.cast_params(variables)

    def forward(x: jnp.ndarray):
        # decode runs inside model.decode here (anchors -> boxes): feed
        # it f32 heads per the keep-list
        raw = model.apply(realize(cast_vars), x, train=False)
        return model.decode(policy.boundary(raw))

    cfg = config or Detect2DConfig(
        model_name="retinanet",
        input_hw=input_hw,
        num_classes=num_classes,
        conf_thresh=0.05,
        iou_thresh=0.5,
        max_det=100,
        scaling="none",
        multi_label=True,
        head_style="scored",
    )
    pipeline = Detect2DPipeline(cfg, forward, precision=policy)
    spec = _detectron_spec(cfg)
    spec.extra["fused_stages"] = list(pipeline.fused_stages)
    spec.extra.update(policy.spec_extra(cast_vars, KEEP_F32_2D))
    return pipeline, spec, variables


def build_fcos_pipeline(
    rng: jax.Array | None = None,
    num_classes: int = 80,
    depth: str = "resnet50",
    input_hw: tuple[int, int] = (480, 640),
    variables=None,
    dtype: jnp.dtype = jnp.float32,
    config: Detect2DConfig | None = None,
    precision: PrecisionPolicy | str | None = None,
) -> tuple[Detect2DPipeline, ModelSpec, dict]:
    """FCOS (anchor-free detectron family; the reference's FCOS_client)."""
    from triton_client_tpu.models.retinanet import FCOS

    policy, dtype = _resolve_precision(precision, dtype)
    model = FCOS(
        num_classes=num_classes, depth=depth, input_hw=input_hw, dtype=dtype
    )
    if variables is None:
        if rng is None:
            rng = jax.random.PRNGKey(0)
        dummy = jnp.zeros((1, *input_hw, 3), jnp.float32)
        variables = model.init(rng, dummy, train=False)
    cast_vars = policy.cast_params(variables)

    def forward(x: jnp.ndarray):
        raw = model.apply(realize(cast_vars), x, train=False)
        return model.decode(policy.boundary(raw))

    cfg = config or Detect2DConfig(
        model_name="fcos",
        input_hw=input_hw,
        num_classes=num_classes,
        conf_thresh=0.05,
        iou_thresh=0.6,
        max_det=100,
        scaling="none",
        multi_label=True,
        head_style="scored",
    )
    pipeline = Detect2DPipeline(cfg, forward, precision=policy)
    spec = _detectron_spec(cfg)
    spec.extra["fused_stages"] = list(pipeline.fused_stages)
    spec.extra.update(policy.spec_extra(cast_vars, KEEP_F32_2D))
    return pipeline, spec, variables


def detectron_infer_fn(pipeline: Detect2DPipeline):
    """Back-compat alias: scored pipelines' infer_fn() already emits the
    detectron contract (boxes/scores/classes/dims)."""
    return pipeline.infer_fn()


def _detectron_spec(cfg: Detect2DConfig) -> ModelSpec:
    return ModelSpec(
        name=cfg.model_name,
        version="1",
        platform="jax",
        inputs=(TensorSpec("images", (-1, -1, -1, 3), "FP32", "NHWC"),),
        outputs=(
            TensorSpec("boxes", (-1, cfg.max_det, 4), "FP32"),
            TensorSpec("scores", (-1, cfg.max_det), "FP32"),
            TensorSpec("classes", (-1, cfg.max_det), "INT64"),
            TensorSpec("dims", (-1,), "INT32"),
        ),
        max_batch_size=8,
        extra={
            "conf_thresh": cfg.conf_thresh,
            "iou_thresh": cfg.iou_thresh,
            "scaling": cfg.scaling,
            "class_names": list(cfg.class_names),
        },
    )


def _build_preprocess(**kwargs):
    # lazy import: preprocess2d imports nothing heavy, but keeping the
    # table entries uniform (callable indirection) avoids import cycles
    from triton_client_tpu.pipelines.preprocess2d import (
        build_preprocess_pipeline,
    )

    return build_preprocess_pipeline(**kwargs)


# family name -> builder; the single dispatch table shared by the CLI
# entry points and the disk model repository.
BUILDERS_2D = {
    "yolov5": build_yolov5_pipeline,
    "yolov4": build_yolov4_pipeline,
    "retinanet": build_retinanet_pipeline,
    "fcos": build_fcos_pipeline,
    "preprocess": _build_preprocess,
}
