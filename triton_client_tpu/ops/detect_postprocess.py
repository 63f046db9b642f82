"""End-to-end 2D detection postprocess: raw head output -> packed detections.

Behavioral parity with the reference's extract_boxes
(clients/postprocess/yolov5_postprocess.py:28-125): confidence gate,
conf = obj * cls, xywh -> xyxy, best-class-only selection, class-offset
batched NMS, max_det cap. Re-designed fixed-shape so the whole thing
jits and vmaps over the batch:

  (B, N, 5+nc) --conf gate + top-k--> (B, max_nms, ...) --NMS--> (B, max_det, 6)

The reference's variable-length outputs and its 10 s NMS watchdog
(yolov5_postprocess.py:51,120-122) are unnecessary here: runtime is
deterministic by construction.

``fused=True`` collapses the post-top-k tail — xywh->xyxy decode,
class offset, suppression loop and packing — into ONE Pallas launch for
the whole batch (ops/pallas_decode.fused_decode_nms_2d: eight frames a
grid step, one a sublane, each group's greedy loop over when none of
its frames has a live candidate) instead of the per-frame nms_padded op
chain. Gate and top-k stay per frame under ``vmap``; the tail is called
once on the batched candidates. Bitwise-identical rows (pinned by
tests/test_fused_parity.py and tests/test_fused_decode_groups.py);
pipelines pick the route at trace time from ops/fused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from triton_client_tpu.ops.boxes import xywh2xyxy
from triton_client_tpu.ops.nms import nms_padded


def _packed_nms(
    candidates, iou_thresh, max_det, class_agnostic,
    box_format: str, fused: bool, interpret: bool,
):
    """The batch's candidates ``(boxes (B, K, 4), scores, classes,
    valid (B, K))`` -> ``(detections, keep, steps)``: nms_padded a frame
    vs the fused single-launch tail. ``box_format`` tells the fused
    kernel whether decode is still pending ("xywh" — the conversion the
    XLA path already did before top-k happens in-kernel instead).
    ``steps``: the greedy steps each group of eight frames ran in the
    kernel, None on the nms_padded route."""
    if fused:
        from triton_client_tpu.ops.pallas_decode import fused_decode_nms_2d

        return fused_decode_nms_2d(
            *candidates,
            iou_thresh=iou_thresh, max_det=max_det, box_format=box_format,
            class_agnostic=class_agnostic, interpret=interpret,
        )

    def one_image(boxes, scores, classes, valid):
        if box_format == "xywh":
            boxes = xywh2xyxy(boxes)
        return nms_padded(
            boxes, scores, classes, valid,
            iou_thresh=iou_thresh, max_det=max_det,
            class_agnostic=class_agnostic,
        )

    return (*jax.vmap(one_image)(*candidates), None)


def _gate_topk(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    classes: jnp.ndarray,
    conf_thresh: float,
    max_nms: int,
):
    """Single-image head of the tail: confidence gate -> top-k prefilter
    -> the NMS candidates ``(boxes, scores, classes, valid)``. Invalid
    top-k slots carry the gate's -inf in ``gated`` but 0.0 in the
    candidates so confs stay clean. Gate + top-k stay XLA on purpose:
    the sort-based top_k beats any in-kernel reformulation and fuses
    into the head."""
    gated = jnp.where(scores > conf_thresh, scores, -jnp.inf)
    k = min(max_nms, gated.shape[0])
    top_scores, top_idx = jax.lax.top_k(gated, k)
    top_valid = top_scores > -jnp.inf
    return (
        boxes[top_idx],
        jnp.where(top_valid, top_scores, 0.0),
        classes[top_idx],
        top_valid,
    )


def _multilabel_topk(
    boxes: jnp.ndarray,
    per_class_scores: jnp.ndarray,
    conf_thresh: float,
    max_nms: int,
):
    """Single-image multi-label head: every (box, class) pair over the
    threshold is a candidate. Top-k runs on the flat (N*nc,) scores;
    boxes/classes are derived from surviving indices (idx // nc,
    idx % nc) so the (N*nc, 4) box expansion is never materialized."""
    nc = per_class_scores.shape[-1]
    flat = per_class_scores.reshape(-1)
    gated = jnp.where(flat > conf_thresh, flat, -jnp.inf)
    k = min(max_nms, gated.shape[0])
    top_scores, top_idx = jax.lax.top_k(gated, k)
    top_valid = top_scores > -jnp.inf
    return (
        boxes[top_idx // nc],
        jnp.where(top_valid, top_scores, 0.0),
        top_idx % nc,
        top_valid,
    )


def _best_class_or_multilabel(boxes, scores, conf_thresh, max_nms, multi_label):
    """One image's candidates from (N, 4) boxes and (N, nc) scores."""
    if multi_label and scores.shape[-1] > 1:
        return _multilabel_topk(boxes, scores, conf_thresh, max_nms)
    return _gate_topk(
        boxes,
        jnp.max(scores, axis=-1),
        jnp.argmax(scores, axis=-1),
        conf_thresh,
        max_nms,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_det", "max_nms", "class_agnostic", "multi_label", "fused",
        "interpret", "return_steps",
    ),
)
def extract_boxes(
    prediction: jnp.ndarray,
    conf_thresh: float = 0.3,
    iou_thresh: float = 0.45,
    max_det: int = 300,
    max_nms: int = 1024,
    class_agnostic: bool = False,
    multi_label: bool = False,
    fused: bool = False,
    interpret: bool = False,
    return_steps: bool = False,
) -> tuple[jnp.ndarray, ...]:
    """Raw YOLO-style predictions -> packed per-image detections.

    Args:
      prediction: (B, N, 5 + nc) decoded [cx, cy, w, h, obj, cls...].
      conf_thresh: final-confidence gate (obj * cls), reference default
        0.3 (communicator/ros_inference.py:148).
      iou_thresh: NMS IoU threshold, reference default 0.45.
      max_det: max detections per image (reference max_det=300).
      max_nms: candidate cap fed to NMS (reference max_nms=30000; fixed
        top-k here — scores below the top max_nms are dropped, which
        only matters in pathologically dense scenes).
      multi_label: emit one candidate per (box, class) over the
        threshold rather than best-class-only.

    Returns:
      (detections, valid): (B, max_det, 6) [x1, y1, x2, y2, conf, cls]
      rows (zeros when invalid) and (B, max_det) bool mask; with
      ``return_steps`` a third value, the greedy steps each group of
      eight frames ran in the fused kernel ((ceil(B / 8),) int32; None
      when not ``fused``).
    """
    # fused path defers xywh->xyxy into the kernel (the "decode" half
    # of decode+NMS — conversion commutes with the top-k gather, and
    # *0.5 is exact, so rows stay bitwise-identical)
    fmt = "xywh" if fused else "xyxy"

    def one_image(pred: jnp.ndarray):
        boxes = pred[:, :4] if fused else xywh2xyxy(pred[:, :4])
        cls_conf = pred[:, 5:] * pred[:, 4, None]  # conf = obj * cls
        return _best_class_or_multilabel(
            boxes, cls_conf, conf_thresh, max_nms, multi_label
        )

    packed = _packed_nms(
        jax.vmap(one_image)(prediction),
        iou_thresh, max_det, class_agnostic, fmt, fused, interpret,
    )
    return packed if return_steps else packed[:2]


@functools.partial(
    jax.jit, static_argnames=("max_det", "max_nms", "fused", "interpret")
)
def extract_boxes_yolov4(
    boxes: jnp.ndarray,
    confs: jnp.ndarray,
    conf_thresh: float = 0.4,
    iou_thresh: float = 0.6,
    max_det: int = 300,
    max_nms: int = 1024,
    fused: bool = False,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """YOLOv4 two-output wire contract -> packed per-image detections.

    Behavioral parity with the reference's post_processing
    (tools/utils.py:166-233): best-class selection over pre-multiplied
    confs, confidence gate, per-class greedy NMS (realized here with the
    class-offset trick instead of a python per-class loop). The
    reference emits 7-element rows duplicating the confidence
    (tools/utils.py:219); here rows are the framework-uniform
    [x1, y1, x2, y2, conf, class].

    Args:
      boxes: (B, N, 1, 4) or (B, N, 4) normalized [x1, y1, x2, y2]
        (examples/YOLOv4/config.pbtxt "boxes").
      confs: (B, N, nc) obj*cls scores (config.pbtxt "confs").

    Returns:
      (detections, valid): (B, max_det, 6) rows in the boxes' coordinate
      units and (B, max_det) bool mask.
    """
    if boxes.ndim == 4:
        boxes = boxes[:, :, 0, :]

    def one_image(b: jnp.ndarray, c: jnp.ndarray):
        return _gate_topk(
            b, jnp.max(c, axis=-1), jnp.argmax(c, axis=-1), conf_thresh, max_nms
        )

    return _packed_nms(
        jax.vmap(one_image)(boxes, confs),
        iou_thresh, max_det, False, "xyxy", fused, interpret,
    )[:2]


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_det", "max_nms", "class_agnostic", "multi_label", "fused",
        "interpret", "return_steps",
    ),
)
def extract_boxes_scored(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    conf_thresh: float = 0.05,
    iou_thresh: float = 0.5,
    max_det: int = 100,
    max_nms: int = 1024,
    class_agnostic: bool = False,
    multi_label: bool = True,
    fused: bool = False,
    interpret: bool = False,
    return_steps: bool = False,
) -> tuple[jnp.ndarray, ...]:
    """Decoded-box detectors (RetinaNet/FCOS) -> packed detections.

    The reference's detectron family has NMS server-side and its client
    consumes finished boxes (clients/postprocess/detectron_postprocess.py:
    26-38); this op IS that server side, in-jit. Defaults follow
    detectron2's test-time config (score 0.05, NMS 0.5, 100 dets).

    Args:
      boxes: (B, N, 4) xyxy in input pixels (already decoded).
      scores: (B, N, nc) per-class probabilities.
      multi_label: detectron semantics — every (box, class) over the
        threshold is a candidate (default), vs best-class-only.

    Returns:
      (detections, valid): (B, max_det, 6) [x1, y1, x2, y2, score,
      class] + (B, max_det) mask; with ``return_steps`` the fused
      kernel's steps a group as a third value (see extract_boxes).
    """
    def one_image(b: jnp.ndarray, s: jnp.ndarray):
        return _best_class_or_multilabel(
            b, s, conf_thresh, max_nms, multi_label
        )

    packed = _packed_nms(
        jax.vmap(one_image)(boxes, scores),
        iou_thresh, max_det, class_agnostic, "xyxy", fused, interpret,
    )
    return packed if return_steps else packed[:2]
