"""Fixed-shape greedy NMS for TPU.

The reference delegates NMS to torchvision.ops.nms (C++/CUDA,
clients/postprocess/yolov5_postprocess.py:108) with data-dependent box
counts. XLA requires static shapes and no data-dependent control flow,
so this is a re-design, not a port:

  * candidate sets are fixed-size: callers pre-gate by confidence and
    top-k to ``max_nms`` boxes, with invalid slots carrying score -inf;
  * suppression runs a fixed ``max_det``-iteration ``lax.fori_loop``:
    each step selects the highest-scoring live box, emits it, and kills
    every live box with IoU > threshold against it;
  * output is always (max_det,) indices plus a validity mask, so the
    whole postprocess stays inside one jit and nothing re-compiles when
    the number of detections changes frame to frame.

Memory is O(max_det * N) via per-iteration IoU rows (no N x N matrix),
so it scales to the reference's 16128-box YOLO heads without blowing
VMEM. Class-aware ("batched") NMS uses the same coordinate-offset trick
as the reference (yolov5_postprocess.py:106-107).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from triton_client_tpu.ops.boxes import box_area


# The (N, N) IoU matrix the fixpoint formulation materializes: 4 bytes
# x N^2 — 64 MB at 4096, past which the sequential loop wins on memory.
_FIXPOINT_MAX_N = 4096


def _nms_mode(n: int, max_det: int) -> str:
    """Route between the NMS formulations (env override:
    TRITON_CLIENT_TPU_NMS=fixpoint|pallas|xla). Decided at trace time —
    shapes are static under jit, so the choice is baked into the
    executable. Auto: the fixpoint matrix form (sequential-step count =
    suppression-chain depth, not max_det) whenever the IoU matrix is
    affordable; the sequential XLA loop otherwise."""
    mode = os.environ.get("TRITON_CLIENT_TPU_NMS", "auto")
    if mode in ("xla", "fixpoint"):
        return mode
    if mode == "pallas":
        from triton_client_tpu.ops.pallas_nms import vmem_fits

        if not vmem_fits(n, max_det):
            import logging

            logging.getLogger(__name__).warning(
                "TRITON_CLIENT_TPU_NMS=pallas but n=%d exceeds the VMEM "
                "budget; falling back to the fixpoint form",
                n,
            )
            return "fixpoint" if n <= _FIXPOINT_MAX_N else "xla"
        return "pallas"
    return "fixpoint" if n <= _FIXPOINT_MAX_N else "xla"


def _iou_row(
    box: jnp.ndarray, box_a: jnp.ndarray, boxes: jnp.ndarray, areas: jnp.ndarray
) -> jnp.ndarray:
    """IoU of one (4,) xyxy box (area ``box_a``) against (N, 4) boxes
    with precomputed (N,) ``areas`` — areas are loop-invariant in the
    suppression loop, so they are computed once outside."""
    lt = jnp.maximum(box[:2], boxes[:, :2])
    rb = jnp.minimum(box[2:], boxes[:, 2:])
    wh = jnp.clip(rb - lt, 0.0, None)
    inter = wh[:, 0] * wh[:, 1]
    return inter / jnp.maximum(box_a + areas - inter, 1e-9)


def nms(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_thresh: float = 0.45,
    max_det: int = 300,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy NMS over (N, 4) xyxy boxes and (N,) scores.

    Returns ``(indices, valid)``: (max_det,) int32 indices into the input
    (arbitrary where invalid) and a (max_det,) bool mask. Slots whose
    input score is -inf (padding) are never selected.

    Formulation routing (fixpoint matrix form / Pallas kernel /
    sequential XLA loop) happens at TRACE time: callers jitted around
    this see the choice baked into their executable until retrace
    (TRITON_CLIENT_TPU_NMS env override). All three produce identical
    kept-index sequences.
    """
    n = boxes.shape[0]
    mode = _nms_mode(n, max_det)
    if mode == "pallas":
        from triton_client_tpu.ops import fused
        from triton_client_tpu.ops.pallas_nms import nms_pallas

        return nms_pallas(
            boxes,
            scores,
            iou_thresh=iou_thresh,
            max_det=max_det,
            # forced via env: compiled on a TPU, interpreted off it
            interpret=fused.fused_interpret(),
        )
    if mode == "fixpoint":
        return _nms_fixpoint(boxes, scores, iou_thresh, max_det=max_det)
    return _nms_xla(boxes, scores, iou_thresh, max_det=max_det)


@functools.partial(jax.jit, static_argnames=("max_det",))
def _nms_fixpoint(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_thresh: float = 0.45,
    max_det: int = 300,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact greedy NMS as a suppression-graph fixpoint — the TPU-shaped
    formulation.

    The textbook greedy loop (argmax -> suppress -> repeat, `_nms_xla`)
    runs max_det tiny sequential steps; on TPU each step is
    latency-bound, so 300 iterations dominate the whole 2D pipeline.
    Greedy NMS is equivalently the unique fixpoint of

        kept_i = valid_i and not any(edge_ji and kept_j)

    over the score-ordered suppression DAG (edge_ji: j outscores i and
    IoU > thresh). Iterating that recurrence finalizes one DAG layer
    per pass, so it converges in max-chain-depth passes (single digits
    in practice) of WIDE (N, N) vector ops instead of max_det narrow
    ones. Equivalence to the sequential loop (incl. first-index tie
    breaks) is pinned by tests against `_nms_xla` and OpenCV's C++ NMS.
    """
    neg_inf = jnp.asarray(-jnp.inf, scores.dtype)
    # Stable descending score order reproduces argmax's first-max-wins
    # tie break; -inf rows (padding) sink to the bottom.
    order = jnp.argsort(-scores, stable=True).astype(jnp.int32)
    sboxes = boxes[order].astype(jnp.float32)
    valid0 = scores[order] > neg_inf

    areas = box_area(sboxes)
    lt = jnp.maximum(sboxes[:, None, :2], sboxes[None, :, :2])
    rb = jnp.minimum(sboxes[:, None, 2:], sboxes[None, :, 2:])
    wh = jnp.clip(rb - lt, 0.0, None)
    inter = wh[..., 0] * wh[..., 1]
    iou = inter / jnp.maximum(areas[:, None] + areas[None, :] - inter, 1e-9)
    return fixpoint_keep_sorted(iou, valid0, order, iou_thresh, max_det)


def fixpoint_keep_sorted(
    siou: jnp.ndarray,
    valid0: jnp.ndarray,
    order: jnp.ndarray,
    iou_thresh,
    max_det: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fixpoint core shared by axis-aligned and rotated-BEV NMS:
    ``siou`` is the (N, N) IoU matrix of SCORE-SORTED candidates,
    ``valid0`` their live mask, ``order`` the sorted->original index
    map. Returns the sequential loop's ((max_det,) indices into the
    ORIGINAL array, valid) contract."""
    n = siou.shape[0]
    # edge[j, i]: j (strictly higher-ranked) suppresses i when kept
    rank = jnp.arange(n)
    edge = (siou > iou_thresh) & (rank[:, None] < rank[None, :]) & valid0[:, None]

    def cond(state):
        kept, prev, it = state
        return (it < n) & jnp.any(kept != prev)

    def body(state):
        kept, _, it = state
        new = valid0 & ~jnp.any(edge & kept[:, None], axis=0)
        return new, kept, it + 1

    kept, _, _ = jax.lax.while_loop(
        cond, body, (valid0, jnp.zeros_like(valid0), jnp.int32(0))
    )

    # Pack the first max_det kept (already score-ordered) into the
    # sequential loop's (indices, valid) contract.
    kept_rank = jnp.cumsum(kept) - 1
    slot = jnp.where(kept & (kept_rank < max_det), kept_rank, max_det)
    indices = jnp.zeros((max_det + 1,), jnp.int32).at[slot].set(order)[:max_det]
    valid = jnp.arange(max_det) < jnp.sum(kept)
    return indices, valid


@functools.partial(jax.jit, static_argnames=("max_det",))
def _nms_xla(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    iou_thresh: float = 0.45,
    max_det: int = 300,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    n = boxes.shape[0]
    neg_inf = jnp.asarray(-jnp.inf, scores.dtype)
    areas = box_area(boxes)

    def body(i, state):
        live_scores, indices, valid = state
        best = jnp.argmax(live_scores)
        best_score = live_scores[best]
        is_valid = best_score > neg_inf
        indices = indices.at[i].set(best.astype(jnp.int32))
        valid = valid.at[i].set(is_valid)
        ious = _iou_row(boxes[best], areas[best], boxes, areas)
        suppress = (ious > iou_thresh) | (jnp.arange(n) == best)
        live_scores = jnp.where(suppress & is_valid, neg_inf, live_scores)
        return live_scores, indices, valid

    indices = jnp.zeros((max_det,), jnp.int32)
    valid = jnp.zeros((max_det,), bool)
    _, indices, valid = jax.lax.fori_loop(0, max_det, body, (scores, indices, valid))
    return indices, valid


@functools.partial(jax.jit, static_argnames=("max_det", "class_agnostic"))
def batched_nms(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    classes: jnp.ndarray,
    iou_thresh: float = 0.45,
    max_det: int = 300,
    class_agnostic: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Class-aware NMS via the per-class coordinate offset trick."""
    # Same spirit as the reference's fixed max_wh=4096 pixel offset
    # (yolov5_postprocess.py:49), but the stride adapts to the data
    # range and the math runs in f32 regardless of input dtype: a fixed
    # 4096 offset in f32 quantizes normalized [0,1] boxes to ~1/32-image
    # steps by class ~80 (corrupting IoU) and cannot separate classes at
    # all for coordinates above 4096; bf16 offsets lose all sub-32px
    # structure from class 1 on.
    boxes32 = boxes.astype(jnp.float32)
    if class_agnostic:
        offset_boxes = boxes32
    else:
        stride = jnp.max(jnp.abs(boxes32)) * 2.0 + 1.0
        offset_boxes = boxes32 + (classes.astype(jnp.float32) * stride)[:, None]
    return nms(offset_boxes, scores, iou_thresh=iou_thresh, max_det=max_det)


@functools.partial(jax.jit, static_argnames=("max_det", "class_agnostic"))
def nms_padded(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    classes: jnp.ndarray,
    valid: jnp.ndarray,
    iou_thresh: float = 0.45,
    max_det: int = 300,
    class_agnostic: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """NMS over a padded candidate set, returning packed (max_det, 6) detections.

    Inputs are fixed-size candidate arrays (from a top-k prefilter);
    ``valid`` masks live slots. Output rows are [x1, y1, x2, y2, score,
    class] with zeros in invalid slots, plus the (max_det,) validity mask
    — the fixed-shape analogue of the reference's variable-length
    "(n, 6) tensor per image" (yolov5_postprocess.py:34).
    """
    masked_scores = jnp.where(valid, scores, -jnp.inf)
    idx, keep = batched_nms(
        boxes,
        masked_scores,
        classes,
        iou_thresh=iou_thresh,
        max_det=max_det,
        class_agnostic=class_agnostic,
    )
    out = jnp.concatenate(
        [
            boxes[idx],
            scores[idx][:, None],
            classes[idx].astype(boxes.dtype)[:, None],
        ],
        axis=-1,
    )
    out = jnp.where(keep[:, None], out, 0.0)
    return out, keep
