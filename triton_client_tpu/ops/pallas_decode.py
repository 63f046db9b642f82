"""Fused decode+NMS+pack Pallas kernels (the detection epilogue).

After the model body, the reference path runs the candidate tail as a
chain of small XLA ops — xywh->xyxy, the class-offset trick, the NMS
formulation, index gathers, concat/where packing (ops/detect_postprocess,
ops/detect3d_postprocess) — each a separate HLO with its own HBM
round-trip for a few-KB working set. This module collapses the tail
into single Pallas launches with every operand VMEM-resident, so
detections are produced on-device in packed form and feed the session
tracker (PR 15) with zero host hops:

  * :func:`fused_decode_nms_2d` — ONE kernel for a whole batch:
    candidate box decode (xywh->xyxy), adaptive class-offset, the
    greedy suppression loop (ops/pallas_nms's proven formulation) and
    the packed ``(max_det, 6)`` detection rows. A grid step takes a
    GROUP of eight frames, one a sublane of every (8, lanes) tile, so a
    greedy step does eight frames' work in the vector registers one
    frame used to fill an eighth of; and a group's loop ends when none
    of its frames has a live candidate, not after ``max_det`` steps
    (a frame keeps tens of boxes, not 300). A lone frame is a group
    with seven dead sublanes. Bitwise-identical to the ``nms_padded``
    reference path a frame (same conversion math, same offset stride
    per frame, same tie-breaks — pinned by tests/test_fused_parity.py
    and tests/test_fused_decode_groups.py). It reports the steps each
    group ran, which the serving channel counts (``nms_steps`` over
    ``nms_frames`` under ``/snapshot``).
  * :func:`fused_residual_decode` — the 3D anchor-residual decode +
    direction rectification for the K top-k candidates as one
    elementwise kernel (collapses decode_boxes + rectify_direction +
    concat into one launch). Bitwise vs the JITTED XLA tail under the
    interpreter — both sides make identical FMA-contraction choices
    under one compiler; an EAGER reference call can differ by 1 ulp on
    the mul+add center columns (LLVM contracts jitted code only).
    Documented ulp-level tolerance on real TPU hardware (Mosaic
    transcendental lowering).
  * :func:`fused_suppress_pack_3d` — rotated-BEV suppression + packing
    in one kernel. The N x N rotated IoU matrix stays where it is
    fastest (the fully lane-parallel XLA polygon clip, round-1/3
    measured); the kernel consumes it and replaces the fixpoint
    while_loop + cumsum-pack + three gathers + concat/where with one
    launch emitting ``(max_det, 9+e)`` rows. Keep sequences are
    bitwise-identical to ``nms_bev`` + ``_nms_pack_one`` (greedy ==
    fixpoint, the equivalence ops/nms pins by test).

What stays deliberately UNFUSED: score gating + top-k compaction
(XLA's sort-based top_k beats any in-kernel reformulation at these
widths and runs fused into the head convs), and the 3D rotated-IoU
matrix (see above). ``perf/profile_fused`` measures both seams.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_client_tpu.ops.pallas_nms import (
    _NEG_INF,
    masked_pick,
    write_lane_col,
)

_LANES = 128
# float32 sublanes of a vector register: the frames the 2D kernel runs
# side by side. A constant of the chip's layout, like _LANES
_SUBLANES = 8


def _round_up(n: int, m: int) -> int:
    return ((max(1, n) + m - 1) // m) * m


# -- 2D: decode + class-offset + NMS + pack, eight frames a grid step ---------

# rows of the kernel's input block [c0..c3 (box_format coords), score
# (0-filled), class, valid] and output block [x1, y1, x2, y2, score,
# class, keep]: each an (8, lanes) tile, one frame a sublane
_CAND_FIELDS = 7
_OUT_FIELDS = 7


def _pick_col(sel, tile):
    """ops/pallas_nms.masked_pick, a frame a sublane: each frame's
    selected lane as an (8, 1) column."""
    return jnp.sum(jnp.where(sel, tile, 0.0), axis=1, keepdims=True)


def _decode_nms_pack_2d_kernel(
    cand_ref,
    thresh_ref,
    out_ref,
    steps_ref,
    live_ref,
    *,
    max_det,
    box_format,
    class_agnostic,
):
    """One GROUP of eight frames: cand_ref (7, 8, N), out_ref
    (7, 8, max_det_pad), every field an (8, lanes) tile with the frame
    along the sublanes, so one vector register holds eight frames' worth
    of a greedy step. The step is ops/pallas_nms._nms_kernel's with
    in-kernel decode and the packing epilogue; each per-frame reduction
    is a lane reduction to an (8, 1) column. Offset coords (IoU space)
    and original coords (output space) both stay resident. The loop ends
    when no frame of the group has a live candidate: a frame that ran
    out earlier writes zeros into a block zeroed before the loop, so the
    rows are the same wherever it ends. steps_ref: the steps it ran."""
    n = cand_ref.shape[2]
    iou_thresh = thresh_ref[0]

    c0, c1, c2, c3 = cand_ref[0], cand_ref[1], cand_ref[2], cand_ref[3]
    score = cand_ref[4]
    clsf = cand_ref[5]
    valid = cand_ref[6] > 0.0

    if box_format == "xywh":  # ops/boxes.xywh2xyxy, bit for bit
        x1, y1 = c0 - c2 * 0.5, c1 - c3 * 0.5
        x2, y2 = c0 + c2 * 0.5, c1 + c3 * 0.5
    elif box_format == "xyxy":
        x1, y1, x2, y2 = c0, c1, c2, c3
    else:
        raise ValueError(f"box_format must be xywh|xyxy, got {box_format!r}")

    if class_agnostic:
        ox1, oy1, ox2, oy2 = x1, y1, x2, y2
    else:
        # ops/nms.batched_nms's adaptive stride, per FRAME: max |coord|
        # over the frame's candidates (fp max is associative, so the
        # reduction reorders bitwise-safely; zero pad lanes cannot
        # raise it)
        m = jnp.maximum(jnp.maximum(jnp.abs(x1), jnp.abs(y1)),
                        jnp.maximum(jnp.abs(x2), jnp.abs(y2)))
        stride = jnp.max(m, axis=1, keepdims=True) * 2.0 + 1.0
        off = clsf * stride
        ox1, oy1, ox2, oy2 = x1 + off, y1 + off, x2 + off, y2 + off

    area = (ox2 - ox1) * (oy2 - oy1)
    live_ref[:] = jnp.where(valid, score, _NEG_INF)
    # lane indices as float32 (exact far beyond any max_nms): the
    # first-index argmax below is then a float min-reduction
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, n), 1).astype(
        jnp.float32
    )
    out_lane = jax.lax.broadcasted_iota(
        jnp.int32, (_SUBLANES, out_ref.shape[2]), 1
    )

    def body(carry):
        i, _ = carry
        live = live_ref[:]
        best_score = jnp.max(live, axis=1, keepdims=True)
        # jnp.argmax's tie-break: the first lane holding the maximum
        best = jnp.min(
            jnp.where(live == best_score, lane, float(n)),
            axis=1, keepdims=True,
        )
        is_valid = best_score > _NEG_INF
        sel = lane == best

        bx1o, by1o = _pick_col(sel, ox1), _pick_col(sel, oy1)
        bx2o, by2o = _pick_col(sel, ox2), _pick_col(sel, oy2)
        barea = _pick_col(sel, area)
        iw = jnp.clip(jnp.minimum(ox2, bx2o) - jnp.maximum(ox1, bx1o), 0.0, None)
        ih = jnp.clip(jnp.minimum(oy2, by2o) - jnp.maximum(oy1, by1o), 0.0, None)
        inter = iw * ih
        iou = inter / jnp.maximum(area + barea - inter, 1e-9)
        suppress = (iou > iou_thresh) | sel
        live_ref[:] = jnp.where(suppress & is_valid, _NEG_INF, live)

        vals = (
            _pick_col(sel, x1), _pick_col(sel, y1),
            _pick_col(sel, x2), _pick_col(sel, y2),
            _pick_col(sel, score), _pick_col(sel, clsf),
            1.0,
        )
        at_i = out_lane == i
        for r, v in enumerate(vals):
            out_ref[r] = jnp.where(
                at_i, jnp.where(is_valid, v, 0.0), out_ref[r]
            )
        return i + 1, jnp.max(is_valid.astype(jnp.int32))

    out_ref[:] = jnp.zeros(out_ref.shape, jnp.float32)
    steps, _ = jax.lax.while_loop(
        lambda carry: (carry[0] < max_det) & (carry[1] > 0),
        body,
        (jnp.int32(0), jnp.int32(1)),
    )
    steps_ref[:] = jnp.full(steps_ref.shape, steps, jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("box_format", "max_det", "class_agnostic", "interpret"),
)
def fused_decode_nms_2d(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    classes: jnp.ndarray,
    valid: jnp.ndarray,
    iou_thresh=0.45,
    max_det: int = 300,
    box_format: str = "xywh",
    class_agnostic: bool = False,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-launch candidate tail of a BATCH: boxes (B, K, 4) in
    ``box_format``, scores (B, K) 0-filled on invalid slots, classes
    (B, K) int, valid (B, K) bool -> packed ``(B, max_det, 6)``
    [x1, y1, x2, y2, score, class] rows + (B, max_det) keep mask — the
    exact ``nms_padded`` contract per frame — and the greedy steps each
    group of eight frames ran, (ceil(B / 8),) int32: the largest kept
    count of its frames plus the step that found nothing, at most
    ``max_det``. The grid is the groups; a batch that is no multiple of
    eight is padded with frames that have no valid candidate."""
    b, k = scores.shape
    b_pad = _round_up(b, _SUBLANES)
    k_pad = _round_up(k, _LANES)
    md_pad = _round_up(max_det, _LANES)

    fields = jnp.concatenate(
        [
            jnp.moveaxis(boxes.astype(jnp.float32), -1, 0),
            jnp.stack(
                [
                    scores.astype(jnp.float32),
                    classes.astype(jnp.float32),
                    valid.astype(jnp.float32),
                ]
            ),
        ]
    )
    cand = jnp.pad(fields, ((0, 0), (0, b_pad - b), (0, k_pad - k)))
    thresh = jnp.reshape(jnp.asarray(iou_thresh, jnp.float32), (1,))

    with jax.named_scope("fused:decode_nms"):
        out, steps = pl.pallas_call(
            functools.partial(
                _decode_nms_pack_2d_kernel,
                max_det=max_det,
                box_format=box_format,
                class_agnostic=class_agnostic,
            ),
            grid=(b_pad // _SUBLANES,),
            out_shape=(
                jax.ShapeDtypeStruct((_OUT_FIELDS, b_pad, md_pad), jnp.float32),
                jax.ShapeDtypeStruct(
                    (b_pad // _SUBLANES, _SUBLANES, _LANES), jnp.int32
                ),
            ),
            in_specs=[
                pl.BlockSpec(
                    (_CAND_FIELDS, _SUBLANES, k_pad), lambda g: (0, g, 0)
                ),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=(
                pl.BlockSpec(
                    (_OUT_FIELDS, _SUBLANES, md_pad), lambda g: (0, g, 0)
                ),
                pl.BlockSpec((None, _SUBLANES, _LANES), lambda g: (g, 0, 0)),
            ),
            scratch_shapes=[pltpu.VMEM((_SUBLANES, k_pad), jnp.float32)],
            interpret=interpret,
        )(cand, thresh)
    dets = jnp.moveaxis(out[0:6, :b, :max_det], 0, -1)
    keep = out[6, :b, :max_det] > 0.0
    return dets, keep, steps[:, 0, 0]


# -- 3D: residual decode + rectify as one elementwise launch ------------------


def _residual_decode_kernel(
    d_ref, a_ref, dir_ref, out_ref, *, num_dir_bins, dir_offset
):
    """models/pointpillars.decode_boxes + rectify_direction, SoA rows.
    d_ref/a_ref: (8, K) delta/anchor rows [x, y, z, dx, dy, dz, r, 0];
    dir_ref: (1, K) f32 direction bin; out_ref: (8, K) decoded rows."""
    xa, ya, za = a_ref[0:1, :], a_ref[1:2, :], a_ref[2:3, :]
    dxa, dya, dza = a_ref[3:4, :], a_ref[4:5, :], a_ref[5:6, :]
    ra = a_ref[6:7, :]
    diag = jnp.sqrt(dxa * dxa + dya * dya)
    out_ref[0:1, :] = d_ref[0:1, :] * diag + xa
    out_ref[1:2, :] = d_ref[1:2, :] * diag + ya
    out_ref[2:3, :] = d_ref[2:3, :] * dza + za
    out_ref[3:4, :] = jnp.exp(jnp.clip(d_ref[3:4, :], -10, 10)) * dxa
    out_ref[4:5, :] = jnp.exp(jnp.clip(d_ref[4:5, :], -10, 10)) * dya
    out_ref[5:6, :] = jnp.exp(jnp.clip(d_ref[5:6, :], -10, 10)) * dza
    rot = d_ref[6:7, :] + ra
    period = 2 * jnp.pi / num_dir_bins
    out = rot - dir_offset
    out = out - jnp.floor(out / period) * period + dir_offset
    out_ref[6:7, :] = out + period * dir_ref[0:1, :]
    out_ref[7:8, :] = jnp.zeros_like(ra)


@functools.partial(
    jax.jit, static_argnames=("num_dir_bins", "dir_offset", "interpret")
)
def fused_residual_decode(
    deltas: jnp.ndarray,
    anchors: jnp.ndarray,
    dir_bin: jnp.ndarray,
    num_dir_bins: int,
    dir_offset: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """(K, 7) deltas + (K, 7) anchors + (K,) dir bins -> (K, 7) decoded
    boxes with rectified heading, one elementwise Pallas launch."""
    k = deltas.shape[0]
    k_pad = _round_up(k, _LANES)
    d = jnp.zeros((8, k_pad), jnp.float32).at[0:7, :k].set(
        deltas.astype(jnp.float32).T
    )
    a = jnp.zeros((8, k_pad), jnp.float32).at[0:7, :k].set(
        anchors.astype(jnp.float32).T
    )
    db = jnp.zeros((1, k_pad), jnp.float32).at[0, :k].set(
        dir_bin.astype(jnp.float32)
    )
    with jax.named_scope("fused:decode_nms"):
        out = pl.pallas_call(
            functools.partial(
                _residual_decode_kernel,
                num_dir_bins=num_dir_bins,
                dir_offset=dir_offset,
            ),
            out_shape=jax.ShapeDtypeStruct((8, k_pad), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=interpret,
        )(d, a, db)
    return out[0:7, :k].T


# -- 3D: rotated suppression + pack in one launch -----------------------------


def _suppress_pack_3d_kernel(
    iou_ref, rows_ref, thresh_ref, out_ref, live_ref, *, max_det, width
):
    """iou_ref: (N, N) rotated IoU of SCORE-SORTED candidates;
    rows_ref: (16, N) sorted rows [box7+extras (width cols), score
    (-inf gated), label, 0...]; out_ref: (16, max_det_pad) rows
    [box7+extras, score, label, keep, 0...]. The greedy loop picks the
    best live candidate, reads its IoU ROW with a masked sublane
    reduction (no dynamic indexing), suppresses, and packs — the
    while_loop fixpoint + gather/concat packing of _nms_pack_one in
    one launch."""
    n = rows_ref.shape[1]
    iou_thresh = thresh_ref[0]
    score = rows_ref[width : width + 1, :]
    live_ref[:] = score  # already -inf on gated/pad slots
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (1, out_ref.shape[1]), 1)
    riota = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)

    def body(i, _):
        live = live_ref[:]
        best_score = jnp.max(live)
        best = jnp.argmax(live[0, :]).astype(jnp.int32)
        is_valid = best_score > _NEG_INF
        sel = lane == best

        # the selected candidate's IoU row, via sublane masking
        iou_row = jnp.sum(
            jnp.where(riota == best, iou_ref[:], 0.0), axis=0, keepdims=True
        )
        suppress = (iou_row > iou_thresh) | sel
        live_ref[:] = jnp.where(suppress & is_valid, _NEG_INF, live)

        for r in range(width + 2):  # box+extras, score, label
            v = masked_pick(sel, rows_ref[r : r + 1, :])
            write_lane_col(
                out_ref, r, out_lane, i, jnp.where(is_valid, v, 0.0)
            )
        write_lane_col(
            out_ref, width + 2, out_lane, i,
            jnp.where(is_valid, 1.0, 0.0),
        )
        return 0

    out_ref[:] = jnp.zeros(out_ref.shape, jnp.float32)
    jax.lax.fori_loop(0, max_det, body, 0)


@functools.partial(jax.jit, static_argnames=("max_det", "interpret"))
def fused_suppress_pack_3d(
    cand_boxes: jnp.ndarray,
    cand_scores: jnp.ndarray,
    cand_labels: jnp.ndarray,
    iou_thresh=0.01,
    max_det: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(K, 7+e) candidates + (K,) -inf-gated scores + (K,) 1-indexed
    labels -> packed ``(max_det, 9+e)`` rows [box7, extras, score,
    label] + keep mask — the exact ``_nms_pack_one`` contract. Sort and
    the rotated IoU matrix stay in XLA (module docstring); suppression
    and packing run in one launch."""
    from triton_client_tpu.ops.boxes3d import boxes7_to_bev, rotated_iou_bev

    k, width = cand_boxes.shape
    k_pad = _round_up(k, _LANES)
    md_pad = _round_up(max_det, _LANES)
    if width + 3 > 16:
        raise ValueError(f"too many box columns for the packed rows: {width}")

    # score-sort exactly like nms_bev (stable, -inf padding sinks)
    order = jnp.argsort(-cand_scores, stable=True).astype(jnp.int32)
    sb = cand_boxes[order].astype(jnp.float32)
    ss = cand_scores[order].astype(jnp.float32)
    sl = cand_labels[order].astype(jnp.float32)
    bev = boxes7_to_bev(sb[:, :7])
    iou = rotated_iou_bev(bev, bev)

    iou_p = jnp.zeros((k_pad, k_pad), jnp.float32).at[:k, :k].set(iou)
    rows = jnp.full((16, k_pad), 0.0, jnp.float32)
    rows = rows.at[0:width, :k].set(sb.T)
    rows = rows.at[width, :].set(_NEG_INF)  # pad lanes never selected
    rows = rows.at[width, :k].set(ss)
    rows = rows.at[width + 1, :k].set(sl)
    thresh = jnp.reshape(jnp.asarray(iou_thresh, jnp.float32), (1,))

    with jax.named_scope("fused:decode_nms"):
        out = pl.pallas_call(
            functools.partial(
                _suppress_pack_3d_kernel, max_det=max_det, width=width
            ),
            out_shape=jax.ShapeDtypeStruct((16, md_pad), jnp.float32),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((1, k_pad), jnp.float32)],
            interpret=interpret,
        )(iou_p, rows, thresh)
    dets = out[0 : width + 2, :max_det].T
    keep = out[width + 2, :max_det] > 0.0
    return dets, keep
