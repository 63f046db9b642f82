"""The grouped 2D decode+NMS kernel (ops/pallas_decode): eight frames a
grid step, one a sublane, each group's greedy loop over when none of its
frames has a live candidate.

Rows and keep mask must be BITWISE what ``nms_padded`` gives a frame,
whatever the batch (a lone frame, a short group, a whole one, a padded
second group, two whole ones), and the steps a group reports must be
what its keep mask implies. Interpret-mode Pallas on the CPU, small
shapes (``max_nms`` 256, ``max_det`` 32); what Mosaic makes of the lane
reductions is the chip's own and is held on the chip
(``chip_smoke.py``, PERF.md), what it accepts by
``tests/test_tpu_compile_detectors.py``.
"""

import functools
import importlib

import jax
import numpy as np
import pytest

from triton_client_tpu.ops.boxes import xywh2xyxy
from triton_client_tpu.ops.detect_postprocess import (
    extract_boxes,
    extract_boxes_scored,
    extract_boxes_yolov4,
)
from triton_client_tpu.ops.nms import nms_padded
from triton_client_tpu.ops.pallas_decode import fused_decode_nms_2d

K = 256  # candidates a frame (max_nms)
MAX_DET = 32
BATCHES = (1, 3, 8, 11, 16)


def _candidates(seed, batch, k=K, extent=64.0, n_classes=3):
    """Seeded overlapping boxes as xywh, with a third of the slots
    invalid (score 0-filled, as the gate leaves them)."""
    rng = np.random.default_rng(seed)
    centre = rng.uniform(0.0, extent, (batch, k, 2))
    size = rng.uniform(extent / 32, extent / 3, (batch, k, 2))
    boxes = np.concatenate([centre, size], -1).astype(np.float32)
    valid = rng.uniform(size=(batch, k)) > 0.33
    scores = np.where(valid, rng.uniform(0.05, 1.0, (batch, k)), 0.0)
    classes = rng.integers(0, n_classes, (batch, k))
    return boxes, scores.astype(np.float32), classes, valid


@functools.lru_cache(maxsize=None)
def _reference_frame(box_format, class_agnostic):
    """``nms_padded`` on ONE frame, jitted once a route: the reference
    knows nothing of batches, so no batch size compiles it anew."""

    def one(b, s, c, v):
        if box_format == "xywh":
            b = xywh2xyxy(b)
        return nms_padded(
            b, s, c, v, max_det=MAX_DET, class_agnostic=class_agnostic
        )

    return jax.jit(one)


def _reference(boxes, scores, classes, valid, box_format="xywh", class_agnostic=False):
    frame = _reference_frame(box_format, class_agnostic)
    rows = [frame(*one) for one in zip(boxes, scores, classes, valid)]
    return np.stack([r[0] for r in rows]), np.stack([r[1] for r in rows])


def _fused(boxes, scores, classes, valid, **kw):
    return fused_decode_nms_2d(
        boxes, scores, classes, valid, max_det=MAX_DET, interpret=True, **kw
    )


def _implied_steps(keep):
    """What a group's loop ran, from the keep mask: as many steps as its
    fullest frame kept, plus the one that found nothing live, and never
    more than ``max_det``."""
    kept = np.asarray(keep).sum(axis=1)
    kept = np.pad(kept, (0, -len(kept) % 8))
    return np.minimum(kept.reshape(-1, 8).max(axis=1) + 1, MAX_DET)


def _assert_same(cands, box_format="xywh", **kw):
    ref_dets, ref_keep = _reference(*cands, box_format=box_format, **kw)
    dets, keep, steps = _fused(*cands, box_format=box_format, **kw)
    np.testing.assert_array_equal(np.asarray(dets), np.asarray(ref_dets))
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(ref_keep))
    np.testing.assert_array_equal(np.asarray(steps), _implied_steps(keep))
    return np.asarray(keep), np.asarray(steps)


@pytest.mark.parametrize("class_agnostic", (False, True))
@pytest.mark.parametrize("box_format", ("xywh", "xyxy"))
@pytest.mark.parametrize("batch", BATCHES)
def test_grouped_kernel_matches_nms_padded(batch, box_format, class_agnostic):
    boxes, scores, classes, valid = _candidates(10 + batch, batch)
    if box_format == "xyxy":
        boxes = np.asarray(xywh2xyxy(boxes))
    keep, steps = _assert_same(
        (boxes, scores, classes, valid), box_format=box_format,
        class_agnostic=class_agnostic,
    )
    assert keep.any() and steps.shape == (-(-batch // 8),)


def _predictions(seed, batch, n=400, nc=2):
    rng = np.random.default_rng(seed)
    pred = rng.uniform(0.0, 1.0, (batch, n, 5 + nc)).astype(np.float32)
    pred[..., :2] *= 64.0
    pred[..., 2:4] = pred[..., 2:4] * 20.0 + 2.0
    return pred


def _call_yolov5(batch, **route):
    return extract_boxes(
        _predictions(1, batch), conf_thresh=0.3, max_det=MAX_DET, max_nms=K,
        **route,
    )


def _call_yolov4(batch, **route):
    pred = _predictions(2, batch)
    boxes = np.asarray(xywh2xyxy(pred[..., :4]))[:, :, None, :] / 64.0
    return extract_boxes_yolov4(
        boxes, pred[..., 5:] * pred[..., 4:5], conf_thresh=0.3,
        max_det=MAX_DET, max_nms=K, **route,
    )


def _call_scored_multilabel(batch, **route):
    pred = _predictions(3, batch)
    return extract_boxes_scored(
        np.asarray(xywh2xyxy(pred[..., :4])), pred[..., 5:], conf_thresh=0.5,
        max_det=MAX_DET, max_nms=K, multi_label=True, **route,
    )


@pytest.mark.parametrize(
    "call", (_call_yolov5, _call_yolov4, _call_scored_multilabel),
    ids=("extract_boxes", "extract_boxes_yolov4", "extract_boxes_scored"),
)
def test_callers_match_their_unfused_route(call):
    batch = 11  # one whole group and a padded one
    ref_dets, ref_valid = call(batch)
    dets, valid = call(batch, fused=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(dets), np.asarray(ref_dets))
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(ref_valid))
    assert np.asarray(valid).any()


def _disjoint(batch, count, k=K):
    """``count`` boxes a frame that overlap nothing: every one is kept."""
    boxes = np.zeros((batch, k, 4), np.float32)
    boxes[..., 0] = 10.0 * np.arange(k) + 5.0
    boxes[..., 1] = 5.0
    boxes[..., 2:] = 4.0
    valid = np.broadcast_to(np.arange(k) < count, (batch, k)).copy()
    scores = np.where(valid, 1.0 - np.arange(k) / (2.0 * k), 0.0)
    return boxes, scores.astype(np.float32), np.zeros((batch, k), np.int64), valid


def test_a_frame_with_no_candidate_beside_one_that_fills_max_det():
    boxes, scores, classes, valid = _disjoint(8, 5)
    valid[2] = False  # dead from step 0
    valid[5] = np.arange(K) < MAX_DET + 9  # runs to the cap
    scores = np.where(valid, 1.0 - np.arange(K) / (2.0 * K), 0.0).astype(np.float32)
    keep, steps = _assert_same((boxes, scores, classes, valid))
    assert keep.sum(axis=1).tolist() == [5, 5, 0, 5, 5, MAX_DET, 5, 5]
    assert steps.tolist() == [MAX_DET]


def test_the_cap_ends_a_group_whose_frames_all_fill_max_det():
    keep, steps = _assert_same(_disjoint(8, MAX_DET + 20))
    assert keep.all() and steps.tolist() == [MAX_DET]


def test_a_group_stops_with_its_fullest_frame():
    boxes, scores, classes, valid = _disjoint(16, 3)
    valid[12] = np.arange(K) < 7
    scores = np.where(valid, 1.0 - np.arange(K) / (2.0 * K), 0.0).astype(np.float32)
    keep, steps = _assert_same((boxes, scores, classes, valid))
    assert steps.tolist() == [4, 8]  # three and seven kept, and the step that found nothing


def test_no_candidate_anywhere_is_one_step():
    boxes, scores, classes, valid = _disjoint(3, 0)
    keep, steps = _assert_same((boxes, scores, classes, valid))
    assert not keep.any() and steps.tolist() == [1]


def test_duplicated_scores_keep_the_first_index():
    boxes, scores, classes, valid = _candidates(5, 8)
    scores = np.where(valid, np.round(scores * 4.0) / 4.0, 0.0).astype(np.float32)
    boxes[:, 1::2] = boxes[:, 0::2]  # and identical boxes: only the order tells them apart
    _assert_same((boxes, scores, classes, valid))


def test_the_class_offset_stride_is_each_frames_own():
    boxes, scores, classes, valid = _candidates(6, 8)
    boxes[3] *= 100.0  # one frame's coordinates a hundredfold the others'
    boxes[4] *= 0.01
    keep, _ = _assert_same((boxes, scores, classes, valid))
    assert keep[3].any() and keep[4].any()


# -- the counter on the served path ------------------------------------------


def test_a_served_request_counts_its_steps_and_frames():
    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.obs.collector import RuntimeCollector
    from triton_client_tpu.pipelines import detect2d
    from triton_client_tpu.runtime.repository import ModelRepository

    n, hw = 96, (14, 16)  # a frame's 672 values ARE its 96 predictions of 7
    cfg = detect2d.Detect2DConfig(
        model_name="planted", input_hw=hw, num_classes=2, conf_thresh=0.3,
        max_det=MAX_DET, max_nms=64, scaling="none", fused="on",
    )
    pipe = detect2d.Detect2DPipeline(cfg, lambda x: x.reshape(x.shape[0], n, 7))
    spec = detect2d._detect2d_spec(cfg, n)
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn(), device_fn=pipe.device_fn())
    chan = TPUChannel(repo)
    collector = RuntimeCollector(channel=chan)
    try:
        frames = _predictions(7, 11, n=n).reshape(11, *hw, 3)
        frames[4, ..., :] = 0.0  # a frame that passes no gate
        out = chan.do_inference(InferRequest(spec.name, {"images": frames})).outputs
        assert set(out) == {"detections", "valid"}  # the wire contract, and no more
        want = int(_implied_steps(out["valid"]).sum())
        got = collector.snapshot()["channel"]
        assert (got["nms_steps"], got["nms_frames"]) == (want, 11)
        chan.do_inference(InferRequest(spec.name, {"images": frames}))
        again = collector.snapshot()["channel"]
        assert (again["nms_steps"], again["nms_frames"]) == (2 * want, 22)
    finally:
        collector.close()


def test_the_benchmarks_reader_takes_the_counters_growth_over_the_window():
    reader = importlib.import_module("benchmarks.layer_metrics.nms_steps_per_frame")
    snap = lambda steps, frames: {"channel": {"nms_steps": steps, "nms_frames": frames, "launched": 9}}
    ctx = {"snapshot_before": snap(5_000, 768), "snapshot_after": snap(5_000 + 10_506, 768 + 2 * 768)}
    assert reader.read(ctx) == pytest.approx(10_506 / 1_536)
    # a program without the counters (the parent) has nothing to read, and says so
    assert reader.read({"snapshot_before": {"channel": {"launched": 1}}, "snapshot_after": {"channel": {"launched": 9}}}) is None
    assert reader.read({"snapshot_before": None, "snapshot_after": {"channel": None}}) is None
    assert reader.read({"snapshot_before": snap(7, 8), "snapshot_after": snap(7, 8)}) is None  # no launch in the window
