"""Per-stage on-chip profile, chained-token PER-DISPATCH variant.

HISTORICAL: kept for the methodology record. Per-dispatch timing pays
the host's dispatch cost per call — prefer perf/_harness.py's
in-jit looped trials (profile_device/profile_ab*) for device-true
numbers.

Each stage is measured the chained way: chained dispatches
through a scalar token, one forced readback per trial, median of
interleaved trials. Run on the live chip: `python profile_stages.py`.
"""

import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPS = 20
TRIALS = 7


def timed(name, step, results):
    tok = jnp.float32(0.0)
    for _ in range(3):
        tok = step(tok)
    float(tok)
    trials = []
    for _ in range(TRIALS):
        tok = jnp.float32(0.0)
        t0 = time.perf_counter()
        for _ in range(REPS):
            tok = step(tok)
        float(tok)
        trials.append((time.perf_counter() - t0) * 1e3 / REPS)
    ms = statistics.median(trials)
    results.append((name, ms))
    print(f"{name:42s} {ms:8.3f} ms", file=sys.stderr)
    return ms


def tokify(*outs):
    parts = []
    for o in jax.tree.leaves(outs):
        parts.append(jnp.sum(o) * 1e-12)
    return sum(parts).astype(jnp.float32)


def profile_yolo():
    from triton_client_tpu.models.yolov5 import init_yolov5
    from triton_client_tpu.ops.detect_postprocess import extract_boxes
    from triton_client_tpu.ops.preprocess import normalize_image

    print("== yolov5n 512 batch 8 ==", file=sys.stderr)
    model, variables = init_yolov5(
        jax.random.PRNGKey(0), num_classes=2, variant="n", input_hw=(512, 512)
    )
    rng = np.random.default_rng(0)
    frames = jnp.asarray(rng.integers(0, 255, (8, 512, 512, 3)).astype(np.float32))

    results = []

    @jax.jit
    def full(tok):
        x = normalize_image(frames + tok * 0.0, "yolo")
        pred = model.decode(model.apply(variables, x, train=False))
        return tokify(extract_boxes(pred, conf_thresh=0.3, iou_thresh=0.45))

    @jax.jit
    def to_heads(tok):
        x = normalize_image(frames + tok * 0.0, "yolo")
        return tokify(model.apply(variables, x, train=False))

    @jax.jit
    def to_decode(tok):
        x = normalize_image(frames + tok * 0.0, "yolo")
        return tokify(model.decode(model.apply(variables, x, train=False)))

    # isolated postprocess on a fixed decoded tensor
    x0 = normalize_image(frames, "yolo")
    pred0 = jax.jit(lambda v, x: model.decode(model.apply(v, x, train=False)))(
        variables, x0
    )
    pred0 = jax.block_until_ready(pred0)

    @jax.jit
    def post_only(tok):
        return tokify(
            extract_boxes(pred0 + tok * 0.0, conf_thresh=0.3, iou_thresh=0.45)
        )

    timed("pre+backbone (raw heads)", to_heads, results)
    timed("pre+backbone+decode", to_decode, results)
    timed("extract_boxes alone (gate+topk+nms)", post_only, results)
    timed("FULL fused pipeline", full, results)
    return results


def profile_pointpillars():
    from triton_client_tpu.dataset_config import detect3d_from_yaml
    from triton_client_tpu.models.pointpillars import (
        augment_points,
        scatter_max_canvas,
    )
    from triton_client_tpu.pipelines.detect3d import build_pointpillars_pipeline
    from triton_client_tpu.ops.voxelize import pad_points

    print("== pointpillars kitti 120k pts ==", file=sys.stderr)
    _, model_cfg, pipe_cfg = detect3d_from_yaml("data/kitti_pointpillars.yaml")
    pipeline, _, _ = build_pointpillars_pipeline(
        jax.random.PRNGKey(0), model_cfg=model_cfg, config=pipe_cfg
    )
    model, variables = pipeline.model, pipeline.variables
    voxel = model.cfg.voxel
    nx, ny, _ = voxel.grid_size

    rng = np.random.default_rng(0)
    n_pts = 120_000
    r = voxel.point_cloud_range
    pts = np.stack(
        [
            rng.uniform(r[0], r[3], n_pts),
            rng.uniform(r[1], r[4], n_pts),
            rng.uniform(r[2], r[5], n_pts),
            rng.uniform(0, 1, n_pts),
        ],
        axis=1,
    ).astype(np.float32)
    padded, m = pad_points(pts, max(pipe_cfg.point_buckets))
    pj, mj = jnp.asarray(padded), jnp.asarray(m)

    results = []

    @jax.jit
    def aug_only(tok):
        feats, vid, valid, cnt = augment_points(pj + tok * 0.0, mj, voxel)
        return tokify(feats, vid, cnt)

    @jax.jit
    def aug_encode(tok):
        feats, vid, valid, cnt = augment_points(pj + tok * 0.0, mj, voxel)
        x = model.apply(
            variables, feats, method=lambda mdl, f: mdl.vfe.encode(f, False)
        )
        return tokify(x, vid, cnt)

    @jax.jit
    def to_canvas(tok):
        feats, vid, valid, cnt = augment_points(pj + tok * 0.0, mj, voxel)
        x = model.apply(
            variables, feats, method=lambda mdl, f: mdl.vfe.encode(f, False)
        )
        canvas = scatter_max_canvas(x, vid, valid, (ny, nx))
        return tokify(canvas)

    @jax.jit
    def to_heads(tok):
        heads = model.apply(
            variables, pj + tok * 0.0, mj, train=False, method=model.from_points
        )
        return tokify(heads)

    inner = pipeline._jit

    @jax.jit
    def full(tok):
        dets, valid = inner(pj + tok * 0.0, mj)
        return tokify(dets, valid)

    timed("augment (incl. mean scatter-add)", aug_only, results)
    timed("augment+vfe encode", aug_encode, results)
    timed("augment+encode+scatter-max canvas", to_canvas, results)
    timed("through backbone+heads", to_heads, results)
    timed("FULL fused pipeline", full, results)
    return results


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "yolo"):
        profile_yolo()
    if which in ("all", "pp"):
        profile_pointpillars()
