"""Fused-kernel before/after: per-stage device time, reference op chain
vs the single Pallas launch, with a roofline verdict per stage.

ISSUE 16's tentpole proof point. For each fused stage this harness
times the REFERENCE XLA route and the FUSED Pallas route on identical
inputs (both jitted, both warmed), prints the per-stage speedup, and
classifies each route against the machine roofline (obs/roofline) so a
win is explained — a bandwidth-bound stage that fused into fewer HBM
round-trips should move its attained fraction, not just its wall time.

Stages:
  voxelize_scatter  models/second._scatter_mean_volume (duplicate-index
                    scatter-add) vs ops/pallas_voxel.fused_mean_volume
                    (sorted one-hot MXU matmul + unique-index set).
                    TPU_FUSED_PIPELINE=grid|manual picks the
                    double-buffer form; ``--pipeline both`` compares.
  decode_nms_2d     ops/detect_postprocess.extract_boxes fused=False vs
                    fused=True (xywh decode + class-offset NMS + pack
                    in one launch).
  decode_nms_3d     ops/detect3d_postprocess.extract_boxes_3d
                    fused=False vs fused=True (BEV suppress + pack).

Off-TPU the fused route runs interpret-mode Pallas: correctness-true,
performance-FALSE — timings are printed but flagged non-representative
(the acceptance numbers come from a real chip). ``--trace DIR``
additionally captures a jax.profiler trace around each fused loop
inside a ``fused:<stage>`` TraceAnnotation and prints obs/opstats'
per-stage device-time split, proving the attribution plane sees fused
launches per stage.

Usage:
    python perf/profile_fused.py [--stages all] [--repeats 20]
                                 [--points 131072] [--cands 1024]
                                 [--trace DIR] [--pipeline grid]
"""

import argparse
import functools
import json
import statistics
import sys
import time

import _harness  # noqa: F401  (sys.path bootstrap)
import numpy as np

import jax
import jax.numpy as jnp

from triton_client_tpu.obs import opstats
from triton_client_tpu.obs.roofline import classify, measure_launch_cost
from triton_client_tpu.ops.fused import fused_interpret
from triton_client_tpu.ops.voxelize import VoxelConfig

STAGES = ("voxelize_scatter", "decode_nms_2d", "decode_nms_3d")

# KITTI-shaped SECOND grid (the scatter-bound front)
KITTI_VOXEL = VoxelConfig(
    point_cloud_range=(0.0, -40.0, -3.0, 70.4, 40.0, 1.0),
    voxel_size=(0.05, 0.05, 0.1),
    max_voxels=40000,
    max_points_per_voxel=5,
)


def _time(fn, args, kwargs, repeats: int) -> float:
    """Median wall ms of a warmed jitted callable."""
    jax.block_until_ready(fn(*args, **kwargs))
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        samples.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(samples)


def _roof(fn, args, kwargs) -> dict:
    lowered = fn.lower(*args, **kwargs)
    from triton_client_tpu.obs.roofline import _cost_dict

    cost = _cost_dict(lowered.cost_analysis())
    return classify(
        float(cost.get("flops", 0.0) or 0.0),
        float(cost.get("bytes accessed", 0.0) or 0.0),
    ).as_dict()


def _report(stage, ref_ms, fused_ms, ref_roof, fused_roof, interpret):
    ratio = ref_ms / fused_ms if fused_ms > 0 else float("inf")
    flag = "  [interpret — NOT representative]" if interpret else ""
    print(f"\n== {stage} =={flag}")
    print(f"  reference  {ref_ms:9.3f} ms   "
          f"{ref_roof['bound']}-bound  I={ref_roof['intensity']:.1f}")
    print(f"  fused      {fused_ms:9.3f} ms   "
          f"{fused_roof['bound']}-bound  I={fused_roof['intensity']:.1f}")
    print(f"  device-time reduction  {ratio:.2f}x")
    row = {
        "stage": stage,
        "ref_ms": ref_ms,
        "fused_ms": fused_ms,
        "speedup": ratio,
        "interpret": interpret,
        "ref_roofline": ref_roof,
        "fused_roofline": fused_roof,
    }
    if not interpret and fused_roof["attainable_calls_per_s"] > 0:
        attainable_ms = 1e3 / fused_roof["attainable_calls_per_s"]
        row["roofline_attained_ratio"] = attainable_ms / fused_ms
        print(f"  roofline attained      "
              f"{row['roofline_attained_ratio']:.1%} of the "
              f"{fused_roof['bound']} ceiling")
    return row


def _maybe_trace(trace_dir, stage, fn, args, kwargs, repeats: int):
    """Re-run the fused loop inside a fused:<stage> TraceAnnotation so
    the capture splits per stage (opstats' CPU fallback path)."""
    if not trace_dir:
        return
    with jax.profiler.TraceAnnotation(f"fused:{stage}"):
        for _ in range(max(2, repeats // 4)):
            jax.block_until_ready(fn(*args, **kwargs))


def stage_voxelize_scatter(args, trace_dir=None):
    from triton_client_tpu.models.second import _scatter_mean_volume
    from triton_client_tpu.ops.pallas_voxel import fused_mean_volume

    voxel = (
        KITTI_VOXEL
        if args.points >= 65536
        else VoxelConfig(
            point_cloud_range=(0.0, -8.0, -3.0, 16.0, 8.0, 1.0),
            voxel_size=(0.5, 0.5, 0.5),
            max_voxels=1024,
            max_points_per_voxel=5,
        )
    )
    rng = np.random.default_rng(0)
    r = voxel.point_cloud_range
    pts = np.column_stack(
        [
            rng.uniform(r[0], r[3], args.points),
            rng.uniform(r[1], r[4], args.points),
            rng.uniform(r[2], r[5], args.points),
            rng.uniform(0, 1, args.points),
        ]
    ).astype(np.float32)
    count = jnp.asarray(args.points, jnp.int32)
    pts = jnp.asarray(pts)
    interpret = fused_interpret()

    ref = jax.jit(functools.partial(_scatter_mean_volume, voxel=voxel))
    fused = jax.jit(
        functools.partial(
            fused_mean_volume, voxel=voxel, interpret=interpret
        )
    )
    a = (pts, count)
    ref_ms = _time(ref, a, {}, repeats=args.repeats)
    fused_ms = _time(fused, a, {}, repeats=args.repeats)
    _maybe_trace(trace_dir, "voxelize_scatter", fused, a, {},
                 repeats=args.repeats)
    return _report(
        "voxelize_scatter", ref_ms, fused_ms,
        _roof(ref, a, {}), _roof(fused, a, {}), interpret,
    )


def stage_decode_nms_2d(args, trace_dir=None):
    from triton_client_tpu.ops.detect_postprocess import extract_boxes

    rng = np.random.default_rng(1)
    pred = rng.uniform(0, 1, (args.batch, args.cands * 4, 5 + 80)).astype(
        np.float32
    )
    pred[..., :2] *= 512.0
    pred[..., 2:4] = pred[..., 2:4] * 60.0 + 4.0
    pred = jnp.asarray(pred)
    interpret = fused_interpret()

    a = (pred,)
    ref_kw = {"conf_thresh": 0.6, "fused": False}
    fus_kw = {"conf_thresh": 0.6, "fused": True, "interpret": interpret}
    ref_ms = _time(extract_boxes, a, ref_kw, repeats=args.repeats)
    fused_ms = _time(extract_boxes, a, fus_kw, repeats=args.repeats)
    _maybe_trace(trace_dir, "decode_nms", extract_boxes, a, fus_kw,
                 repeats=args.repeats)
    return _report(
        "decode_nms_2d", ref_ms, fused_ms,
        _roof(extract_boxes, a, ref_kw), _roof(extract_boxes, a, fus_kw),
        interpret,
    )


def stage_decode_nms_3d(args, trace_dir=None):
    from triton_client_tpu.ops.detect3d_postprocess import extract_boxes_3d

    rng = np.random.default_rng(2)
    boxes = np.zeros((args.batch, args.cands, 7), np.float32)
    boxes[..., 0] = rng.uniform(0, 70, (args.batch, args.cands))
    boxes[..., 1] = rng.uniform(-40, 40, (args.batch, args.cands))
    boxes[..., 2] = rng.uniform(-2, 0, (args.batch, args.cands))
    boxes[..., 3:6] = rng.uniform(1.0, 5.0, (args.batch, args.cands, 3))
    boxes[..., 6] = rng.uniform(-np.pi, np.pi, (args.batch, args.cands))
    scores = rng.uniform(0, 1, (args.batch, args.cands, 3)).astype(
        np.float32
    )
    boxes, scores = jnp.asarray(boxes), jnp.asarray(scores)
    interpret = fused_interpret()

    a = (boxes, scores)
    ref_kw = {"fused": False}
    fus_kw = {"fused": True, "interpret": interpret}
    ref_ms = _time(extract_boxes_3d, a, ref_kw, repeats=args.repeats)
    fused_ms = _time(extract_boxes_3d, a, fus_kw, repeats=args.repeats)
    _maybe_trace(trace_dir, "decode_nms", extract_boxes_3d, a, fus_kw,
                 repeats=args.repeats)
    return _report(
        "decode_nms_3d", ref_ms, fused_ms,
        _roof(extract_boxes_3d, a, ref_kw), _roof(extract_boxes_3d, a, fus_kw),
        interpret,
    )


RUNNERS = {
    "voxelize_scatter": stage_voxelize_scatter,
    "decode_nms_2d": stage_decode_nms_2d,
    "decode_nms_3d": stage_decode_nms_3d,
}


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--stages", default="all",
                   help=f"comma list of {', '.join(STAGES)} (or all)")
    p.add_argument("--repeats", type=int, default=20)
    p.add_argument("--points", type=int, default=131072,
                   help="cloud rows for voxelize_scatter (<65536 uses a "
                        "tiny grid — the CPU/interpret rig size)")
    p.add_argument("--cands", type=int, default=1024,
                   help="NMS candidate rows per image")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="capture a profiler trace of the fused loops and "
                        "print opstats' per-stage split")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write the per-stage rows as JSON")
    args = p.parse_args()

    names = (
        list(STAGES) if args.stages == "all"
        else [s.strip() for s in args.stages.split(",") if s.strip()]
    )
    for s in names:
        if s not in RUNNERS:
            raise SystemExit(f"unknown stage {s!r} (have {list(RUNNERS)})")

    backend = jax.default_backend()
    interpret = fused_interpret()
    print(f"backend={backend}  interpret={interpret}", file=sys.stderr)
    if interpret:
        print(
            "WARNING: Pallas interpret mode — fused timings are "
            "correctness-true, performance-false; run on a TPU for "
            "acceptance numbers",
            file=sys.stderr,
        )

    rows = []
    if args.trace:
        with jax.profiler.trace(args.trace):
            for s in names:
                rows.append(RUNNERS[s](args, trace_dir=args.trace))
    else:
        for s in names:
            rows.append(RUNNERS[s](args))

    if args.trace:
        try:
            summary = opstats.summarize_profile_dir(args.trace)
            print("\n== opstats per-stage device-time split ==")
            for stage, us in sorted(
                (summary.get("stages") or {}).items(), key=lambda kv: -kv[1]
            ):
                print(f"  fused:{stage:20s} {us / 1e3:10.2f} ms")
            if not summary.get("stages"):
                print("  (no fused: scope markers or windows in capture)")
        except FileNotFoundError as e:
            print(f"trace parse skipped: {e}", file=sys.stderr)

    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"backend": backend, "stages": rows}, fh, indent=2)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
