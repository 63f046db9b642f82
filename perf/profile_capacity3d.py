"""3D capacity study (VERDICT r3 #5): is model width free at lidar rates?

The 2D answer is proven (v5n 1.7% MFU -> v5l 35% at the same b8 and
still ~1,000 fps: serve the largest variant the accuracy budget wants).
This runs the same protocol over the 3D family: PointPillars variants
with wider VFE / wider + deeper BEV backbones (scaling the reference's
pointpillar hyperparameters, /root/reference/data/pointpillar.yaml:
110-142 — VFE 64, blocks (3,5,5) x (64,128,256)) and the SECOND dense
tail at 2x width, each at b1 through the FULL pipeline (voxelize ->
model -> BEV NMS) on a structured 120k-pt scan, reporting scans/s and
MFU from the compiled executable's own FLOP count.

Protocol = bench.py's (chained token, in-jit reps, interleaved
trials); Configs are built through bench._make_3d so the fencing and
accounting are literally the same code the headline rows use.

Run: python perf/profile_capacity3d.py   (TPU, ~15 min fresh)
"""

import _harness  # noqa: F401  (repo path + compilation cache)

import dataclasses
import json
import sys

import jax

import bench
from triton_client_tpu.dataset_config import detect3d_from_yaml
from triton_client_tpu.pipelines.detect3d import (
    build_pointpillars_pipeline,
    build_second_pipeline,
    Detect3DConfig,
)


def pp_case(name: str, **model_over) -> bench.Config:
    _, model_cfg, pipe_cfg = detect3d_from_yaml("data/kitti_pointpillars.yaml")
    if model_over:
        model_cfg = dataclasses.replace(model_cfg, **model_over)
    pipeline, _, _ = build_pointpillars_pipeline(
        jax.random.PRNGKey(0), model_cfg=model_cfg, config=pipe_cfg
    )
    return bench._make_3d(
        pipeline, max(pipe_cfg.point_buckets), name,
        f"{name}_scans_per_sec", reps=40,
    )


def second_case(name: str, **model_over) -> bench.Config:
    cfg = Detect3DConfig(model_name="second_iou")
    kwargs = {}
    if model_over:
        from triton_client_tpu.models.second import SECONDConfig

        kwargs["model_cfg"] = dataclasses.replace(SECONDConfig(), **model_over)
    pipeline, _, _ = build_second_pipeline(
        jax.random.PRNGKey(0), config=cfg, **kwargs
    )
    return bench._make_3d(
        pipeline, max(cfg.point_buckets), name,
        f"{name}_scans_per_sec", reps=30,
    )


def main() -> None:
    variants = [
        # (label, factory) — base first; widths scale the reference's
        # pointpillar.yaml hyperparameters
        ("pp_base", lambda: pp_case("pp_base")),
        ("pp_vfe128", lambda: pp_case("pp_vfe128", vfe_filters=128)),
        ("pp_wide2x", lambda: pp_case(
            "pp_wide2x",
            backbone_filters=(128, 256, 512),
            upsample_filters=(256, 256, 256),
        )),
        ("pp_deep2x", lambda: pp_case(
            "pp_deep2x", backbone_layers=(6, 10, 10),
        )),
        ("pp_capacity", lambda: pp_case(
            "pp_capacity",
            vfe_filters=128,
            backbone_filters=(128, 256, 512),
            upsample_filters=(256, 256, 256),
            backbone_layers=(6, 10, 10),
        )),
        ("second_base", lambda: second_case("second_base")),
        ("second_wide2x", lambda: second_case(
            "second_wide2x",
            backbone_filters=(256, 512), middle_filters=(32, 64, 128),
        )),
    ]
    configs = []
    for label, factory in variants:
        try:
            c = factory()
            c.warmup()
            configs.append(c)
            print(f"warm {label} flops/call={c.flops_per_call}",
                  file=sys.stderr)
        except Exception as e:
            print(f"{label} failed: {e}", file=sys.stderr)
    for _ in range(9):  # interleaved trials, bench protocol
        for c in configs:
            c.run_trial()
    for c in configs:
        row = c.result(with_latency=False)
        print(json.dumps({
            "variant": c.name,
            "scans_per_sec": row["value"],
            "per_call_ms": row["per_call_ms"],
            "mfu": row.get("mfu"),
            "gflops_per_scan": round((c.flops_per_call or 0) / 1e9, 1),
            "spread": row["trial_spread"],
        }))


if __name__ == "__main__":
    main()
