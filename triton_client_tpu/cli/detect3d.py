"""3D detection entry point (main3d.py / bag3d.py parity).

Runs PointPillars over recorded .npy point clouds (the reference's
tools/pc_extractor.py output format), a synthetic stream, or a live
PointCloud2 topic (``ros:<topic>``, gated).

Usage:
  python -m triton_client_tpu.cli.detect3d -i ./clouds --sink jsonl
  python -m triton_client_tpu.cli.detect3d -i synthetic:16
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from triton_client_tpu.cli.common import (
    _check_async_flags,
    add_common_flags,
    parse_dtype,
    make_profiler,
    make_sink,
    maybe_device_trace,
    print_report,
)


def parse_args(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__)
    add_common_flags(parser)
    # None sentinels: "not passed" must be distinguishable from the
    # default so YAML --config values aren't silently clobbered.
    parser.add_argument("--score", type=float, default=None, help="default 0.1")
    parser.add_argument(
        "--z-offset",
        type=float,
        default=None,
        help="sensor z correction, default 0 (reference adds 1.5, "
        "ros_inference3d.py:128)",
    )
    parser.add_argument(
        "--config",
        default="",
        help="dataset/model YAML (data/kitti_pointpillars.yaml etc.; the "
        "reference's data/pointpillar.yaml role) — overrides -m",
    )
    parser.add_argument(
        "--sweeps",
        type=int,
        default=None,
        help="aggregate the last N scans with a per-point time-lag "
        "channel before inference (nuScenes 10-sweep semantics, "
        "reference data/nusc_centerpoint_pp_02voxel_two_pfn_10sweep.py); "
        "default: the config's nsweeps (1)",
    )
    parser.add_argument(
        "--show", action="store_true",
        help="open an interactive Open3D window per frame (close it to "
        "advance; the reference's visualize_open3d draw_scenes loop). "
        "Needs open3d installed; --sink keeps working without it",
    )
    parser.add_argument(
        "--poses",
        default="",
        help="ego-pose source for --sweeps > 1: 'odom[:topic]' (read "
        "the input bag's nav_msgs/Odometry topic) or a pose JSONL "
        "({frame_id, pose:[x,y,z,qx,qy,qz,qw]}); older sweeps are then "
        "transformed into the keyframe's sensor frame (ego-motion "
        "compensation). Without it sweeps stack untransformed — exact "
        "only for a stationary platform",
    )
    parser.add_argument(
        "--vfe",
        default=None,
        choices=("auto", "grouped"),
        help="voxel-feature path: 'auto' (sort-free scatter VFE when the "
        "model supports it — the fast path) or 'grouped' (exact OpenPCDet "
        "(V, K) budget semantics: caps at max_voxels/max_points_per_voxel)",
    )
    args = parser.parse_args(argv)
    # keep the raw argv so --repo guards can tell an explicitly passed
    # flag from a parser default (cli/common.flags_given)
    import sys

    args.argv = list(argv) if argv is not None else sys.argv[1:]
    return args


def _check_poses_args(args, nsweeps: int | None = None) -> None:
    """--poses usage guards, cheap and decidable from args (+ the
    resolved nsweeps when known). Called twice: early in main (before
    the expensive model build) and in _run_3d (with real nsweeps)."""
    if not args.poses:
        return
    import os

    if nsweeps is not None:
        too_few = nsweeps <= 1
    else:
        too_few = args.sweeps is not None and args.sweeps <= 1
    if too_few:
        raise SystemExit(
            "--poses only affects multi-sweep aggregation; add --sweeps N"
        )
    if args.poses == "odom" or args.poses.startswith("odom:"):
        if not args.input.endswith(".bag"):
            raise SystemExit(
                "--poses odom[:topic] reads the INPUT bag's odometry "
                "topic; the input must be a .bag"
            )
    elif not os.path.exists(args.poses):
        raise SystemExit(f"--poses: no such pose file {args.poses!r}")


def _build_pose_lookup(args):
    """args.poses (already validated) -> pose_lookup callback."""
    if args.poses == "odom" or args.poses.startswith("odom:"):
        from triton_client_tpu.io.bag_io import bag_pose_lookup

        _, _, topic = args.poses.partition(":")
        return bag_pose_lookup(args.input, topic or None)
    from triton_client_tpu.io.bag_io import pose_lookup_from_jsonl

    return pose_lookup_from_jsonl(args.poses)


def main(argv=None) -> None:
    args = parse_args(argv)
    from triton_client_tpu.utils.compilation_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()  # before the first compile
    if args.sink == "images":
        raise SystemExit(
            "--sink images is 2D-only (3D results are box arrays, not "
            "annotated frames); use --sink jsonl"
        )

    if args.async_set:
        _check_async_flags(args)

    _check_poses_args(args)
    if args.show:
        # fail before the expensive model build, not after
        try:
            from triton_client_tpu.io.viz3d import _require_open3d

            _require_open3d()
        except ImportError as e:
            raise SystemExit(str(e))

    from triton_client_tpu.drivers.driver import (
        InferenceDriver,
        channel_infer3d,
        detect3d_infer,
        detect3d_infer_async,
    )
    from triton_client_tpu.pipelines.detect3d import (
        BUILDERS_3D as builders,
        default_detect3d_config,
    )

    if args.channel.startswith("grpc:"):
        if not args.model_name:
            raise SystemExit("--channel grpc:... requires -m/--model-name")
        if args.repo:
            raise SystemExit(
                "--repo is in-process mode; in remote mode the SERVER "
                "loads the repository (serve -r ...)"
            )
        if args.config or args.score is not None or args.vfe is not None:
            # Thresholds/model config are baked into the SERVER's jitted
            # pipeline (the repo entry's config.yaml) — silently
            # accepting them here would mislead.
            raise SystemExit(
                "--config/--score/--vfe are server-side in remote mode: set "
                "them in the model repository entry's config.yaml"
            )
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        channel = GRPCChannel(
            args.channel[len("grpc:"):],
            use_shared_memory=args.use_shared_memory,
        )
        infer = channel_infer3d(
            channel,
            args.model_name,
            model_version=args.model_version,
            z_offset=args.z_offset,  # None -> served metadata value
            asynchronous=args.async_set,
        )
        _run_3d(args, infer, args.model_name, nsweeps=args.sweeps or 1)
        return

    if args.repo:
        from triton_client_tpu.cli.common import flags_given, load_repo_pipeline

        overrides = {}
        if args.score is not None:
            overrides["score_thresh"] = args.score
        if args.z_offset is not None:
            overrides["z_offset"] = args.z_offset
        if args.vfe is not None:
            overrides["vfe"] = args.vfe
        pipe, spec = load_repo_pipeline(
            args, overrides, "3d",
            conflicts={
                "--config": bool(args.config),
                "--dtype": flags_given(getattr(args, "argv", None), "--dtype"),
            },
        )
        infer = (
            detect3d_infer_async(pipe) if args.async_set else detect3d_infer(pipe)
        )
        _run_3d(
            args, infer, spec.name,
            nsweeps=args.sweeps if args.sweeps is not None
            else pipe.config.nsweeps,
        )
        return

    model_cfg = None
    if args.config:
        from triton_client_tpu.dataset_config import detect3d_from_yaml

        name, model_cfg, cfg = detect3d_from_yaml(args.config)
    else:
        name = args.model_name or "pointpillars"
        cfg = default_detect3d_config(name)
    # explicitly-passed CLI flags win over config-file/default values
    if args.score is not None:
        cfg = dataclasses.replace(cfg, score_thresh=args.score)
    if args.z_offset is not None:
        cfg = dataclasses.replace(cfg, z_offset=args.z_offset)
    if args.vfe is not None:
        cfg = dataclasses.replace(cfg, vfe=args.vfe)
    if name not in builders:
        raise SystemExit(f"unknown 3D model '{name}' (choose from {sorted(builders)})")
    pipe, spec, _ = builders[name](
        jax.random.PRNGKey(0), model_cfg=model_cfg, config=cfg,
        dtype=parse_dtype(args.dtype),
    )
    infer = detect3d_infer_async(pipe) if args.async_set else detect3d_infer(pipe)
    _run_3d(
        args, infer, spec.name,
        nsweeps=args.sweeps if args.sweeps is not None else cfg.nsweeps,
    )


def _run_3d(args, infer, model_name: str, nsweeps: int = 1) -> None:
    """Shared driver tail for local (TPUChannel) and remote (gRPC)
    modes: ROS subscriber or pull-driven file/bag source."""
    if args.input.startswith("ros:"):
        if args.show:
            raise SystemExit(
                "--show is replay-only (the live ROS path publishes box "
                "arrays for rviz instead); drop --show for ros: inputs"
            )
        if nsweeps > 1:
            # live aggregation needs per-message stamps + ego poses the
            # subscribed topics don't carry; replay sources support it
            raise SystemExit(
                "--sweeps > 1 is replay-only (bag/.npy sources); the live "
                "ROS path runs single-sweep"
            )
        from triton_client_tpu.drivers import ros

        node = ros.RosDetect3D(
            infer,
            sub_topic=args.input[len("ros:") :],
            pub_topic="/tpu_detections/boxes3d",
        )
        node.spin()
        return

    from triton_client_tpu.drivers.driver import InferenceDriver
    from triton_client_tpu.io.sources import open_source

    source = open_source(args.input, args.limit, kind="pointcloud")
    _check_poses_args(args, nsweeps)
    if nsweeps > 1:
        from triton_client_tpu.ops.sweeps import sweep_source

        pose_lookup = _build_pose_lookup(args) if args.poses else None
        source = sweep_source(source, nsweeps, pose_lookup)
    evaluator = gt_lookup = None
    if args.gt:
        from triton_client_tpu.eval.detection_map import Detection3DEvaluator
        from triton_client_tpu.io.synthdata import load_gt3d_lookup

        evaluator = Detection3DEvaluator()
        gt_lookup = load_gt3d_lookup(args.gt)
    if args.show:
        from triton_client_tpu.io.viz3d import ShowSink3D

        try:
            sink = ShowSink3D(gt_lookup)
        except ImportError as e:
            raise SystemExit(str(e))
    else:
        sink = make_sink(args)
    profiler = make_profiler(args)
    driver = InferenceDriver(
        infer,
        source,
        sink=sink,
        prefetch=args.prefetch,
        warmup=args.warmup,
        evaluator=evaluator,
        gt_lookup=gt_lookup,
        profiler=profiler,
        inflight=args.inflight if args.async_set else 1,
    )
    with maybe_device_trace(args):
        stats = driver.run(max_frames=args.limit)
    if profiler is not None:
        import sys

        print(profiler.report(), file=sys.stderr)
    summary = evaluator.summary() if evaluator is not None else None
    print_report(stats, summary, {"model": model_name})


if __name__ == "__main__":
    main()
