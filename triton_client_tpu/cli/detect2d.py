"""2D detection entry point.

The composition is the reference's main.py:116-139 triple — client
(model pipeline) + channel + inference driver — with the remote Triton
hop replaced by the in-process TPU channel. ``--input ros:<topic>``
selects the live ROS adapter when rospy is available; anything else is
pull-driven replay (bag2d.py semantics).

Usage:
  python -m triton_client_tpu.cli.detect2d -m yolov5n -i ./frames --sink images
  python -m triton_client_tpu.cli.detect2d -m yolov4 -i synthetic:64 --gt gt.jsonl
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from triton_client_tpu.cli.common import (
    _check_async_flags,
    add_common_flags,
    parse_dtype,
    load_gt_lookup,
    load_names,
    make_profiler,
    make_sink,
    maybe_device_trace,
    parse_mesh,
    print_report,
)


def _run_streaming(args, channel, spec, class_names) -> None:
    """Pump every source frame through ONE bidirectional
    ModelStreamInfer stream and sink responses as they arrive — requests
    pipeline instead of blocking one round-trip per frame."""
    import time

    import numpy as np

    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.drivers.driver import latency_stats
    from triton_client_tpu.io.sources import open_source

    if args.input.startswith("ros:"):
        raise SystemExit("--streaming is replay-mode only; drop it for ros:")

    source = open_source(args.input, args.limit)
    frames = iter(source)
    first = next(frames, None)
    if first is None:
        raise SystemExit("input source is empty")
    # Warmup through the unary path so the server-side jit compile
    # (minutes cold on TPU) never lands in the streamed latency stats —
    # matching the InferenceDriver/MultiCameraDriver methodology.
    for _ in range(args.warmup):
        channel.do_inference(
            InferRequest(
                model_name=args.model_name,
                model_version=args.model_version,
                inputs={"images": np.asarray(first.data)[None]},
            )
        )

    in_flight = {}
    sent = {}

    def req_iter():
        import itertools

        for i, frame in enumerate(itertools.chain([first], frames)):
            if args.limit and i >= args.limit:
                break
            rid = str(i)
            in_flight[rid] = frame
            sent[rid] = time.perf_counter()
            yield InferRequest(
                model_name=args.model_name,
                model_version=args.model_version,
                inputs={"images": np.asarray(frame.data)[None]},
                request_id=rid,
            )

    sink = make_sink(args, class_names)
    latencies = []
    n = 0
    t0 = time.perf_counter()
    stream_timeout = args.stream_timeout_s if args.stream_timeout_s > 0 else None
    try:
        for resp in channel.infer_stream(
            req_iter(), stream_timeout_s=stream_timeout
        ):
            latencies.append(time.perf_counter() - sent.pop(resp.request_id))
            frame = in_flight.pop(resp.request_id)
            out = {
                k: (v[0] if np.ndim(v) > 0 and np.shape(v)[0] == 1 else v)
                for k, v in resp.outputs.items()
            }
            sink.write(frame, out)
            n += 1
    finally:
        sink.close()
    wall = time.perf_counter() - t0
    print_report(
        latency_stats(latencies, frames=n, wall_s=wall, ticks=n),
        None,
        {"model": spec.name, "streaming": True},
    )


def _run_multicam(args, channel, spec, class_names) -> None:
    """Lockstep N-camera batch serving over the mesh data axis."""
    import copy
    import os

    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.drivers.multicam import MultiCameraDriver
    from triton_client_tpu.io.sources import open_source

    if args.gt:
        raise SystemExit(
            "--gt is single-stream only; run the evaluation pass without "
            "--cameras (accuracy is camera-independent)"
        )
    if args.input.startswith("ros:"):
        raise SystemExit(
            "--cameras is replay/synthetic-only for now; live multi-topic "
            "ROS batching needs one subscriber per topic (run one "
            "detect2d per topic, or drop --cameras)"
        )

    sources = [
        open_source(args.input, args.limit) for _ in range(args.cameras)
    ]
    profiler = make_profiler(args)

    def infer(inputs):
        resp = channel.do_inference(
            InferRequest(
                model_name=args.model_name or spec.name,
                model_version=args.model_version,
                inputs=inputs,
            )
        )
        return resp.outputs

    if profiler is not None:
        infer = profiler.wrap("infer_batch", infer)

    # One sink per camera rooted at <output>/cam<i>/ so per-camera
    # outputs never collide on shared frame-numbered filenames.
    sinks = []
    for ci in range(args.cameras):
        cam_args = copy.copy(args)
        cam_args.output = os.path.join(args.output, f"cam{ci}")
        sinks.append(make_sink(cam_args, class_names))

    def cam_sink(ci, frame, result):
        sinks[ci].write(frame, result)

    driver = MultiCameraDriver(infer, sources, sink=cam_sink, warmup=args.warmup)
    try:
        with maybe_device_trace(args):
            stats = driver.run(max_ticks=args.limit)
    finally:
        # flush buffered sinks even when infer raises mid-run (the
        # single-stream driver closes its sink in a finally too)
        for sink in sinks:
            sink.close()
    if profiler is not None:
        import sys

        print(profiler.report(), file=sys.stderr)
    print_report(stats, None, {"model": spec.name, "cameras": args.cameras})


def parse_args(argv=None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__)
    add_common_flags(parser)
    parser.add_argument(
        "--mesh", default="",
        help="device mesh for the in-process channel, e.g. 'data=4' "
        "(multi-camera DP serving) or 'data=4,model=2'",
    )
    parser.add_argument(
        "--cameras", type=int, default=1,
        help="replicate the input source N times and run the lockstep "
        "multi-camera driver: one (N, H, W, 3) batch per tick, sharded "
        "over the mesh data axis (the reference's 'ensemble "
        "multi-camera' serving, README.md:119)",
    )
    parser.add_argument(
        "--stream-timeout-s", type=float, default=3600.0,
        help="whole-stream deadline for --streaming (0 = unbounded for "
        "long-lived live sessions)",
    )
    parser.add_argument(
        "--input-size", type=int, default=512, help="model input H=W (reference 512)"
    )
    # None -> per-model reference defaults (yolov5: 0.3/0.45
    # ros_inference.py:148; yolov4: 0.4/0.6 tools/utils.py post_processing)
    parser.add_argument("--conf", type=float, default=None)
    parser.add_argument("--iou", type=float, default=None)
    parser.add_argument(
        "--width", type=float, default=1.0, help="YOLOv4 width multiple"
    )
    parser.add_argument(
        "--mxu-opt", action="store_true",
        help="yolov5 only: space-to-depth stem + 32-channel floor — the "
        "MXU-shaped layout (+16%% at b8 on a v5e chip, measured). Same "
        "detection function; upstream weights import losslessly",
    )
    args = parser.parse_args(argv)
    # keep the raw argv so --repo guards can tell an explicitly passed
    # flag from a parser default (cli/common.flags_given)
    import sys

    args.argv = list(argv) if argv is not None else sys.argv[1:]
    return args


def build(args):
    """Model name -> (pipeline, spec). yolov5{n,s,m,l,x}, yolov4,
    retinanet[_<depth>] or fcos[_<depth>] (depth: tiny|resnet18|34|50).
    With --repo, the model is instead loaded from the repository entry
    (trained weights + its config.yaml; --conf/--iou still override)."""
    if args.repo:
        from triton_client_tpu.cli.common import flags_given, load_repo_pipeline

        overrides = {}
        if args.conf is not None:
            overrides["conf_thresh"] = args.conf
        if args.iou is not None:
            overrides["iou_thresh"] = args.iou
        argv = getattr(args, "argv", None)
        return load_repo_pipeline(
            args, overrides, "2d",
            conflicts={
                "--input-size": flags_given(argv, "--input-size"),
                "--classes": flags_given(argv, "-c", "--classes"),
                "--width": flags_given(argv, "--width"),
                "--scaling": flags_given(argv, "-s", "--scaling"),
                "--dtype": flags_given(argv, "--dtype"),
                "--mxu-opt": args.mxu_opt,
            },
        )
    from triton_client_tpu.pipelines.detect2d import (
        Detect2DConfig,
        build_fcos_pipeline,
        build_retinanet_pipeline,
        build_yolov4_pipeline,
        build_yolov5_pipeline,
    )

    name = args.model_name or "yolov5n"
    hw = (args.input_size, args.input_size)
    is_v4 = name == "yolov4"
    cfg = Detect2DConfig(
        model_name=name,
        input_hw=hw,
        num_classes=args.classes,
        conf_thresh=args.conf if args.conf is not None else (0.4 if is_v4 else 0.3),
        iou_thresh=args.iou if args.iou is not None else (0.6 if is_v4 else 0.45),
        scaling=args.scaling,
    )
    if name.startswith("yolov5"):
        variant = name[len("yolov5") :] or "n"
        pipe, spec, _ = build_yolov5_pipeline(
            jax.random.PRNGKey(0),
            variant=variant,
            num_classes=args.classes,
            input_hw=hw,
            config=cfg,
            dtype=parse_dtype(args.dtype),
            s2d=args.mxu_opt,
            ch_floor=32 if args.mxu_opt else 0,
        )
    elif args.mxu_opt:
        raise SystemExit("--mxu-opt is yolov5-only")
    elif name == "yolov4":
        pipe, spec, _ = build_yolov4_pipeline(
            jax.random.PRNGKey(0),
            num_classes=args.classes,
            width=args.width,
            input_hw=hw,
            config=cfg,
            dtype=parse_dtype(args.dtype),
        )
    elif name.partition("_")[0] in ("retinanet", "fcos"):
        from triton_client_tpu.models.retinanet import RESNET_DEPTHS

        base, _, depth = name.partition("_")
        depth = depth or "resnet50"
        if depth not in RESNET_DEPTHS:
            raise SystemExit(
                f"unknown backbone depth '{depth}' (choose from {sorted(RESNET_DEPTHS)})"
            )
        builder = build_retinanet_pipeline if base == "retinanet" else build_fcos_pipeline
        # Detectron family: no /255 scaling, detectron2 test thresholds,
        # reference input 640x480 (RetinaNet_detectron/config.pbtxt:3-8).
        cfg = dataclasses.replace(
            cfg,
            conf_thresh=args.conf if args.conf is not None else 0.05,
            # Per-model detectron2 test-time NMS: 0.5 retinanet, 0.6 fcos.
            iou_thresh=args.iou
            if args.iou is not None
            else (0.5 if base == "retinanet" else 0.6),
            max_det=100,
            scaling="none",
            multi_label=True,
            head_style="scored",
        )
        pipe, spec, _ = builder(
            jax.random.PRNGKey(0),
            num_classes=args.classes,
            depth=depth,
            input_hw=hw,
            config=cfg,
            dtype=parse_dtype(args.dtype),
        )
    else:
        raise SystemExit(
            f"unknown 2D model '{name}' "
            "(yolov5[nsmlx] | yolov4 | retinanet[_depth] | fcos[_depth])"
        )
    return pipe, spec


def main(argv=None) -> None:
    args = parse_args(argv)
    from triton_client_tpu.utils.compilation_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()  # before the first compile
    if args.sink == "bag":
        raise SystemExit(
            "--sink bag is 3D-only (the output bag carries point clouds + "
            "jsk box arrays, bag_inference3d.py:182-183); use --sink "
            "images or jsonl"
        )
    if args.async_set:
        _check_async_flags(args)
    from triton_client_tpu.drivers.driver import InferenceDriver, channel_infer

    if args.channel.startswith("grpc:"):
        # Remote mode: the reference's actual topology — model runs in
        # the serving process, this client only decodes/draws/publishes.
        if not args.model_name:
            raise SystemExit("--channel grpc:... requires -m/--model-name")
        if args.repo:
            raise SystemExit(
                "--repo is in-process mode; in remote mode the SERVER "
                "loads the repository (serve -r ...)"
            )
        if args.conf is not None or args.iou is not None:
            # Thresholds are baked into the SERVER's jitted pipeline
            # (repo entry config.yaml) — same guard as detect3d's.
            raise SystemExit(
                "--conf/--iou are server-side in remote mode: set them in "
                "the model repository entry's config.yaml"
            )
        if args.mesh:
            raise SystemExit(
                "--mesh is server-side in remote mode: pass it to "
                "'serve --mesh ...' instead"
            )
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        channel = GRPCChannel(
            args.channel[len("grpc:"):],
            use_shared_memory=args.use_shared_memory,
        )
        spec = channel.get_metadata(args.model_name, args.model_version)
        class_names = load_names(args.names) or tuple(
            spec.extra.get("class_names", ())
        )
        if args.streaming:
            # the reference defines --streaming but never exercises it
            # (main.py:66-70); here it is the pipelined ModelStreamInfer
            # path: requests flow while earlier responses are in flight.
            if args.gt:
                raise SystemExit(
                    "--gt is unary-mode only; drop --streaming to evaluate"
                )
            if args.cameras > 1:
                raise SystemExit(
                    "--cameras batches locally; it does not combine with "
                    "--streaming"
                )
            if args.profile or args.profile_trace:
                raise SystemExit(
                    "--profile/--profile-trace are not wired for the "
                    "streaming path yet; per-request latency is already "
                    "in the report"
                )
            _run_streaming(args, channel, spec, class_names)
            return
        infer = channel_infer(
            channel,
            args.model_name,
            model_version=args.model_version,
            asynchronous=args.async_set,
        )
    else:
        if args.streaming:
            raise SystemExit(
                "--streaming is the remote ModelStreamInfer path; use "
                "-u grpc:<host:port> (in-process inference has no wire "
                "to stream over)"
            )
        pipe, spec = build(args)
        # --names wins; a --repo entry's own class vocabulary (its
        # config.yaml class_names_file) labels sinks like the grpc
        # path's served metadata does
        class_names = load_names(args.names) or tuple(
            spec.extra.get("class_names", ())
        )

        from triton_client_tpu.channel.tpu_channel import TPUChannel
        from triton_client_tpu.runtime.repository import ModelRepository

        repo = ModelRepository()
        repo.register(spec, pipe.infer_fn())
        channel = TPUChannel(repo, mesh_config=parse_mesh(args.mesh))
        infer = channel_infer(channel, spec.name, asynchronous=args.async_set)

    if args.cameras > 1:
        _run_multicam(args, channel, spec, class_names)
        return

    if args.input.startswith("ros:"):
        from triton_client_tpu.drivers import ros

        node = ros.RosDetect2D(
            infer,
            sub_topic=args.input[len("ros:") :],
            pub_topic="/tpu_detections/image",
            class_names=class_names,
        )
        node.spin()
        return

    from triton_client_tpu.io.sources import open_source

    source = open_source(args.input, args.limit)
    evaluator = gt_lookup = None
    if args.gt:
        from triton_client_tpu.eval import DetectionEvaluator

        evaluator = DetectionEvaluator()
        gt_lookup = load_gt_lookup(args.gt)

    profiler = make_profiler(args)
    driver = InferenceDriver(
        infer,
        source,
        sink=make_sink(args, class_names),
        prefetch=max(args.prefetch, args.batch_size),
        warmup=args.warmup,
        evaluator=evaluator,
        gt_lookup=gt_lookup,
        profiler=profiler,
        batch_size=args.batch_size,
        inflight=args.inflight if args.async_set else 1,
    )
    with maybe_device_trace(args):
        stats = driver.run(max_frames=args.limit)
    if profiler is not None:
        import sys

        print(profiler.report(), file=sys.stderr)
    summary = evaluator.summary() if evaluator is not None else None
    print_report(stats, summary, {"model": spec.name})
    if summary is not None and args.prometheus_port > 0:
        # Keep the process (and the metrics HTTP server) alive so a
        # Prometheus scrape can actually happen — the reference exporter
        # lives inside a long-running ROS node (evaluate_inference.py:52).
        import sys
        import time as _time

        from triton_client_tpu.eval.prometheus_export import EvalPrometheusExporter

        exporter = EvalPrometheusExporter(args.prometheus_port)
        for frame_stats in evaluator.per_frame_summaries():
            exporter.observe(*frame_stats)
        print(
            f"serving eval metrics on :{args.prometheus_port}; Ctrl-C to exit",
            file=sys.stderr,
        )
        try:
            while True:
                _time.sleep(3600)
        except KeyboardInterrupt:
            pass


if __name__ == "__main__":
    main()
