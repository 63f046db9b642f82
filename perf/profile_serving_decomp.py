"""Serving pipeline decomposition + depth A/B (VERDICT r4 Weak
#5/#6): where does the served/ceiling gap go?

Round 5 instrumented the batcher (runtime/continuous.py stats()
``decomp_ms``): per device batch, mean milliseconds in
  * queue_wait — first request staged -> executor slot acquired
    (includes pipeline-depth backpressure);
  * exec_wait — submit -> executor thread picks the group up;
  * stage    — host merge build (np.asarray + concat copy);
  * device   — the inner channel call (device_put + jit + readback).

The sum x batches vs the wall window tells which leg owns the gap
between served fps and device_ceiling_fps. The A/B axis:
  * pipeline_depth 1 / 2 / 4 — how many formed batches may be in
    flight against the device at once.

Usage: python perf/profile_serving_decomp.py [--duration 25] [--clients 16]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from triton_client_tpu.utils.compilation_cache import enable_persistent_cache

enable_persistent_cache()

import jax  # noqa: E402


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--duration", type=float, default=25.0)
    p.add_argument("--clients", type=int, default=16)
    p.add_argument("--input-size", type=int, default=512)
    args = p.parse_args(argv)

    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.obs import RuntimeCollector
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
    from triton_client_tpu.runtime.repository import ModelRepository
    from triton_client_tpu.runtime.server import InferenceServer
    from triton_client_tpu.utils.loadgen import run_pool

    hw = (args.input_size, args.input_size)
    pipe, spec, _ = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=hw
    )
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn())
    inner = TPUChannel(repo)
    rng = np.random.default_rng(0)
    frame = rng.integers(0, 255, (1, *hw, 3)).astype(np.uint8)
    k = 1
    while k <= 16:
        inner.do_inference(
            InferRequest(
                model_name=spec.name,
                inputs={"images": np.repeat(frame, k, axis=0)},
            )
        )
        k *= 2

    # device ceiling for the same batch (host-memory source)
    direct = np.repeat(frame, 16, axis=0)
    pipe.infer(direct)
    t0 = time.perf_counter()
    for _ in range(3):
        pipe.infer(direct)
    ceiling_fps = 16 / ((time.perf_counter() - t0) / 3)

    cases = [
        ("depth1", dict(pipeline_depth=1)),
        ("depth2", dict(pipeline_depth=2)),
        ("depth4", dict(pipeline_depth=4)),
    ]
    for name, kw in cases:
        batching = ContinuousBatchingChannel(
            inner, max_batch=8, max_merge=16, **kw
        )
        # the same snapshot/delta API the Prometheus custom collector
        # scrapes in production — perf rows and dashboards read
        # identical numbers instead of hand-diffing stats()
        collector = RuntimeCollector(channel=batching)
        server = InferenceServer(
            repo, batching, address="127.0.0.1:0",
            max_workers=args.clients + 8,
        )
        server.start()
        s0 = collector.snapshot()
        try:
            res = run_pool(
                f"127.0.0.1:{server.port}", spec.name, {"images": frame},
                clients=args.clients, duration_s=args.duration,
                deadline_s=300.0,
            )
            s1 = collector.snapshot()
            stats = RuntimeCollector.delta(s1, s0).get("batching", {})
            # level quantities (means / free-slot count), not counters:
            # read from the raw snapshot, not the delta
            stats["decomp_ms"] = s1["batching"].get("decomp_ms")
            lat = res.latencies_ms
            row = {
                "case": name,
                "fps": round(res.fps, 2),
                "served": res.served_frames,
                "ceiling_fps": round(ceiling_fps, 2),
                "served_over_ceiling": round(res.fps / ceiling_fps, 3),
                "p50_ms": round(float(np.percentile(lat, 50)), 1) if lat else None,
                "p99_ms": round(float(np.percentile(lat, 99)), 1) if lat else None,
                "decomp_ms": stats.get("decomp_ms"),
                "decomp_batches": stats.get("decomp_batches"),
                "mean_batch": round(
                    stats.get("merged_frames", 0)
                    / max(stats.get("merges", 1), 1), 2,
                ),
                "errors": len(res.errors),
            }
            print(json.dumps(row), flush=True)
        finally:
            server.stop()
            batching.close()


if __name__ == "__main__":
    main()
