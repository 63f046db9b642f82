"""Streaming / async serving A/B (VERDICT r4 Weak #2).

The reference carries --streaming and --async flags it never uses
(main.py:59-70); this framework implemented both for real
(runtime/server.py ModelStreamInfer; channel.do_inference_async).
This harness puts NUMBERS on them: the same KServe server + batcher +
yolov5n-512 pipeline as bench.measure_serving, driven by the loadgen
pool in each client protocol:

  * unary wire / unary shm  — the bench baseline rows;
  * stream wire, inflight 1 — per-request overhead of a long-lived
    bidirectional stream vs per-call unary dispatch;
  * stream wire, inflight 4 — pipelining inside one stream session;
  * async wire, inflight 2/4 — call-futures pipelining per client.

Where the server-side device dispatch is the bottleneck, protocol
deltas surface in request latency shape and batcher occupancy more
than in fps. Run with the host otherwise idle.

Usage: python perf/profile_serving_modes.py [--duration 25] [--clients 16]
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
    while k <= 16:  # precompile the bucket sizes
        inner.do_inference(
            InferRequest(
                model_name=spec.name,
                inputs={"images": np.repeat(frame, k, axis=0)},
            )
        )
        k *= 2
    batching = ContinuousBatchingChannel(inner, max_batch=8, max_merge=16)
    server = InferenceServer(
        repo, batching, address="127.0.0.1:0", max_workers=args.clients + 8
    )
    server.start()
    addr = f"127.0.0.1:{server.port}"

    # use_shared_memory is pinned per case: loopback channels now
    # auto-negotiate shm by default, which would silently turn every
    # "wire" case into an shm case
    cases = [
        ("unary_wire", dict(mode="unary", use_shared_memory=False)),
        ("unary_shm", dict(mode="unary", use_shared_memory=True)),
        ("stream_wire_if1", dict(
            mode="stream", inflight=1, use_shared_memory=False)),
        ("stream_wire_if4", dict(
            mode="stream", inflight=4, use_shared_memory=False)),
        ("stream_shm_b4", dict(
            mode="stream", inflight=4, stream_group=4,
            use_shared_memory=True)),
        ("async_wire_if2", dict(
            mode="async", inflight=2, use_shared_memory=False)),
        ("async_wire_if4", dict(
            mode="async", inflight=4, use_shared_memory=False)),
    ]
    try:
        for name, kw in cases:
            stats0 = batching.stats()
            t0 = time.perf_counter()
            res = run_pool(
                addr, spec.name, {"images": frame},
                clients=args.clients, duration_s=args.duration,
                deadline_s=300.0, **kw,
            )
            stats = batching.stats()
            lat = res.latencies_ms
            row = {
                "case": name,
                "clients": args.clients,
                "window_s": round(time.perf_counter() - t0, 1),
                "fps": round(res.fps, 2),
                "served": res.served_frames,
                "p50_ms": round(float(np.percentile(lat, 50)), 1) if lat else None,
                "p99_ms": round(float(np.percentile(lat, 99)), 1) if lat else None,
                "device_batches": stats.get("merges", 0) - stats0.get("merges", 0),
                "mean_batch": round(
                    (stats.get("merged_frames", 0) - stats0.get("merged_frames", 0))
                    / max(stats.get("merges", 0) - stats0.get("merges", 0), 1),
                    2,
                ),
                "errors": len(res.errors),
            }
            if res.errors:
                row["first_error"] = res.errors[0][:160]
            print(json.dumps(row), flush=True)
    finally:
        server.stop()
        batching.close()


if __name__ == "__main__":
    main()
