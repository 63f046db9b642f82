"""Multi-frame stream groups under a simulated WAN RTT (ISSUE 13).

A robot on a WAN link pays ~100 ms of RTT per gRPC message; the
multi-frame stream protocol packs G frames into ONE ModelStreamInfer
message so that cost is paid once per group instead of once per frame.
On loopback the RTT is ~0 and the win is invisible, so this harness
SIMULATES the link: a closed-loop stream client sleeps ``--rtt-ms``
once per message boundary (exactly the cost model of one in-flight
message on a long fat pipe), then measures served fps per group size.

Expected shape: fps(G) ~ G / (rtt + G * serve_s) — near-linear scaling
in G while the RTT term dominates, flattening once the device leg
does. The ``speedup_vs_g1`` column is the acceptance number: group
throughput must SCALE with group size.

The model is deliberately tiny (channel mean over a camera frame) so
the transport term dominates on any rig; pass ``--rtt-ms 0`` to see
the loopback-only protocol overhead instead.

Usage: python perf/profile_stream_groups.py [--rtt-ms 93]
       [--duration 8] [--groups 1,2,4,8,16]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import queue
import sys
import time

import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from triton_client_tpu.utils.compilation_cache import enable_persistent_cache

enable_persistent_cache()

import jax.numpy as jnp  # noqa: E402


def drive(chan, model, frame, group, rtt_s, duration_s) -> dict:
    from triton_client_tpu.channel.base import InferRequest

    sent: queue.Queue = queue.Queue(maxsize=group)
    t_end = time.perf_counter() + duration_s

    def gen():
        i = 0
        while time.perf_counter() < t_end:
            if rtt_s > 0 and i % group == 0:
                # one simulated WAN round trip per MESSAGE: the
                # whole point of packing G frames into one
                time.sleep(rtt_s)
            sent.put(1)  # closed loop: at most `group` frames in flight
            i += 1
            yield InferRequest(model_name=model, inputs={"images": frame})

    n = 0
    t0 = time.perf_counter()
    for _resp in chan.infer_stream(
        gen(), stream_timeout_s=120.0, group_size=group
    ):
        sent.get()
        n += 1
    wall = time.perf_counter() - t0
    return {"group": group, "served": n, "fps": round(n / wall, 2)}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--rtt-ms", type=float, default=93.0,
                   help="simulated per-message round trip (paper rig: 93)")
    p.add_argument("--duration", type=float, default=8.0)
    p.add_argument("--groups", default="1,2,4,8,16")
    p.add_argument("--input-size", type=int, default=256)
    args = p.parse_args(argv)

    from triton_client_tpu.channel.grpc_channel import GRPCChannel
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository
    from triton_client_tpu.runtime.server import InferenceServer

    hw = args.input_size
    spec = ModelSpec(
        name="frame_mean",
        version="1",
        platform="jax",
        inputs=(TensorSpec("images", (-1, hw, hw, 3), "UINT8"),),
        outputs=(TensorSpec("mean", (-1, 3), "FP32"),),
        max_batch_size=64,
    )
    repo = ModelRepository()
    repo.register(
        spec,
        lambda inputs: {
            "mean": jnp.mean(
                jnp.asarray(inputs["images"], jnp.float32), axis=(1, 2)
            )
        },
    )
    server = InferenceServer(
        repo, TPUChannel(repo), address="127.0.0.1:0",
        uds_address="auto", max_workers=8,
    )
    server.start()
    frame = (
        np.random.default_rng(0)
        .integers(0, 255, (1, hw, hw, 3))
        .astype(np.uint8)
    )
    chan = GRPCChannel(server.uds_address, timeout_s=60.0)
    rtt_s = args.rtt_ms / 1e3
    try:
        # warm: compile + learn the path before any timed window
        drive(chan, spec.name, frame, 1, 0.0, 1.0)
        base_fps = None
        for g in (int(v) for v in args.groups.split(",")):
            row = drive(chan, spec.name, frame, g, rtt_s, args.duration)
            if base_fps is None:
                base_fps = row["fps"] or 1.0
            row["rtt_ms"] = args.rtt_ms
            row["transport"] = chan.transport
            row["speedup_vs_g1"] = round(row["fps"] / base_fps, 2)
            print(json.dumps(row), flush=True)
    finally:
        chan.close()
        server.stop()


if __name__ == "__main__":
    main()
