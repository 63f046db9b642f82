"""Data-axis sweep for the mesh-sharded serving channel (round 7).

One ShardedTPUChannel (channel/sharded_channel.py) serves yolov5n over
meshes of 1/2/4/8 devices; per width the harness reports

  * ``aggregate_frames_per_sec`` — batch / per-device shard program
    time: the whole-mesh serving throughput when each device executes
    its shard concurrently (real hardware). Measured from the SHARD
    program itself (the jitted device_fn at batch/width rows, the exact
    per-device computation of the pure-DP executable — replicated
    params, no collectives), so the number is independent of how the
    harness host schedules virtual devices;
  * ``per_chip_frames_per_sec`` — aggregate / width, comparable to
    bench.py's ``*_per_chip`` rows;
  * ``e2e_frames_per_sec`` — measured wall through the full channel
    (stage -> sharded launch -> readback) on THIS host. On virtual
    host-platform devices every "device" time-shares the same cores, so
    shard programs serialize and this row stays flat — it is the
    dispatch-overhead check, not the scaling claim;
  * ``bitwise_identical`` — per-request outputs equal to the
    single-device TPUChannel, byte for byte (the round-7 contract:
    sharding must never change an answer);
  * ``speedup_vs_single`` — aggregate fps over the width-1 aggregate.

Self-provisioning: run under any backend; when fewer than ``--devices``
devices are live the script re-execs itself in a virtual CPU mesh
(``--xla_force_host_platform_device_count``) — from a parent that
has not touched jax, so no chip is held across the re-exec. The
on-chip mesh check is ``python chip_smoke.py --chips 4``.

Usage: python perf/profile_serving_sharded.py [--devices 8]
       [--widths 1,2,4,8] [--batch 8] [--rounds 6] [--hw 256]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def _reexec_with_virtual_mesh(n: int) -> None:
    """Replace this process with a child holding an n-device virtual
    CPU mesh; jax must not have been imported when this is called."""
    if os.environ.get("_TCR_MULTICHIP_CHILD"):
        raise RuntimeError(
            f"multichip child still has too few devices (wanted {n}); "
            "virtual CPU mesh provisioning failed"
        )
    env = dict(os.environ)
    kept = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    )
    env["XLA_FLAGS"] = (
        f"{kept} --xla_force_host_platform_device_count={n}".strip()
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["_TCR_MULTICHIP_CHILD"] = "1"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *sys.argv[1:]], env=env
    )
    sys.exit(proc.returncode)


def _needs_virtual_mesh(n: int) -> bool:
    """Decide on env alone — importing jax to count devices would
    initialize the backend we may need to replace."""
    if os.environ.get("_TCR_MULTICHIP_CHILD"):
        return False
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return True
    for f in os.environ.get("XLA_FLAGS", "").split():
        if f.startswith("--xla_force_host_platform_device_count="):
            return int(f.split("=", 1)[1]) < n
    return True


def _median_ms(fn, trials: int = 5) -> float:
    fn()  # warm
    acc = []
    for _ in range(trials):
        t0 = time.perf_counter()
        fn()
        acc.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(acc)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--devices", type=int, default=8,
                   help="virtual host devices to provision")
    p.add_argument("--widths", default="1,2,4,8",
                   help="data-axis widths to sweep (divisors of --batch)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--rounds", type=int, default=6,
                   help="timed e2e requests per width")
    p.add_argument("--hw", type=int, default=256,
                   help="square input size for yolov5n")
    args = p.parse_args(argv)
    if _needs_virtual_mesh(args.devices):
        _reexec_with_virtual_mesh(args.devices)

    import _harness  # noqa: F401  (repo-path + compilation-cache bootstrap)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_client_tpu.channel import (
        InferRequest,
        ShardedTPUChannel,
        TPUChannel,
    )
    from triton_client_tpu.parallel.mesh import MeshConfig
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline
    from triton_client_tpu.runtime.repository import ModelRepository

    assert len(jax.devices()) >= args.devices, jax.devices()
    widths = [int(w) for w in args.widths.split(",") if w]
    hw = (args.hw, args.hw)
    pipe, spec, _ = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=hw
    )
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn(), device_fn=pipe.device_fn())
    frames = (
        np.random.default_rng(0)
        .integers(0, 255, (args.batch, *hw, 3))
        .astype(np.float32)
    )

    # parity + e2e reference: the single-device channel
    single = TPUChannel(
        repo, MeshConfig(data=1, model=1), devices=jax.devices()[:1]
    )
    ref = single.do_inference(InferRequest(spec.name, {"images": frames}))
    device_fn = jax.jit(pipe.device_fn())
    base_aggregate = None
    for width in widths:
        if args.batch % width:
            raise SystemExit(f"--batch {args.batch} not divisible by {width}")
        chan = ShardedTPUChannel(
            repo,
            MeshConfig(data=width, model=1),
            devices=jax.devices()[:width],
        )
        resp = chan.do_inference(InferRequest(spec.name, {"images": frames}))
        bitwise = all(
            np.array_equal(resp.outputs[k], ref.outputs[k])
            and resp.outputs[k].dtype == ref.outputs[k].dtype
            for k in ref.outputs
        )
        # per-device shard program: device_fn on batch/width rows — the
        # exact computation each mesh device runs under pure DP
        shard_in = {"images": jnp.asarray(frames[: args.batch // width])}
        t_shard_ms = _median_ms(
            lambda: jax.block_until_ready(device_fn(shard_in))
        )
        aggregate = args.batch / (t_shard_ms / 1e3)

        def e2e():
            futs = [
                chan.do_inference_async(
                    InferRequest(spec.name, {"images": frames})
                )
                for _ in range(args.rounds)
            ]
            for f in futs:
                f.result()

        wall_ms = _median_ms(e2e, trials=3)
        if base_aggregate is None:
            base_aggregate = aggregate
        row = {
            "case": f"yolov5n_{args.hw}_b{args.batch}_data{width}",
            "data_axis": width,
            "batch": args.batch,
            "shard_rows": args.batch // width,
            "shard_exec_ms": round(t_shard_ms, 2),
            "aggregate_frames_per_sec": round(aggregate, 2),
            "per_chip_frames_per_sec": round(aggregate / width, 2),
            "e2e_frames_per_sec": round(
                args.rounds * args.batch / (wall_ms / 1e3), 2
            ),
            "bitwise_identical": bool(bitwise),
            "donated_launches": chan.stats()["donated_launches"],
            "speedup_vs_single": round(aggregate / base_aggregate, 2),
        }
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
