"""Kernel-attribution acceptance drive: op coverage vs the ledger, plus
the per-model roofline report, on a live serving process.

ISSUE 14's acceptance bar is quantitative: the per-op attribution
(``/profile`` -> obs/opstats) must account for >= 90% of the
DeviceTimeLedger's device seconds over the same window — otherwise the
"which op do I fuse first" runbook is ranking a minority of the time
and the top-K table lies. This harness measures that number end to end:

  1. build the warmed YOLOv5n pipeline behind a full InferenceServer
     with the telemetry plane up (``metrics_port="auto"``);
  2. drive it with a client pool (utils/loadgen) for the whole run;
  3. mid-drive, take a ledger snapshot, hit ``/profile?seconds=N``
     (which now parses the capture into the op summary), take another
     ledger snapshot;
  4. report: attributed op seconds / ledger device-seconds delta
     (the coverage fraction), the top-K op table, and each model's
     roofline row (bound class + attainable-fps ceiling) from
     ``/snapshot``.

On the CPU backend the ledger times host-measured block durations, so
coverage is informational; the >= 90% gate is opt-in (``--gate``) and
meant for the real chip.

Usage:
    python perf/profile_roofline.py [--seconds 3] [--clients 4]
                                    [--top-k 15] [--gate]
"""

import argparse
import json
import sys
import threading
import time
import urllib.request

import _harness  # noqa: F401  (sys.path bootstrap)
import numpy as np

import jax

from triton_client_tpu.channel.base import InferRequest
from triton_client_tpu.channel.tpu_channel import TPUChannel
from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline
from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
from triton_client_tpu.runtime.repository import ModelRepository
from triton_client_tpu.runtime.server import InferenceServer

HW = (512, 512)
MAX_BATCH = 8
COVERAGE_FLOOR = 0.90


def build_warm():
    pipe, spec, _ = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2, input_hw=HW
    )
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn())
    inner = TPUChannel(repo)
    rng = np.random.default_rng(0)
    frame = rng.integers(0, 255, (1, *HW, 3)).astype(np.uint8)
    for k in range(1, MAX_BATCH + 1):
        print(f"precompile b{k}", file=sys.stderr, flush=True)
        inner.do_inference(
            InferRequest(
                model_name=spec.name,
                inputs={"images": np.repeat(frame, k, axis=0)},
            )
        )
    return repo, inner, spec, frame


def _get_json(url: str, timeout: float) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seconds", type=float, default=3.0,
                   help="profile capture window")
    p.add_argument("--clients", type=int, default=4)
    p.add_argument("--top-k", type=int, default=15)
    p.add_argument("--gate", action="store_true",
                   help=f"exit nonzero below {COVERAGE_FLOOR:.0%} coverage")
    args = p.parse_args()

    repo, inner, spec, frame = build_warm()
    batching = ContinuousBatchingChannel(inner, max_batch=MAX_BATCH)
    server = InferenceServer(
        repo, batching, address="127.0.0.1:0", max_workers=8,
        metrics_port="auto",
    )
    server.start()
    base = f"http://127.0.0.1:{server.metrics_port}"
    drive_s = args.seconds + 8.0  # pool must outlive ramp + capture

    from triton_client_tpu.utils.loadgen import run_pool

    pool: dict = {}

    def drive():
        pool["res"] = run_pool(
            f"127.0.0.1:{server.port}",
            spec.name,
            {"images": frame},
            clients=args.clients,
            duration_s=drive_s,
            deadline_s=300.0,
            stagger_s=0.1,
        )

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    time.sleep(2.0)  # let the pool ramp before the capture window

    led0 = server.device_time.snapshot()
    doc = _get_json(
        f"{base}/profile?seconds={args.seconds}&top_k={args.top_k}",
        timeout=args.seconds + 60.0,
    )
    led1 = server.device_time.snapshot()
    t.join(timeout=drive_s + 60.0)
    res = pool.get("res")

    summary = doc.get("op_summary")
    if not summary:
        raise SystemExit(
            f"/profile returned no op summary: "
            f"{doc.get('op_summary_error', doc)}"
        )

    ledger_delta_s = (
        led1.get("total_device_seconds", 0.0)
        - led0.get("total_device_seconds", 0.0)
    )
    attributed_s = sum((summary.get("models") or {}).values()) / 1e6
    total_op_s = summary.get("total_op_time_us", 0.0) / 1e6
    coverage = attributed_s / ledger_delta_s if ledger_delta_s > 0 else 0.0

    print("\n== op attribution coverage ==", flush=True)
    if res is not None:
        print(f"served {res.served_frames} frames at {res.fps:.1f} fps "
              f"({len(res.errors)} errors)")
    print(f"capture window          {args.seconds:.1f} s")
    print(f"ledger device seconds   {ledger_delta_s:.3f} s")
    print(f"op time (all modules)   {total_op_s:.3f} s")
    print(f"op time attributed      {attributed_s:.3f} s")
    print(f"coverage of ledger      {coverage:.1%}  "
          f"(floor {COVERAGE_FLOOR:.0%})")
    for model, us in sorted(
        (summary.get("models") or {}).items(), key=lambda kv: -kv[1]
    ):
        print(f"  {model:24s} {us / 1e3:10.2f} ms")
    unattr = summary.get("unattributed_us", 0.0)
    print(f"  {'(unattributed)':24s} {unattr / 1e3:10.2f} ms")

    print(f"\n== top-{args.top_k} ops by device time ==", flush=True)
    for row in summary.get("ops", []):
        print(
            f"  {str(row.get('model') or '-'):16s} "
            f"{row['kind']:13s} x{row['occurrences']:<5d} "
            f"{row['time_us'] / 1e3:9.2f} ms {row['share']:6.1%}  "
            f"{row['op'][:60]}"
        )

    print("\n== roofline ==", flush=True)
    snap = _get_json(f"{base}/snapshot", timeout=30.0)
    for row in snap.get("models", []):
        roof = row.get("roofline")
        if not roof:
            continue
        print(
            f"  {row.get('model')}:{row.get('version')}  "
            f"{roof['bound']}-bound  I={roof['intensity']:.1f} flop/B  "
            f"ceiling {roof['attainable_fps']:.1f} fps"
        )

    server.stop()
    batching.close()

    if args.gate and coverage < COVERAGE_FLOOR:
        raise SystemExit(
            f"coverage {coverage:.1%} below the {COVERAGE_FLOOR:.0%} floor"
        )


if __name__ == "__main__":
    main()
