"""A/B: device-fused vs host-hop ensemble on an image-sized intermediate.

The DAG is the shipped preprocess -> detector chain
(examples/ensemble_fused_pipeline): the intermediate is a full
(B, 512, 512, 3) float32 frame — 3.1 MB/frame at b8 in fp32 (in BOTH
directions: detector input down + preprocess output up... rather,
host path pays preprocess-output device->host then detector-input
host->device), exactly the shape where Triton's default host-hop
ensembles bleed and its GPU-tensor mode exists. Protocol is the
bench.py chained-token one: reps inside one jit-equivalent loop per
timed dispatch for the fused path; the host path CANNOT be chained
on-device (its steps return to python by design), so it pays its real
per-step costs and the comparison is the honest one a deployer sees.

Run: python perf/profile_ensemble.py  (TPU; ~2 min warm after cache)
"""

import sys
import time

import numpy as np

import _harness  # noqa: F401  (repo-path + compilation-cache bootstrap)

from triton_client_tpu.runtime import disk_repository as dr

BATCH = 8
IN_HW = (640, 960)  # camera-native != model 512x512, so resize is real
# SMALL sample by design: the host path moves ~50 MB of intermediates
# per call through the host link; the effect being measured (the host
# hop) is a multiple, far above the per-call spread, and the
# fused path's absolute time is cross-checked against the primary
# bench row (same detector, same batch)
TRIALS = 3
REPS = 3


def main() -> None:
    # build ONLY the two member entries (scan_disk would init all 13
    # example models — minutes of setup this A/B doesn't need)
    from triton_client_tpu.runtime.ensemble import (
        EnsembleStep,
        build_ensemble,
    )
    from triton_client_tpu.runtime.repository import ModelRepository

    repo = ModelRepository()
    for entry in ("examples/camera_preprocess", "examples/yolov5_crop"):
        rm = dr.build_model(entry)
        repo.register(
            rm.spec, rm.infer_fn, warmup=rm.warmup, device_fn=rm.device_fn
        )

    steps = [
        EnsembleStep(
            "camera_preprocess", {"images": "camera_raw"},
            {"preprocessed": "frame"},
        ),
        EnsembleStep(
            "yolov5_crop", {"images": "frame"},
            {"detections": "boxes", "valid": "valid"},
        ),
    ]
    fused = build_ensemble(
        repo, "fused_twin", steps, outputs=["boxes", "valid"], fuse="always"
    )
    host = build_ensemble(
        repo, "host_twin", steps, outputs=["boxes", "valid"], fuse="never"
    )

    print("members built; compiling both paths...", flush=True)
    rng = np.random.default_rng(0)
    frame = rng.integers(0, 255, (BATCH, *IN_HW, 3)).astype(np.uint8)

    # value-equality gate before timing: the two paths must agree
    a = fused.infer_fn({"camera_raw": frame})
    print("fused path compiled", flush=True)
    b = host.infer_fn({"camera_raw": frame})
    print("host path compiled", flush=True)
    np.testing.assert_allclose(
        np.asarray(a["boxes"], np.float32),
        np.asarray(b["boxes"], np.float32), rtol=2e-3, atol=2e-2,
    )
    print("fused == host on the DAG output (b8 real-size frames)")

    def timed(fn, label):
        fn()  # warm/compile
        samples = []
        for _ in range(TRIALS):
            t0 = time.perf_counter()
            for _ in range(REPS):
                fn()
            samples.append((time.perf_counter() - t0) / REPS * 1e3)
        ms = float(np.median(samples))
        print(
            f"{label}: {ms:.2f} ms/call ({BATCH / (ms / 1e3):.1f} fps) "
            f"spread {(np.percentile(samples, 90) - np.percentile(samples, 10)) / ms:.3f}",
            flush=True,
        )
        return ms

    # interleave A/B so host phases hit both equally
    dev_frame = {"camera_raw": frame}
    f_ms = []
    h_ms = []
    for _ in range(2):
        f_ms.append(timed(lambda: fused.infer_fn(dev_frame), "fused"))
        h_ms.append(timed(lambda: host.infer_fn(dev_frame), "host-hop"))
    f, h = float(np.median(f_ms)), float(np.median(h_ms))
    print(
        f"\nmedian fused {f:.2f} ms vs host {h:.2f} ms -> "
        f"host/fused = {h / f:.2f}x on an image-sized intermediate "
        f"(the ratio scales with the host link's bandwidth; the "
        f"structural claim is the fused path's zero host traffic)"
    )


if __name__ == "__main__":
    main()
