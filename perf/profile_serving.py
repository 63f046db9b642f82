"""Serving-path decomposition: where do the seconds go between the
~1 s/b8 device leg and the served rate?

Two instruments:

  * the DEVICE-PATH sweep (default mode): served rate across a client
    range, where the batcher's serial device dispatch is the ceiling.
    It motivated pipeline_depth=2 dispatch overlap and the
    shared-memory transport (on-chip numbers: not measured since):
    shm's win is CONTENTION RELIEF — it frees the 1-core host for the
    dispatch thread while batches are in flight;
  * the NULL-MODEL control (`null` mode) removes the device leg
    entirely (host-only channel — NOT TPUChannel, whose device_put
    would silently re-add an upload) and shows the pure stack serving
    399-459 fps wire vs 627-1,412 fps shm at full 786 KB payloads on
    one core: the payload codec is the dominant per-request stack
    cost, and shm deletes it.

This harness builds ONE warmed pipeline (the expensive part: 8 merge-
size compiles), then sweeps (server workers, clients,
transport) over short windows, reusing the warm repo. Usage:

    python perf/profile_serving.py            # device-path sweep
    python perf/profile_serving.py 8 4 shm    # one combo
    python perf/profile_serving.py null       # stack-only control
"""

import sys
import time

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
    # device leg for one b8 batch from host memory
    direct = np.repeat(frame, MAX_BATCH, axis=0)
    pipe.infer(direct)
    t0 = time.perf_counter()
    for _ in range(3):
        pipe.infer(direct)
    direct_ms = (time.perf_counter() - t0) / 3 * 1e3
    return repo, inner, spec, frame, direct_ms


def run_combo(repo, inner, spec, frame, workers, clients, use_shm,
              duration_s=8.0):
    from triton_client_tpu.utils.loadgen import run_pool

    batching = ContinuousBatchingChannel(inner, max_batch=MAX_BATCH)
    server = InferenceServer(
        repo, batching, address="127.0.0.1:0", max_workers=workers
    )
    server.start()
    res = run_pool(
        f"127.0.0.1:{server.port}",
        spec.name,
        {"images": frame},
        clients=clients,
        duration_s=duration_s,
        deadline_s=300.0,
        use_shared_memory=use_shm,
        stagger_s=0.1,
    )
    stats = batching.stats()
    server.stop()
    batching.close()
    p50 = (
        float(np.percentile(res.latencies_ms, 50))
        if res.latencies_ms else float("nan")
    )
    mode = "shm " if use_shm else "wire"
    print(
        f"workers={workers:2d} clients={clients:2d} {mode}: "
        f"{res.fps:6.2f} fps  p50={p50:8.1f} ms  frames={res.served_frames}  "
        f"errors={len(res.errors)}  batches={stats.get('batches')}",
        flush=True,
    )
    return res.fps


class _HostChannel(TPUChannel):
    """TPUChannel minus the device: dispatches straight to the
    registered numpy function. The null control's guarantee ('no
    device leg at all') must hold on ANY backend — the base channel
    device_puts each batch, which would silently add a per-request
    upload and invalidate the control."""

    def do_inference(self, request):
        from triton_client_tpu.channel.base import InferResponse

        model = self._repository.get(request.model_name, request.model_version)
        return InferResponse(
            model_name=request.model_name,
            model_version=request.model_version or "1",
            outputs=model.infer_fn(request.inputs),
            request_id=request.request_id,
        )


def build_null():
    """Serving-STACK-only rig: a null model (numpy passthrough of a
    tiny output) behind the same repo/server path but a host-only
    channel — no device leg on any backend. Wire-vs-shm here is the
    codec/copy/handoff cost in isolation, the number the 512x512
    device-path sweep cannot show (there the device leg hides it)."""
    from triton_client_tpu.config import ModelSpec, TensorSpec

    spec = ModelSpec(
        name="null512",
        version="1",
        platform="jax",
        inputs=(TensorSpec("images", (-1, *HW, 3), "UINT8"),),
        outputs=(TensorSpec("sum", (-1,), "FP32"),),
        max_batch_size=MAX_BATCH,
    )
    repo = ModelRepository()
    repo.register(
        spec,
        lambda inputs: {
            # touch one row per image so the input bytes are really
            # consumed (a pure constant could hide a broken transport)
            "sum": np.asarray(inputs["images"][:, 0, 0, 0], np.float32)
        },
    )
    inner = _HostChannel(repo)
    rng = np.random.default_rng(0)
    frame = rng.integers(0, 255, (1, *HW, 3)).astype(np.uint8)
    return repo, inner, spec, frame


def main():
    if sys.argv[1:2] == ["null"]:
        repo, inner, spec, frame = build_null()
        print("null model (no device leg): pure serving-stack rates",
              flush=True)
        for workers, clients in ((4, 4), (8, 8)):
            for use_shm in (False, True):
                run_combo(repo, inner, spec, frame, workers, clients,
                          use_shm, duration_s=6.0)
        return
    repo, inner, spec, frame, direct_ms = build_warm()
    print(f"direct b8 batch: {direct_ms:.0f} ms "
          f"(device-leg ceiling {MAX_BATCH / direct_ms * 1e3:.1f} fps)",
          flush=True)
    if len(sys.argv) > 3:
        w, c, mode = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
        run_combo(repo, inner, spec, frame, w, c, mode == "shm")
        return
    for workers, clients in ((2, 2), (4, 4), (8, 8), (24, 16)):
        for use_shm in (False, True):
            run_combo(repo, inner, spec, frame, workers, clients, use_shm)


if __name__ == "__main__":
    main()
