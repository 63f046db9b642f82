"""Where does the serving second go? Thread-stack sampling decomposition.

bench.measure_serving r4 rows show the device idle ~75% of the window
while 32 closed-loop clients wait ~16 s per request — so the limiter is
in the HOST path, but the aggregate stats can't say which layer. This
harness runs the same stack (pipeline -> TPUChannel -> dispatch-time
batcher -> KServe gRPC server -> loadgen clients) with:

  * a poor-man's py-spy: a sampler thread walks sys._current_frames()
    every 50 ms and buckets every thread's innermost non-idle frame —
    after the window the histogram IS the wall-clock decomposition;
  * process CPU time vs wall (host-core saturation check);
  * the device-busy tap (sum of inner do_inference wall).

Run: python perf/profile_serving_stacks.py  (TPU, ~3 min warm cache)
"""

import _harness  # noqa: F401

import collections
import sys
import threading
import time

import numpy as np
import jax

import os
CLIENTS = int(os.environ.get("STACKS_CLIENTS", "16"))
DURATION_S = 30.0
DEPTH = int(os.environ.get("STACKS_DEPTH", "2"))
SAMPLE_EVERY_S = 0.05


class StackSampler(threading.Thread):
    """Samples every live thread's stack; buckets leaf frames."""

    def __init__(self):
        super().__init__(daemon=True)
        self.counts: collections.Counter = collections.Counter()
        self.samples = 0
        self._stop = threading.Event()
        self._me = None

    def run(self):
        self._me = threading.get_ident()
        while not self._stop.is_set():
            frames = sys._current_frames()
            self.samples += 1
            for tid, frame in frames.items():
                if tid == self._me:
                    continue
                # walk down past pure waiting shims to a labeled leaf
                f = frame
                leaf = f"{f.f_code.co_filename.split('/')[-1]}:{f.f_code.co_name}"
                # keep one caller for context
                if f.f_back is not None:
                    b = f.f_back
                    leaf = (
                        f"{b.f_code.co_filename.split('/')[-1]}:"
                        f"{b.f_code.co_name} -> {leaf}"
                    )
                self.counts[leaf] += 1
            time.sleep(SAMPLE_EVERY_S)

    def stop(self):
        self._stop.set()


def main() -> None:
    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
    from triton_client_tpu.runtime.repository import ModelRepository
    from triton_client_tpu.runtime.server import InferenceServer
    from triton_client_tpu.utils.loadgen import run_pool

    pipe, spec, _ = build_yolov5_pipeline(
        jax.random.PRNGKey(0), variant="n", num_classes=2,
        input_hw=(512, 512),
    )
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn())
    inner = TPUChannel(repo)

    device_busy = [0.0]
    dev_calls = []
    lock = threading.Lock()
    inner_infer = inner.do_inference

    def tapped(req):
        t0 = time.perf_counter()
        try:
            return inner_infer(req)
        finally:
            dt = time.perf_counter() - t0
            with lock:
                device_busy[0] += dt
                # input-agnostic batch bucket (same r5/r6 fix as
                # bench.measure_serving's tap: a non-image request
                # must not KeyError, and its batch is the first
                # tensor's leading dim, not a silent 1)
                arr = req.inputs.get("images")
                if arr is None and req.inputs:
                    arr = next(iter(req.inputs.values()))
                shape = np.shape(arr) if arr is not None else ()
                dev_calls.append(
                    (int(shape[0]) if shape else 1, round(dt, 3))
                )

    inner.do_inference = tapped

    # leg decomposition: time upload / jit / readback inside the
    # pipeline call the serving path makes (quiet-process calls are
    # fast from any thread; the inflation needs the serving machinery
    # live, so measure it in situ)
    import jax.numpy as jnp
    legs = []
    orig_infer = type(pipe).infer

    def timed_infer(self, frames):
        t0 = time.perf_counter()
        squeeze = frames.ndim == 3
        if squeeze:
            frames = frames[None]
        orig_hw = (frames.shape[1], frames.shape[2])
        dev = jnp.asarray(frames)
        dev.block_until_ready()
        t1 = time.perf_counter()
        dets, valid = self._jit(dev, orig_hw)
        jax.block_until_ready((dets, valid))
        t2 = time.perf_counter()
        dets, valid = np.asarray(dets), np.asarray(valid)
        t3 = time.perf_counter()
        with lock:
            legs.append((int(frames.shape[0]), round(t1 - t0, 2),
                         round(t2 - t1, 2), round(t3 - t2, 2)))
        return (dets[0], valid[0]) if squeeze else (dets, valid)

    pipe.infer = timed_infer.__get__(pipe)

    rng = np.random.default_rng(0)
    frame = rng.integers(0, 255, (1, 512, 512, 3)).astype(np.uint8)
    k = 1
    while k <= 16:
        inner_infer(InferRequest(model_name=spec.name,
                                 inputs={"images": np.repeat(frame, k, 0)}))
        k *= 2

    batching = ContinuousBatchingChannel(
        inner, max_batch=8, max_merge=16, pipeline_depth=DEPTH
    )
    server = InferenceServer(
        repo, batching, address="127.0.0.1:0", max_workers=CLIENTS + 8
    )
    server.start()
    addr = f"127.0.0.1:{server.port}"

    sampler = StackSampler()
    t_cpu0 = [0.0]
    t_wall0 = [0.0]
    probe_log = []

    def prober():
        """Mid-window environment probes: raw upload bandwidth and a
        direct b16 pipeline call, concurrent with the serving load —
        if THESE collapse too, the slowdown is the host link under
        load, not the serving stack."""
        import jax.numpy as jnp
        blob = np.zeros((16, 512, 512, 3), np.uint8)
        time.sleep(8.0)
        for _ in range(2):
            t0 = time.perf_counter()
            jnp.asarray(blob).block_until_ready()
            dt = time.perf_counter() - t0
            probe_log.append(("upload16_mbps", round(blob.nbytes / 1e6 / dt, 1)))
            t0 = time.perf_counter()
            pipe.infer(np.repeat(frame, 16, axis=0))
            probe_log.append(("direct16_s", round(time.perf_counter() - t0, 2)))
            time.sleep(6.0)

    def window_start():
        with lock:
            device_busy[0] = 0.0
            dev_calls.clear()
        sampler.start()
        threading.Thread(target=prober, daemon=True).start()
        t_cpu0[0] = time.process_time()
        t_wall0[0] = time.perf_counter()

    res = run_pool(
        addr, spec.name, {"images": frame},
        clients=CLIENTS, duration_s=DURATION_S, deadline_s=240.0,
        on_window_start=window_start,
    )
    cpu = time.process_time() - t_cpu0[0]
    wall = time.perf_counter() - t_wall0[0]
    sampler.stop()
    server.stop()
    batching.close()

    print(f"depth={DEPTH} clients={CLIENTS}")
    print(f"\nserved {res.served_frames} frames in {res.wall_s:.1f}s "
          f"({res.fps:.2f} fps), p50 "
          f"{np.percentile(res.latencies_ms, 50) / 1e3:.1f}s, "
          f"errors={len(res.errors)}")
    print(f"process CPU {cpu:.1f}s / wall {wall:.1f}s = "
          f"{cpu / wall:.2f} cores (1.0 = host core saturated)")
    with lock:
        print(f"device busy {device_busy[0]:.1f}s / wall {wall:.1f}s = "
              f"{device_busy[0] / wall:.2f}")
        print(f"device calls (batch, s): {dev_calls[:40]}")
    print(f"in-window probes: {probe_log}")
    with lock:
        print(f"legs (batch, upload_s, jit_s, readback_s): {legs[-25:]}")
    print(f"\ntop thread-leaf frames ({sampler.samples} samples x "
          f"~{CLIENTS + 12} threads):")
    total = sum(sampler.counts.values())
    for leaf, n in sampler.counts.most_common(24):
        print(f"  {n / total * 100:5.1f}%  {leaf}")


if __name__ == "__main__":
    main()
