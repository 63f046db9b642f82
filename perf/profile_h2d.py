"""Probe: in what FORM does a launch's frame batch cross to the device fastest?

The replay cell stages one request of ``uint8 [768, 512, 512, 3]`` (604 MB)
a launch with ONE ``jax.device_put`` of the request's shm view. This script
moves the same bytes, written by ANOTHER process into a shared-memory region
(as a caller's frames are), in other views of the same buffer, in row-chunks,
two at a time, as float32 and bfloat16 frames and at a batch of eight, and
prints one JSON line a variant:

  * ``put_ms`` / ``gb_per_s``: median over ``--reps`` of the host clock from
    ``jax.device_put`` to ``block_until_ready`` on its result; ``enqueue_ms``
    is how long ``device_put`` itself held the caller;
  * ``layout``: ``x.format.layout`` of the device array, what the device holds;
  * ``restore_ms``: a jitted program that brings the staged array back to
    ``bfloat16 [N, H/2, W/2, 12]``, the space-to-depth form the YOLOv5 stem
    convolves (models/yolov5.py), from that variant's form — a fast copy that
    costs the step as much again is no gain; ``restore_equal`` says the
    program's result equals variant ``wire``'s bit for bit.

Run it on the chip (``chiprun -- python perf/profile_h2d.py``); on the CPU
only as a rehearsal (``--frames 8 --reps 2``): a CPU timing is not a speed.
The lines are also written to ``chiprun_out/profile_h2d.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from multiprocessing import shared_memory

_WRITER = """
import sys
import numpy as np
from multiprocessing import shared_memory, resource_tracker
name, nbytes, seed = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
shm = shared_memory.SharedMemory(name=name)
resource_tracker.unregister(shm._name, "shared_memory")
out = np.ndarray((nbytes,), np.uint8, buffer=shm.buf)
rng = np.random.default_rng(seed)
step = 1 << 24
for i in range(0, nbytes, step):
    out[i:i + step] = rng.integers(0, 256, min(step, nbytes - i), dtype=np.uint8)
del out
shm.close()
"""


def _written_region(nbytes: int, seed: int) -> shared_memory.SharedMemory:
    """A region of ``nbytes`` filled by another process (no jax in it)."""
    shm = shared_memory.SharedMemory(create=True, size=nbytes)
    subprocess.run(
        [sys.executable, "-c", _WRITER, shm.name, str(nbytes), str(seed)], check=True
    )
    return shm


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--frames", type=int, default=768)
    ap.add_argument("--size", type=int, default=512)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=34)
    ap.add_argument("--out", default="chiprun_out/profile_h2d.jsonl")
    args = ap.parse_args(argv)
    n, s, reps = args.frames, args.size, args.reps
    nbytes = n * s * s * 3

    # the writer runs BEFORE this process touches jax (a chip belongs to
    # one process; the writer needs none, but nothing is left to chance)
    shm = _written_region(nbytes, args.seed)
    shm_b = _written_region(nbytes, args.seed + 1)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "count": jax.device_count(),
    }
    flat = np.ndarray((nbytes,), np.uint8, buffer=shm.buf)
    flat_b = np.ndarray((nbytes,), np.uint8, buffer=shm_b.buf)
    row = s * s * 3  # bytes a frame

    def stem_form(x):
        """Wire-shaped uint8 frames -> what the stem convolves."""
        x = (x.astype(jnp.float32) / 255.0).astype(jnp.bfloat16)
        x = x.reshape(n, s // 2, 2, s // 2, 2, 3)
        return jnp.transpose(x, (0, 1, 3, 2, 4, 5)).reshape(n, s // 2, s // 2, 12)

    def from_u32(x):
        return lax.bitcast_convert_type(x, jnp.uint8).reshape(x.shape[0], s, s, 3)

    # name -> (host view of the SAME bytes, inverse on the device)
    u8 = lambda *shape: flat.reshape(n, *shape)
    u32 = lambda *shape: flat.view(np.uint32).reshape(n, *shape)
    back = lambda x: x.reshape(x.shape[0], s, s, 3)
    forms = {
        "wire": (u8(s, s, 3), lambda x: x),
        "rows_x_1536": (u8(s, s * 3), back),
        "x_1024": (u8(row // 1024, 1024), back),  # [768, 768, 1024] at 512
        "x_128": (u8(row // 128, 128), back),
        "flat_rows": (u8(row), back),
        "u32_rows_x_384": (u32(s, s * 3 // 4), from_u32),
        "u32_x_128": (u32(row // 4 // 128, 128), from_u32),
    }

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    sink = open(args.out, "w")

    def emit(rec):
        rec = {**rec, "device": device, "bytes": nbytes, "frames": n}
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    def timed_put(views):
        """One staging: every view put back to back, then all waited for."""
        t0 = time.perf_counter()
        ys = [jax.device_put(v, dev) for v in views]
        t1 = time.perf_counter()
        jax.block_until_ready(ys)
        return ys, (t1 - t0) * 1e3, (time.perf_counter() - t0) * 1e3

    def timed_put_threads(views, pool):
        t0 = time.perf_counter()
        ys = list(pool.map(
            lambda v: jax.block_until_ready(jax.device_put(v, dev)), views))
        return ys, 0.0, (time.perf_counter() - t0) * 1e3

    def median_run(fn, arg):
        jax.block_until_ready(fn(arg))  # compile + first run
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arg))
            times.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(times)

    def measure(name, views, put, restore, reference, moved=nbytes):
        ys, _, _ = put(views)  # warm: page faults, the transfer's set-up
        layout = str(ys[0].format.layout)
        enq, total = [], []
        for _ in range(reps):
            del ys
            ys, e, t = put(views)
            enq.append(e)
            total.append(t)
        ms = statistics.median(total)
        rec = {
            "variant": name,
            "host_shape": [list(v.shape) for v in views][:2],
            "host_dtype": str(views[0].dtype),
            "pieces": len(views),
            "put_ms": ms,
            "put_ms_min": min(total),
            "put_ms_max": max(total),
            "enqueue_ms": statistics.median(enq),
            "gb_per_s": moved / ms / 1e6,
            "layout": layout,
        }
        if restore is not None:
            fn = jax.jit(restore)
            arg = ys if len(ys) > 1 else ys[0]
            rec["restore_ms"] = median_run(fn, arg)
            got = fn(arg)
            if reference is not None:
                rec["restore_equal"] = bool(jnp.array_equal(got, reference))
            rec["restore_layout"] = str(got.format.layout)
            del got
        del ys
        emit(rec)
        return rec

    # (a)-(d): one put of each form
    wire_view, _ = forms["wire"]
    reference = jax.jit(stem_form)(jax.device_put(wire_view, dev))
    results = {}
    for name, (view, inverse) in forms.items():
        results[name] = measure(
            name, [view], timed_put,
            (lambda inv: lambda x: stem_form(inv(x)))(inverse), reference,
        )

    # (e): the best single form in row-chunks, back to back and from threads
    best = min(results, key=lambda k: results[k]["put_ms"])
    best_view, best_inverse = forms[best]
    for k in (2, 4, 8):
        if n % k:
            continue
        r = n // k
        pieces = [best_view[i * r:(i + 1) * r] for i in range(k)]
        join = lambda ys: stem_form(
            jnp.concatenate([best_inverse(y) for y in ys], axis=0))
        measure(f"{best}.chunks{k}", pieces, timed_put, join, reference)
        with ThreadPoolExecutor(k) as pool:
            measure(
                f"{best}.chunks{k}.threads", pieces,
                lambda v: timed_put_threads(v, pool), None, None,
            )
        # and the wire form in chunks, which says whether chunks help
        # what the dense view does not cure
        if best != "wire":
            wire_pieces = [wire_view[i * r:(i + 1) * r] for i in range(k)]
            measure(f"wire.chunks{k}", wire_pieces, timed_put, None, None)

    # (f): two whole batches in flight at once (two regions, two threads):
    # do they share one link's rate, or does each keep its own?
    for name in dict.fromkeys(("wire", best)):
        view = forms[name][0]
        other = flat_b.view(view.dtype).reshape(view.shape)
        with ThreadPoolExecutor(2) as pool:
            measure(
                f"{name}.two_in_flight", [view, other],
                lambda v: timed_put_threads(v, pool), None, None,
                moved=2 * nbytes,
            )

    # (g): who else stages frames: float32 and bfloat16 frames (the same
    # bytes, a quarter and half of the frames) and a camera's batch of eight
    as_f32 = flat.view(np.float32).reshape(n // 4, s, s, 3)
    measure("wire_f32", [as_f32], timed_put, None, None)
    measure("x_128_f32", [as_f32.reshape(n // 4, -1, 128)], timed_put, None, None)
    as_bf16 = flat.view(jnp.bfloat16.dtype).reshape(n // 2, s, s, 3)
    measure("wire_bf16", [as_bf16], timed_put, None, None)
    measure("x_128_bf16", [as_bf16.reshape(n // 2, -1, 128)], timed_put, None, None)
    eight = forms["wire"][0][:8]
    measure("wire.b8", [eight], timed_put, None, None, moved=eight.nbytes)
    measure("x_128.b8", [eight.reshape(8, -1, 128)], timed_put, None, None, moved=eight.nbytes)

    sink.close()
    del flat, flat_b, as_f32, as_bf16, eight, wire_view, best_view, forms, u8, u32
    for region in (shm, shm_b):
        try:
            region.close()
        except BufferError:
            pass  # a view is still alive somewhere: the mapping goes with the process
        region.unlink()
    return 0


if __name__ == "__main__":
    sys.exit(main())
