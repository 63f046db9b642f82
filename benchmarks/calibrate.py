#!/usr/bin/env python3
"""Calibrate ``peaks.py`` once on the chip: what a large bf16 matmul
and a plain 2 GiB elementwise pass reach of the published peaks. Run by
the builder (``chiprun -- python3 benchmarks/calibrate.py``), not by
the benchmark. Each timing covers many calls, ended by
``block_until_ready``, and spans well over 250 ms."""

from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks import peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    peak = peaks.peaks(dev.device_kind)

    def timed(fn, *args, calls: int):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / calls

    n = 8192
    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.ones((n, n), jnp.bfloat16)
    t_mm = timed(jax.jit(lambda x, y: x @ y), a, b, calls=100)
    flops = 2.0 * n**3 / t_mm
    x = jnp.ones((2**29,), jnp.float32)  # 2 GiB read + 2 GiB written
    t_ew = timed(jax.jit(lambda v: v * 1.0001 + 1.0), x, calls=50)
    bw = 2.0 * x.nbytes / t_ew
    print(json.dumps({
        "device_kind": dev.device_kind,
        "matmul_bf16_8192_s": t_mm, "matmul_flops_per_s": flops,
        "matmul_share_of_published": flops / peak["flops_per_s"]["bf16"],
        "elementwise_2GiB_s": t_ew, "elementwise_bytes_per_s": bw,
        "elementwise_share_of_published": bw / peak["bytes_per_s"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
