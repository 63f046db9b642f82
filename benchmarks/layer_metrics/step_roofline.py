"""The least time the chip could take for one launch, over the time it
took: the larger of operations over peak FLOP/s and least bytes over
peak bytes/s (``ops_bytes/<family>.py``, ``peaks.py``), over
``device_ms_per_launch``. The rows of a launch are the batcher's
``batch_rows_mean`` rounded to what the replay cells launch."""

import importlib

from benchmarks import peaks

from . import batch_rows_mean, device_ms_per_launch


def read(ctx):
    ms = device_ms_per_launch.read(ctx)
    rows = batch_rows_mean.read(ctx)
    if not ms or not rows:
        return None
    cfg = ctx["cfg"]
    counts = importlib.import_module(f"benchmarks.ops_bytes.{cfg['ops_bytes']}").count(cfg, round(rows))
    peak = peaks.peaks(ctx["device"]["kind"])
    t_flops = counts["flops"] / peak["flops_per_s"][counts["flops_dtype"]]
    t_bytes = counts["bytes"] / peak["bytes_per_s"]
    ctx["roofline_bound"] = "compute" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) * 1e3 / ms
