"""The least time the chip could take for one block launch over the time
it took: ``ops_bytes/<family>.count_block`` at the window's mean
sessions a launch, mean history a session and share of committing rows
(the larger of least bytes over peak bytes/s and operations over peak
FLOP/s; bytes bound it), over ``lm_block_ms``."""

import importlib

from benchmarks import peaks

from . import block_sessions_mean, lm_block_ms
from ._sessions import delta, mean_context


def read(ctx):
    ms, sessions, context = lm_block_ms.read(ctx), block_sessions_mean.read(ctx), mean_context(ctx)
    if not ms or not sessions or context is None:
        return None
    cfg = ctx["cfg"]
    commits = delta(ctx, "lm_block_commit_rows") / delta(ctx, "lm_block_rows")
    counts = importlib.import_module(f"benchmarks.ops_bytes.{cfg['ops_bytes']}").count_block(cfg, sessions, context, commits)
    peak = peaks.peaks(ctx["device"]["kind"])
    least_s = max(counts["bytes"] / peak["bytes_per_s"], counts["flops"] / peak["flops_per_s"][counts["flops_dtype"]])
    return 100.0 * least_s * 1e3 / ms
