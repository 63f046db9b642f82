"""The least time the chip could take for one step launch over the time
it took: ``ops_bytes/<family>.count_step`` at the window's mean sessions
a launch and mean history a session (the larger of least bytes over
peak bytes/s and operations over peak FLOP/s; bytes bound it), over
``lm_step_ms``."""

import importlib

from benchmarks import peaks

from . import lm_step_ms, step_sessions_mean
from ._sessions import mean_context


def read(ctx):
    ms, sessions, context = lm_step_ms.read(ctx), step_sessions_mean.read(ctx), mean_context(ctx)
    if not ms or not sessions or context is None:
        return None
    cfg = ctx["cfg"]
    counts = importlib.import_module(f"benchmarks.ops_bytes.{cfg['ops_bytes']}").count_step(cfg, sessions, context)
    peak = peaks.peaks(ctx["device"]["kind"])
    least_s = max(counts["bytes"] / peak["bytes_per_s"], counts["flops"] / peak["flops_per_s"][counts["flops_dtype"]])
    return 100.0 * least_s * 1e3 / ms
