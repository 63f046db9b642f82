"""Median of the callers' copies into their shm regions that ended
inside the window: ``SharedMemoryRegion.write`` keeps the last of them
as ``(t0, t1, nbytes)`` on ``time.perf_counter``, and the load
generator's callers are threads of this process, on the window's
clock. A program without that log reports nothing."""

import numpy as np


def read(ctx):
    from triton_client_tpu.runtime import shared_memory

    write_log = getattr(shared_memory, "write_log", None)
    if write_log is None:
        return None
    window = ctx["window"]
    ms = [(t1 - t0) * 1e3 for t0, t1, _ in write_log() if window.t_start <= t1 <= window.t_end]
    return float(np.median(ms)) if ms else None
