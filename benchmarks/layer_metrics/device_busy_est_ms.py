"""Median over launches of ``busy_k``: the device's time on a launch as
the host's clock alone gives it (from the later of frames on the
device, program enqueued and previous launch done, to outputs ready).
What an operator has without a profiler, beside ``device_ms_per_launch``."""

import numpy as np

from ._launches import gaps


def read(ctx):
    rows = gaps(ctx)
    return float(np.median([r["busy_s"] for r in rows]) * 1e3) if rows else None
