"""Mean over launches of ``gap_k``: how long the device waited before
launch k, on the host's clock. The MEAN, because mean gap + mean busy
is the launch period, which is what ``throughput`` follows; the median
is no measure here: with two executor threads launches arrive in pairs,
so one gap in two is 0 and the median reads 0 or the long gap by which
kind is one more (my chip run, PR 26). The run's log carries, as one
line, the median, the whole window's wait split by what launch k's
request was doing in it, the share of the ``h2d`` time that ran while
the previous launch computed, and the idle share these spans give."""

import json

import numpy as np

from ._launches import gaps


def read(ctx):
    rows = gaps(ctx)
    if not rows:
        return None
    gap, busy = sum(r["gap_s"] for r in rows), sum(r["busy_s"] for r in rows)
    h2d = sum(r["h2d_s"] for r in rows)
    print(json.dumps({
        "host_gap_by_state_s": {k: sum(r["by_state"][k] for r in rows) for k in rows[0]["by_state"]},
        "host_gap_median_ms": float(np.median([r["gap_s"] for r in rows]) * 1e3),
        "h2d_overlap": sum(r["h2d_overlap_s"] for r in rows) / h2d if h2d else None,
        "idle_share_host_clock": gap / (gap + busy) if gap + busy else None,
        "launches": len(rows),
    }), flush=True)
    return float(gap / len(rows) * 1e3)
