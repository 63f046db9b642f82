"""Imbalance of the routed experts held here: the busiest expert's rows
over the mean expert's, over all expert layers, from the counter
``expert_rows`` (rows each held expert saw, summed from a small output
of every launch) before and after the window."""

import numpy as np

from ._sessions import stats


def read(ctx):
    before, after = stats(ctx, "snapshot_before"), stats(ctx)
    if not after or not after.get("expert_rows"):
        return None
    rows = np.asarray(after["expert_rows"], float)
    if before and before.get("expert_rows"):
        rows = rows - np.asarray(before["expert_rows"], float)
    return float(rows.max() / rows.mean()) if rows.mean() > 0 else None
