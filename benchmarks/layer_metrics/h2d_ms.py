"""Median over launches of the staged channel's ``h2d`` span: from just
before the frames are placed to their arrival on the device. The run's
log carries the rate it moved them at beside it."""

import json

import numpy as np

from ._launches import records


def read(ctx):
    recs = records(ctx)
    if not recs:
        return None
    seconds = np.asarray([r["h2d"][1] - r["h2d"][0] for r in recs])
    moved = [r["bytes"] / s / 1e9 for r, s in zip(recs, seconds) if r.get("bytes") and s > 0]
    print(json.dumps({"h2d_gb_per_s": float(np.median(moved)) if moved else None, "launches": len(recs)}), flush=True)
    return float(np.median(seconds) * 1e3)
