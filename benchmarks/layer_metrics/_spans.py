"""Helpers shared by the span readers: the server's ``/traces`` export
(Chrome trace events, one ``tid`` a request) as per-request span sums."""

from __future__ import annotations

import numpy as np


def per_request_ms(ctx: dict, names: tuple[str, ...]) -> np.ndarray:
    """For every traced request that finished ok: the summed duration,
    in ms, of its spans called one of ``names``."""
    events = (ctx.get("traces") or {}).get("traceEvents", [])
    ok = {e["tid"] for e in events
          if e.get("ph") == "X" and e["name"] == "request" and e["args"].get("status") == "ok"}
    sums: dict = {}
    for e in events:
        if e.get("ph") == "X" and e["name"] in names and e["tid"] in ok:
            sums[e["tid"]] = sums.get(e["tid"], 0.0) + e["dur"] / 1e3
    return np.asarray(list(sums.values()), float)


def counter_delta(ctx: dict, *path: str):
    """``snapshot_after[path] - snapshot_before[path]``."""
    before, after = ctx["snapshot_before"], ctx["snapshot_after"]
    for key in path:
        before, after = before[key], after[key]
    return after, before
