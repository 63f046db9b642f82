"""Helpers shared by the span readers: the server's ``/traces`` export
(Chrome trace events, one ``tid`` a request) as per-request span sums."""

from __future__ import annotations

import numpy as np


def undisturbed(ctx: dict) -> tuple[float, float] | None:
    """Where the mix pins its device trace to the window (``run.py``
    then states ``spans_until``, the moment the profiler started): the
    interval on ``time.perf_counter`` from the window's start to that
    moment. Spans are read there: stopping the profiler slows the
    serving process for some fifteen seconds (``parse`` + ``encode``
    read 0.125 ms a request before it and 1.17 ms after: PERF.md
    section 6, PR 31). None where the mix pins nothing, or the export
    does not say its clock: every traced request then counts."""
    base = ((ctx.get("traces") or {}).get("clock") or {}).get("base_perf_counter_s")
    if ctx.get("spans_until") is None or base is None or ctx.get("window") is None:
        return None
    return ctx["window"].t_start - base, ctx["spans_until"] - base  # on the export's own zero


def per_request_ms(ctx: dict, names: tuple[str, ...]) -> np.ndarray:
    """For every traced request that finished ok (inside ``undisturbed``,
    where that says an interval): the summed duration, in ms, of its
    spans called one of ``names``."""
    events = (ctx.get("traces") or {}).get("traceEvents", [])
    inside = undisturbed(ctx)
    ok = {e["tid"] for e in events
          if e.get("ph") == "X" and e["name"] == "request" and e["args"].get("status") == "ok"
          and (inside is None or inside[0] * 1e6 <= e["ts"] and e["ts"] + e["dur"] <= inside[1] * 1e6)}
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
