"""The gap before a launch of session steps or blocks, cut at events of
BOTH launches: the yardstick's own copy of what the program's
``obs/launch_timeline.py`` ``cycles`` does (as ``_launches.py`` is of
``host_gaps``), over ``_launches.records(ctx)`` and the ``/traces``
events, on the host's clock. ``host_gap_ms`` splits a gap by what the
waiting launch's EARLIEST request was doing; a merged session launch
waits for its LAST member, and before that for the previous launch's
answers to leave, so that split names no layer.

For a launch k whose device window is ``lm_step`` or ``lm_block`` and
that directly follows launch k-1, with ``a`` = k-1's ``device_execute``
end, ``b`` = k's start on the device as ``_launches.gaps`` has it
(``max(h2d end, launch end, a)``), and ``S`` = the sessions in both
launches (``args.session`` of a member's ``request`` event):

    e2 = the last ``batch_respond`` end among k-1's members (its future
         set; k-1's ``readback`` end where no batcher answered it)
    e3 = the last ``request`` end among k-1's members whose session is
         in S (e2 where S is empty)
    e4 = the first ``front`` begin among k's members
    e5 = the last ``batch_queue`` begin among k's members

each clipped into ``[a, b]`` and made non-decreasing in that order:

    handback = [a, e2]   device-to-host copy, split, futures (staged channel, batcher)
    answer   = [e2, e3]  the handlers wake one by one, build and account the answers (front end)
    away     = [e3, e4]  every answer out, no request in: gRPC both ways, the callers
    intake   = [e4, e5]  k's requests come through the handlers one by one (front end)
    restage  = [e5, b]   close, hold, slot, stage, transfer, launch (batcher, staged channel)

The five add up to the gap. Where k's sessions were not in k-1 (two
cohorts that take turns) ``answer`` is empty and e4, e5 lie before
``a``: the gap reads handback + restage. A program whose requests carry
no ``session`` or no ``front`` span yields nothing."""

from __future__ import annotations

import json
import math

import numpy as np

from ._launches import records

PHASES = ("handback", "answer", "away", "intake", "restage")
_STEP_WINDOWS = ("lm_step", "lm_block")


def _members(ctx: dict) -> tuple[dict, bool]:
    """launch_id -> its members that are a session's requests (session,
    ``request`` end, ``front`` begin, ``batch_queue`` begin,
    ``batch_respond`` end; seconds on the records' clock), and whether
    any request of the export has a ``front`` span."""
    doc = ctx.get("traces") or {}
    base = (doc.get("clock") or {}).get("base_perf_counter_s") or 0.0
    by_tid: dict = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X":
            continue
        row = by_tid.setdefault(e["tid"], {"launches": set()})
        t0, t1 = base + e["ts"] / 1e6, base + (e["ts"] + e["dur"]) / 1e6
        launch_id = (e.get("args") or {}).get("launch_id")
        if launch_id is not None:
            row["launches"].add(launch_id)
        elif e["name"] == "request":
            row["session"], row["request_end"] = e["args"].get("session"), t1
        elif e["name"] in ("front", "batch_queue"):
            row[e["name"]] = t0
        elif e["name"] == "batch_respond":
            row["future"] = t1
    out: dict = {}
    for row in by_tid.values():
        if row.get("session"):
            for launch_id in row["launches"]:
                out.setdefault(launch_id, []).append(row)
    return out, any("front" in row for row in by_tid.values())


def cycles(ctx: dict) -> list[dict] | None:
    """A row for every counted gap: ``launch_id``, ``gap_s``,
    ``closed`` (k's sessions all in k-1) and ``by_phase``; None where
    the program stamps no ``session`` or no ``front``."""
    members, fronts = _members(ctx)
    if not members or not fronts:
        return None
    rows, prev = [], None
    for rec in records(ctx):
        done = prev["device_execute"][1] if prev else -math.inf
        now = members.get(rec["launch_id"])
        if (
            prev is not None and rec["launch_id"] == prev["launch_id"] + 1 and now
            and any(name in rec for name in _STEP_WINDOWS)
        ):
            a, b = done, max(done, rec["h2d"][1], rec["launch"][1])
            before = members.get(prev["launch_id"], [])
            sessions = {m["session"] for m in now}
            e2 = max((m["future"] for m in before if "future" in m),
                     default=prev.get("readback", (a, a))[1])
            e3 = max((m["request_end"] for m in before if m["session"] in sessions), default=e2)
            e4 = min(m.get("front", math.inf) for m in now)
            e5 = max((m["batch_queue"] for m in now if "batch_queue" in m), default=e4)
            cuts = [a]
            for e in (e2, e3, e4, e5):
                cuts.append(max(cuts[-1], min(e, b)))
            cuts.append(b)
            rows.append({
                "launch_id": rec["launch_id"], "gap_s": b - a,
                "closed": sessions <= {m["session"] for m in before},
                "by_phase": {p: cuts[i + 1] - cuts[i] for i, p in enumerate(PHASES)},
            })
        prev = rec
    return rows


def read(ctx: dict, phase: str):
    """MEAN ms of ``phase`` over the counted gaps, so that the five
    entries add up to those gaps' mean; the run's log carries, as one
    line, the medians (all gaps, closed cycles), the closed cycles'
    mean, the counts and the counted gaps' mean."""
    if "_cycles" not in ctx:  # five readers, one pass over the export
        ctx["_cycles"] = cycles(ctx)
    rows = ctx["_cycles"]
    if not rows:
        return None
    ms = np.asarray([r["by_phase"][phase] for r in rows]) * 1e3
    closed = np.asarray([r["closed"] for r in rows], bool)
    print(json.dumps({f"cycle_{phase}_ms": {
        "median_ms": float(np.median(ms)),
        "median_closed_ms": float(np.median(ms[closed])) if closed.any() else None,
        "mean_closed_ms": float(ms[closed].mean()) if closed.any() else None,
        "gaps": len(rows), "closed": int(closed.sum()),
        "gap_mean_ms": float(np.mean([r["gap_s"] for r in rows]) * 1e3),
        "gap_mean_closed_ms": float(np.mean([r["gap_s"] for r, c in zip(rows, closed) if c]) * 1e3)
        if closed.any() else None,
    }}), flush=True)
    return float(ms.mean())
