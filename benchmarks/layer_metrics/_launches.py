"""Helpers shared by the launch readers: the server's ``/traces``
export regrouped by LAUNCH (the staged channel stamps ``args.launch_id``
on ``slot_wait``, ``stage``, ``h2d``, ``launch``, ``device_execute`` and
``readback``), and the gaps between consecutive launches on the host's
clock. The arithmetic is the yardstick's own copy of what the program's
``obs/launch_timeline.py`` does, so that a later change there cannot
move what the benchmark reads.

For consecutive launches k-1, k (``ready`` = end of ``device_execute``):

    dev_start_k = max(h2d_end_k, launch_end_k, ready_{k-1})
    busy_k      = ready_k - dev_start_k
    gap_k       = max(0, dev_start_k - ready_{k-1})

and ``gap_k`` is split by what launch k's earliest request was doing in
it (``STATES``, claimed in that order; the rest is ``other``). A program
that stamps no ``launch_id`` (the parent of PR 26) yields no records,
and every reader then reports nothing."""

from __future__ import annotations

import math

STATES = ("no_request", "parse", "batch_queue", "batch_merge", "slot_wait", "h2d", "launch")
_OWN = ("request", "parse", "batch_queue", "batch_merge")  # a member request's own spans


def records(ctx: dict) -> list[dict]:
    """One record a launch that has ``h2d``, ``launch`` and
    ``device_execute``, in ``launch_id`` order, times in seconds: on
    ``time.perf_counter`` where the export says its clock, and then only
    the launches that were ready inside the measured window (before
    ``spans_until``, where the mix pins its device trace to the window:
    ``_spans.undisturbed``)."""
    doc = ctx.get("traces") or {}
    base = (doc.get("clock") or {}).get("base_perf_counter_s")
    interval = lambda e: ((base or 0.0) + e["ts"] / 1e6, (base or 0.0) + (e["ts"] + e["dur"]) / 1e6)
    events = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    requests: dict = {}
    for e in events:
        if e["name"] in _OWN:
            requests.setdefault(e["tid"], {})[e["name"]] = interval(e)
    out: dict = {}
    for e in events:
        launch_id = (e.get("args") or {}).get("launch_id")
        if launch_id is None:
            continue
        rec = out.setdefault(launch_id, {"launch_id": launch_id, "request_start": math.inf})
        rec[e["name"]] = interval(e)
        if e["name"] == "h2d":
            rec["bytes"] = e["args"].get("bytes")
        member = requests.get(e["tid"], {})
        start = member.get("request", (math.inf,))[0]
        if start < rec["request_start"]:
            rec.update({name: member.get(name) for name in _OWN[1:]}, request_start=start)
    recs = [out[k] for k in sorted(out) if all(n in out[k] for n in ("h2d", "launch", "device_execute"))]
    window = ctx.get("window")
    if base is not None and window is not None:
        until = window.t_end if ctx.get("spans_until") is None else min(window.t_end, ctx["spans_until"])
        recs = [r for r in recs if window.t_start <= r["device_execute"][1] <= until]
    return recs


def _split(a: float, b: float, rec: dict) -> dict:
    left = [(a, b)] if b > a else []
    out = {}
    for state in STATES:
        iv = (-math.inf, rec["request_start"]) if state == "no_request" else rec.get(state)
        took, rest = 0.0, []
        for s, e in left:
            lo, hi = (max(s, iv[0]), min(e, iv[1])) if iv else (s, s)
            if hi > lo:
                took += hi - lo
                rest += [(s, lo), (hi, e)]
            else:
                rest.append((s, e))
        left = [(s, e) for s, e in rest if e > s]
        out[state] = took
    out["other"] = sum(e - s for s, e in left)
    return out


def gaps(ctx: dict) -> list[dict]:
    """A row for every launch that directly follows another:
    ``busy_s``, ``gap_s``, ``by_state``, ``h2d_s`` and how much of its
    ``h2d`` lay inside the previous launch's busy interval."""
    rows, prev, prev_busy = [], None, None
    for rec in records(ctx):
        ready = rec["device_execute"][1]
        start = max(rec["h2d"][1], rec["launch"][1], prev["device_execute"][1] if prev else -math.inf)
        if prev is not None and rec["launch_id"] == prev["launch_id"] + 1:
            done = prev["device_execute"][1]
            h0, h1 = rec["h2d"]
            rows.append({
                "launch_id": rec["launch_id"], "busy_s": ready - start, "gap_s": max(0.0, start - done),
                "by_state": _split(done, start, rec), "h2d_s": h1 - h0,
                "h2d_overlap_s": max(0.0, min(h1, prev_busy[1]) - max(h0, prev_busy[0])),
            })
        prev, prev_busy = rec, (start, ready)
    return rows
