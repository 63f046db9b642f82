"""Launches on one timeline: why the device waits between them.

The staged channel stamps every span of a launch with its
``launch_id`` (``channel/staged.py``), so the tracer's ring can be
regrouped by LAUNCH instead of by request:

  * :func:`launches` — one record per ``launch_id``: the ``h2d``,
    ``launch`` and ``slot_wait`` intervals, ``ready`` (the end of
    ``device_execute``), the end of ``readback``, ``rows``, ``bytes``,
    and the earliest member request's start with its ``parse``,
    ``batch_queue`` and ``batch_merge`` intervals. All on
    ``time.perf_counter``.
  * :func:`host_gaps` — for consecutive launches, how long the device
    was busy with launch k, how long it waited before it, and what
    launch k's request was doing in that wait (:data:`STATES`).
    Without a device trace the busy interval is ESTIMATED on the host
    clock: from the later of "frames on the device", "program
    enqueued" and "previous launch done" to ``ready``.
  * :func:`cycles` — the same wait before a launch of session steps or
    blocks, cut at events of BOTH launches into five phases that name a
    layer each (:data:`PHASES`): ``host_gaps`` splits a gap by what the
    waiting launch's EARLIEST request did, and a merged session launch
    waits for its LAST member, and before that for the previous
    launch's answers to leave.
  * :func:`align` — puts a device trace's ``jit_mdl_*`` module events
    (session-relative nanoseconds) on the host clock by matching module
    ENDS to ``ready``: a thread already sits in ``block_until_ready``
    when a module ends, so the lag is a wake-up latency. A module's
    START cannot be matched to the ``launch`` span: it waits for its
    frames, and that lag is the unknown.
  * :func:`timeline` — what ``/profile`` reports: the gaps between the
    device's own modules, split by host state and by phase of the
    cycle, where a device trace aligned; the host-clock estimate
    otherwise.

stdlib only, over the ``{plane: {line: [(name, start_ns, dur_ns)]}}``
form that ``jax.profiler.ProfileData`` yields (:func:`module_events`).
"""

from __future__ import annotations

import math
import pathlib
import re
import statistics

#: what launch k's request was doing while the device waited for it,
#: claimed in this order (``batch_merge`` is the batcher's copy of the
#: members' rows into one buffer; ``slot_wait`` lies inside ``stage``;
#: ``h2d`` starts where it ends); whatever is left is ``other``
STATES = (
    "no_request", "parse", "batch_queue", "batch_merge", "slot_wait", "h2d",
    "launch",
)
_OWN = ("parse", "batch_queue", "batch_merge")  # a member's, not the launch's
_MEMBER = (*_OWN, "front", "batch_respond")  # what a member's facts are read from

_INTERVALS = ("slot_wait", "h2d", "launch")
#: the device windows of launches whose members are sessions' steps
#: (one token, or a block model's block): the launches a cycle ends at
_STEP_WINDOWS = ("lm_step", "lm_block")
#: the wait before such a launch, in the order its parts follow each
#: other: the previous launch's answers copied off the device, split
#: and handed to their futures (staged channel, batcher); the handlers
#: woken one by one, each answer built and accounted (front end); every
#: answer out and no request in (gRPC both ways, the callers); the next
#: launch's requests through the handlers one by one (front end); the
#: group closed, held, staged, transferred, launched (batcher, staged
#: channel)
PHASES = ("handback", "answer", "away", "intake", "restage")
_DEVICE_PLANE = re.compile(r"^/device:\w+:(\d+)$")


def launches(traces) -> list[dict]:
    """Complete launch records from ``RequestTrace`` objects, in
    ``launch_id`` order. Every member of a merged launch shows the
    launch's spans; the record keeps the earliest member's own
    ``parse``/``batch_queue``/``batch_merge`` (the launch waited for
    nobody before it). Where they exist, also: ``window`` (the name of
    the launch's device window: ``lm_prefill``, ``lm_step``,
    ``lm_block``), ``members`` (of every member that is a session's
    request: ``session``, its ``request`` interval, where its ``front``
    and its ``batch_queue`` began, when its future was set)."""
    out: dict[int, dict] = {}
    for tr in traces:
        own = {}
        ids = set()
        for s in list(tr.spans):
            launch_id = (s.attrs or {}).get("launch_id")
            if launch_id is None:
                if s.name in _MEMBER:
                    own[s.name] = (s.t0, s.t1)
                continue
            ids.add(launch_id)
            rec = out.setdefault(
                launch_id, {"launch_id": launch_id, "request_start": math.inf}
            )
            if s.name in _INTERVALS:
                rec[s.name] = (s.t0, s.t1)
                if s.name == "h2d":
                    rec["rows"] = s.attrs.get("rows")
                    rec["bytes"] = s.attrs.get("bytes")
            elif s.name == "device_execute":
                rec["ready"] = s.t1
            elif s.name == "readback":
                rec["readback_end"] = s.t1
            elif s.name.startswith("lm_"):
                rec["window"] = s.name
        for launch_id in ids:
            rec = out[launch_id]
            if tr.t_start < rec["request_start"]:
                rec.update(
                    {name: own.get(name) for name in _OWN},
                    request_start=tr.t_start,
                )
            if getattr(tr, "session", ""):
                rec.setdefault("members", []).append({
                    "session": tr.session,
                    "request": (tr.t_start, tr.t_end),
                    "front": own.get("front", (tr.t_start,))[0],
                    "batch_queue": own.get("batch_queue", (None,))[0],
                    "future": own.get("batch_respond", (None, None))[1],
                })
    return [
        out[k] for k in sorted(out)
        if all(name in out[k] for name in ("h2d", "launch", "ready"))
    ]


def _split(a: float, b: float, rec: dict) -> dict:
    """The seconds of ``[a, b]`` by :data:`STATES` of ``rec``'s request."""
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


def _host_busy(recs: list[dict]) -> dict:
    """launch_id -> estimated (start, end) of the device's work on it."""
    busy, prev_ready = {}, -math.inf
    for r in recs:
        busy[r["launch_id"]] = (max(r["h2d"][1], r["launch"][1], prev_ready), r["ready"])
        prev_ready = r["ready"]
    return busy


def host_gaps(recs: list[dict], busy: dict | None = None) -> list[dict]:
    """One row per launch that directly follows another: ``busy_s``,
    ``gap_s`` (the device's wait before it; 0 where launches overlap),
    ``by_state`` (that wait split by :data:`STATES` + ``other``), and
    how much of its ``h2d`` ran while the previous launch computed.
    ``busy`` (launch_id -> (start, end) on the host clock) comes from
    an aligned device trace; without it the host-clock estimate."""
    recs = sorted(recs, key=lambda r: r["launch_id"])
    if busy is None:
        busy = _host_busy(recs)
    rows = []
    for prev, rec in zip(recs, recs[1:]):
        a, b = busy.get(prev["launch_id"]), busy.get(rec["launch_id"])
        if rec["launch_id"] != prev["launch_id"] + 1 or a is None or b is None:
            continue
        h0, h1 = rec["h2d"]
        rows.append({
            "launch_id": rec["launch_id"],
            "busy_s": b[1] - b[0],
            "gap_s": max(0.0, b[0] - a[1]),
            "by_state": _split(a[1], b[0], rec),
            "h2d_s": h1 - h0,
            "h2d_overlap_s": max(0.0, min(h1, a[1]) - max(h0, a[0])),
        })
    return rows


def cycles(recs: list[dict], busy: dict | None = None) -> list[dict]:
    """One row per launch of session steps or blocks that directly
    follows another launch: the device's wait before it (``gap_s``, as
    :func:`host_gaps` has it) cut into :data:`PHASES` at events of BOTH
    launches. With ``a`` the previous launch's end and ``b`` this
    launch's start on the device, and ``S`` the sessions in both:

      e2  the previous launch's last future set: the latest
          ``batch_respond`` end among its members (its ``readback``'s
          end where the launch went down alone and no batcher answered
          it)
      e3  the last ``request`` end among the previous launch's members
          whose session is in ``S`` (e2 where ``S`` is empty)
      e4  the first ``front`` begin among this launch's members
      e5  the last ``batch_queue`` begin among this launch's members

    each clipped into ``[a, b]`` and made non-decreasing in that order:
    ``handback`` = [a, e2], ``answer`` = [e2, e3], ``away`` = [e3, e4],
    ``intake`` = [e4, e5], ``restage`` = [e5, b]; the five add up to the
    gap. Where this launch's members were not in the previous one (two
    cohorts that take turns) ``answer`` is empty and e4, e5 lie before
    ``a``: the gap reads ``handback`` + ``restage``. ``closed`` says
    whether every session of this launch was in the previous one. A
    launch of another kind, or one whose members carry no session, gives
    no row (its gap keeps :func:`host_gaps`' states)."""
    recs = sorted(recs, key=lambda r: r["launch_id"])
    if busy is None:
        busy = _host_busy(recs)
    rows = []
    for prev, rec in zip(recs, recs[1:]):
        a, b = busy.get(prev["launch_id"]), busy.get(rec["launch_id"])
        if (
            rec["launch_id"] != prev["launch_id"] + 1 or a is None or b is None
            or rec.get("window") not in _STEP_WINDOWS or not rec.get("members")
        ):
            continue
        a, b = a[1], max(a[1], b[0])
        before, now = prev.get("members", ()), rec["members"]
        sessions = {m["session"] for m in now}
        shared = [m for m in before if m["session"] in sessions]
        e2 = max(
            (m["future"] for m in before if m["future"] is not None),
            default=prev.get("readback_end", a),
        )
        e3 = max(
            (m["request"][1] for m in shared if m["request"][1] is not None), default=e2
        )
        e4 = min(m["front"] for m in now)
        e5 = max(
            (m["batch_queue"] for m in now if m["batch_queue"] is not None), default=e4
        )
        cuts = [a]
        for e in (e2, e3, e4, e5):
            cuts.append(max(cuts[-1], min(e, b)))
        cuts.append(b)
        rows.append({
            "launch_id": rec["launch_id"],
            "gap_s": b - a,
            "closed": sessions <= {m["session"] for m in before},
            "by_phase": {p: cuts[i + 1] - cuts[i] for i, p in enumerate(PHASES)},
        })
    return rows


def module_events(log_dir) -> list[tuple[str, int, int]]:
    """The ``jit_mdl_*`` events of the first device plane's ``XLA
    Modules`` line in the newest trace under ``log_dir`` (none on a
    backend whose trace has no such line, e.g. the CPU's)."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        return []
    data = ProfileData.from_file(str(files[-1]))
    planes = sorted(
        (int(m.group(1)), p) for p in data.planes if (m := _DEVICE_PLANE.match(p.name))
    )
    for _, plane in planes[:1]:
        for line in plane.lines:
            if line.name == "XLA Modules":
                return [
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events if e.name.startswith("jit_mdl_")
                ]
    return []


def align(events, recs, near_s: float | None = None, within_s: float = 2.0) -> dict | None:
    """``offset_s`` such that device time + offset is host time.

    Module i (by end) is paired with launch i + shift (by ``ready``);
    the shift whose lags ``ready - end`` spread least (median absolute
    deviation; more pairs win a tie) is the right one, because a wrong
    one adds the launches' own irregular spacing. The smallest lag is
    the offset (a wake-up is never early), ``residual_ms`` the median
    lag above it. ``near_s``: where the trace's zero is known roughly
    (the profiler session starts at zero), offsets further than
    ``within_s`` from it are not considered. None under 3 pairs."""
    modules = sorted(events, key=lambda e: e[1] + e[2])
    by_ready = sorted(recs, key=lambda r: r["ready"])
    need = max(3, min(len(modules), len(by_ready)) // 2)
    best = None
    for shift in range(1 - len(modules), len(by_ready)):
        pairs = [
            (by_ready[i + shift], m) for i, m in enumerate(modules)
            if 0 <= i + shift < len(by_ready)
        ]
        lags = [r["ready"] - (s + d) / 1e9 for r, (_, s, d) in pairs]
        if len(lags) < need or (near_s is not None and abs(min(lags) - near_s) > within_s):
            continue
        mid = statistics.median(lags)
        key = (statistics.median(abs(x - mid) for x in lags), -len(lags))
        if best is None or key < best[0]:
            best = (key, shift, lags, pairs)
    if best is None:
        return None
    _, shift, lags, pairs = best
    offset = min(lags)
    return {
        "offset_s": offset,
        "residual_ms": (statistics.median(lags) - offset) * 1e3,
        "matched": len(lags),
        "shift": shift,
        # launch_id -> the module's (start, end) on the host clock
        "busy": {
            r["launch_id"]: (s / 1e9 + offset, (s + d) / 1e9 + offset)
            for r, (_, s, d) in pairs
        },
    }


def timeline(traces, events=(), near_s: float | None = None) -> dict:
    """The ``launch_timeline`` object of ``/profile``. With module
    events that align, busy intervals are the device's own and
    ``launches`` counts the matched ones; otherwise ``offset_s`` is
    None and every number is the host-clock estimate.
    ``cycle_by_phase_s`` (:func:`cycles`) is on the same clock as
    ``idle_by_state_s``."""
    recs = launches(traces)
    found = align(events, recs, near_s) if events else None
    busy = found and found["busy"]
    rows = host_gaps(recs, busy)
    cycle = cycles(recs, busy)
    h2d_s = sum(r["h2d_s"] for r in rows)
    return {
        "offset_s": found and found["offset_s"],
        "residual_ms": found and found["residual_ms"],
        "launches": found["matched"] if found else len(rows),
        "busy_s": sum(r["busy_s"] for r in rows),
        "idle_by_state_s": {
            k: sum(r["by_state"][k] for r in rows) for k in (*STATES, "other")
        },
        # the waits before launches of session steps or blocks (a part
        # of the idle time above), by phase of the cycle
        "cycle_by_phase_s": {p: sum(r["by_phase"][p] for r in cycle) for p in PHASES},
        "h2d_overlap": sum(r["h2d_overlap_s"] for r in rows) / h2d_s if h2d_s else None,
    }
