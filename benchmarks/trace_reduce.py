"""Reduction from a jax profiler trace to the benchmark's device numbers.

Kept with the benchmark so that every PR computes them the same way.
The trace is read with ``jax.profiler.ProfileData`` (no other
dependency) into plain ``{plane: {line: [(name, start_ns, dur_ns)]}}``
and everything below works on that form, which is also what the
recorded fixture under ``tests/data`` holds.

On a TPU v5e (looked at by hand, PERF.md section 3) each chip is a
plane ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event per
executed HLO op, and ``XLA Modules`` one event per executed program,
named ``<module>(<fingerprint>)``; the serving channel names its
launchers ``jit_mdl_<model>_<version>``. The host is ``/host:CPU``
with one line per thread; it is NOT read. The benchmark traces with
the host tracer off: at its level 1 a b8 uint8 batch's
``XlaLinearize`` took 300 ms instead of 5, the served rate fell from
148 to 8 launches/s and the device read 96% idle where 34% is true
(PERF.md section 6). So an idle gap is named by where it lies on the
device's own lines, not by a host event.

  * busy: the union of the ``XLA Ops`` intervals of a device plane,
    averaged over the chips used;
  * window: first to last device op over the chips used, without the
    trace's first ``HEAD_LEFT_OUT_S`` seconds (starting the profiler
    held a replay's launches up for 0.6 s; ``run.py`` starts it in a
    last round of warm-up, so what is left lies in the window). NOT the host
    plane's span: the host tracer starts before and stops after the
    device's, and its events do not say when the device was traced;
  * launches: per module name, count and summed device time, of the
    modules that lie INSIDE the window: one that touches its head
    (begun before the head left out ended, or at the trace's own first
    event where the trace is read whole) or its end (nothing on the
    device's lines ends after it) is left out of both. The profiler
    stops inside a launch, and a module cut there, counted as a whole
    launch with part of its time, read 197.6 ms a launch where the host
    clock said 208.5 (16-17 modules in the trace; PERF.md section 6);
  * breakdown: the ten ops with most device time, and the idle time
    by where the gap lies: ``inside <module>`` (a program is running
    and no op is: it waits for memory or a transfer) or ``between
    launches`` (the device waits for the host), the latter split by
    length so that a stall does not hide among the ordinary gaps.
"""

from __future__ import annotations

import bisect
import gzip
import json
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HEAD_LEFT_OUT_S = 1.5
GAP_CLASSES_MS = (1.0, 10.0, 100.0)  # between launches: under 1 ms, 1-10, 10-100, over 100


def read_xplane(path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return planes


def read_recorded(path) -> dict:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return {p: {l: [tuple(e) for e in ev] for l, ev in lines.items()} for p, lines in doc.items()}


def write_recorded(planes: dict, path, per_line: int) -> None:
    """A fixture: the first ``per_line`` events of every line."""
    cut = {p: {l: sorted(ev, key=lambda e: e[1])[:per_line] for l, ev in lines.items()}
           for p, lines in planes.items()}
    with gzip.open(path, "wt") as f:
        json.dump(cut, f)


def union_ns(intervals) -> tuple[int, list[tuple[int, int]]]:
    """Total covered length and the merged intervals."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def op_name(event_name: str) -> str:
    """``%fusion.431 = bf16[...] fusion(...)`` -> ``fusion.431``."""
    return event_name.split(" = ")[0].lstrip("%")


def module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(planes: dict, chips: int) -> dict:
    device_planes = sorted(
        (int(m.group(1)), name) for name in planes if (m := DEVICE_PLANE.match(name))
    )[:chips]
    if not device_planes:
        raise ValueError(f"no device plane among {sorted(planes)}")
    starts, ends, busy, ops, launches, gaps = [], [], [], {}, {}, []
    for _, name in device_planes:
        lines = planes[name]
        op_events = lines.get(OPS_LINE)
        if op_events is None:
            raise ValueError(f"{name}: no {OPS_LINE!r} line among {sorted(lines)}")
        module_events = lines.get(MODULES_LINE, [])
        first = min(s for _, s, _ in op_events + module_events)  # the trace's own edges on this chip
        last = max(s + d for _, s, d in op_events + module_events)
        head = min(s for _, s, _ in op_events) + int(HEAD_LEFT_OUT_S * 1e9)
        if any(s >= head for _, s, _ in op_events):  # a trace shorter than the head is read whole
            op_events = [e for e in op_events if e[1] >= head]
            module_events = [e for e in module_events if e[1] >= head]  # traced from their start
            first = head - 1
        whole = [e for e in module_events if first < e[1] and e[1] + e[2] < last]  # touch neither edge
        spans = [(s, s + d) for _, s, d in op_events]
        covered, merged = union_ns(spans)
        busy.append(covered)
        starts.append(min(s for s, _ in spans))
        ends.append(max(e for _, e in spans))
        for op, _, d in op_events:
            op = op_name(op)
            ops[op] = ops.get(op, 0) + d
        for mod, _, d in whole:
            row = launches.setdefault(module_name(mod), [0, 0])
            row[0] += 1
            row[1] += d
        modules = sorted((s, s + d, module_name(n)) for n, s, d in module_events)
        module_starts = [m[0] for m in modules]
        for a, b in zip(merged, merged[1:]):
            i = bisect.bisect_right(module_starts, a[1]) - 1  # the program running when the gap opens
            inside = f"inside {modules[i][2]}" if i >= 0 and b[0] <= modules[i][1] else None
            gaps.append((b[0] - a[1], inside))
    named: dict[str, int] = {}
    for length, inside in gaps:
        if inside is None:
            ms = length / 1e6
            edge = next((e for e in GAP_CLASSES_MS if ms < e), None)
            inside = f"between launches, gap under {edge:g} ms" if edge else "between launches, gap over 100 ms"
        named[inside] = named.get(inside, 0) + length
    top = lambda table: [
        [k, v / 1e9] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:10]
    ]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (max(ends) - min(starts)) / 1e9,
        "launches": {k: {"count": c, "device_s": d / 1e9 / len(device_planes)}
                     for k, (c, d) in launches.items()},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(named)},
    }


def reduce_dir(log_dir, chips: int, record_to=None) -> dict:
    """Reduce the newest trace under ``log_dir``; ``record_to`` also
    keeps its first events as a fixture (``write_recorded``)."""
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = read_xplane(files[-1])
    if record_to:
        write_recorded(planes, record_to, per_line=300)
    return reduce(planes, chips)
