"""Microseconds of this process's interpreter a request costs the
serving front end: the handler threads' CPU time inside ``ModelInfer``
(``time.thread_time``: a thread that waits for its answer accrues none,
so this is parse, admission, the batcher's entry, the answer's message
and the accounting, with the interpreter's lock changing hands between
them) over the requests handled, both grown over the window
(``/snapshot`` -> ``front_end``: ``handler_cpu_s``, ``handler_requests``,
runtime/server.py ``_Servicer.front_stats``). ``frontend_ms`` is two
spans of a traced request (``parse``, ``encode``), not this. The run's
log carries the four counters' growth and the front memo's hit share
(``front_memo_hits`` over hits and misses: the requests whose tensor
descriptors had been planned before) as one JSON line. A program
without the counters (the parent of the PR that brought them) yields
nothing."""

import json

COUNTERS = ("handler_cpu_s", "handler_requests", "front_memo_hits", "front_memo_misses")


def read(ctx):
    before = (ctx.get("snapshot_before") or {}).get("front_end") or {}
    after = (ctx.get("snapshot_after") or {}).get("front_end") or {}
    if any(name not in after for name in COUNTERS[:2]):
        return None
    grown = {name: after[name] - before.get(name, 0) for name in COUNTERS if name in after}
    if not grown["handler_requests"]:
        return None
    looked_up = grown.get("front_memo_hits", 0) + grown.get("front_memo_misses", 0)
    hit_share = grown.get("front_memo_hits", 0) / looked_up if looked_up else None
    print(json.dumps({"front_end": {**grown, "hit_share": hit_share}}), flush=True)
    return 1e6 * grown["handler_cpu_s"] / grown["handler_requests"]
