"""Sessions a step launch over the window: counter ``lm_step_sessions``
(sum of sessions over step launches) over ``lm_step_launches``. The
run's log carries beside it where a step's round trip goes (what
decides how many sessions are back when the next launch forms): the
caller's median latency, of it the server's ``request`` span, and of
that the wait for a launch and the launch's device window; the rest of
the caller's latency is wire, gRPC's threads and the load generator."""

import json

import numpy as np

from ._sessions import delta
from ._spans import per_request_ms


def read(ctx):
    sessions, launches = delta(ctx, "lm_step_sessions"), delta(ctx, "lm_step_launches")
    events = (ctx.get("traces") or {}).get("traceEvents", [])
    steps = {e["tid"] for e in events if e.get("ph") == "X" and e["name"] == "lm_step"}
    if steps and ctx.get("window") is not None:
        only = {**ctx, "traces": {"traceEvents": [e for e in events if e.get("tid") in steps]}}
        median = lambda *names: float(np.median(per_request_ms(only, names)))
        print(json.dumps({"step_round_trip_ms": {
            "caller": float(np.median(ctx["window"].latencies_ms)), "server_request": median("request"),
            "wait_for_launch": median("batch_queue"), "lm_step": median("lm_step")}}), flush=True)
    return sessions / launches if launches else None
