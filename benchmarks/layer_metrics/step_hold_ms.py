"""Milliseconds a step launch of the window waited AT A FREE DEVICE for
sessions that were about to come back: the batcher's counter
``step_hold_s`` (``/snapshot`` -> ``batching``,
``runtime/continuous.py``) over the window's step launches
(``lm_step_launches``), after less before. 0 where the rule stands aside
(the last of a launch's sessions is not back within a launch's time);
where it engages, the wait is what buys ``step_sessions_mean``. The run's log
carries the four counters' growth over the window (``step_holds``: step
groups that so waited, ``step_hold_s``, ``step_hold_joined``: sessions
that came in meanwhile, ``step_hold_expired``: waits that ran out) and
the times the rule compares (``step_launch_ms`` against
``step_return_ms`` plus two ``step_return_dev_ms``) as one JSON line. A
program without the counters (the parent of the PR that brought them)
yields nothing."""

import json

from ._sessions import delta
from ._spans import counter_delta

COUNTERS = ("step_holds", "step_hold_s", "step_hold_joined", "step_hold_expired")
GAUGES = ("step_launch_ms", "step_return_ms", "step_return_dev_ms")


def read(ctx):
    after = (ctx.get("snapshot_after") or {}).get("batching") or {}
    if any(name not in after for name in COUNTERS):
        return None
    grown = {}
    for name in COUNTERS:
        late, early = counter_delta(ctx, "batching", name)
        grown[name] = late - early
    print(json.dumps({"step_hold": {**grown, **{g: after.get(g) for g in GAUGES}}}), flush=True)
    launches = delta(ctx, "lm_step_launches")
    return 1e3 * grown["step_hold_s"] / launches if launches else None
