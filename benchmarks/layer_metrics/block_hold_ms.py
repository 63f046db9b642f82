"""Milliseconds a block launch of the window waited AT A FREE DEVICE for
sessions that were about to come back: the batcher's counter
``step_hold_s`` (``/snapshot`` -> ``batching``; a block group is a
session-step group to ``runtime/continuous.py``) over the window's block
launches (``lm_block_launches``). ``step_hold_ms`` divides the same
counter by ``lm_step_launches``, of which a block model has none. The
run's log carries the four counters' growth and the times the rule
compares, as ``step_hold_ms`` logs them. A program without the counters
yields nothing."""

import json

from ._sessions import delta
from ._spans import counter_delta
from .step_hold_ms import COUNTERS, GAUGES


def read(ctx):
    after = (ctx.get("snapshot_after") or {}).get("batching") or {}
    launches = delta(ctx, "lm_block_launches")
    if any(name not in after for name in COUNTERS) or not launches:
        return None
    grown = {}
    for name in COUNTERS:
        late, early = counter_delta(ctx, "batching", name)
        grown[name] = late - early
    print(json.dumps({"block_hold": {**grown, **{g: after.get(g) for g in GAUGES}}}), flush=True)
    return 1e3 * grown["step_hold_s"] / launches
