"""Share of the window's step or block launches whose group closed while
a launch was still ahead of it, in %: the growth of the batcher's
counter ``step_early_closes`` (``/snapshot`` -> ``batching``,
``runtime/continuous.py``) over the growth of ``lm_step_launches`` +
``lm_block_launches`` (a block group is a session-step group to the
batcher; a model has launches of one of the two kinds). Near 100 where
step launches of one model follow each other and their sessions take
longer to come back than a launch lasts: each group then closes behind
the launch that runs, and the device takes it with no gap; 0 where the
sessions in the launch ahead are worth waiting for, or every session is
in it. The run's log carries the growth of the three counters
(``step_early_closes``; ``step_early_by_event``: those of them closed
when the launch ahead's outputs were ready on the device, not by the
prediction; ``step_early_missed``: steps staged between an early close
and the end of the launch ahead, which is what the rule costs) and the
two times the prediction adds up (``step_device_ms``, ``step_lead_ms``)
as one JSON line. A program without the counters (the parent of the PR
that brought them) yields nothing."""

import json

from ._sessions import delta
from ._spans import counter_delta

COUNTERS = ("step_early_closes", "step_early_by_event", "step_early_missed")
GAUGES = ("step_device_ms", "step_lead_ms")


def read(ctx):
    after = (ctx.get("snapshot_after") or {}).get("batching") or {}
    if any(name not in after for name in COUNTERS):
        return None
    launches = (delta(ctx, "lm_step_launches") or 0) + (delta(ctx, "lm_block_launches") or 0)
    if not launches:
        return None
    grown = {}
    for name in COUNTERS:
        late, early = counter_delta(ctx, "batching", name)
        grown[name] = late - early
    print(json.dumps({"step_early": {**grown, **{g: after.get(g) for g in GAUGES}}}), flush=True)
    return 100.0 * grown["step_early_closes"] / launches
