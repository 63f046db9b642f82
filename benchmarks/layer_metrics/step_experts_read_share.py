"""Share of the held experts that the window's step launches read, in
per cent, for a program whose step launch reads the experts its rows
chose and no other (models/ling.py since PR 45: ops/experts.py given
the layers' stacks): counter ``lm_step_experts_chosen`` (held experts,
summed over expert layers and step launches, that some valid row of the
launch chose) over ``lm_step_experts_held`` (expert layers x experts
held, a step launch). A program whose step launch runs every held expert
over every row (the A.X-K1 family) reads 100% whatever this share says,
and has no entry. The run's log carries the counters' growth."""

import json

from ._sessions import delta


def read(ctx):
    chosen, held = delta(ctx, "lm_step_experts_chosen"), delta(ctx, "lm_step_experts_held")
    if chosen is None or not held:
        return None
    print(json.dumps({"step_experts": {"lm_step_experts_chosen": chosen, "lm_step_experts_held": held}}), flush=True)
    return 100.0 * chosen / held
