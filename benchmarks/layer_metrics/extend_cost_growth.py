"""What a turn costs at a long context over what it costs at a short
one: the median DEVICE time a token of the full-size extend launches
(``lm_prefill`` spans whose ``args.tokens`` is the mix's
``inputs.params.turn``) whose ``args.context`` is at least ``LONG`` over
that of those under ``SHORT``. 1.0 is a turn that costs the same at 50k
as at 8k, which is what layers that hold a state and not rows are for;
what is over 1 is the layers whose cache grows with the context. A
launch's span runs from its dispatch, so it holds the wait for the launch
ahead as well: the time counted is the launch's BUSY interval
(``_launches.records``: from the later of its own dispatch and the
launch ahead being ready, to its being ready), read where the trace has
not disturbed the spans. With four callers the launch ahead of an early
turn is another 4,096-token launch and ahead of a late one often a step:
the raw spans read long turns CHEAPER than short ones (0.81: my chip
run, PR 44). The log carries the state's counters and gauge
(``lm_state_resets``, ``lm_state_carries``, ``session_state_bytes``). A
program without them (a model whose slots hold no state; the parent of
the PR that brought them) yields nothing."""

import json

import numpy as np

from ._launches import records
from ._sessions import delta, gauges

SHORT, LONG = 16384, 32768


def read(ctx):
    resets, carries = delta(ctx, "lm_state_resets"), delta(ctx, "lm_state_carries")
    if resets is None or carries is None or not carries:
        return None
    turn = int(ctx["traffic"]["inputs"]["params"]["turn"])
    busy, prev = {}, None
    for rec in records(ctx):
        if prev is not None and prev["launch_id"] + 1 == rec["launch_id"]:  # else the launch ahead is not known
            busy[rec["launch_id"]] = rec["device_execute"][1] - max(rec["h2d"][1], rec["launch"][1], prev["device_execute"][1])
        prev = rec
    contexts = {e["args"]["launch_id"]: e["args"].get("context", 0) for e in (ctx.get("traces") or {}).get("traceEvents", [])
                if e.get("ph") == "X" and e["name"] == "lm_prefill" and e["args"].get("tokens") == turn and "launch_id" in e["args"]}
    per_token = lambda keep: [1e6 * busy[k] / turn for k, c in contexts.items() if keep(c) and k in busy]
    short, long_ = per_token(lambda c: c < SHORT), per_token(lambda c: c >= LONG)
    state_bytes = [s.get("session_state_bytes", 0) for s in gauges(ctx)]
    print(json.dumps({"extend_cost": {"us_per_token_short": float(np.median(short)) if short else None,
                                      "us_per_token_long": float(np.median(long_)) if long_ else None,
                                      "launches": [len(short), len(long_)], "lm_state_resets": resets,
                                      "lm_state_carries": carries,
                                      "session_state_bytes_mean": float(np.mean(state_bytes)) if state_bytes else None}}),
          flush=True)
    if not short or not long_:
        return None
    return float(np.median(long_) / np.median(short))
