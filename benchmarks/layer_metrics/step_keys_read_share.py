"""Share of their rows' slots that the window's step launches fetch, in
per cent, for a program whose step launch reads a slot's latent rows in
place, a block of positions at a time up to the block that holds the
row's position (ops/latent_attention.py since PR 49): counter
``lm_step_keys_fetched`` (positions fetched, summed over the rows of
each step launch's shape, pad rows too, and the layers that attend so)
over ``lm_step_keys_whole`` (rows of the launch's shape x the slot's
positions x those layers: what a program that slices every row's slot
out whole fetches). A cell whose slot is one block reads 100 and has no
entry. The run's log carries the counters' growth."""

import json

from ._sessions import delta


def read(ctx):
    fetched, whole = delta(ctx, "lm_step_keys_fetched"), delta(ctx, "lm_step_keys_whole")
    if fetched is None or not whole:
        return None
    print(json.dumps({"step_keys": {"lm_step_keys_fetched": fetched, "lm_step_keys_whole": whole}}), flush=True)
    return 100.0 * fetched / whole
