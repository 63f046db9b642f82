"""Median per request of the front end's own spans: ``parse`` (wire or
shm -> arrays) + ``encode`` (arrays -> response)."""

import numpy as np

from ._spans import per_request_ms


def read(ctx):
    ms = per_request_ms(ctx, ("parse", "encode"))
    return float(np.median(ms)) if len(ms) else None
