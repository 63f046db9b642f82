"""Median per request of the staged channel's host work: ``stage``
(host -> device, which covers ``slot_wait``) + ``readback``."""

import numpy as np

from ._spans import per_request_ms


def read(ctx):
    ms = per_request_ms(ctx, ("stage", "readback"))
    return float(np.median(ms)) if len(ms) else None
