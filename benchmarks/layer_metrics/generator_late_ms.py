"""p95 of (send time - due time) of the benchmark's own open-loop
generator: a starved generator must not read as a fast server."""

import numpy as np


def read(ctx):
    late = ctx["window"].late_ms
    return float(np.percentile(late, 95)) if late else None
