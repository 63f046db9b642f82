"""Median per request of ``batch_queue``: admission to dispatch of the
request's group. The span covers ``merge_wait`` (the part spent in the
ready queue), so the two are not added. The median, because the one
end-to-end latency the benchmark can bound is the median (PERF.md
section 2); the run's log carries the p95 beside it."""

import numpy as np

from ._spans import per_request_ms


def read(ctx):
    ms = per_request_ms(ctx, ("batch_queue",))
    if not len(ms):
        return None
    print(f'{{"queue_wait_p95_ms": {float(np.percentile(ms, 95))}}}', flush=True)
    return float(np.median(ms))
