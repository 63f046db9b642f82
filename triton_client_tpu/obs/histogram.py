"""Fixed-bucket latency histograms for the SLO observability ring.

PR 2's collector exports counters and gauges; percentiles existed only
inside ``StageProfiler``'s in-memory reservoir, invisible to
``snapshot()``/``delta()`` and to any scraper that wants a windowed
p99. This module is the missing primitive: a thread-safe fixed-bucket
histogram whose snapshot is a plain dict of numbers, so it rides the
same ``RuntimeCollector.snapshot()``/``delta()`` path as every counter
— perf scripts diff two snapshots and read the WINDOW's percentiles,
exactly like they diff staged/launched counts today.

Representation choices, all load-bearing:

  * buckets are NON-cumulative per-bucket counts keyed by the upper
    bound's repr (``"0.005"`` ... ``"inf"``). ``delta()``'s recursive
    numeric diff then yields the window's per-bucket counts for free;
    cumulative counts would survive the diff too, but non-cumulative
    keeps ``quantile_from_snapshot`` trivially correct on both a raw
    snapshot and a delta.
  * bounds are FIXED at construction (default: the serving-latency
    ladder ``PrometheusStageExporter`` already exports, widened at the
    sub-millisecond end for device-execute spans). Fixed bounds mean
    two histograms — or two snapshots of one — are always mergeable
    and diffable; adaptive bounds are not.
  * ``observe`` is one bisect + two adds under a per-histogram lock —
    cheap enough to feed from ``Tracer.finish`` on every request
    without measurable throughput cost (the <=2% acceptance gate).

``HistogramFamily`` keys child histograms by ``(model, stage)`` — the
label set the collector exports as ``tpu_serving_latency_seconds`` —
with the stage names the tentpole fixes: queue_delay, merge_wait,
device_execute, readback, e2e.
"""

from __future__ import annotations

import bisect
import math
import threading

# Upper bounds in seconds. Spans from 250us (a fast device_execute on a
# warm small model) to 60s (an e2e over a degraded WAN link); the +Inf
# overflow bucket is implicit. Matches the spirit of profiling._BUCKETS
# but extends both ends so per-stage spans and WAN e2e both resolve.
DEFAULT_BUCKETS: tuple[float, ...] = (
    0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)

# The per-request span names that feed SLO stages, and the stage label
# each exports under. batch_queue covers admission window + ready-queue
# + slot backpressure end to end; merge_wait (recorded per member by
# the batcher) is the ready-queue portion alone.
SLO_STAGES: dict[str, str] = {
    "batch_queue": "queue_delay",
    "merge_wait": "merge_wait",
    "device_execute": "device_execute",
    "readback": "readback",
}


class LatencyHistogram:
    """One fixed-bucket histogram (counts + sum), thread-safe."""

    __slots__ = ("_bounds", "_counts", "_overflow", "_sum", "_count", "_lock")

    def __init__(self, buckets: tuple[float, ...] = DEFAULT_BUCKETS) -> None:
        self._bounds = tuple(sorted(float(b) for b in buckets))
        if not self._bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self._counts = [0] * len(self._bounds)
        self._overflow = 0
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, seconds: float) -> None:
        v = float(seconds)
        if v < 0 or math.isnan(v):
            v = 0.0  # clock skew / bad sample: clamp, never throw
        i = bisect.bisect_left(self._bounds, v)
        with self._lock:
            if i < len(self._bounds):
                self._counts[i] += 1
            else:
                self._overflow += 1
            self._sum += v
            self._count += 1

    def snapshot(self) -> dict:
        """``{"buckets": {"<bound>": n, ..., "inf": n}, "sum": s,
        "count": c}`` — every leaf numeric, so ``RuntimeCollector.delta``
        diffs two snapshots into the window's histogram."""
        with self._lock:
            buckets = {
                repr(b): c for b, c in zip(self._bounds, self._counts)
            }
            buckets["inf"] = self._overflow
            return {"buckets": buckets, "sum": self._sum, "count": self._count}

    def quantile(self, q: float) -> float:
        return quantile_from_snapshot(self.snapshot(), q)

    @property
    def count(self) -> int:
        """Observations so far: one read, no lock (a reader that needs
        the buckets with it takes ``snapshot``)."""
        return self._count

    @property
    def bounds(self) -> tuple[float, ...]:
        return self._bounds


def quantile_from_snapshot(snap: dict, q: float) -> float:
    """Estimate the ``q``-quantile (0..1) from a histogram snapshot OR
    a ``delta()`` of two snapshots (non-cumulative bucket counts).

    Linear interpolation inside the target bucket — the same estimator
    Prometheus' ``histogram_quantile`` uses — so a test can bound the
    error by the bucket's width. Returns 0.0 on an empty histogram and
    the largest finite bound when the quantile lands in +Inf."""
    buckets = snap.get("buckets") or {}
    items = sorted(
        ((float(k), int(v)) for k, v in buckets.items() if k != "inf"),
    )
    overflow = int(buckets.get("inf", 0))
    total = sum(c for _, c in items) + overflow
    if total <= 0:
        return 0.0
    rank = max(0.0, min(1.0, float(q))) * total
    seen = 0
    lo = 0.0
    for bound, c in items:
        if seen + c >= rank and c > 0:
            frac = (rank - seen) / c
            return lo + (bound - lo) * frac
        seen += c
        lo = bound
    return items[-1][0] if items else 0.0


class HistogramFamily:
    """Child ``LatencyHistogram`` per (model, stage) label pair.

    ``observe`` creates children lazily under the family lock; reads
    (``snapshot``/``quantile``) take one consistent pass. Keys join as
    ``"model|stage"`` in snapshots — the same ``|``-joined convention
    the collector's error counter uses, so ``delta()`` output stays
    flat and JSON-friendly."""

    def __init__(self, buckets: tuple[float, ...] = DEFAULT_BUCKETS) -> None:
        self._buckets = tuple(buckets)
        self._children: dict[tuple[str, str], LatencyHistogram] = {}
        self._lock = threading.Lock()

    def child(self, model: str, stage: str) -> LatencyHistogram:
        key = (str(model), str(stage))
        h = self._children.get(key)
        if h is None:
            with self._lock:
                h = self._children.get(key)
                if h is None:
                    h = self._children[key] = LatencyHistogram(self._buckets)
        return h

    def observe(self, model: str, stage: str, seconds: float) -> None:
        self.child(model, stage).observe(seconds)

    def quantile(self, model: str, stage: str, q: float) -> float:
        with self._lock:
            h = self._children.get((str(model), str(stage)))
        return h.quantile(q) if h is not None else 0.0

    def count(self, model: str, stage: str) -> int:
        with self._lock:
            h = self._children.get((str(model), str(stage)))
        return h.snapshot()["count"] if h is not None else 0

    def snapshot(self) -> dict:
        with self._lock:
            children = dict(self._children)
        return {
            f"{model}|{stage}": h.snapshot()
            for (model, stage), h in sorted(children.items())
        }

    def items(self):
        with self._lock:
            return sorted(self._children.items())
