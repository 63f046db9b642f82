"""Request-scoped serving telemetry (the observability subsystem).

The reference's operator story is Triton's ``nv_inference_*`` counters
scraped into Grafana (README.md:88-109). Our serving plane does far
more than a request counter can describe — the overlapped dispatch
path (channel/tpu_channel.py) decomposes a request's wall latency into
queue wait, batch formation, H2D staging, device execute and deferred
readback — so this package makes that decomposition first-class:

- ``trace``     — per-request spans (trace-id, monotonic clock,
  ~zero-cost when disabled), a bounded ring buffer of recent request
  traces, and Chrome-trace/Perfetto JSON export.
- ``collector`` — bridges the in-process ``stats()`` dicts of
  TPUChannel and the batcher, HBM ``memory_stats()`` and jit
  compile events into Prometheus gauges/counters, with a ``snapshot()``
  API so perf scripts and production read identical numbers.
- ``http``      — one HTTP endpoint on the metrics port serving
  ``/metrics`` (Prometheus exposition), ``/traces`` (Chrome trace
  JSON) and ``/snapshot`` (raw collector stats).
"""

from triton_client_tpu.obs.trace import (
    LaunchRecord,
    RequestTrace,
    Span,
    Tracer,
    chrome_trace,
)
from triton_client_tpu.obs.collector import (
    METRIC_TYPES,
    CompileEvents,
    RuntimeCollector,
)
from triton_client_tpu.obs.histogram import (
    DEFAULT_BUCKETS,
    SLO_STAGES,
    HistogramFamily,
    LatencyHistogram,
    quantile_from_snapshot,
)
from triton_client_tpu.obs.http import TelemetryServer
from triton_client_tpu.obs.slo import SLOTracker

__all__ = [
    "DEFAULT_BUCKETS",
    "METRIC_TYPES",
    "SLO_STAGES",
    "CompileEvents",
    "HistogramFamily",
    "LatencyHistogram",
    "LaunchRecord",
    "RequestTrace",
    "RuntimeCollector",
    "SLOTracker",
    "Span",
    "TelemetryServer",
    "Tracer",
    "chrome_trace",
    "quantile_from_snapshot",
]
