"""Per-stage latency profiling + device tracing (SURVEY.md §5 gap).

Lives in ``obs/`` since ISSUE 11 so the repo has ONE timing substrate:
the request-scoped tracer (obs/trace.py) feeds its finished spans into
a StageProfiler from this module, and the drivers/CLIs record their
pipeline stages into the same reservoir. (The ``utils/profiling.py``
deprecation shim has been removed — import from here.)

The reference has NO tracer — only commented-out ``time.time()`` pairs
around the 3D callback (ros_inference3d.py:122,209-210) and print-based
stage timing in the legacy postprocess (tools/utils.py:179-231). This
module is the first-class replacement:

- ``StageProfiler``: thread-safe rolling reservoir of wall-clock
  durations per named stage -> p50/p95/p99/mean/count snapshots.
- ``profiled(profiler, stage)``: context manager / function wrapper.
- ``device_trace``: jax.profiler trace context (XLA + TPU timeline,
  viewable in TensorBoard/Perfetto) for the on-device view host timers
  can't see.
- ``PrometheusStageExporter``: per-stage Histograms on a metrics port —
  the serving-side analogue of Triton's :8002 endpoint the reference
  scrapes (data/prometheus.yml:26-29).
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Iterator

import numpy as np

_QUANTILES = (50.0, 95.0, 99.0)


class StageProfiler:
    """Rolling per-stage duration reservoir.

    Keeps the most recent ``window`` samples per stage (enough for
    stable tail quantiles at camera rates without unbounded memory over
    long-running serving processes).
    """

    def __init__(self, window: int = 4096) -> None:
        import collections

        self._window = int(window)
        self._lock = threading.Lock()
        # deque(maxlen=...) evicts in O(1); a list's front-deletion would
        # memmove the whole window on every sample in the serving path.
        self._stages: dict[str, "collections.deque[float]"] = {}
        self._deque = collections.deque
        self._counts: dict[str, int] = {}
        self._listeners: list[Callable[[str, float], None]] = []

    def record(self, stage: str, seconds: float) -> None:
        self.record_many(((stage, seconds),))

    def record_many(self, samples) -> None:
        """``(stage, seconds)`` samples under ONE hold of the lock (the
        tracer hands over a finished request's spans at once, on the
        handler thread the caller's next request waits behind)."""
        with self._lock:
            stages, counts = self._stages, self._counts
            for stage, seconds in samples:
                buf = stages.get(stage)
                if buf is None:
                    buf = stages[stage] = self._deque(maxlen=self._window)
                buf.append(float(seconds))
                counts[stage] = counts.get(stage, 0) + 1
            listeners = list(self._listeners)
        for listener in listeners:
            for stage, seconds in samples:
                try:
                    listener(stage, seconds)
                except Exception:  # noqa: BLE001 — observability must never
                    # fail the observed path (e.g. a gRPC request)
                    import logging

                    logging.getLogger(__name__).warning(
                        "profiler listener failed for stage %r", stage, exc_info=True
                    )

    def add_listener(self, fn: Callable[[str, float], None]) -> None:
        """Observe every sample as it lands (Prometheus export hook)."""
        with self._lock:
            self._listeners.append(fn)

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record(name, time.perf_counter() - t0)

    def wrap(self, name: str, fn: Callable) -> Callable:
        def wrapped(*args, **kwargs):
            with self.stage(name):
                return fn(*args, **kwargs)

        return wrapped

    def summary(self) -> dict[str, dict[str, float]]:
        """stage -> {count, mean_ms, p50_ms, p95_ms, p99_ms}."""
        with self._lock:
            stages = {k: np.asarray(v) for k, v in self._stages.items() if v}
            counts = dict(self._counts)
        out = {}
        for name, samples in stages.items():
            ms = samples * 1e3
            row = {"count": float(counts.get(name, len(samples)))}
            row["mean_ms"] = float(ms.mean())
            for q in _QUANTILES:
                row[f"p{int(q)}_ms"] = float(np.percentile(ms, q))
            out[name] = row
        return out

    def report(self) -> str:
        """Human-readable per-stage table (driver end-of-run print)."""
        rows = self.summary()
        if not rows:
            return "(no stage samples)"
        width = max(len(n) for n in rows)
        lines = [
            f"{'stage'.ljust(width)}  count    mean    p50    p95    p99  (ms)"
        ]
        for name, r in sorted(rows.items()):
            lines.append(
                f"{name.ljust(width)}  {int(r['count']):5d}  "
                f"{r['mean_ms']:6.2f} {r['p50_ms']:6.2f} "
                f"{r['p95_ms']:6.2f} {r['p99_ms']:6.2f}"
            )
        return "\n".join(lines)


def _device_only_options():
    """Profiler options for a capture that must not slow what it
    measures, chosen from the platform: on a TPU the device's own lines
    only. With the host tracer at its default level (let alone the
    Python tracer) the host's staging slowed 18-60x while a capture ran
    and the device read 96% idle where 34% was true (PERF.md section
    6). Elsewhere None, the defaults: on the CPU backend the ops ARE
    host events."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    return options


@contextlib.contextmanager
def device_trace(log_dir: str) -> Iterator[None]:
    """jax.profiler trace window: captures the device timeline into
    ``log_dir`` (open with TensorBoard's profile plugin or Perfetto).
    Complements StageProfiler: host timers see walls, this sees what
    the chip did inside them. The one capture helper of the program:
    ``/profile`` and the continuous sampler go through it, so no
    capture of a live TPU server runs the host tracers."""
    import jax

    options = _device_only_options()
    if options is None:
        jax.profiler.start_trace(log_dir)
    else:
        jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named region inside a device trace (jax.profiler.TraceAnnotation)
    — shows host-side spans alongside device ops in the timeline."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# Latency buckets (seconds) tuned for camera-rate serving: 1 ms .. 10 s.
_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
    5.0, 10.0,
)


class PrometheusStageExporter:
    """Per-stage latency Histograms + request counter on a metrics port.

    The serving-side analogue of the Triton metrics endpoint the
    reference scrapes on :8002 (README.md:88-95, data/prometheus.yml).
    Import-gated like the reference's degraded-feature pattern
    (communicator/__init__.py:5-8).

    One histogram FAMILY with a ``stage`` label (round 4; was one
    metric name per stage): rate()/histogram_quantile() drop
    ``__name__``, so name-encoded stages could not be grouped in
    PromQL without recording rules — the label design is also how
    Triton's own nv_inference_* metrics carry the model. The serving
    stage label is ``infer_<model>``, matching the profiler's stage
    naming (runtime/server.py _infer); request traces land as
    ``span_<name>`` stages through obs.Tracer.

    ``registry``: the prometheus CollectorRegistry to export into
    (default the process-global ``prometheus_client.REGISTRY``). A
    second exporter on the same (registry, namespace) reuses the
    already-registered family instead of degrading to a no-op, so
    tests and multi-server processes can each export; pass each server
    its own registry for fully independent series.
    """

    # (registry -> {family name -> Histogram}): a second exporter on
    # the same registry records into the SAME family rather than
    # hitting prometheus's duplicate-registration ValueError and
    # silently recording nothing (the pre-telemetry failure mode).
    _family_cache = None
    _family_cache_lock = threading.Lock()

    def __init__(
        self,
        port: int = 8002,
        namespace: str = "tpu_serving",
        registry=None,
    ) -> None:
        import weakref

        import prometheus_client

        if registry is None:
            registry = prometheus_client.REGISTRY
        self._lock = threading.Lock()
        self._label_sources: dict[str, str] = {}
        self._warned: set[tuple[str, str]] = set()
        self._children: dict = {}  # stage -> its labelled series
        name = f"{namespace}_stage_latency_seconds"
        cls = type(self)
        with cls._family_cache_lock:
            if cls._family_cache is None:
                cls._family_cache = weakref.WeakKeyDictionary()
            per_registry = cls._family_cache.setdefault(registry, {})
            family = per_registry.get(name)
            if family is None:
                try:
                    family = prometheus_client.Histogram(
                        name,
                        "wall-clock latency per pipeline/serving stage",
                        labelnames=("stage",),
                        buckets=_BUCKETS,
                        registry=registry,
                    )
                    per_registry[name] = family
                except ValueError:
                    # the name is taken by a collector we did not
                    # create and cannot reuse: export nothing rather
                    # than poison the record path
                    import logging

                    logging.getLogger(__name__).warning(
                        "metric family %s already registered by a "
                        "foreign collector; this exporter records "
                        "nothing", name,
                    )
                    family = None
        self._family = family
        if port:
            prometheus_client.start_http_server(port, registry=registry)

    def observe(self, stage: str, seconds: float) -> None:
        if self._family is None:
            return
        child = self._children.get(stage)
        if child is not None:
            # a stage's series, resolved once (the serving path records
            # ``infer_<model>`` on every request)
            child.observe(seconds)
            return
        safe = "".join(c if c.isalnum() else "_" for c in stage)
        collision = None
        with self._lock:
            # two distinct stage names sanitizing to one label value
            # ('a.b' and 'a_b') would silently merge their series —
            # warn once per colliding PAIR (the first-seen source is
            # kept so alternating names cannot re-trigger every call)
            first = self._label_sources.setdefault(safe, stage)
            if first != stage and (safe, stage) not in self._warned:
                self._warned.add((safe, stage))
                collision = first
            child = self._family.labels(stage=safe)
        if collision is not None:
            import logging

            logging.getLogger(__name__).warning(
                "stage label %r now receives both %r and %r — series "
                "merged", safe, collision, stage,
            )
        self._children[stage] = child
        child.observe(seconds)

    def attach(self, profiler: StageProfiler) -> "PrometheusStageExporter":
        profiler.add_listener(self.observe)
        return self
