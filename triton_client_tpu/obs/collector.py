"""Runtime collector: in-process serving counters -> Prometheus.

TPUChannel and ContinuousBatchingChannel keep their hot-path counters in plain
dicts (``stats()``) so recording costs an increment under a lock the
path already holds. Until this module, those numbers were visible only
to offline perf scripts that diffed ``stats()`` dicts by hand
(perf/profile_serving_overlap.py, perf/profile_serving_decomp.py).
``RuntimeCollector`` is the bridge:

- ``snapshot()`` / ``delta()`` — one structured read of everything
  (channel, batcher, HBM, jit compile events, error counts), used by
  the perf scripts AND by the Prometheus export, so offline and
  production read identical numbers;
- Prometheus custom collector — registered into a (per-server)
  registry, it converts each snapshot into typed gauge/counter
  families at scrape time: no background thread, no double
  bookkeeping, scrape-time consistency with ``stats()``.

Compile events ride ``jax.monitoring``: every
``.../backend_compile_duration`` event increments a process-global
counter (count + cumulative seconds), so a recompile storm — e.g. an
unbucketed shape leaking one executable per batch size — shows up as a
climbing ``tpu_serving_jit_compiles_total`` instead of mystery tail
latency.
"""

from __future__ import annotations

import logging
import threading

from triton_client_tpu.obs.roofline import device_info, model_row

log = logging.getLogger(__name__)

# Every family the collector always exports, name -> prometheus type.
# Families exist (HELP/TYPE lines) even when their component is absent
# or idle, so a refactor that drops a series fails the smoke test
# (tests/test_telemetry.py) instead of silently blanking a dashboard.
# Device HBM gauges are deliberately NOT here: they exist only on
# backends whose devices report memory_stats() (TPU/GPU, not CPU).
METRIC_TYPES: dict[str, str] = {
    # server / request plane
    "tpu_serving_inflight_requests": "gauge",
    "tpu_serving_request_errors_total": "counter",
    # TPUChannel staging slots
    "tpu_serving_inflight_batches": "gauge",
    "tpu_serving_staging_slots_active": "gauge",
    "tpu_serving_pipeline_depth": "gauge",
    "tpu_serving_staged_requests_total": "counter",
    "tpu_serving_launched_batches_total": "counter",
    "tpu_serving_donated_launches_total": "counter",
    "tpu_serving_stage_slot_waits_total": "counter",
    "tpu_serving_slot_occupancy_launches_total": "counter",
    # serving mesh shape (ShardedTPUChannel: batches split over the
    # data axis; 1/1 on a single-executable channel, 0 when no channel)
    "tpu_serving_data_axis_size": "gauge",
    "tpu_serving_mesh_devices": "gauge",
    # batch formation
    "tpu_serving_queue_depth": "gauge",
    "tpu_serving_batch_active_slots": "gauge",
    "tpu_serving_batch_fill_ratio": "gauge",
    "tpu_serving_batch_merges_total": "counter",
    "tpu_serving_batched_frames_total": "counter",
    "tpu_serving_padded_frames_total": "counter",
    "tpu_serving_batch_launch_frees_total": "counter",
    "tpu_serving_merge_occupancy_total": "counter",
    # dispatcher stall watchdog (round 15): the heartbeat age and its
    # thresholded boolean — a wedged dispatcher (batcher_stall fault, a
    # hung device call) previously queued requests forever in silence
    "tpu_serving_dispatcher_stalled": "gauge",
    "tpu_serving_dispatcher_last_progress_seconds": "gauge",
    # padding-tax plane (ISSUE 8): pad_fraction is the headline share
    # of device rows that were padding; batch_occupancy is the merge
    # occupancy as a real histogram, live;
    # ragged_* count the packed-batch path where padding is replaced by
    # a segment table (pad rows there are alignment slack only)
    "tpu_serving_pad_fraction": "gauge",
    "tpu_serving_batch_occupancy": "histogram",
    "tpu_serving_ragged_batches_total": "counter",
    "tpu_serving_ragged_rows_total": "counter",
    "tpu_serving_ragged_pad_rows_total": "counter",
    # per-model precision policy + quantized param footprint (round 10:
    # a bf16/int8 registration should visibly shrink param_bytes — the
    # HBM-occupancy regression check in tests/test_precision.py)
    "tpu_serving_model_precision_info": "gauge",
    "tpu_serving_model_param_bytes": "gauge",
    # jit compile events (process-global)
    "tpu_serving_jit_compiles_total": "counter",
    "tpu_serving_jit_compile_seconds_total": "counter",
    # tracer ring buffer
    "tpu_serving_traces_finished_total": "counter",
    "tpu_serving_trace_buffered": "gauge",
    # SLO observability ring (round 11): per model x stage latency
    # histograms fed from finished trace spans, attainment counters per
    # (model, priority, outcome), the tail-exemplar ring depth, and
    # launches whose request deadline had already expired at launch time
    "tpu_serving_latency_seconds": "histogram",
    "tpu_serving_slo_requests_total": "counter",
    "tpu_serving_slo_tail_buffered": "gauge",
    "tpu_serving_deadline_expired_launches_total": "counter",
    # overload-control plane (round 12): requests deliberately shed at
    # each stage of the pipeline (admission door / bounded queue /
    # batch merge / pre-launch / breaker), per-model circuit-breaker
    # state (0 closed, 1 half-open, 2 open) and cumulative opens,
    # admission queue depth, and the drain flag orchestrators watch
    "tpu_serving_shed_total": "counter",
    "tpu_serving_breaker_state": "gauge",
    "tpu_serving_breaker_opens_total": "counter",
    "tpu_serving_admission_queue_depth": "gauge",
    "tpu_serving_draining": "gauge",
    # multi-tenant lifecycle plane (round 13): the HBM paging budget
    # and what currently occupies it (total + per tenant), model counts
    # per lifecycle state, promotion/eviction churn with the promotion
    # latency distribution (the cold-start tax a capacity plan must
    # price), per-tenant admission sheds and served frames (fair-share
    # goodput per tenant, the Gemma-comparison discipline: capacity is
    # a number per tenant at SLO)
    "tpu_serving_hbm_budget_bytes": "gauge",
    "tpu_serving_hbm_resident_bytes": "gauge",
    "tpu_serving_tenant_hbm_bytes": "gauge",
    "tpu_serving_lifecycle_models": "gauge",
    "tpu_serving_model_promotions_total": "counter",
    "tpu_serving_model_evictions_total": "counter",
    "tpu_serving_promotion_seconds": "histogram",
    "tpu_serving_tenant_shed_total": "counter",
    "tpu_serving_tenant_served_frames_total": "counter",
    # device-time attribution plane (ISSUE 11): cumulative device-
    # execute seconds per model×tenant (the standing account the trace
    # plane's device_execute spans only showed per request), the
    # rolling-window busy ratio over elapsed wall × devices, and live
    # per-model MFU against the precision policy's analytic peak — the
    # same per-chip accounting the bench records, now on the scrape
    "tpu_serving_device_seconds_total": "counter",
    "tpu_serving_device_utilization_ratio": "gauge",
    "tpu_serving_mfu": "gauge",
    # host-transport plane (round 13): which transport carried each
    # request's tensors (grpc / uds / shm / uds+shm), payload bytes by
    # path (the wire-vs-shm mix a host-gap regression shows up in
    # first), and the multi-frame stream group-size distribution
    "tpu_serving_transport_info": "gauge",
    "tpu_serving_transport_requests_total": "counter",
    "tpu_serving_wire_bytes_total": "counter",
    "tpu_serving_shm_bytes_total": "counter",
    "tpu_serving_stream_group_size": "histogram",
    # kernel-attribution plane (ISSUE 14): per-XLA-op device time over
    # the continuous sampler's last capture window (top-K by model, op,
    # fusion kind), the window length and capture/skip counters, the
    # per-model roofline placement from cost_analysis()-measured
    # flops/bytes (arithmetic intensity, binding ceiling class,
    # attainable-fps ceiling), and the metric-history ring depth
    # streaming-session plane (ISSUE 15): device-resident per-stream
    # tracker slots — live occupancy of the bounded pool, in-flight
    # session frames, slot churn (created/restarted/ended/expired/
    # LRU-reclaimed/rejected), frames advanced through session state,
    # and track births/deaths folded from device counters at scrape
    # time (per-stream device-seconds ride the device_seconds_total
    # tenant axis as stream:<id>)
    "tpu_serving_sessions_active": "gauge",
    "tpu_serving_session_slot_occupancy": "gauge",
    "tpu_serving_session_inflight_frames": "gauge",
    "tpu_serving_sessions_total": "counter",
    "tpu_serving_sessions_rejected_total": "counter",
    "tpu_serving_session_frames_total": "counter",
    "tpu_serving_track_births_total": "counter",
    "tpu_serving_track_deaths_total": "counter",
    # temporal-reuse plane (ISSUE 19): per-frame reuse decisions
    # (full detector / tracker-coast / ROI-tile partial recompute),
    # the per-stream adaptive keyframe interval, reuse auto-disables
    # (per-stream ID-churn gate, quality-plane window violations),
    # cross-camera suppressed views, and the ROI tile economy
    "tpu_serving_frames_total": "counter",
    "tpu_serving_stream_effective_k": "gauge",
    "tpu_serving_temporal_disabled_total": "counter",
    "tpu_serving_suppressed_views_total": "counter",
    "tpu_serving_partial_tiles_total": "counter",
    "tpu_serving_op_device_seconds": "gauge",
    "tpu_serving_op_sample_window_seconds": "gauge",
    "tpu_serving_op_samples_total": "counter",
    "tpu_serving_op_sample_skips_total": "counter",
    "tpu_serving_model_roofline_info": "gauge",
    "tpu_serving_model_arithmetic_intensity": "gauge",
    "tpu_serving_model_attainable_fps": "gauge",
    "tpu_serving_history_buffered": "gauge",
    # continuous quality plane (ISSUE 17): shadow-scored online
    # accuracy in rolling windows per model x served variant (mAP vs
    # the f32 reference as pseudo-GT, CenterPoint velocity MAE,
    # tracking ID-switch delta), the shadow sidecar's throughput/lag/
    # drop accounting, and the canary lifecycle (hash-sliced traffic
    # fraction, state info gauge, promote/rollback counters) — the
    # accuracy column published next to every capacity family, own
    # tpu_quality namespace so dashboards can select the plane whole
    "tpu_quality_map50": "gauge",
    "tpu_quality_map": "gauge",
    "tpu_quality_velocity_mae": "gauge",
    "tpu_quality_id_switch_rate": "gauge",
    "tpu_quality_scored_frames_total": "counter",
    "tpu_quality_shadow_lag_seconds": "gauge",
    "tpu_quality_shadow_dropped_total": "counter",
    "tpu_quality_canary_fraction": "gauge",
    "tpu_quality_canary_info": "gauge",
    "tpu_quality_promotions_total": "counter",
    "tpu_quality_rollbacks_total": "counter",
}

_HBM_KINDS = ("bytes_in_use", "bytes_limit", "peak_bytes_in_use")


class CompileEvents:
    """Process-global jit compile-event counter (jax.monitoring).

    One listener per process, installed lazily on first use; jax has no
    listener removal API short of clear_event_listeners, so the
    singleton stays for the process lifetime — which is exactly the
    scope a compile counter wants."""

    _instance: "CompileEvents | None" = None
    _install_lock = threading.Lock()

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_seconds = 0.0

    @classmethod
    def install(cls) -> "CompileEvents":
        with cls._install_lock:
            if cls._instance is None:
                inst = cls()
                try:
                    import jax.monitoring

                    jax.monitoring.register_event_duration_secs_listener(
                        inst._on_event
                    )
                except Exception:  # jax absent/too old: counter stays 0
                    pass
                cls._instance = inst
            return cls._instance

    def _on_event(self, name: str, duration: float, **kwargs) -> None:
        # "/jax/core/compile/backend_compile_duration" fires once per
        # XLA compilation; the other /jax/core/compile/* events are
        # tracing/lowering stages we fold out to keep 1 event == 1
        # executable.
        if name.endswith("backend_compile_duration"):
            with self._lock:
                self.compiles += 1
                self.compile_seconds += float(duration)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "compiles": self.compiles,
                "compile_seconds": self.compile_seconds,
            }


def _split_channel(channel):
    """(batcher | None, TPUChannel | None) from a channel stack.

    Duck-typed: the batcher is anything with ``inner`` + ``stats``; the
    staging channel is anything with ``stats`` + ``pipeline_depth``."""
    batching, tpu, c = None, None, channel
    if c is not None and hasattr(c, "inner") and hasattr(c, "stats"):
        batching = c
        c = c.inner
    if c is not None and hasattr(c, "stats") and hasattr(c, "pipeline_depth"):
        tpu = c
    return batching, tpu


class RuntimeCollector:
    """One structured read of the serving plane's runtime state.

    Works with or without prometheus_client: ``snapshot()``/``delta()``
    are plain dicts (the perf-script API); passing ``registry=``
    additionally registers this object as a Prometheus custom collector
    whose families are generated from a snapshot at scrape time."""

    def __init__(
        self,
        channel=None,
        tracer=None,
        namespace: str = "tpu_serving",
        registry=None,
        repository=None,
        histograms=None,
        slo=None,
        admission=None,
        lifecycle=None,
        device_time=None,
    ) -> None:
        """``histograms``: an obs.histogram.HistogramFamily of per
        (model, stage) latency histograms; ``slo``: an obs.slo.
        SLOTracker; ``admission``: a runtime.admission.
        AdmissionController; ``lifecycle``: a runtime.lifecycle.
        ModelLifecycleManager; ``device_time``: an obs.device_time.
        DeviceTimeLedger. All optional — their metric families export
        empty (HELP/TYPE only) when absent, so the family inventory
        test keeps pinning the series names either way."""
        self._batching, self._tpu = _split_channel(channel)
        self._tracer = tracer
        self._repository = repository
        self._histograms = histograms
        self._slo = slo
        self._admission = admission
        self._lifecycle = lifecycle
        self._device_time = device_time
        self._ns = namespace
        self._compile = CompileEvents.install()
        self._lock = threading.Lock()
        self._inflight_requests = 0
        self._errors: dict[tuple[str, str], int] = {}
        # admission-door sheds ("model|priority|stage"); the channel
        # and batcher keep their own stage sheds, merged at snapshot
        self._shed: dict[str, int] = {}
        # host-transport mix: requests per negotiated transport label,
        # input payload bytes split wire vs shm, and the multi-frame
        # stream group-size occupancy
        self._transport_requests: dict[str, int] = {}
        self._wire_bytes = 0
        self._shm_bytes = 0
        self._stream_groups: dict[int, int] = {}
        # kernel-attribution plane: the sampler's last per-op window
        # (gauges show the latest capture; the counter accumulates) and
        # the optional sampler/history components attached post-build
        self._op_rows: list = []
        self._op_window_s = 0.0
        self._op_samples = 0
        self._sampler = None
        self._history = None
        self._quality = None
        self._temporal = None
        self._draining = False
        self._front_end = self._front_active = None
        self._registry = None
        if registry is not None:
            registry.register(self)
            self._registry = registry

    # -- request-plane hooks (called by the server) ---------------------------

    def request_started(self) -> None:
        with self._lock:
            self._inflight_requests += 1

    def request_finished(self) -> None:
        with self._lock:
            self._inflight_requests -= 1

    def record_error(self, model: str, code: str) -> None:
        with self._lock:
            key = (model, code)
            self._errors[key] = self._errors.get(key, 0) + 1

    def record_shed(self, model: str, priority: int, stage: str) -> None:
        """One request deliberately rejected at ``stage`` (the server
        calls this for admission-door sheds; channel/batcher stages
        count their own and are merged at snapshot time)."""
        with self._lock:
            key = f"{model}|{int(priority)}|{stage}"
            self._shed[key] = self._shed.get(key, 0) + 1

    def record_transport(
        self, transport: str, wire_bytes: int, shm_bytes: int
    ) -> None:
        """One inference request's transport mix: the negotiated label
        (grpc/uds/shm/uds+shm) and how many input-payload bytes each
        path moved."""
        with self._lock:
            self._transport_requests[transport] = (
                self._transport_requests.get(transport, 0) + 1
            )
            self._wire_bytes += int(wire_bytes)
            self._shm_bytes += int(shm_bytes)

    def record_stream_group(self, size: int) -> None:
        """One packed multi-frame stream message of ``size`` frames."""
        with self._lock:
            self._stream_groups[int(size)] = (
                self._stream_groups.get(int(size), 0) + 1
            )

    def record_op_sample(self, rows, window_s: float) -> None:
        """The continuous sampler's sink: the top-K per-op rows of one
        capture window (obs.opstats.summarize row shape). Gauges export
        the LAST window; the samples counter accumulates."""
        with self._lock:
            self._op_rows = list(rows or [])
            self._op_window_s = float(window_s or 0.0)
            self._op_samples += 1

    def attach_sampler(self, sampler) -> None:
        """Wire the ContinuousSampler whose stats() (skips, duty cycle)
        this collector exports — attached after construction because the
        sampler itself takes the collector as its sink."""
        self._sampler = sampler

    def attach_history(self, history) -> None:
        """Wire the MetricHistory whose ring depth this collector
        exports."""
        self._history = history

    def attach_front_end(self, stats, active) -> None:
        """Wire the gRPC servicer (runtime/server.py): its
        ``front_stats`` (the handler threads' CPU time a request, the
        front memo's hit counts) land under ``/snapshot["front_end"]``,
        its transport mix is added to this collector's own, and its
        in-flight count (``active``) is the gauge: a request then takes
        one lock for that, and none here."""
        self._front_end = stats
        self._front_active = active

    def attach_temporal(self, temporal) -> None:
        """Wire the temporal reuse plane (runtime/temporal.py) whose
        per-stream coast/partial/suppression decisions export as the
        ``tpu_serving_frames_total``-family metrics and land under
        ``/snapshot["temporal"]`` (ISSUE 19)."""
        self._temporal = temporal

    def attach_quality(self, quality, legacy_eval: bool = True) -> None:
        """Wire the continuous quality plane (eval/quality_plane.py)
        whose rolling windows export as the ``tpu_quality_*`` families
        and land under ``/snapshot["quality"]``.

        ``legacy_eval``: also fold the reference's eval Summaries
        (``model_precision``/``model_recall``/``model_ap``/...) into
        THIS collector's registry — the ISSUE 17 satellite retiring the
        standalone port-7658 exporter: one scrape endpoint serves both
        spellings from the same windows."""
        self._quality = quality
        if legacy_eval and self._registry is not None:
            try:
                from triton_client_tpu.eval import prometheus_export

                if prometheus_export.available():
                    quality.attach_legacy_exporter(
                        prometheus_export.EvalPrometheusExporter(
                            registry=self._registry
                        )
                    )
            except Exception:  # pragma: no cover - registry collisions
                log.debug(
                    "legacy eval summaries not folded", exc_info=True
                )

    def hlo_modules(self) -> dict[str, str]:
        """``{hlo_module: model_name}`` over every registered model —
        the op->model attribution map the sampler and /profile hand to
        obs.opstats (each spec.extra's ``hlo_module`` is recorded at
        launcher build by obs.roofline.record_launch_cost)."""
        out: dict[str, str] = {}
        if self._repository is None:
            return out
        try:
            listing = self._repository.list_models()
        except Exception:
            return out
        for name, version in listing:
            try:
                extra = self._repository.get(name, version).spec.extra
            except Exception:
                continue
            module = extra.get("hlo_module")
            if module:
                out[str(module)] = name
        return out

    def set_draining(self, draining: bool) -> None:
        with self._lock:
            self._draining = bool(draining)

    # -- snapshot API (perf scripts + scrape share this) ----------------------

    def snapshot(self) -> dict:
        with self._lock:
            inflight = self._inflight_requests
            errors = {f"{m}|{c}": n for (m, c), n in self._errors.items()}
            shed = dict(self._shed)
            draining = self._draining
            transport = {
                "requests": dict(self._transport_requests),
                "wire_bytes": self._wire_bytes,
                "shm_bytes": self._shm_bytes,
                "stream_groups": dict(self._stream_groups),
            }
            op_sample = {
                "rows": list(self._op_rows),
                "window_s": self._op_window_s,
                "samples": self._op_samples,
            }
        snap = {
            "channel": self._tpu.stats() if self._tpu is not None else None,
            "batching": (
                self._batching.stats() if self._batching is not None else None
            ),
            "inflight_requests": inflight
            + (self._front_active() if self._front_active else 0),
            "errors": errors,
            "compile": self._compile.snapshot(),
            "memory": self._memory(),
            # what every number in this snapshot was measured on
            "device": device_info(),
        }
        # one shed ledger across the whole pipeline: admission-door
        # sheds (recorded here) + the queue/merge/launch/breaker stages
        # the batcher and staged channel count in their own stats()
        for src in (snap["channel"], snap["batching"]):
            for key, n in ((src or {}).get("shed") or {}).items():
                shed[key] = shed.get(key, 0) + n
        snap["shed"] = shed
        snap["draining"] = int(draining)
        if self._front_end is not None:
            front = snap["front_end"] = self._front_end()
            for label, (n, wire, shm) in front.pop("transport").items():
                transport["requests"][label] = (
                    transport["requests"].get(label, 0) + n
                )
                transport["wire_bytes"] += wire
                transport["shm_bytes"] += shm
        snap["transport"] = transport
        if self._admission is not None:
            snap["admission"] = self._admission.stats()
        if self._lifecycle is not None:
            snap["lifecycle"] = self._lifecycle.stats()
        if self._tracer is not None:
            snap["tracer"] = self._tracer.stats()
        if self._device_time is not None:
            snap["device_time"] = self._device_time.snapshot()
        session_stats = getattr(self._tpu, "session_stats", None)
        if session_stats is not None:
            # the tracker's stats() drains the deferred device-counter
            # folds — the only host read of tracker state, at scrape
            # time, never on the frame path; a model that declares its
            # own session state (token sessions) reports under "models"
            sessions = session_stats()
            if sessions is not None:
                snap["sessions"] = sessions
        snap["op_sample"] = op_sample
        if self._sampler is not None:
            snap["sampler"] = self._sampler.stats()
        if self._history is not None:
            snap["history"] = self._history.stats()
        if self._quality is not None:
            snap["quality"] = self._quality.snapshot()
        if self._temporal is not None:
            snap["temporal"] = self._temporal.stats()
        if self._histograms is not None:
            # numeric-leaved per-(model|stage) bucket counts + sum:
            # delta() of two snapshots is the WINDOW's histogram, and
            # obs.histogram.quantile_from_snapshot reads percentiles
            # off either form — perf scripts get p99 through the same
            # path as every counter
            snap["histograms"] = self._histograms.snapshot()
        if self._slo is not None:
            snap["slo"] = self._slo.stats()
        models = self._models()
        if models is not None:
            snap["models"] = models
        return snap

    def _models(self) -> list | None:
        """Per-registered-model precision + param footprint rows (round
        10), read from each ModelSpec's extra at snapshot time so a
        model reload is reflected on the next scrape."""
        if self._repository is None:
            return None
        rows = []
        try:
            listing = self._repository.list_models()
        except Exception:
            return None
        for name, version in listing:
            try:
                extra = self._repository.get(name, version).spec.extra
            except Exception:
                continue
            row = {
                "model": name,
                "version": version,
                "precision": str(extra.get("precision", "f32")),
                "param_bytes": int(extra.get("param_bytes", 0) or 0),
                # which Pallas fusions this model's launcher routes
                # (ops/fused; empty = the plain XLA route)
                "fused_stages": list(extra.get("fused_stages") or ()),
            }
            # roofline placement once the channel has recorded the
            # XLA-measured launch cost (obs.roofline.record_launch_cost
            # at first launch; absent until then / without a cost model)
            if extra.get("measured_flops_per_call") is not None:
                row["roofline"] = model_row(extra)
                row["pallas_kernels"] = int(extra.get("pallas_kernels", 0))
            rows.append(row)
        return rows

    @staticmethod
    def delta(new: dict, old: dict) -> dict:
        """Recursive numeric diff of two snapshots, zero/empty leaves
        dropped — the structured replacement for the hand-rolled
        ``stats()`` delta-diffing the perf scripts used to do."""

        def diff(n, o):
            if isinstance(n, dict):
                o = o if isinstance(o, dict) else {}
                out = {}
                for k, v in n.items():
                    r = diff(v, o.get(k))
                    if r not in (None, 0, 0.0, {}):
                        out[k] = r
                return out
            if isinstance(n, bool) or not isinstance(n, (int, float)):
                return None
            base = o if isinstance(o, (int, float)) and not isinstance(o, bool) else 0
            return n - base

        return diff(new, old if isinstance(old, dict) else {})

    def _memory(self) -> dict:
        """Per-device memory_stats() (HBM on TPU; None/absent on CPU)."""
        out = {}
        try:
            if self._tpu is not None:
                devices = list(self._tpu.fetch_channel().devices.flat)
            else:
                import jax

                devices = jax.local_devices()
        except Exception:
            return out
        for d in devices:
            try:
                ms = d.memory_stats()
            except Exception:
                ms = None
            if ms:
                out[str(getattr(d, "id", d))] = {
                    k: v for k, v in ms.items() if isinstance(v, (int, float))
                }
        return out

    # -- Prometheus custom-collector protocol ---------------------------------

    def describe(self):
        # Registered as an "unchecked" collector: families are dynamic
        # (labels appear as models/depths are observed), so describe()
        # returns nothing rather than a stale inventory.
        return []

    def collect(self):
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
            HistogramMetricFamily,
        )

        snap = self.snapshot()
        chan = snap["channel"] or {}
        bat = snap["batching"] or {}
        ns = self._ns

        def gauge(name, doc, value, labels=None, samples=()):
            fam = GaugeMetricFamily(name, doc, labels=labels or [])
            if labels:
                for lv, v in samples:
                    fam.add_metric(lv, v)
            else:
                fam.add_metric([], value)
            return fam

        def counter(name, doc, value, labels=None, samples=()):
            fam = CounterMetricFamily(name, doc, labels=labels or [])
            if labels:
                for lv, v in samples:
                    fam.add_metric(lv, v)
            else:
                fam.add_metric([], value)
            return fam

        yield gauge(
            f"{ns}_inflight_requests",
            "gRPC requests currently being served",
            snap["inflight_requests"],
        )
        yield counter(
            f"{ns}_request_errors_total",
            "failed requests by model and gRPC status code",
            0,
            labels=["model", "code"],
            samples=[
                (key.split("|", 1), n) for key, n in snap["errors"].items()
            ],
        )

        # TPUChannel staging slots
        yield gauge(
            f"{ns}_inflight_batches",
            "launched, not-yet-retired device batches",
            chan.get("inflight", 0),
        )
        yield gauge(
            f"{ns}_staging_slots_active",
            "staging slots currently held (stage..retire)",
            chan.get("slots_active", 0),
        )
        yield gauge(
            f"{ns}_pipeline_depth",
            "configured staging pipeline depth",
            chan.get("pipeline_depth", 0),
        )
        yield counter(
            f"{ns}_staged_requests_total",
            "requests staged onto the device mesh",
            chan.get("staged", 0),
        )
        yield counter(
            f"{ns}_launched_batches_total",
            "device batches launched",
            chan.get("launched", 0),
        )
        yield counter(
            f"{ns}_donated_launches_total",
            "launches through the donated-buffer jit path",
            chan.get("donated_launches", 0),
        )
        yield counter(
            f"{ns}_stage_slot_waits_total",
            "stage() calls that blocked on a staging slot",
            chan.get("stage_slot_waits", 0),
        )
        yield counter(
            f"{ns}_slot_occupancy_launches_total",
            "launches observed at each in-flight depth",
            0,
            labels=["inflight"],
            samples=[
                ([str(k)], v)
                for k, v in (chan.get("slot_occupancy") or {}).items()
            ],
        )
        yield gauge(
            f"{ns}_data_axis_size",
            "mesh data-axis width request batches shard over "
            "(1 = single-executable channel, 0 = no channel)",
            chan.get("data_axis_size", 0),
        )
        yield gauge(
            f"{ns}_mesh_devices",
            "devices claimed by the serving mesh",
            chan.get("mesh_devices", 0),
        )

        # batch formation
        yield gauge(
            f"{ns}_queue_depth",
            "requests staged, awaiting dispatch",
            bat.get("ready_depth", 0),
        )
        yield gauge(
            f"{ns}_batch_active_slots",
            "batcher execution slots currently active",
            bat.get("active_slots", 0),
        )
        yield gauge(
            f"{ns}_dispatcher_stalled",
            "1 when the dispatch loop's heartbeat is older than the "
            "stall threshold (watchdog also logs the episode)",
            bat.get("dispatcher_stalled", 0),
        )
        yield gauge(
            f"{ns}_dispatcher_last_progress_seconds",
            "seconds since the dispatch loop last made progress",
            bat.get("dispatcher_last_progress_age_s", 0.0),
        )
        merges = bat.get("merges", 0)
        fill = 0.0
        if merges and bat.get("max_merge"):
            fill = bat.get("merged_frames", 0) / merges / bat["max_merge"]
        yield gauge(
            f"{ns}_batch_fill_ratio",
            "mean merged frames per dispatch / max_merge",
            fill,
        )
        yield counter(
            f"{ns}_batch_merges_total",
            "device batches formed at dispatch time",
            merges,
        )
        yield counter(
            f"{ns}_batched_frames_total",
            "frames merged into device batches",
            bat.get("merged_frames", 0),
        )
        by_model = bat.get("padded_by_model")
        if by_model is None and bat.get("padded_frames"):
            # a duck-typed batcher without the per-model ledger: keep
            # the total visible rather than dropping the series
            by_model = {"unknown": bat["padded_frames"]}
        yield counter(
            f"{ns}_padded_frames_total",
            "pad rows added by bucket padding, per model",
            0,
            labels=["model"],
            samples=[([m], n) for m, n in (by_model or {}).items()],
        )
        yield counter(
            f"{ns}_batch_launch_frees_total",
            "execution slots freed at launch (pre-readback)",
            bat.get("launch_frees", 0),
        )
        yield counter(
            f"{ns}_merge_occupancy_total",
            "dispatches observed at each merged frame count",
            0,
            labels=["frames"],
            samples=[
                ([str(k)], v)
                for k, v in (bat.get("merge_occupancy") or {}).items()
            ],
        )
        # the padding-tax plane (ISSUE 8): headline pad share + the
        # occupancy distribution as a real histogram, so dashboards get
        # quantiles without scraping the labeled counter above
        yield gauge(
            f"{ns}_pad_fraction",
            "share of device rows shipped as padding "
            "(dense bucket pad + ragged alignment slack)",
            bat.get("pad_fraction", 0.0),
        )
        occ_hist = HistogramMetricFamily(
            f"{ns}_batch_occupancy",
            "real frames per formed device batch",
            labels=[],
        )
        occ = {int(k): v for k, v in (bat.get("merge_occupancy") or {}).items()}
        cum, cum_buckets = 0, []
        for bound in (1, 2, 4, 8, 16, 32, 64, 128):
            cum += sum(v for k, v in occ.items() if bound / 2 < k <= bound)
            cum_buckets.append((repr(float(bound)), cum))
        cum_buckets.append(("+Inf", sum(occ.values())))
        occ_hist.add_metric(
            [], cum_buckets, float(sum(k * v for k, v in occ.items()))
        )
        yield occ_hist
        yield counter(
            f"{ns}_ragged_batches_total",
            "packed ragged batches dispatched (segment-table execution)",
            bat.get("ragged_batches", 0),
        )
        yield counter(
            f"{ns}_ragged_rows_total",
            "real rows executed through packed ragged batches",
            bat.get("ragged_rows", 0),
        )
        yield counter(
            f"{ns}_ragged_pad_rows_total",
            "alignment pad rows shipped with packed ragged batches",
            bat.get("ragged_pad_rows", 0),
        )

        # per-model precision + param footprint (empty families when no
        # repository is wired — the HELP/TYPE lines still export so the
        # telemetry smoke test pins the series names)
        models = snap.get("models") or []
        yield gauge(
            f"{ns}_model_precision_info",
            "serving precision policy per registered model (info gauge)",
            0,
            labels=["model", "version", "precision"],
            samples=[
                ([m["model"], m["version"], m["precision"]], 1)
                for m in models
            ],
        )
        yield gauge(
            f"{ns}_model_param_bytes",
            "registered parameter bytes per model (post-quantization)",
            0,
            labels=["model", "version"],
            samples=[
                ([m["model"], m["version"]], m["param_bytes"])
                for m in models
            ],
        )

        # jit compile events
        comp = snap["compile"]
        yield counter(
            f"{ns}_jit_compiles_total",
            "XLA backend compilations observed (jax.monitoring)",
            comp["compiles"],
        )
        yield counter(
            f"{ns}_jit_compile_seconds_total",
            "cumulative seconds spent in XLA backend compilation",
            comp["compile_seconds"],
        )

        # tracer ring buffer
        tr = snap.get("tracer") or {}
        yield counter(
            f"{ns}_traces_finished_total",
            "request traces finished",
            tr.get("finished", 0),
        )
        yield gauge(
            f"{ns}_trace_buffered",
            "request traces held in the export ring buffer",
            tr.get("buffered", 0),
        )

        # SLO observability ring: per model x stage latency histograms
        # (fed from finished trace spans) and attainment counters. The
        # families export even when the components are absent so the
        # series names stay pinned by the telemetry smoke test.
        lat = HistogramMetricFamily(
            f"{ns}_latency_seconds",
            "request latency per model and pipeline stage "
            "(queue_delay/merge_wait/device_execute/readback/e2e)",
            labels=["model", "stage"],
        )
        for key, h in (snap.get("histograms") or {}).items():
            model, _, stage = key.partition("|")
            cum, cum_buckets = 0, []
            for bound, c in sorted(
                (float(b), n)
                for b, n in h["buckets"].items()
                if b != "inf"
            ):
                cum += c
                cum_buckets.append((repr(bound), cum))
            cum_buckets.append(("+Inf", h["count"]))
            lat.add_metric([model, stage], cum_buckets, h["sum"])
        yield lat
        slo = snap.get("slo") or {}
        yield counter(
            f"{ns}_slo_requests_total",
            "requests scored against their latency SLO, by outcome",
            0,
            labels=["model", "priority", "outcome"],
            samples=[
                (key.split("|", 1) + [outcome], cell[outcome])
                for key, cell in (slo.get("requests") or {}).items()
                for outcome in ("met", "missed")
            ],
        )
        yield gauge(
            f"{ns}_slo_tail_buffered",
            "SLO-violating / p99+ exemplar traces held in the tail ring",
            slo.get("tail_buffered", 0),
        )
        yield counter(
            f"{ns}_deadline_expired_launches_total",
            "batches launched after their request deadline had passed",
            chan.get("deadline_expired_launches", 0),
        )

        # overload-control plane: sheds by pipeline stage, breaker
        # state machine, admission queue depth, drain flag
        yield counter(
            f"{ns}_shed_total",
            "requests deliberately rejected, by model, priority, and "
            "pipeline stage (admission/queue/merge/launch/breaker)",
            0,
            labels=["model", "priority", "stage"],
            samples=[
                (key.split("|", 2), n)
                for key, n in (snap.get("shed") or {}).items()
            ],
        )
        breaker = chan.get("breaker") or {}
        yield gauge(
            f"{ns}_breaker_state",
            "per-model circuit-breaker state "
            "(0 closed, 1 half-open, 2 open)",
            0,
            labels=["model"],
            samples=[([m], c["state"]) for m, c in breaker.items()],
        )
        yield counter(
            f"{ns}_breaker_opens_total",
            "circuit-breaker open transitions per model",
            0,
            labels=["model"],
            samples=[([m], c["opens"]) for m, c in breaker.items()],
        )
        adm = snap.get("admission") or {}
        yield gauge(
            f"{ns}_admission_queue_depth",
            "admitted-but-unfinished requests per model "
            "(the admission controller's queue-depth knee input)",
            0,
            labels=["model"],
            samples=[
                ([m], d) for m, d in (adm.get("inflight") or {}).items()
            ],
        )
        yield gauge(
            f"{ns}_draining",
            "1 while the server is draining (SIGTERM / drain())",
            snap.get("draining", 0),
        )

        # multi-tenant lifecycle plane: HBM budget/residency, lifecycle
        # state counts, promotion/eviction churn + promotion latency,
        # per-tenant sheds and served frames. Families export empty
        # when no lifecycle manager is wired.
        lc = snap.get("lifecycle") or {}
        yield gauge(
            f"{ns}_hbm_budget_bytes",
            "configured HBM paging budget (0 = unbudgeted)",
            lc.get("budget_bytes", 0),
        )
        yield gauge(
            f"{ns}_hbm_resident_bytes",
            "estimated bytes of WARM model params under the budget",
            lc.get("resident_bytes", 0),
        )
        yield gauge(
            f"{ns}_tenant_hbm_bytes",
            "resident model bytes billed to each tenant",
            0,
            labels=["tenant"],
            samples=[
                ([t], b)
                for t, b in (lc.get("tenant_resident_bytes") or {}).items()
            ],
        )
        yield gauge(
            f"{ns}_lifecycle_models",
            "registered models per lifecycle state "
            "(cold/warming/warm/evicting)",
            0,
            labels=["state"],
            samples=[([s], n) for s, n in (lc.get("states") or {}).items()],
        )
        lc_models = [
            (key.partition(":"), row)
            for key, row in (lc.get("models") or {}).items()
        ]
        yield counter(
            f"{ns}_model_promotions_total",
            "COLD -> WARM promotions per model",
            0,
            labels=["model", "version"],
            samples=[
                ([name, version], row["promotions"])
                for (name, _, version), row in lc_models
            ],
        )
        yield counter(
            f"{ns}_model_evictions_total",
            "WARM -> COLD evictions per model",
            0,
            labels=["model", "version"],
            samples=[
                ([name, version], row["evictions"])
                for (name, _, version), row in lc_models
            ],
        )
        promo = HistogramMetricFamily(
            f"{ns}_promotion_seconds",
            "COLD -> WARM promotion latency (make-room + page-in)",
            labels=[],
        )
        ph = lc.get("promotion_latency") or {"buckets": {}, "sum": 0.0,
                                             "count": 0}
        cum, cum_buckets = 0, []
        for bound, c in sorted(
            (float(b), n) for b, n in ph["buckets"].items() if b != "inf"
        ):
            cum += c
            cum_buckets.append((repr(bound), cum))
        cum_buckets.append(("+Inf", ph["count"]))
        promo.add_metric([], cum_buckets, ph["sum"])
        yield promo
        yield counter(
            f"{ns}_tenant_shed_total",
            "requests shed at the admission door per tenant "
            "(in-flight cap + per-model knees)",
            0,
            labels=["tenant"],
            samples=[
                ([t], n)
                for t, n in (adm.get("tenant_rejects") or {}).items()
            ],
        )
        yield counter(
            f"{ns}_tenant_served_frames_total",
            "frames dispatched per tenant by the fair-share scheduler",
            0,
            labels=["tenant"],
            samples=[
                ([t], n)
                for t, n in (bat.get("tenant_served_frames") or {}).items()
            ],
        )

        # device-time attribution plane: cumulative device-seconds per
        # model×tenant, rolling-window utilization, live per-model MFU
        dt = snap.get("device_time") or {}
        dt_window = dt.get("window") or {}
        yield counter(
            f"{ns}_device_seconds_total",
            "cumulative device-execute seconds per model and tenant",
            0,
            labels=["model", "tenant"],
            samples=[
                (key.split("|", 1), v)
                for key, v in (dt.get("device_seconds") or {}).items()
            ],
        )
        yield gauge(
            f"{ns}_device_utilization_ratio",
            "rolling-window busy device-seconds over elapsed wall x "
            "devices (the live device-time ceiling of ROADMAP item 1)",
            dt_window.get("utilization", 0.0),
        )
        yield gauge(
            f"{ns}_mfu",
            "live model flops utilization over the rolling window, per "
            "model (analytic flops against the precision policy peak)",
            0,
            labels=["model"],
            samples=[
                ([m], v) for m, v in (dt_window.get("mfu") or {}).items()
            ],
        )

        # streaming-session plane: the bounded slot pool's live state
        # plus churn/track counters (per-stream device-seconds already
        # ride device_seconds_total's tenant axis as stream:<id>)
        ses = snap.get("sessions") or {}
        yield gauge(
            f"{ns}_sessions_active",
            "streaming sessions currently holding a device-resident "
            "tracker slot",
            ses.get("active_sessions", 0),
        )
        yield gauge(
            f"{ns}_session_slot_occupancy",
            "active sessions over the slot pool size",
            ses.get("slot_occupancy", 0.0),
        )
        yield gauge(
            f"{ns}_session_inflight_frames",
            "session frames between launch and resolve (slot refcounts)",
            ses.get("inflight_frames", 0),
        )
        yield counter(
            f"{ns}_sessions_total",
            "session slot transitions by event (created / restarted / "
            "ended / expired / reclaimed)",
            0,
            labels=["event"],
            samples=[
                ([ev], ses.get(f"{ev}_total", 0))
                for ev in (
                    "created", "restarted", "ended", "expired", "reclaimed"
                )
            ],
        )
        yield counter(
            f"{ns}_sessions_rejected_total",
            "session frames shed because the slot pool was full and "
            "unreclaimable",
            ses.get("rejected_total", 0),
        )
        yield counter(
            f"{ns}_session_frames_total",
            "frames advanced through device-resident session state",
            ses.get("frames_total", 0),
        )
        yield counter(
            f"{ns}_track_births_total",
            "tracks born across all sessions (device counters folded at "
            "scrape/end, never on the frame path)",
            ses.get("track_births_total", 0),
        )
        yield counter(
            f"{ns}_track_deaths_total",
            "tracks retired across all sessions",
            ses.get("track_deaths_total", 0),
        )

        # temporal-reuse plane (ISSUE 19): every frame's reuse decision
        # (full detector / tracker coast / ROI-tile partial recompute),
        # the per-stream adaptive keyframe interval, reuse disables by
        # reason, cross-camera suppression, and the tile economy
        tmp = snap.get("temporal") or {}
        yield counter(
            f"{ns}_frames_total",
            "stream frames by reuse decision: full detector pass, "
            "tracker-coast, or ROI-tile partial recompute",
            0,
            labels=["mode"],
            samples=[
                (["full"], tmp.get("frames_full_total", 0)),
                (["coast"], tmp.get("frames_coast_total", 0)),
                (["partial"], tmp.get("frames_partial_total", 0)),
            ],
        )
        yield gauge(
            f"{ns}_stream_effective_k",
            "current adaptive keyframe interval per stream (frames "
            "between full detector passes; 1 = every frame)",
            0,
            labels=["stream"],
            samples=[
                ([str(sid)], k)
                for sid, k in sorted(
                    (tmp.get("effective_k") or {}).items()
                )
            ],
        )
        yield counter(
            f"{ns}_temporal_disabled_total",
            "streams/models where temporal reuse auto-disabled: "
            "per-stream ID-churn gate (churn) or quality-plane window "
            "violation (quality)",
            0,
            labels=["reason"],
            samples=[
                (["churn"], tmp.get("auto_disabled_total", 0)),
                (["quality"], tmp.get("quality_disabled_total", 0)),
            ],
        )
        yield counter(
            f"{ns}_suppressed_views_total",
            "camera views skipped because all their tracked objects "
            "project into already-processed overlap regions",
            tmp.get("suppressed_views_total", 0),
        )
        yield counter(
            f"{ns}_partial_tiles_total",
            "ROI tiles actually re-detected (selected) vs the full "
            "tile-grid size of those frames (possible)",
            0,
            labels=["kind"],
            samples=[
                (["selected"], tmp.get("partial_tiles_total", 0)),
                (["possible"], tmp.get("partial_tiles_possible_total", 0)),
            ],
        )

        # kernel-attribution plane (ISSUE 14): per-op device time over
        # the sampler's last capture window, sampler counters, and each
        # model's roofline placement from the measured launch cost
        op = snap.get("op_sample") or {}
        samp = snap.get("sampler") or {}
        yield gauge(
            f"{ns}_op_device_seconds",
            "device time per XLA op over the sampler's last capture "
            "window (top-K by time; model attributed via HLO module / "
            "launch annotations)",
            0,
            labels=["model", "op", "kind"],
            samples=[
                (
                    [
                        str(r.get("model") or "unattributed"),
                        str(r.get("op", "?")),
                        str(r.get("kind", "other")),
                    ],
                    float(r.get("time_us", 0.0)) / 1e6,
                )
                for r in (op.get("rows") or [])
            ],
        )
        yield gauge(
            f"{ns}_op_sample_window_seconds",
            "length of the sampler's last profiler capture window",
            op.get("window_s", 0.0),
        )
        yield counter(
            f"{ns}_op_samples_total",
            "profiler capture windows delivered by the continuous "
            "sampler",
            op.get("samples", 0),
        )
        yield counter(
            f"{ns}_op_sample_skips_total",
            "sampler windows skipped because /profile held the capture "
            "guard",
            samp.get("skipped_busy", 0),
        )
        roofline_rows = [
            (m, m["roofline"]) for m in models if m.get("roofline")
        ]
        yield gauge(
            f"{ns}_model_roofline_info",
            "roofline bound class per model from XLA-measured "
            "flops/bytes (info gauge: compute/bandwidth)",
            0,
            labels=["model", "version", "bound"],
            samples=[
                ([m["model"], m["version"], r["bound"]], 1)
                for m, r in roofline_rows
            ],
        )
        yield gauge(
            f"{ns}_model_arithmetic_intensity",
            "measured flops per HBM byte of one launch "
            "(XLA cost model at the serving batch)",
            0,
            labels=["model", "version"],
            samples=[
                ([m["model"], m["version"]], r["intensity"])
                for m, r in roofline_rows
                if r["intensity"] == r["intensity"]
                and r["intensity"] not in (float("inf"),)
            ],
        )
        yield gauge(
            f"{ns}_model_attainable_fps",
            "roofline-ceiling frames/s at the measured batch (the "
            "honest headroom next to the served rate)",
            0,
            labels=["model", "version"],
            samples=[
                ([m["model"], m["version"]], r["attainable_fps"])
                for m, r in roofline_rows
            ],
        )
        hist_stats = snap.get("history") or {}
        yield gauge(
            f"{ns}_history_buffered",
            "metric-history snapshots buffered in the ring",
            hist_stats.get("buffered", 0),
        )

        # continuous quality plane (ISSUE 17): per model x served
        # variant rolling-window accuracy vs the f32 shadow reference,
        # the shadow sidecar's lag/drop accounting, and the canary
        # lifecycle. Own tpu_quality namespace (not ns-prefixed): the
        # accuracy column next to every capacity family.
        q = snap.get("quality") or {}
        q_pairs = q.get("pairs") or {}

        def pair_window_samples(field):
            out = []
            for key in sorted(q_pairs):
                last = q_pairs[key].get("last")
                if last is not None and field in last:
                    out.append((key.split("|", 1), last[field]))
            return out

        for field, doc in (
            ("map50", "rolling-window online mAP@0.5 of the served "
                      "variant scored against the shadow f32 reference "
                      "as pseudo-GT (0.995 = parity ceiling)"),
            ("map", "rolling-window online mAP@[.5:.95] vs the shadow "
                    "reference"),
            ("velocity_mae", "mean |velocity| error of matched "
                             "detections vs the shadow reference "
                             "(CenterPoint velocity head; 0 on 2D)"),
            ("id_switch_rate", "excess track births per frame of the "
                               "primary tracking stream vs the shadow "
                               "reference stream (ops/tracking "
                               "reference stepping)"),
        ):
            yield gauge(
                f"tpu_quality_{field}", doc, 0,
                labels=["model", "variant"],
                samples=pair_window_samples(field),
            )
        yield counter(
            "tpu_quality_scored_frames_total",
            "sampled frames scored against the shadow reference",
            0,
            labels=["model", "variant"],
            samples=[
                (key.split("|", 1), q_pairs[key].get("scored_frames", 0))
                for key in sorted(q_pairs)
            ],
        )
        yield gauge(
            "tpu_quality_shadow_lag_seconds",
            "lag between a sampled request being served and its shadow "
            "score landing (last scored frame)",
            0,
            labels=["model", "variant"],
            samples=[
                (key.split("|", 1), q_pairs[key].get("last_lag_s", 0.0))
                for key in sorted(q_pairs)
            ],
        )
        mirror = q.get("mirror") or {}
        yield counter(
            "tpu_quality_shadow_dropped_total",
            "sampled frames dropped because the shadow queue was full "
            "(the sidecar sheds itself, never the serving path)",
            mirror.get("dropped", 0),
        )
        canary = q.get("canary") or {}
        canary_models = canary.get("models") or {}
        yield gauge(
            "tpu_quality_canary_fraction",
            "fraction of the primary's traffic hash-sliced to the "
            "canary variant (1.0 = promoted, 0.0 = rolled back)",
            0,
            labels=["model", "variant"],
            samples=[
                ([m, c["variant"]], c["fraction"])
                for m, c in sorted(canary_models.items())
            ],
        )
        yield gauge(
            "tpu_quality_canary_info",
            "canary lifecycle state per model (info gauge: "
            "canary/promoted/rolled_back)",
            0,
            labels=["model", "variant", "state"],
            samples=[
                ([m, c["variant"], c["state"]], 1)
                for m, c in sorted(canary_models.items())
            ],
        )
        yield counter(
            "tpu_quality_promotions_total",
            "canary variants promoted to full traffic after N clean "
            "quality windows",
            canary.get("promotions", 0),
        )
        yield counter(
            "tpu_quality_rollbacks_total",
            "canary variants auto-rolled-back on a quality-budget "
            "violation (f32 re-pinned; exemplar trace ids in the log)",
            canary.get("rollbacks", 0),
        )

        # host-transport plane: negotiated transport per request, the
        # wire-vs-shm payload byte split, and the multi-frame stream
        # group-size distribution
        tp = snap.get("transport") or {}
        tp_requests = tp.get("requests") or {}
        yield gauge(
            f"{ns}_transport_info",
            "transports observed carrying inference requests "
            "(grpc/uds/shm/uds+shm; info gauge, 1 per observed label)",
            0,
            labels=["transport"],
            samples=[([t], 1) for t in sorted(tp_requests)],
        )
        yield counter(
            f"{ns}_transport_requests_total",
            "inference requests per negotiated transport",
            0,
            labels=["transport"],
            samples=[([t], n) for t, n in sorted(tp_requests.items())],
        )
        yield counter(
            f"{ns}_wire_bytes_total",
            "input payload bytes that travelled as gRPC raw content",
            tp.get("wire_bytes", 0),
        )
        yield counter(
            f"{ns}_shm_bytes_total",
            "input payload bytes that travelled through shared memory",
            tp.get("shm_bytes", 0),
        )
        groups = {
            int(k): v for k, v in (tp.get("stream_groups") or {}).items()
        }
        group_hist = HistogramMetricFamily(
            f"{ns}_stream_group_size",
            "frames per packed multi-frame stream message",
            labels=[],
        )
        cum, cum_buckets = 0, []
        for bound in (1, 2, 4, 8, 16, 32, 64):
            cum += sum(v for k, v in groups.items() if bound / 2 < k <= bound)
            cum_buckets.append((repr(float(bound)), cum))
        cum_buckets.append(("+Inf", sum(groups.values())))
        group_hist.add_metric(
            [], cum_buckets, float(sum(k * v for k, v in groups.items()))
        )
        yield group_hist

        # device HBM (absent on backends without memory_stats)
        if snap["memory"]:
            fam = GaugeMetricFamily(
                f"{ns}_device_hbm_bytes",
                "per-device memory_stats() bytes",
                labels=["device", "kind"],
            )
            for dev, stats in snap["memory"].items():
                for kind in _HBM_KINDS:
                    if kind in stats:
                        fam.add_metric([dev, kind], stats[kind])
            yield fam
            # per-device occupancy: the mesh-serving balance check — on
            # a healthy data-parallel channel every device sits at the
            # same ratio (params replicated + 1/N of the batch)
            occ = GaugeMetricFamily(
                f"{ns}_device_hbm_occupancy_ratio",
                "per-device bytes_in_use / bytes_limit",
                labels=["device"],
            )
            for dev, stats in snap["memory"].items():
                if stats.get("bytes_limit"):
                    occ.add_metric(
                        [dev],
                        stats.get("bytes_in_use", 0) / stats["bytes_limit"],
                    )
            yield occ

    def close(self) -> None:
        if self._registry is not None:
            try:
                self._registry.unregister(self)
            except KeyError:
                pass
            self._registry = None
