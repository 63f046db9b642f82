"""Device-time ledger: per-model×tenant device-seconds + live MFU.

ROADMAP items 1/2/4 all argue about device *time* — how much of it the
chips spend executing vs idle, and which tenant consumed it — yet until
this ledger nothing accumulated it: the ``device_execute`` span lands
in each request's trace and histogram bucket and is forgotten. The
ledger is the standing account: every launch's device-execute window
(``t_launched -> block_until_ready``, the same interval the trace
records, so ledger totals reconcile with the histogram sum by
construction) accrues into

  * cumulative per-``model|tenant`` device-seconds
    (``tpu_serving_device_seconds_total{model,tenant}``),
  * a rolling-window device-utilization ratio — busy device-seconds
    over elapsed wall × device count
    (``tpu_serving_device_utilization_ratio``),
  * live per-model MFU — achieved flops over the window against the
    precision policy's peak (``tpu_serving_mfu{model}``), from the
    model's own flops and the per-device peak
    (``spec.extra["flops_per_call"]`` + ``extra["precision"]``).

``record`` runs on the resolve() readback path (executor threads,
once per launch) and is rooted in tpulint's HOT_PATH_ROOTS: pure float
and dict work under one short lock, no host syncs.
"""

from __future__ import annotations

import collections
import threading
import time

# the single home of the per-chip peaks, so served MFU and the
# roofline ceiling divide by one denominator
from triton_client_tpu.obs.roofline import peak_flops


class DeviceTimeLedger:
    """Accumulates per-launch device-execute durations.

    ``tenants``: a ``runtime.lifecycle.TenantTable`` (or anything
    answering ``tenant_of(model) -> str``); models outside any tenant
    land under ``"default"``. ``devices``: chips the busy ratio is
    normalized over. ``window_s``: rolling window for the LIVE
    utilization/MFU gauges (cumulative counters never reset).

    Flops metadata is learned lazily per model from the ``spec_extra``
    mapping the channel passes on each record (first one wins):
    ``flops_per_call`` — analytic flops of one launch at its serving
    batch — and ``precision`` — the policy name ``peak_flops`` scales
    the device's peak by. Models without flops metadata still account
    device-seconds; their MFU is simply not reported — and neither is
    anyone's on a ``device_kind`` (default: the live device's) that
    ``obs.roofline.DEVICE_PEAKS`` does not list.
    """

    def __init__(
        self,
        tenants=None,
        devices: int = 1,
        window_s: float = 60.0,
        buckets: int = 12,
        device_kind: str | None = None,
    ) -> None:
        self._tenants = tenants
        self._device_kind = device_kind
        self._devices = max(1, int(devices))
        self._window_s = float(window_s)
        self._n_buckets = max(2, int(buckets))
        self._bucket_w = self._window_s / self._n_buckets
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        # cumulative account (the Prometheus counters)
        self._device_s: collections.Counter = collections.Counter()
        self._launches: collections.Counter = collections.Counter()
        self._total_device_s = 0.0
        # per-model flops metadata learned from spec.extra
        self._flops_per_call: dict[str, float] = {}
        self._peak_flops: dict[str, float] = {}
        # rolling window: ring of (bucket_index, {model: [dev_s, flops]})
        self._ring: collections.deque = collections.deque(
            maxlen=self._n_buckets
        )
        self._tenant_cache: dict[str, str] = {}

    # -- recording ------------------------------------------------------------

    def tenant_of(self, model: str) -> str:
        tenant = self._tenant_cache.get(model)
        if tenant is None:
            tenant = "default"
            if self._tenants is not None:
                try:
                    tenant = self._tenants.tenant_of(model) or "default"
                except Exception:
                    tenant = "default"
            self._tenant_cache[model] = tenant
        return tenant

    def record(
        self, model: str, duration_s: float, spec_extra=None, tenant=None
    ) -> None:
        """Account one launch's device-execute window. Called from the
        channel's resolve() with the SAME (t_launched, t_ready)
        interval the trace's device_execute span gets — the two
        measurements cannot drift.

        ``tenant`` overrides the table lookup — streaming-session
        launches pass ``stream:<sequence_id>`` so the tenant axis
        answers "device seconds per live stream" directly
        (runtime/sessions.py)."""
        if duration_s < 0:
            duration_s = 0.0
        if tenant is None:
            tenant = self.tenant_of(model)
        flops = self._flops_per_call.get(model)
        if flops is None and spec_extra:
            try:
                flops = float(spec_extra.get("flops_per_call") or 0.0)
            except (TypeError, ValueError):
                flops = 0.0
            self._flops_per_call[model] = flops
            precision = str(spec_extra.get("precision") or "f32")
            self._peak_flops[model] = (
                peak_flops(precision, self._device_kind) or 0.0
            )
        now = time.perf_counter()
        idx = int(now / self._bucket_w)
        with self._lock:
            self._device_s[f"{model}|{tenant}"] += duration_s
            self._launches[model] += 1
            self._total_device_s += duration_s
            if not self._ring or self._ring[-1][0] != idx:
                self._ring.append((idx, {}))
            per_model = self._ring[-1][1]
            cell = per_model.get(model)
            if cell is None:
                cell = per_model[model] = [0.0, 0.0]
            cell[0] += duration_s
            cell[1] += flops or 0.0

    # -- reading --------------------------------------------------------------

    def _window_totals(self, now: float):
        """(elapsed_s, busy_s, {model: [dev_s, flops]}) over the live
        window — caller holds the lock."""
        idx_now = int(now / self._bucket_w)
        floor = idx_now - self._n_buckets + 1
        busy = 0.0
        per_model: dict[str, list[float]] = {}
        for idx, models in self._ring:
            if idx < floor:
                continue
            for model, (dev_s, flops) in models.items():
                cell = per_model.get(model)
                if cell is None:
                    cell = per_model[model] = [0.0, 0.0]
                cell[0] += dev_s
                cell[1] += flops
                busy += dev_s
        elapsed = min(now - self._t0, self._window_s)
        return max(elapsed, 1e-9), busy, per_model

    def utilization(self) -> float:
        """Rolling-window busy fraction: device-seconds executed over
        elapsed wall × devices."""
        now = time.perf_counter()
        with self._lock:
            elapsed, busy, _ = self._window_totals(now)
        return min(1.0, busy / (elapsed * self._devices))

    def mfu(self) -> dict[str, float]:
        """Live per-model MFU over the rolling window (only models
        with flops metadata)."""
        now = time.perf_counter()
        with self._lock:
            elapsed, _, per_model = self._window_totals(now)
            peaks = dict(self._peak_flops)
        out = {}
        for model, (_dev_s, flops) in per_model.items():
            peak = peaks.get(model) or 0.0
            if flops > 0 and peak > 0:
                out[model] = flops / elapsed / (peak * self._devices)
        return out

    def device_seconds(self) -> dict[str, float]:
        """Cumulative ``{"model|tenant": seconds}``."""
        with self._lock:
            return dict(self._device_s)

    def snapshot(self) -> dict:
        now = time.perf_counter()
        with self._lock:
            elapsed, busy, per_model = self._window_totals(now)
            device_s = dict(self._device_s)
            launches = dict(self._launches)
            total = self._total_device_s
            peaks = dict(self._peak_flops)
            uptime = now - self._t0
        mfu = {
            model: flops / elapsed / ((peaks.get(model) or 0.0) * self._devices)
            for model, (_d, flops) in per_model.items()
            if flops > 0 and peaks.get(model)
        }
        return {
            "devices": self._devices,
            "uptime_s": uptime,
            "device_seconds": device_s,
            "launches": launches,
            "total_device_seconds": total,
            "busy_fraction": min(1.0, total / (max(uptime, 1e-9) * self._devices)),
            "window": {
                "seconds": elapsed,
                "device_seconds": busy,
                "utilization": min(1.0, busy / (elapsed * self._devices)),
                "mfu": mfu,
            },
        }
