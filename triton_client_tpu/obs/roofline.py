"""Roofline attribution: measured flops/bytes -> bound class + ceiling.

ROADMAP item 2 says the chips are almost idle (MFU 1.4-7%) but nothing
in the stack can say *why*: is a model compute-bound (fuse harder, use
the MXU at int8) or bandwidth-bound (keep intermediates in VMEM, shrink
the working set)? The roofline model answers with two numbers per
model:

  arithmetic intensity  I = flops / bytes            (flop per HBM byte)
  machine knee          K = peak_flops / peak_bw     (flop per byte)

I >= K means the MXU ceiling binds (compute-bound: the attainable rate
is ``peak_flops / flops`` calls/s); I < K means the HBM ceiling binds
(bandwidth-bound: ``peak_bw / bytes`` calls/s). The attainable-fps
ceiling next to the measured fps is the honest headroom statement —
"yolov5n serves 1,685 fps against an 8,900 fps roofline" names the gap
a kernel PR must close.

flops/bytes come MEASURED from XLA's own cost model at launcher-build
time (``jax.stages.Lowered.cost_analysis()`` — no backend compile, a
few ms of tracing the launcher already paid) and are recorded into
``model.spec.extra``:

  measured_flops_per_call / measured_bytes_per_call   XLA cost model
  measured_batch                                      rows they were
                                                      measured at
  flops_per_call                                      overwritten with
                                                      the measured
                                                      value (the ledger
                                                      and MFU gauges
                                                      then use it)
  analytic_flops_per_call                             the previous
                                                      hand-maintained
                                                      seed, kept as a
                                                      labeled
                                                      comparison only
  pallas_kernels                                      Mosaic custom
                                                      calls in the
                                                      lowered launcher
                                                      (0 = none, or
                                                      interpreted)
  hlo_module                                          the jit module
                                                      name opstats maps
                                                      device ops back
                                                      to this model by

This module is also the single home of the per-chip peaks, ONE table
keyed by the ``device_kind`` jax reports: served MFU
(obs/device_time.py), bench MFU and the roofline all divide by the same
denominator, and a device that is not in the table gets NO figure — the
MFU gauge stays absent and the ``roofline`` CLI says so — instead of
another chip's peak.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

#: the ``device_kind`` jax reports for one TPU v5e chip
V5E = "TPU v5 lite"

#: Per-chip peaks keyed by ``jax.devices()[0].device_kind``. ``flops``
#: is the bf16 MXU rate; the MXU runs f32 inputs at the bf16 MAC rate
#: under jax's default precision, so f32/bf16/int8-weight policies all
#: see the same ceiling and int8 activations double it
#: (_POLICY_MXU_RATE). HBM bandwidth is precision-independent — the
#: bytes themselves shrink with narrower dtypes, which is already in
#: the measured byte count.
DEVICE_PEAKS = {
    V5E: {
        "flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
        "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}

_POLICY_MXU_RATE = {"f32": 1.0, "bf16": 1.0, "int8w": 1.0, "int8": 2.0}


def device_info() -> dict:
    """The device this process computes on, as jax reports it —
    ``serve`` prints it first, ``/snapshot["device"]`` carries it and
    ``chip_smoke.py`` ends with it, so no reading can be mistaken for
    another device's."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def _peaks(device_kind: str | None) -> dict | None:
    if device_kind is None:
        device_kind = device_info()["kind"]
    return DEVICE_PEAKS.get(device_kind)


def peak_flops(precision: str | None, device_kind: str | None = None):
    """Peak flop/s of ``device_kind`` (default: the live device) under
    a precision policy; ``None`` for a device not in DEVICE_PEAKS."""
    peaks = _peaks(device_kind)
    if peaks is None:
        return None
    return peaks["flops"] * _POLICY_MXU_RATE.get(str(precision or "f32"), 1.0)


def peak_bytes_per_s(device_kind: str | None = None):
    """Peak HBM bytes/s of ``device_kind`` (default: the live device);
    ``None`` for a device not in DEVICE_PEAKS."""
    peaks = _peaks(device_kind)
    return None if peaks is None else peaks["hbm_bytes_per_s"]


@dataclass
class RooflineRow:
    """One model's (or op's) position against the machine roofline."""

    flops: float
    bytes: float
    precision: str = "f32"
    batch: int = 1
    #: derived — knee/bound/attainable stay at their defaults for a
    #: device that is not in DEVICE_PEAKS
    intensity: float = 0.0
    knee: float = 0.0
    bound: str = "unknown"
    attainable_calls_per_s: float = 0.0
    attainable_fps: float = 0.0

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "bytes": self.bytes,
            "precision": self.precision,
            "batch": self.batch,
            "intensity": self.intensity,
            "knee": self.knee,
            "bound": self.bound,
            "attainable_calls_per_s": self.attainable_calls_per_s,
            "attainable_fps": self.attainable_fps,
        }


def classify(
    flops: float,
    bytes_accessed: float,
    precision: str = "f32",
    batch: int = 1,
    device_kind: str | None = None,
) -> RooflineRow:
    """Roofline position of one launch: arithmetic intensity against
    the machine knee, the binding ceiling, and the attainable call/fps
    rate if ONLY that ceiling bound (the ideal-overlap upper bound an
    actual serving rate is compared to). ``device_kind`` defaults to
    the live device; one that is not in DEVICE_PEAKS yields the
    intensity alone (``bound == "unknown"``, no knee, no ceiling)."""
    flops = max(0.0, float(flops or 0.0))
    bytes_accessed = max(0.0, float(bytes_accessed or 0.0))
    batch = max(1, int(batch or 1))
    row = RooflineRow(
        flops=flops, bytes=bytes_accessed, precision=str(precision or "f32"),
        batch=batch,
    )
    if flops > 0 or bytes_accessed > 0:
        row.intensity = (
            flops / bytes_accessed if bytes_accessed > 0 else float("inf")
        )
    pf = peak_flops(precision, device_kind)
    pb = peak_bytes_per_s(device_kind)
    if pf is None or pb is None:
        return row
    row.knee = pf / pb
    if flops <= 0 and bytes_accessed <= 0:
        return row
    compute_rate = pf / flops if flops > 0 else float("inf")
    memory_rate = pb / bytes_accessed if bytes_accessed > 0 else float("inf")
    row.bound = "compute" if compute_rate <= memory_rate else "bandwidth"
    row.attainable_calls_per_s = min(compute_rate, memory_rate)
    row.attainable_fps = row.attainable_calls_per_s * batch
    return row


# -- measured cost capture (launcher-build / first-launch time) ---------------


def _cost_dict(cost) -> dict:
    """Normalize jax's cost_analysis return (dict, or list-of-dict on
    some backends) to one flat dict."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def launcher_name(model) -> str:
    """The python-identifier name the channel gives a model's jitted
    launcher, so the HLO module (``jit_<this>``) names the model in
    profiler traces — opstats' primary op->model attribution key."""
    raw = f"mdl_{model.spec.name}_{model.spec.version}"
    return re.sub(r"[^0-9a-zA-Z_]", "_", raw)


def hlo_module_for(model) -> str:
    """The HLO module name xla emits for the named launcher."""
    return "jit_" + launcher_name(model)


def name_launcher(fn, model):
    """Stamp a launcher callable with the model's launcher name BEFORE
    ``jax.jit`` wraps it — jit takes the module name from the wrapped
    function's ``__name__``."""
    name = launcher_name(model)
    try:
        fn.__name__ = name
        fn.__qualname__ = name
    except (AttributeError, TypeError):
        pass
    return fn


def measure_launch_cost(launcher, *args, batch_rows: int = 1) -> dict:
    """Measured flops/bytes of one launcher call at the given args'
    shapes, via XLA's cost model on the LOWERED module — tracing only,
    no backend compile, so calling this next to the first launch adds
    milliseconds to a path that is about to pay a full compile anyway.

    Returns ``{"flops", "bytes", "batch", "pallas_kernels"}`` (zeros
    when the cost model reports nothing). ``pallas_kernels`` counts the
    Mosaic custom calls in the lowered module: a Pallas kernel lowers
    to one on a TPU and to plain ops under the interpreter, so the
    count says whether the launcher the channel is about to compile
    really holds its fused stages as kernels."""
    lowered = launcher.lower(*args)
    cost = _cost_dict(lowered.cost_analysis())
    return {
        "flops": float(cost.get("flops", 0.0) or 0.0),
        "bytes": float(cost.get("bytes accessed", 0.0) or 0.0),
        "batch": max(1, int(batch_rows or 1)),
        "pallas_kernels": lowered.as_text().count("tpu_custom_call"),
    }


def record_launch_cost(model, launcher, *args, batch_rows: int = 1) -> dict:
    """Measure one launcher call and record the result into
    ``model.spec.extra`` (see the module docstring for the keys).
    The previous hand-maintained ``flops_per_call`` seed — if any — is
    preserved as ``analytic_flops_per_call`` and then OVERWRITTEN with
    the measured value, so every downstream flops consumer (the
    DeviceTimeLedger's MFU, the collector's model rows, bench) divides
    by what XLA actually scheduled rather than what a human last
    derived."""
    measured = measure_launch_cost(launcher, *args, batch_rows=batch_rows)
    extra = model.spec.extra
    seed = extra.get("flops_per_call")
    if seed is not None and "analytic_flops_per_call" not in extra:
        extra["analytic_flops_per_call"] = seed
    if measured["flops"] > 0:
        extra["flops_per_call"] = measured["flops"]
    extra["measured_flops_per_call"] = measured["flops"]
    extra["measured_bytes_per_call"] = measured["bytes"]
    extra["measured_batch"] = measured["batch"]
    extra["pallas_kernels"] = measured["pallas_kernels"]
    extra.setdefault("hlo_module", hlo_module_for(model))
    return measured


def model_row(
    extra: dict,
    measured_fps: float | None = None,
    device_kind: str | None = None,
) -> dict:
    """Roofline report row from a model's ``spec.extra`` (the shape the
    collector's ``models`` snapshot section and the ``roofline`` CLI
    share). ``measured_fps`` — when known — is reported next to the
    attainable ceiling as ``attained_fraction``."""
    flops = float(extra.get("measured_flops_per_call") or 0.0)
    bytes_ = float(extra.get("measured_bytes_per_call") or 0.0)
    batch = int(extra.get("measured_batch") or 1)
    precision = str(extra.get("precision") or "f32")
    row = classify(flops, bytes_, precision, batch, device_kind).as_dict()
    analytic = extra.get("analytic_flops_per_call")
    if analytic is not None:
        row["analytic_flops_per_call"] = float(analytic)
    if measured_fps is not None and row["attainable_fps"] > 0:
        row["measured_fps"] = float(measured_fps)
        row["attained_fraction"] = float(measured_fps) / row["attainable_fps"]
    return row
