"""Roofline classification (obs/roofline): the math, the measured-cost
capture at launcher-build time, and the channel integration that records
XLA's flops/bytes into ``spec.extra`` on the first launch.
"""

import types

import numpy as np
import pytest

from triton_client_tpu.obs.roofline import (
    DEVICE_PEAKS,
    V5E,
    classify,
    hlo_module_for,
    launcher_name,
    measure_launch_cost,
    model_row,
    name_launcher,
    peak_bytes_per_s,
    peak_flops,
    record_launch_cost,
)

# the tests below state the device they compute a ceiling for: the live
# one is a CPU, which has no peaks (test_unlisted_device_gets_no_figure)
V5E_PEAK_FLOPS = DEVICE_PEAKS[V5E]["flops"]
V5E_PEAK_HBM_BPS = DEVICE_PEAKS[V5E]["hbm_bytes_per_s"]


def _model(name="m", version="1", extra=None):
    return types.SimpleNamespace(
        spec=types.SimpleNamespace(name=name, version=version,
                                   extra=dict(extra or {}))
    )


# -- classification math ------------------------------------------------------


def test_compute_bound_when_intensity_above_knee():
    # I = 1e12/1e9 = 1000 flop/B >> knee (~240): the MXU ceiling binds
    row = classify(1e12, 1e9, precision="bf16", batch=8, device_kind=V5E)
    assert row.bound == "compute"
    assert row.intensity == pytest.approx(1000.0)
    assert row.knee == pytest.approx(V5E_PEAK_FLOPS / V5E_PEAK_HBM_BPS)
    assert row.attainable_calls_per_s == pytest.approx(V5E_PEAK_FLOPS / 1e12)
    assert row.attainable_fps == pytest.approx(row.attainable_calls_per_s * 8)


def test_bandwidth_bound_when_intensity_below_knee():
    # I = 1 flop/B << knee: HBM binds; ceiling = peak_bw / bytes
    row = classify(1e9, 1e9, precision="f32", batch=1, device_kind=V5E)
    assert row.bound == "bandwidth"
    assert row.attainable_calls_per_s == pytest.approx(V5E_PEAK_HBM_BPS / 1e9)


def test_int8_activations_double_the_flops_ceiling():
    f32 = classify(1e12, 1e6, precision="f32", device_kind=V5E)
    int8 = classify(1e12, 1e6, precision="int8", device_kind=V5E)
    assert peak_flops("int8", V5E) == 2 * V5E_PEAK_FLOPS
    assert int8.attainable_calls_per_s == pytest.approx(
        2 * f32.attainable_calls_per_s
    )
    # int8-WEIGHT policies run the MXU at the bf16 MAC rate
    assert classify(
        1e12, 1e6, precision="int8w", device_kind=V5E
    ).attainable_calls_per_s == pytest.approx(f32.attainable_calls_per_s)


def test_zero_cost_is_unknown_and_zero_bytes_is_compute():
    empty = classify(0, 0, device_kind=V5E)
    assert empty.bound == "unknown"
    assert empty.attainable_fps == 0.0
    no_bytes = classify(1e9, 0, device_kind=V5E)
    assert no_bytes.bound == "compute"
    assert no_bytes.intensity == float("inf")


def test_as_dict_round_trips_the_row():
    d = classify(
        2e12, 1e9, precision="bf16", batch=4, device_kind=V5E
    ).as_dict()
    assert d["bound"] == "compute"
    assert set(d) == {
        "flops", "bytes", "precision", "batch", "intensity", "knee",
        "bound", "attainable_calls_per_s", "attainable_fps",
    }


# -- launcher naming ----------------------------------------------------------


def test_launcher_name_sanitizes_and_module_prefix():
    m = _model(name="yolo-v5n", version="1.0")
    assert launcher_name(m) == "mdl_yolo_v5n_1_0"
    assert hlo_module_for(m) == "jit_mdl_yolo_v5n_1_0"


def test_name_launcher_stamps_the_module_name():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    m = _model(name="det2d", version="1")
    fn = name_launcher(lambda x: x * 2.0, m)
    assert fn.__name__ == "mdl_det2d_1"
    jitted = jax.jit(fn)
    lowered = jitted.lower(jnp.ones((2,), jnp.float32))
    # XLA takes the module name from the wrapped function's __name__
    assert "mdl_det2d_1" in lowered.as_text()[:2000]


# -- measured cost capture ----------------------------------------------------


def test_measure_and_record_launch_cost_with_real_jit():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64), jnp.float32)
    measured = measure_launch_cost(f, x, batch_rows=64)
    # 64x64x64 matmul: 2*N^3 = 524288 flops by XLA's count
    assert measured["flops"] == pytest.approx(2 * 64**3, rel=0.5)
    assert measured["bytes"] > 0
    assert measured["batch"] == 64

    m = _model(extra={"flops_per_call": 123.0})
    record_launch_cost(m, f, x, batch_rows=64)
    extra = m.spec.extra
    # the hand-maintained seed survives as the labeled comparison
    # column; the live flops_per_call is now XLA's measured number
    assert extra["analytic_flops_per_call"] == 123.0
    assert extra["flops_per_call"] == extra["measured_flops_per_call"]
    assert extra["measured_flops_per_call"] > 0
    assert extra["measured_bytes_per_call"] > 0
    assert extra["measured_batch"] == 64
    assert extra["hlo_module"] == "jit_mdl_m_1"


def test_model_row_reports_attained_fraction():
    extra = {
        "measured_flops_per_call": 1e12,
        "measured_bytes_per_call": 1e9,
        "measured_batch": 8,
        "precision": "bf16",
        "analytic_flops_per_call": 9e11,
    }
    row = model_row(extra, measured_fps=100.0, device_kind=V5E)
    assert row["bound"] == "compute"
    assert row["analytic_flops_per_call"] == 9e11
    assert row["measured_fps"] == 100.0
    assert row["attained_fraction"] == pytest.approx(
        100.0 / row["attainable_fps"]
    )
    assert "measured_fps" not in model_row(extra, device_kind=V5E)


def test_unlisted_device_gets_no_figure():
    """A device that is not in DEVICE_PEAKS (the CPU these tests run
    on, by default) yields the measured intensity and nothing that
    needs a peak — never v5e's ceiling under another device's name."""
    assert "cpu" not in DEVICE_PEAKS
    assert peak_flops("bf16", "cpu") is None
    assert peak_bytes_per_s("cpu") is None
    for row in (
        classify(1e12, 1e9, precision="bf16", batch=8, device_kind="cpu"),
        classify(1e12, 1e9, precision="bf16", batch=8),  # the live device
    ):
        assert row.intensity == pytest.approx(1000.0)
        assert (row.bound, row.knee, row.attainable_fps) == ("unknown", 0.0, 0.0)
    assert DEVICE_PEAKS[V5E]["source"]


# -- channel integration ------------------------------------------------------


def test_first_launch_records_measured_cost_into_spec_extra():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository

    def device_fn(inputs):
        x = inputs["x"]
        return {"y": jnp.tanh(x @ jnp.ones((4, 4), jnp.float32))}

    spec = ModelSpec(
        name="costed", version="1",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
    )
    spec.extra["flops_per_call"] = 777.0
    repo = ModelRepository()
    repo.register(
        spec, lambda inputs: {"y": np.asarray(inputs["x"])},
        device_fn=device_fn,
    )
    chan = TPUChannel(repo)
    try:
        x = np.ones((2, 4), np.float32)
        chan.do_inference(InferRequest("costed", {"x": x}))
        extra = repo.get("costed", "1").spec.extra
        assert extra["measured_flops_per_call"] > 0
        assert extra["measured_bytes_per_call"] > 0
        assert extra["measured_batch"] == 2
        assert extra["analytic_flops_per_call"] == 777.0
        assert extra["flops_per_call"] == extra["measured_flops_per_call"]
        assert extra["hlo_module"] == "jit_mdl_costed_1"
    finally:
        getattr(chan, "close", lambda: None)()


def test_collector_model_rows_gain_roofline_after_measurement(monkeypatch):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    # the collector reads the LIVE device's peaks, and this CPU has
    # none: list it (with v5e's numbers) so the row gains a bound
    monkeypatch.setitem(
        DEVICE_PEAKS, jax.devices()[0].device_kind, DEVICE_PEAKS[V5E]
    )

    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.obs.collector import RuntimeCollector
    from triton_client_tpu.runtime.repository import ModelRepository

    spec = ModelSpec(
        name="roof", version="1",
        inputs=(TensorSpec("x", (-1, 8), "FP32"),),
        outputs=(TensorSpec("y", (-1, 8), "FP32"),),
    )
    repo = ModelRepository()
    repo.register(
        spec, lambda inputs: {"y": np.asarray(inputs["x"])},
        device_fn=lambda inputs: {
            "y": inputs["x"] @ jnp.ones((8, 8), jnp.float32)
        },
    )
    chan = TPUChannel(repo)
    collector = RuntimeCollector(repository=repo)
    try:
        rows = {m["model"]: m for m in collector.snapshot()["models"]}
        assert "roofline" not in rows["roof"]  # nothing measured yet
        chan.do_inference(
            InferRequest("roof", {"x": np.ones((2, 8), np.float32)})
        )
        rows = {m["model"]: m for m in collector.snapshot()["models"]}
        roof = rows["roof"]["roofline"]
        assert roof["bound"] in ("compute", "bandwidth")
        assert roof["attainable_fps"] > 0
        # attribution map now knows this model's HLO module
        assert collector.hlo_modules() == {"jit_mdl_roof_1": "roof"}
    finally:
        collector.close()
        getattr(chan, "close", lambda: None)()
