"""Continuous quality plane (ISSUE 17): deterministic shadow sampling,
rolling-window online scoring, precision-budget gating, and the
canary promote/rollback lifecycle.

Covers the PR's acceptance contract:
  * ``sample_decision``/``slice_decision`` are pure functions of the
    trace id — every process reaches the same verdict with no shared
    state — and the two decisions hash in independent domains;
  * ``QualityScorer`` windows score primary-vs-shadow pairs with the
    offline COCO math (2D packed detections and 3D pred_boxes with
    velocity MAE), roll at ``window_frames``, and persist tracker
    identity across the window boundary;
  * ``QualityGate`` floors derive from the precision parity budgets
    (runtime/precision.py MAP_BUDGETS) and empty windows never gate;
  * ``CanaryController`` promotes after N consecutive clean windows,
    rolls back on the first violation (f32 re-pinned, exemplars kept,
    optional TPU_FUSED_KERNELS=0), and counts its slice exactly;
  * the ``quality_corrupt`` fault point drives an in-process rollback
    with the corrupting variant ejected before serving 1% of traffic;
  * the folded legacy eval Summaries and the ``tpu_quality_*``
    families serve the SAME numbers from one registry (satellite:
    retiring the standalone port-7658 exporter);
  * the slow E2E drive: a live server + quality plane promotes a clean
    int8 variant to full traffic and the promoted/rolled-back state is
    visible on a real /metrics scrape and /snapshot.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from triton_client_tpu.eval.quality_plane import (
    AP_CEILING,
    CanaryController,
    QualityGate,
    QualityPlane,
    QualityScorer,
    infer_primary,
    parse_canary_spec,
    precision_of_name,
)
from triton_client_tpu.eval.shadow import (
    ShadowMirror,
    corrupt_detections,
    sample_decision,
    slice_decision,
)
from triton_client_tpu.runtime.faults import (
    FaultPlan,
    FaultRule,
    install_fault_plan,
)
from triton_client_tpu.runtime.precision import MAP_BUDGETS


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    prev = install_fault_plan(None)
    yield
    install_fault_plan(prev)


# -- helpers ------------------------------------------------------------------

# a fixed, self-consistent detection frame: scoring it against itself
# is a perfect detector (map50 == AP_CEILING)
_DETS = np.array(
    [
        [10.0, 10.0, 60.0, 60.0, 0.9, 0.0],
        [100.0, 20.0, 180.0, 90.0, 0.8, 1.0],
        [200.0, 200.0, 260.0, 250.0, 0.7, 2.0],
    ],
    np.float32,
)
_VALID = np.ones(3, bool)


def _outputs(shift=0.0):
    det = _DETS.copy()
    det[:, :4] += shift
    return {"detections": det, "valid": _VALID.copy()}


def _rows3d(vel=0.0):
    # 9-col pred_boxes: x y z dx dy dz heading vx vy
    boxes = np.array(
        [
            [1.0, 2.0, 0.5, 4.0, 2.0, 1.5, 0.1, 1.0 + vel, 0.0],
            [10.0, -3.0, 0.4, 4.2, 1.9, 1.6, 1.2, 0.0, 2.0 + vel],
        ],
        np.float32,
    )
    return {
        "pred_boxes": boxes,
        "pred_scores": np.array([0.9, 0.8], np.float32),
        "pred_labels": np.array([1, 2], np.int32),
    }


def _det_repo(names=("qp_det", "qp_det_int8")):
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository

    repo = ModelRepository()
    for name in names:
        spec = ModelSpec(
            name=name,
            version="1",
            inputs=(TensorSpec("x", (-1, 4), "FP32"),),
            outputs=(
                TensorSpec("detections", (-1, 6), "FP32"),
                TensorSpec("valid", (-1,), "BOOL"),
            ),
        )
        repo.register(
            spec,
            lambda inputs: {
                "detections": _DETS.copy(),
                "valid": _VALID.copy(),
            },
        )
    return repo


def _serving_stack(repo, **server_kw):
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
    from triton_client_tpu.runtime.server import InferenceServer

    chan = ContinuousBatchingChannel(
        TPUChannel(repo), max_batch=4
    )
    server = InferenceServer(
        repo, chan, address="127.0.0.1:0", metrics_port="auto", **server_kw
    )
    server.start()
    return chan, server


class _RefChannel:
    """Fake shadow-dispatch handle: always answers with the clean
    reference outputs (and records what it was asked)."""

    def __init__(self, outputs=None):
        self.outputs = outputs or _outputs()
        self.requests = []
        self._lock = threading.Lock()

    def do_inference(self, request):
        from triton_client_tpu.channel.base import InferResponse

        with self._lock:
            self.requests.append(request.model_name)
        return InferResponse(
            model_name=request.model_name,
            model_version="1",
            outputs={k: np.copy(v) for k, v in self.outputs.items()},
        )


# -- deterministic sampling ---------------------------------------------------


def test_sample_decision_edges_and_determinism():
    assert not sample_decision("t-1", 0.0)
    assert not sample_decision("", 0.5)  # id-less traffic never sampled
    assert sample_decision("t-1", 1.0)
    # pure function: same verdict on every call, in every process
    verdicts = [sample_decision("trace-abc", 0.3) for _ in range(10)]
    assert len(set(verdicts)) == 1
    # rate is honoured statistically over many ids
    ids = [f"trace-{i}" for i in range(4000)]
    hits = sum(sample_decision(t, 0.25) for t in ids)
    assert 0.20 < hits / len(ids) < 0.30
    # monotone in rate: a sampled id stays sampled at any higher rate
    kept = [t for t in ids[:500] if sample_decision(t, 0.1)]
    assert all(sample_decision(t, 0.5) for t in kept)


def test_slice_decision_independent_domain():
    ids = [f"trace-{i}" for i in range(4000)]
    sampled = {t for t in ids if sample_decision(t, 0.5)}
    sliced = {t for t in ids if slice_decision(t, 0.5)}
    assert sampled != sliced  # different hash domains
    # independence: P(sampled & sliced) ~ P(sampled) * P(sliced)
    both = len(sampled & sliced) / len(ids)
    assert 0.17 < both < 0.33
    assert not slice_decision("", 0.9)
    assert slice_decision("t", 1.0)


def test_corrupt_detections_deterministic_and_gross():
    out = _outputs()
    a = corrupt_detections(out, "trace-7")
    b = corrupt_detections(out, "trace-7")
    np.testing.assert_array_equal(a["detections"], b["detections"])
    # the perturbation is unmistakably out of any precision budget
    shift = np.abs(a["detections"][:, :4] - out["detections"][:, :4])
    assert shift.min() >= 50.0
    # the original is never touched
    np.testing.assert_array_equal(out["detections"], _DETS)
    # a different trace id corrupts differently (seeded from the id)
    c = corrupt_detections(out, "trace-8")
    assert not np.array_equal(a["detections"], c["detections"])


# -- rolling-window scoring ---------------------------------------------------


def test_scorer_2d_window_rolls_and_scores_identical_pair():
    windows = []
    scorer = QualityScorer(
        window_frames=4, on_window=lambda m, v, w: windows.append((m, v, w))
    )
    for i in range(4):
        scorer.score_pair(
            "det", "det", _outputs(), _outputs(), 0.001, f"t{i}"
        )
    assert len(windows) == 1
    model, variant, w = windows[0]
    assert (model, variant) == ("det", "det")
    assert w["frames"] == 4
    assert w["map50"] == pytest.approx(AP_CEILING, abs=1e-3)
    assert w["gateable"] is True
    assert w["exemplars"] == ["t0", "t1", "t2", "t3"]
    # window state reset: next window starts counting from zero
    snap = scorer.snapshot()
    assert snap["pairs"]["det|det"]["window_frames"] == 0
    assert snap["pairs"]["det|det"]["scored_frames"] == 4
    assert snap["pairs"]["det|det"]["windows"] == 1


def test_scorer_2d_degraded_primary_scores_low():
    windows = []
    scorer = QualityScorer(
        window_frames=3, on_window=lambda m, v, w: windows.append(w)
    )
    for i in range(3):
        # primary boxes shifted far off the shadow reference
        scorer.score_pair(
            "det", "det_int8", _outputs(shift=80.0), _outputs(), 0.0, f"t{i}"
        )
    assert windows and windows[0]["map50"] < 0.1


def test_scorer_3d_velocity_mae():
    windows = []
    scorer = QualityScorer(
        window_frames=2, on_window=lambda m, v, w: windows.append(w)
    )
    for i in range(2):
        scorer.score_pair(
            "pp", "pp_int8", _rows3d(vel=0.5), _rows3d(vel=0.0), 0.0, f"t{i}"
        )
    assert len(windows) == 1
    w = windows[0]
    # one velocity component off by 0.5 per box: MAE over (vx, vy) is
    # (0.5 + 0.0) / 2
    assert w["velocity_mae"] == pytest.approx(0.25, abs=0.05)
    assert w["map50"] == pytest.approx(AP_CEILING, abs=1e-3)


def test_scorer_accepts_batched_serving_outputs():
    # serving responses carry a unit batch axis — (1, n, 6) detections,
    # (1, n) valid — the exact shapes a live GRPCChannel hands back;
    # scoring must treat them as the offline (n, 6) contract
    windows = []
    scorer = QualityScorer(
        window_frames=2, on_window=lambda m, v, w: windows.append(w)
    )
    batched = {
        "detections": _DETS[None, :, :].copy(),
        "valid": _VALID[None, :].copy(),
    }
    for i in range(2):
        scorer.score_pair("det", "det", batched, batched, 0.0, f"t{i}")
    assert scorer.snapshot()["unscorable"] == 0
    assert windows and windows[0]["map50"] == pytest.approx(
        AP_CEILING, abs=1e-3
    )
    # corrupt_detections handles the batched shape the same way
    corrupted = corrupt_detections(batched, "t0")
    assert corrupted["detections"].shape == _DETS.shape
    assert np.abs(
        corrupted["detections"][:, :4] - _DETS[:, :4]
    ).min() >= 50.0


def test_scorer_unscorable_outputs_counted_not_raised():
    scorer = QualityScorer(window_frames=2)
    scorer.score_pair("m", "m", {"y": np.zeros(3)}, {"y": np.zeros(3)}, 0, "t")
    snap = scorer.snapshot()
    assert snap["unscorable"] == 1
    # the frame never counted toward a window
    assert snap["pairs"]["m|m"]["scored_frames"] == 0
    assert snap["pairs"]["m|m"]["windows"] == 0


# -- gate ---------------------------------------------------------------------


def test_gate_floors_follow_precision_budgets():
    gate = QualityGate(tolerance=0.01)
    for policy, budget in MAP_BUDGETS.items():
        variant = f"det_{policy}" if policy != "f32" else "det"
        assert precision_of_name(variant) == policy
        assert gate.floor_for(variant) == pytest.approx(
            AP_CEILING * (1.0 - budget) - 0.01
        )
    # the ladder is ordered: looser policies get lower floors
    assert (
        gate.floor_for("det")
        > gate.floor_for("det_bf16")
        > gate.floor_for("det_int8w")
        > gate.floor_for("det_int8")
    )


def test_gate_verdicts_and_reasons():
    gate = QualityGate(velocity_budget=0.3, id_switch_budget=0.1)
    base = {
        "map50": 0.99, "velocity_mae": 0.0, "id_switch_rate": 0.0,
        "gateable": True,
    }
    clean, reason = gate.evaluate("det", base)
    assert clean and reason == "clean"
    # f32 has zero budget: anything visibly under the ceiling violates
    clean, reason = gate.evaluate("det", {**base, "map50": 0.5})
    assert not clean and "budget floor" in reason
    # int8's 15% budget tolerates the same drop to 0.8
    clean, _ = gate.evaluate("det_int8", {**base, "map50": 0.85})
    assert clean
    clean, reason = gate.evaluate("det", {**base, "velocity_mae": 0.9})
    assert not clean and "velocity_mae" in reason
    clean, reason = gate.evaluate("det", {**base, "id_switch_rate": 0.5})
    assert not clean and "id_switch_rate" in reason
    # absence of evidence never trips a rollback
    clean, reason = gate.evaluate(
        "det", {"map50": 0.0, "gateable": False}
    )
    assert clean and "not gated" in reason


# -- canary lifecycle ---------------------------------------------------------


def _clean_window():
    return {
        "map50": AP_CEILING, "velocity_mae": 0.0, "id_switch_rate": 0.0,
        "gateable": True, "exemplars": ["e1", "e2"],
    }


def test_canary_fraction_validation():
    c = CanaryController()
    with pytest.raises(ValueError):
        c.set_canary("det", "det_int8", 0.0)
    with pytest.raises(ValueError):
        c.set_canary("det", "det_int8", 1.5)
    c.set_canary("det", "det_int8", 1.0)  # full-slice canary is legal


def test_canary_route_slice_counting():
    c = CanaryController()
    c.set_canary("det", "det_int8", 0.3)
    ids = [f"t{i}" for i in range(2000)]
    got_variant = sum(c.route("det", t) == "det_int8" for t in ids)
    stats = c.stats()["models"]["det"]
    assert stats["served_variant"] == got_variant
    assert stats["served_primary"] == len(ids) - got_variant
    assert 0.25 < got_variant / len(ids) < 0.35
    # unknown models route untouched and uncounted
    assert c.route("other", "t1") == "other"
    # the slice is the hash decision exactly (replayable offline)
    assert all(
        (c.route("det", t) == "det_int8") == slice_decision(t, 0.3)
        for t in ids[:100]
    )


def test_canary_promotes_after_consecutive_clean_windows():
    c = CanaryController(promote_after=3)
    c.set_canary("det", "det_int8", 0.2)
    for _ in range(2):
        c.on_window("det", "det_int8", _clean_window(), True, "clean")
    assert c.stats()["models"]["det"]["state"] == "canary"
    c.on_window("det", "det_int8", _clean_window(), True, "clean")
    s = c.stats()["models"]["det"]
    assert s["state"] == "promoted"
    assert s["fraction"] == 1.0
    assert c.stats()["promotions"] == 1
    # promoted: every request rides the variant
    assert c.route("det", "any") == "det_int8"
    # further windows don't re-promote
    c.on_window("det", "det_int8", _clean_window(), True, "clean")
    assert c.stats()["promotions"] == 1


def test_canary_rollback_on_violation_resets_clean_streak():
    c = CanaryController(promote_after=3)
    c.set_canary("det", "det_int8", 0.2)
    c.on_window("det", "det_int8", _clean_window(), True, "clean")
    bad = {**_clean_window(), "map50": 0.1,
           "exemplars": [f"e{i}" for i in range(9)]}
    c.on_window("det", "det_int8", bad, False, "map50 under floor")
    s = c.stats()["models"]["det"]
    assert s["state"] == "rolled_back"
    assert s["fraction"] == 0.0
    assert s["clean_windows"] == 0
    assert s["reason"] == "map50 under floor"
    assert s["exemplars"] == ["e4", "e5", "e6", "e7", "e8"]  # last 5
    assert c.stats()["rollbacks"] == 1
    # rolled back: all traffic re-pinned to the primary
    assert c.route("det", "t1") == "det"
    # a later clean window does NOT resurrect the ejected variant
    c.on_window("det", "det_int8", _clean_window(), True, "clean")
    assert c.stats()["models"]["det"]["state"] == "rolled_back"
    # verdicts for a different variant never touch this canary
    c.on_window("det", "det_other", bad, False, "x")
    assert c.stats()["rollbacks"] == 1


def test_canary_rollback_pins_fused_kernels_off():
    prev = os.environ.pop("TPU_FUSED_KERNELS", None)
    try:
        c = CanaryController(pin_fused_off=True)
        c.set_canary("det", "det_int8", 0.2)
        c.on_window(
            "det", "det_int8", _clean_window(), False, "budget violated"
        )
        assert os.environ.get("TPU_FUSED_KERNELS") == "0"
    finally:
        if prev is None:
            os.environ.pop("TPU_FUSED_KERNELS", None)
        else:
            os.environ["TPU_FUSED_KERNELS"] = prev


def test_parse_canary_spec_and_infer_primary():
    assert parse_canary_spec("det:det_int8=0.05") == ("det", "det_int8", 0.05)
    assert parse_canary_spec("det_int8=0.25") == (None, "det_int8", 0.25)
    with pytest.raises(ValueError):
        parse_canary_spec("det_int8")  # no fraction
    names = ["det", "det_large", "pp"]
    assert infer_primary("det_int8", names) == "det"
    assert infer_primary("det_large_int8", names) == "det_large"  # longest
    assert infer_primary("pp-bf16", names) == "pp"
    assert infer_primary("det", names) is None  # never its own primary
    assert infer_primary("detint8", names) is None  # needs a separator


# -- shadow mirror ------------------------------------------------------------


def test_mirror_self_scoring_without_channel():
    scored = []
    mirror = ShadowMirror(
        channel=None,
        score=lambda m, v, p, s, lag, t: scored.append((m, v, t)),
    )
    try:
        assert mirror.enqueue("det", "det", {"x": 1}, _outputs(), "t1")
        assert mirror.drain(5.0)
        deadline = time.monotonic() + 5.0
        while not scored and time.monotonic() < deadline:
            time.sleep(0.005)
        assert scored == [("det", "det", "t1")]
        assert mirror.stats()["scored"] == 1
    finally:
        mirror.close()
    # closed mirror refuses new work instead of queueing it forever
    assert not mirror.enqueue("det", "det", {"x": 1}, _outputs(), "t2")


def test_mirror_dispatches_variant_to_reference():
    ref = _RefChannel()
    scored = []
    mirror = ShadowMirror(
        channel=ref,
        score=lambda m, v, p, s, lag, t: scored.append((v, s)),
    )
    try:
        mirror.enqueue("det", "det_int8", {"x": 1}, _outputs(shift=2.0), "t1")
        mirror.drain(5.0)
        deadline = time.monotonic() + 5.0
        while not scored and time.monotonic() < deadline:
            time.sleep(0.005)
        # the shadow ran on the reference (primary) model...
        assert ref.requests == ["det"]
        variant, shadow_outputs = scored[0]
        assert variant == "det_int8"
        # ...and the scorer saw the reference's clean outputs
        np.testing.assert_array_equal(shadow_outputs["detections"], _DETS)
    finally:
        mirror.close()


def test_mirror_full_queue_drops_never_blocks():
    gate = threading.Event()

    def slow_score(*a):
        gate.wait(5.0)

    mirror = ShadowMirror(channel=None, score=slow_score, queue_depth=2)
    try:
        sent = [
            mirror.enqueue("m", "m", {}, _outputs(), f"t{i}")
            for i in range(8)
        ]
        assert not all(sent)  # overflow dropped, not queued
        assert mirror.stats()["dropped"] >= 1
    finally:
        gate.set()
        mirror.close()


# -- the plane end to end (in-process) ---------------------------------------


def test_plane_self_scoring_promotes_canary():
    ref = _RefChannel()
    plane = QualityPlane(
        channel=ref, sample_rate=1.0, window_frames=4, promote_after=2
    )
    try:
        plane.set_canary("det", "det_int8", 0.5)
        for i in range(40):
            tid = f"t{i}"
            served = plane.route("det", tid)
            plane.observe("det", served, tid, {"x": 1}, _outputs())
            if plane.canary.stats()["models"]["det"]["state"] == "promoted":
                break
            plane.drain(5.0)
        plane.drain(5.0)
        time.sleep(0.05)  # worker finishes its in-hand item
        snap = plane.snapshot()
        assert snap["canary"]["models"]["det"]["state"] == "promoted"
        assert snap["canary"]["promotions"] == 1
        assert snap["observed"] >= 8
        assert snap["sampled"] == snap["observed"]  # rate 1.0
        # the int8 slice scored against the f32 reference dispatch
        assert "det|det_int8" in snap["pairs"]
        assert ref.requests and set(ref.requests) == {"det"}
        # history row carries the last finished windows per pair
        row = plane.history_row()
        assert any(k.startswith("det|") for k in row)
        for v in row.values():
            assert set(v) >= {"map50", "map", "velocity_mae"}
    finally:
        plane.close()


def test_plane_quality_corrupt_fault_drives_rollback():
    """Satellite acceptance: a seeded ``quality_corrupt`` fault on the
    variant trips the gate on the variant's FIRST finished window and
    the ejected variant never reaches 1% of total traffic."""
    install_fault_plan(FaultPlan(
        [FaultRule(point="quality_corrupt", model="det_int8",
                   count=100_000)],
        seed=7,
    ))
    ref = _RefChannel()
    plane = QualityPlane(
        channel=ref, sample_rate=1.0, window_frames=4, promote_after=3
    )
    try:
        plane.set_canary("det", "det_int8", 0.05)
        total = 2000
        for i in range(total):
            tid = f"t{i}"
            served = plane.route("det", tid)
            plane.observe("det", served, tid, {"x": 1}, _outputs())
            if i % 50 == 0:
                plane.drain(10.0)
        plane.drain(10.0)
        time.sleep(0.1)
        snap = plane.snapshot()
        c = snap["canary"]["models"]["det"]
        assert c["state"] == "rolled_back"
        assert "budget floor" in c["reason"]
        assert c["exemplars"]  # trace exemplars kept for the postmortem
        assert snap["canary"]["rollbacks"] == 1
        # ejected before serving 1% of traffic
        assert c["served_variant"] / total < 0.01
        assert snap["mirror"]["corrupted"] >= 4
        # the primary's own self-scoring windows stayed clean
        assert snap["canary"]["promotions"] == 0
    finally:
        plane.close()


def test_plane_sample_rate_zero_observes_but_never_scores():
    plane = QualityPlane(sample_rate=0.0)
    try:
        for i in range(10):
            plane.observe("det", "det", f"t{i}", {}, _outputs())
        snap = plane.snapshot()
        assert snap["observed"] == 10
        assert snap["sampled"] == 0
        assert snap["pairs"] == {}
    finally:
        plane.close()


# -- export: collector families + folded legacy exporter ----------------------


def _drive_plane_windows(plane, n=4):
    for i in range(n):
        plane.observe("det", "det", f"t{i}", {}, _outputs())
    plane.drain(5.0)
    deadline = time.monotonic() + 5.0
    while not plane.scorer.last_windows() and time.monotonic() < deadline:
        time.sleep(0.005)


def test_collector_emits_quality_families_and_folds_legacy():
    prometheus_client = pytest.importorskip("prometheus_client")
    from triton_client_tpu.obs.collector import RuntimeCollector

    registry = prometheus_client.CollectorRegistry()
    collector = RuntimeCollector(registry=registry)
    plane = QualityPlane(sample_rate=1.0, window_frames=4)
    try:
        collector.attach_quality(plane)
        # satellite 1: the fold wired the legacy exporter into the SAME
        # registry the tpu_quality_* families live in
        assert plane.legacy_exporter is not None
        plane.set_canary("det", "det_int8", 0.25)
        _drive_plane_windows(plane)
        text = prometheus_client.generate_latest(registry).decode()
        window = plane.scorer.last_windows()[("det", "det")]
        for family in (
            "tpu_quality_map50", "tpu_quality_map",
            "tpu_quality_velocity_mae", "tpu_quality_id_switch_rate",
            "tpu_quality_scored_frames_total",
            "tpu_quality_shadow_lag_seconds",
            "tpu_quality_shadow_dropped_total",
            "tpu_quality_canary_fraction", "tpu_quality_canary_info",
            "tpu_quality_promotions_total", "tpu_quality_rollbacks_total",
        ):
            assert family in text, family
        # both spellings serve the same numbers from the same windows:
        # the legacy Summary's per-window observation equals the
        # tpu_quality gauge for the same pair
        sample = lambda name, labels: registry.get_sample_value(name, labels)
        pair = {"model": "det", "variant": "det"}
        assert sample("tpu_quality_map50", pair) == pytest.approx(
            window["map50"]
        )
        assert sample("model_precision_sum", {}) == pytest.approx(
            window["precision"]
        )
        assert sample("model_ap_sum", {}) == pytest.approx(window["map50"])
        assert sample("model_f1_sum", {}) == pytest.approx(window["f1"])
        assert sample("model_precision_count", {}) == 1.0
        # canary lifecycle families carry the armed slice
        assert sample(
            "tpu_quality_canary_fraction",
            {"model": "det", "variant": "det_int8"},
        ) == pytest.approx(0.25)
        assert sample(
            "tpu_quality_canary_info",
            {"model": "det", "variant": "det_int8", "state": "canary"},
        ) == 1.0
        # /snapshot carries the structured read
        snap = collector.snapshot()
        assert "det|det" in snap["quality"]["pairs"]
    finally:
        plane.close()


def test_legacy_exporter_observe_window_shim():
    prometheus_client = pytest.importorskip("prometheus_client")
    from triton_client_tpu.eval import prometheus_export

    ex = prometheus_export.EvalPrometheusExporter(start_server=False)
    ex.observe_window(
        {"precision": 0.9, "recall": 0.8, "map50": 0.7, "f1": 0.85}
    )
    r = ex.registry
    assert r.get_sample_value("model_precision_sum", {}) == pytest.approx(0.9)
    assert r.get_sample_value("model_recall_sum", {}) == pytest.approx(0.8)
    assert r.get_sample_value("model_ap_sum", {}) == pytest.approx(0.7)
    assert r.get_sample_value("model_f1_sum", {}) == pytest.approx(0.85)


def test_history_ring_carries_quality_rows():
    from triton_client_tpu.obs.history import MetricHistory

    class _Ledger:
        def snapshot(self):
            return {}

    plane = QualityPlane(sample_rate=1.0, window_frames=4)
    hist = MetricHistory(ledger=_Ledger(), interval_s=3600.0)
    try:
        hist.attach_quality(plane)
        _drive_plane_windows(plane)
        entry = hist.tick()
        assert entry is not None and "quality" in entry
        assert entry["quality"]["det|det"]["map50"] == pytest.approx(
            AP_CEILING, abs=1e-3
        )
        # the ring holds the same entry for replay-at-restart reads
        assert hist.snapshots(1)[-1]["quality"] == entry["quality"]
    finally:
        plane.close()


# -- loadgen hook -------------------------------------------------------------


def test_loadgen_request_factory_stamps_identity():
    from triton_client_tpu.utils.loadgen import run_open_loop

    class _Future:
        def result(self):
            return None

    class _Chan:
        def __init__(self):
            self.ids = []
            self._lock = threading.Lock()

        def do_inference(self, request):
            return None  # warm path

        def do_inference_async(self, request):
            with self._lock:
                self.ids.append(request.request_id)
            return _Future()

    import dataclasses

    chan = _Chan()
    result = run_open_loop(
        chan,
        [("det", {"x": np.zeros((1, 4), np.float32)})],
        rate_qps=500.0,
        duration_s=0.25,
        seed=3,
        request_factory=lambda req, i: dataclasses.replace(
            req, request_id=f"qp-{i}"
        ),
    )
    assert result.scheduled == len(chan.ids)
    assert chan.ids == [f"qp-{i}" for i in range(len(chan.ids))]
    assert result.completed == result.scheduled


# -- router integration -------------------------------------------------------


def test_router_canary_rewrite_and_observe():
    from triton_client_tpu.channel.base import InferResponse
    from triton_client_tpu.runtime.router import FrontDoorRouter

    served = []

    class _Chan:
        def __init__(self, endpoint):
            self.endpoint = endpoint

        def do_inference(self, request):
            return self.do_inference_async(request).result()

        def do_inference_async(self, request):
            from triton_client_tpu.channel.base import InferFuture

            def _answer():
                served.append((request.model_name, request.request_id))
                return InferResponse(
                    model_name=request.model_name,
                    model_version="1",
                    outputs=_outputs(),
                    request_id=request.request_id,
                )

            return InferFuture(_answer)

        def server_ready(self, timeout_s=None):
            return True

        def model_ready(self, model, model_version="", timeout_s=None):
            return True

        def close(self):
            pass

    router = FrontDoorRouter(
        ["ep0"], channel_factory=_Chan, probe_interval_s=0.0
    )
    plane = QualityPlane(sample_rate=1.0, window_frames=4)
    try:
        router.attach_quality(plane)
        # the router's own stack is the shadow dispatch handle
        assert plane.mirror._channel is router
        plane.set_canary("det", "det_int8", 0.5)
        from triton_client_tpu.channel.base import InferRequest

        n = 30
        for i in range(n):
            router.do_inference(
                InferRequest("det", {"x": np.zeros((1, 4), np.float32)},
                             request_id=f"r{i}")
            )
        plane.drain(5.0)
        time.sleep(0.05)
        # the canary slice reached the wire under the VARIANT name
        wire_models = {m for m, _ in served}
        assert "det_int8" in wire_models and "det" in wire_models
        # the rewrite is the hash slice exactly (request_id keys the
        # hash when the router has no tracer)
        for model, rid in served[:n]:
            assert (model == "det_int8") == slice_decision(rid, 0.5)
        snap = router.snapshot()
        # shadow dispatches re-traverse the router (observed again) but
        # carry no request_id, so they are never re-sampled: no loops
        assert snap["quality"]["observed"] >= n
        assert snap["quality"]["sampled"] == n
        assert "det|det_int8" in snap["quality"]["pairs"]
    finally:
        plane.close()
        router.close()


# -- serve CLI ----------------------------------------------------------------


def test_serve_cli_builds_quality_plane(tmp_path):
    import argparse
    import contextlib
    import io
    import shutil

    from triton_client_tpu.cli import serve

    shutil.copytree("examples/yolov5_crop", tmp_path / "yolov5_crop")
    shutil.copytree("examples/yolov5_crop", tmp_path / "yolov5_crop_int8")
    args = argparse.Namespace(
        model_repository=str(tmp_path),
        address="127.0.0.1:0",
        max_workers=4,
        mesh="",
        batching=False,
        max_batch=8,
        pipeline_depth=2,
        metrics_port=0,
        warmup=False,
        verbose=False,
        canary=["yolov5_crop_int8=0.1"],
        quality_sample=0.0,  # canary arms the default 0.25
        quality_window=8,
        quality_promote_after=2,
        quality_pin_fused_off=False,
    )
    with contextlib.redirect_stdout(io.StringIO()) as out:
        server = serve.build_server(args)
    try:
        assert server.quality is not None
        assert server.quality.sample_rate == pytest.approx(0.25)
        models = server.quality.canary.stats()["models"]
        assert models["yolov5_crop"]["variant"] == "yolov5_crop_int8"
        assert models["yolov5_crop"]["fraction"] == pytest.approx(0.1)
        assert "canary armed" in out.getvalue()
    finally:
        server.quality.close()


# -- E2E: live server drives --------------------------------------------------


def _drive_ids(server, model, n, prefix="r"):
    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.grpc_channel import GRPCChannel

    x = np.zeros((1, 4), np.float32)
    c = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=30.0)
    try:
        for i in range(n):
            out = c.do_inference(
                InferRequest(model, {"x": x}, request_id=f"{prefix}{i}")
            )
            assert out.outputs["detections"].shape == (3, 6)
    finally:
        c.close()


@pytest.mark.slow
def test_e2e_server_promotes_clean_int8_canary():
    """Acceptance drive: a clean int8 variant is auto-promoted to full
    traffic, verified from the live /metrics scrape and /snapshot."""
    pytest.importorskip("grpc")
    pytest.importorskip("prometheus_client")
    repo = _det_repo()
    plane = QualityPlane(
        sample_rate=1.0, window_frames=6, promote_after=2
    )
    plane.set_canary("qp_det", "qp_det_int8", 0.4)
    chan, server = _serving_stack(repo, quality=plane)
    try:
        # the server auto-attached its own stack as the shadow channel
        assert plane.mirror._channel is chan
        deadline = time.monotonic() + 60.0
        n = 0
        while time.monotonic() < deadline:
            _drive_ids(server, "qp_det", 40, prefix=f"w{n}-")
            n += 40
            plane.drain(10.0)
            if plane.canary.stats()["models"]["qp_det"]["state"] == \
                    "promoted":
                break
        snap_local = plane.snapshot()
        c = snap_local["canary"]["models"]["qp_det"]
        assert c["state"] == "promoted", c
        assert c["fraction"] == 1.0
        assert c["served_variant"] > 0 and c["served_primary"] > 0
        # both slices scored against the f32 reference
        assert "qp_det|qp_det_int8" in snap_local["pairs"]
        last = snap_local["pairs"]["qp_det|qp_det_int8"]["last"]
        assert last["map50"] == pytest.approx(AP_CEILING, abs=1e-3)
        # verified from the scraped families, not just object state
        base = f"http://127.0.0.1:{server.metrics_port}"
        text = urllib.request.urlopen(
            base + "/metrics", timeout=10
        ).read().decode()
        assert 'tpu_quality_canary_info{model="qp_det",' in text
        assert 'state="promoted"' in text
        assert "tpu_quality_promotions_total 1.0" in text
        assert 'tpu_quality_map50{model="qp_det",variant="qp_det_int8"}' \
            in text
        assert "tpu_quality_canary_fraction{" in text
        snap = json.load(
            urllib.request.urlopen(base + "/snapshot", timeout=10)
        )
        assert snap["quality"]["canary"]["promotions"] == 1
    finally:
        server.stop()
        chan.close()


@pytest.mark.slow
def test_e2e_server_rolls_back_corrupt_canary_under_one_percent():
    """Acceptance drive: a quality_corrupt-seeded variant is ejected
    before serving 1% of total traffic, and the rollback is visible on
    the scraped tpu_quality_* families."""
    pytest.importorskip("grpc")
    pytest.importorskip("prometheus_client")
    install_fault_plan(FaultPlan(
        [FaultRule(point="quality_corrupt", model="qp_det_int8",
                   count=1_000_000)],
        seed=7,
    ))
    repo = _det_repo()
    plane = QualityPlane(
        sample_rate=1.0, window_frames=4, promote_after=3
    )
    # a thin slice: the window needs ~80 requests to fill, after which
    # the gate fires on the FIRST variant window
    plane.set_canary("qp_det", "qp_det_int8", 0.05)
    chan, server = _serving_stack(repo, quality=plane)
    try:
        total = 0
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            _drive_ids(server, "qp_det", 50, prefix=f"c{total}-")
            total += 50
            plane.drain(10.0)
            if plane.canary.stats()["rollbacks"]:
                break
        assert plane.canary.stats()["rollbacks"] == 1
        # keep serving: every post-rollback request rides the primary
        _drive_ids(server, "qp_det", max(0, 1000 - total), prefix="post-")
        total = max(total, 1000)
        plane.drain(10.0)
        snap = plane.snapshot()
        c = snap["canary"]["models"]["qp_det"]
        assert c["state"] == "rolled_back"
        assert "budget floor" in c["reason"]
        assert c["served_variant"] / total < 0.01, (
            c["served_variant"], total
        )
        assert snap["mirror"]["corrupted"] >= 4
        base = f"http://127.0.0.1:{server.metrics_port}"
        text = urllib.request.urlopen(
            base + "/metrics", timeout=10
        ).read().decode()
        assert "tpu_quality_rollbacks_total 1.0" in text
        assert 'state="rolled_back"' in text
        # the ejected canary carries zero traffic on the gauge
        assert (
            'tpu_quality_canary_fraction'
            '{model="qp_det",variant="qp_det_int8"} 0.0'
        ) in text
    finally:
        server.stop()
        chan.close()
