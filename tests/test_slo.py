"""SLO observability ring: histograms, deadline scoring, open-loop
loadgen, and tail-exemplar export.

Covers the PR's acceptance contract:
  * fixed-bucket histogram counts/sums are exact and quantile estimates
    sit within one bucket width of numpy's ground truth, on both a raw
    snapshot and a ``RuntimeCollector.delta`` window;
  * ``poisson_schedule`` is a pure function of its seed (the open-loop
    capacity number is replayable) and ``co_percentile`` ranks the
    never-completed tail as +Inf (coordinated-omission safety);
  * ``SLOTracker`` scores met/missed per (model, priority) with the
    admission-stamped deadline authoritative over wall time, counts
    errors as missed, and retains exemplar traces only for violators
    (or p99+ once the e2e histogram has enough samples);
  * a live localhost server under a generous SLO attains 100% and its
    e2e histogram count reconciles with traces finished; under an
    impossible SLO every request scores missed, the staged launcher
    counts deadline-expired launches, and the violating traces export
    at ``/traces?slo_violations=1``;
  * one open-loop window against the live server completes requests
    and feeds the same histograms.
"""

import json
import math
import threading
import urllib.request

import numpy as np
import pytest

from triton_client_tpu.obs.collector import RuntimeCollector
from triton_client_tpu.obs.histogram import (
    DEFAULT_BUCKETS,
    HistogramFamily,
    LatencyHistogram,
    quantile_from_snapshot,
)
from triton_client_tpu.obs.slo import SLOTracker
from triton_client_tpu.utils.loadgen import (
    OpenLoopResult,
    co_percentile,
    poisson_schedule,
)

jax = pytest.importorskip("jax")


# -- helpers ------------------------------------------------------------------


def _repo(name="double", sleep_s=0.0):
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository

    spec = ModelSpec(
        name=name,
        version="1",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
    )

    def infer(inputs):
        if sleep_s:
            import time

            time.sleep(sleep_s)
        return {"y": np.asarray(inputs["x"]) * 2.0}

    repo = ModelRepository()
    repo.register(spec, infer)
    return repo, spec


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


def _drive_clients(server, model="double", clients=4, rounds=3):
    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.grpc_channel import GRPCChannel

    x = np.arange(8, dtype=np.float32).reshape(2, 4)

    def one():
        c = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=30.0)
        try:
            for _ in range(rounds):
                c.do_inference(InferRequest(model, {"x": x}))
        finally:
            c.close()

    threads = [threading.Thread(target=one) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return clients * rounds


def _bucket_width_at(value):
    """Width of the DEFAULT_BUCKETS bucket containing ``value`` — the
    quantile estimator's error bound."""
    lo = 0.0
    for b in DEFAULT_BUCKETS:
        if value <= b:
            return b - lo
        lo = b
    return float("inf")


# -- histogram primitive ------------------------------------------------------


class TestHistogram:
    def test_counts_and_sum_exact(self):
        h = LatencyHistogram(buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(5.56)
        assert snap["buckets"] == {
            repr(0.01): 2, repr(0.1): 1, repr(1.0): 1, "inf": 1,
        }

    def test_bad_samples_clamp_to_zero(self):
        h = LatencyHistogram(buckets=(0.01, 1.0))
        h.observe(-3.0)
        h.observe(float("nan"))
        snap = h.snapshot()
        assert snap["count"] == 2 and snap["sum"] == 0.0
        assert snap["buckets"][repr(0.01)] == 2

    def test_quantiles_within_bucket_width_of_numpy(self):
        rng = np.random.default_rng(7)
        samples = rng.uniform(0.0005, 0.9, size=2000)
        h = LatencyHistogram()
        for v in samples:
            h.observe(float(v))
        for q in (50, 90, 99):
            true = float(np.percentile(samples, q))
            est = h.quantile(q / 100.0)
            assert abs(est - true) <= _bucket_width_at(true), (q, est, true)

    def test_quantile_in_overflow_returns_largest_bound(self):
        h = LatencyHistogram(buckets=(0.01, 1.0))
        h.observe(50.0)
        assert h.quantile(0.99) == 1.0

    def test_empty_quantile_is_zero(self):
        assert quantile_from_snapshot({"buckets": {}}, 0.99) == 0.0
        assert LatencyHistogram().quantile(0.5) == 0.0

    def test_family_delta_windows_the_histogram(self):
        fam = HistogramFamily()
        for _ in range(100):
            fam.observe("m", "e2e", 0.004)
        snap1 = {"histograms": fam.snapshot()}
        for _ in range(100):
            fam.observe("m", "e2e", 0.4)
        snap2 = {"histograms": fam.snapshot()}
        window = RuntimeCollector.delta(snap2, snap1)["histograms"]["m|e2e"]
        # the window holds ONLY the second batch: its p50 sits in the
        # 0.4-second bucket, nowhere near the first batch's 4 ms
        assert window["count"] == 100
        est = quantile_from_snapshot(window, 0.5)
        assert abs(est - 0.4) <= _bucket_width_at(0.4)
        # while the raw snapshot's p50 straddles both batches
        full = snap2["histograms"]["m|e2e"]
        assert full["count"] == 200

    def test_family_keys_and_accessors(self):
        fam = HistogramFamily()
        fam.observe("m", "e2e", 0.01)
        assert "m|e2e" in fam.snapshot()
        assert fam.count("m", "e2e") == 1
        assert fam.count("m", "absent") == 0
        assert fam.quantile("m", "absent", 0.5) == 0.0


# -- open-loop schedule + CO-safe percentiles ---------------------------------


class TestOpenLoopMath:
    def test_poisson_schedule_is_seed_deterministic(self):
        a_off, a_pick = poisson_schedule(50.0, 2.0, seed=3, weights=[1, 3])
        b_off, b_pick = poisson_schedule(50.0, 2.0, seed=3, weights=[1, 3])
        np.testing.assert_array_equal(a_off, b_off)
        np.testing.assert_array_equal(a_pick, b_pick)
        c_off, _ = poisson_schedule(50.0, 2.0, seed=4, weights=[1, 3])
        assert len(a_off) != len(c_off) or not np.array_equal(a_off, c_off)

    def test_poisson_schedule_rate_and_mix(self):
        off, picks = poisson_schedule(200.0, 5.0, seed=0, weights=[1, 3])
        assert np.all(off < 5.0) and np.all(np.diff(off) >= 0)
        # ~1000 arrivals at 200 qps x 5 s; Poisson sd ~32
        assert 800 <= len(off) <= 1200
        frac = np.mean(picks == 1)
        assert 0.6 <= frac <= 0.9  # 3/4 of the mix, with slack

    def test_poisson_schedule_empty_on_zero_rate(self):
        off, picks = poisson_schedule(0.0, 5.0)
        assert len(off) == 0 and len(picks) == 0

    def test_co_percentile_ranks_missing_tail_as_inf(self):
        lats = [10.0] * 90  # 10 of 100 scheduled never completed
        assert co_percentile(lats, 100, 50.0) == 10.0
        assert co_percentile(lats, 100, 90.0) == 10.0
        assert co_percentile(lats, 100, 99.0) == float("inf")

    def test_open_loop_result_attainment_over_scheduled(self):
        res = OpenLoopResult(
            offered_qps=10.0, scheduled=10, completed=8, wall_s=1.0,
            latencies_ms=[5.0] * 6 + [50.0] * 2,
        )
        # 6 of 10 SCHEDULED within 10 ms — drops are not laundered
        assert res.attainment(10.0) == pytest.approx(0.6)
        assert res.percentile(99.0) == float("inf")
        assert res.achieved_qps == pytest.approx(8.0)


# -- SLO tracker (unit) -------------------------------------------------------


class TestSLOTracker:
    def test_wall_clock_scoring_and_attainment(self):
        t = SLOTracker(slo_ms=100.0)
        assert t.enabled
        t.observe_request("m", wall_s=0.05)
        t.observe_request("m", wall_s=0.25)
        s = t.stats()
        assert s["met"] == 1 and s["missed"] == 1
        assert s["requests"] == {"m|0": {"met": 1, "missed": 1}}
        assert t.attainment() == pytest.approx(0.5)

    def test_deadline_is_authoritative_over_wall(self):
        t = SLOTracker(slo_ms=100.0)
        # tiny wall but the admission deadline has passed: missed
        t.observe_request("m", wall_s=0.001, deadline_s=10.0, now=11.0)
        # long wall but the (stretched) deadline has not: met
        t.observe_request("m", wall_s=5.0, deadline_s=100.0, now=50.0)
        s = t.stats()
        assert s["requests"]["m|0"] == {"met": 1, "missed": 1}

    def test_errors_count_as_missed(self):
        t = SLOTracker(slo_ms=1000.0)
        t.observe_request("m", wall_s=0.001, status="INTERNAL")
        assert t.stats()["missed"] == 1

    def test_per_model_override_and_deadline_for(self):
        t = SLOTracker(slo_ms=100.0, per_model={"fast": 10.0})
        assert t.slo_s("fast") == pytest.approx(0.01)
        assert t.slo_s("other") == pytest.approx(0.1)
        assert t.deadline_for("fast", 5.0) == pytest.approx(5.01)
        none = SLOTracker(slo_ms=0.0)
        assert not none.enabled
        assert none.deadline_for("m", 5.0) is None

    def test_set_budget_arms_a_live_tracker(self):
        t = SLOTracker(slo_ms=0.0)
        t.observe_request("m", wall_s=5.0)  # unscored: no budget yet
        t.set_budget(100.0)
        assert t.enabled
        t.observe_request("m", wall_s=5.0)
        t.set_budget(10_000.0, model="m")  # per-model override wins
        t.observe_request("m", wall_s=5.0)
        s = t.stats()
        assert s["requests"]["m|0"] == {"met": 1, "missed": 1}

    def test_unbudgeted_requests_are_not_scored(self):
        t = SLOTracker(slo_ms=0.0)
        t.observe_request("m", wall_s=99.0)
        s = t.stats()
        assert s["met"] == 0 and s["missed"] == 0 and s["requests"] == {}
        assert t.attainment() == 1.0

    def test_priority_splits_the_counter_key(self):
        t = SLOTracker(slo_ms=100.0)
        t.observe_request("m", wall_s=0.01, priority=0)
        t.observe_request("m", wall_s=0.01, priority=2)
        assert set(t.stats()["requests"]) == {"m|0", "m|2"}

    def test_tail_retains_only_violators(self):
        t = SLOTracker(slo_ms=100.0, tail_capacity=8)
        t.observe_request("m", wall_s=0.01, trace="fast")
        t.observe_request("m", wall_s=0.5, trace="slow")
        assert t.violations() == ["slow"]
        s = t.stats()
        assert s["tail_buffered"] == 1 and s["tail_retained"] == 1

    def test_tail_ring_is_bounded(self):
        t = SLOTracker(slo_ms=1.0, tail_capacity=4)
        for i in range(10):
            t.observe_request("m", wall_s=1.0, trace=i)
        assert t.violations() == [6, 7, 8, 9]
        assert t.violations(2) == [8, 9]
        assert t.stats()["tail_retained"] == 10

    def test_p99_criterion_needs_min_samples_then_retains(self):
        fam = HistogramFamily()
        t = SLOTracker(slo_ms=0.0, histograms=fam)
        # below the sample floor: a slow-but-met request is NOT kept
        for _ in range(50):
            fam.observe("m", "e2e", 0.001)
        t.observe_request("m", wall_s=10.0, trace="early")
        assert t.violations() == []
        # past the floor: at/above live p99 qualifies even when met
        for _ in range(100):
            fam.observe("m", "e2e", 0.001)
        t.observe_request("m", wall_s=10.0, trace="late")
        t.observe_request("m", wall_s=0.0001, trace="fast")
        assert t.violations() == ["late"]

    def test_p99_is_estimated_anew_once_the_histogram_has_grown(self, monkeypatch):
        """Not on every request: the estimate stands until the e2e
        histogram has 3% more samples (at least 100 more)."""
        fam = HistogramFamily()
        t = SLOTracker(slo_ms=0.0, histograms=fam)
        reads = []
        real = LatencyHistogram.quantile
        monkeypatch.setattr(
            LatencyHistogram, "quantile",
            lambda self, q: (reads.append(self.count), real(self, q))[1],
        )
        for _ in range(100):
            fam.observe("m", "e2e", 0.001)
        t.observe_request("m", wall_s=0.0005, trace="a")  # the first estimate, at 100
        assert reads == [100]
        # the histogram moves up by 99 slow samples: the threshold is still the old one
        for _ in range(99):
            fam.observe("m", "e2e", 1.0)
        t.observe_request("m", wall_s=0.01, trace="over_the_old_p99")
        assert reads == [100] and t.violations() == ["over_the_old_p99"]
        # the 200th sample: estimated anew, and 0.01 s is no tail any more
        fam.observe("m", "e2e", 1.0)
        t.observe_request("m", wall_s=0.01, trace="under_the_new_p99")
        assert reads == [100, 200] and t.violations() == ["over_the_old_p99"]
        # from 3,200 samples on the step is 3% (n >> 5), not 100
        for _ in range(6200):
            fam.observe("m", "e2e", 1.0)
        t.observe_request("m", wall_s=0.01, trace="b")
        assert reads[-1] == 6400 and t._p99["m"][0] == 6400 + 200
        # another model has an estimate of its own
        assert t._p99_s("other") == float("inf") and "other" not in t._p99


# -- live server --------------------------------------------------------------


class TestLiveServer:
    def test_generous_slo_all_met_and_histograms_reconcile(self):
        pytest.importorskip("grpc")
        pytest.importorskip("prometheus_client")
        repo, spec = _repo()
        chan, server = _serving_stack(repo, slo_ms=60_000.0)
        try:
            n = _drive_clients(server, clients=4, rounds=3)
            s = server.slo.stats()
            assert s["met"] == n and s["missed"] == 0
            assert s["requests"] == {f"{spec.name}|0": {"met": n, "missed": 0}}
            snap = server.collector.snapshot()
            hists = snap["histograms"]
            # every finished trace landed exactly one e2e sample, and
            # the batching path produced the attribution stages
            assert hists[f"{spec.name}|e2e"]["count"] == n
            assert snap["tracer"]["finished"] == n
            for stage in ("queue_delay", "merge_wait", "device_execute"):
                assert hists[f"{spec.name}|{stage}"]["count"] >= 1, stage
            # stage spans nest inside e2e: per-request means must too
            e2e = hists[f"{spec.name}|e2e"]
            q = hists[f"{spec.name}|queue_delay"]
            assert q["sum"] <= e2e["sum"]
            base = f"http://127.0.0.1:{server.metrics_port}"
            text = urllib.request.urlopen(
                base + "/metrics", timeout=10
            ).read().decode()
            assert "# TYPE tpu_serving_latency_seconds histogram" in text
            assert (
                f'tpu_serving_latency_seconds_count'
                f'{{model="{spec.name}",stage="e2e"}} {float(n)}'
            ) in text
            assert (
                f'tpu_serving_slo_requests_total'
                f'{{model="{spec.name}",outcome="met",priority="0"}}'
            ) in text
        finally:
            server.stop()
            chan.close()

    def test_impossible_slo_misses_expires_and_exports_violators(self):
        pytest.importorskip("grpc")
        pytest.importorskip("prometheus_client")
        repo, spec = _repo(sleep_s=0.03)
        chan, server = _serving_stack(repo, slo_ms=1.0)
        try:
            n = _drive_clients(server, clients=4, rounds=2)
            s = server.slo.stats()
            assert s["missed"] == n and s["met"] == 0
            # requests queued behind a 30 ms execution launch after
            # their 1 ms deadline: the staged launcher counted them
            snap = server.collector.snapshot()
            assert snap["channel"]["deadline_expired_launches"] >= 1
            assert s["tail_buffered"] >= 1
            base = f"http://127.0.0.1:{server.metrics_port}"
            doc = json.load(urllib.request.urlopen(
                base + "/traces?slo_violations=1", timeout=10
            ))
            reqs = [
                e for e in doc["traceEvents"]
                if e.get("ph") == "X" and e["name"] == "request"
            ]
            assert len(reqs) == min(n, 64)
        finally:
            server.stop()
            chan.close()

    def test_no_slo_scores_nothing_but_histograms_still_fill(self):
        pytest.importorskip("grpc")
        repo, spec = _repo()
        chan, server = _serving_stack(repo)  # slo_ms defaults to 0
        try:
            n = _drive_clients(server, clients=2, rounds=2)
            s = server.slo.stats()
            assert s["met"] == 0 and s["missed"] == 0
            snap = server.collector.snapshot()
            assert snap["histograms"][f"{spec.name}|e2e"]["count"] == n
        finally:
            server.stop()
            chan.close()


# -- open-loop against the live server ---------------------------------------


@pytest.mark.slow
def test_open_loop_window_feeds_the_ring():
    pytest.importorskip("grpc")
    from triton_client_tpu.utils.loadgen import run_open_loop

    repo, spec = _repo()
    chan, server = _serving_stack(repo, slo_ms=30_000.0)
    try:
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        res = run_open_loop(
            f"127.0.0.1:{server.port}",
            [(spec.name, {"x": x})],
            rate_qps=40.0,
            duration_s=1.5,
            seed=5,
            deadline_s=30.0,
        )
        # the schedule is the seed's: same seed, same population
        off, _ = poisson_schedule(40.0, 1.5, seed=5, weights=[1.0])
        assert res.scheduled == len(off)
        assert res.completed == res.scheduled, res.errors
        assert math.isfinite(res.percentile(99.0))
        assert res.attainment(30_000.0) == 1.0
        # server side scored and measured the same population (+1 warm)
        assert server.slo.stats()["met"] == res.scheduled + 1
        snap = server.collector.snapshot()
        assert snap["histograms"][f"{spec.name}|e2e"]["count"] == (
            res.scheduled + 1
        )
    finally:
        server.stop()
        chan.close()
