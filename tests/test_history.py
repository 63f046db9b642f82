"""Metric history ring (obs/history): ledger-delta windowing, the
bounded ring, the persist/load/restore round trip, the /history
endpoint, and the InferenceServer drain-persist path.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from triton_client_tpu.obs.history import MetricHistory


class _FakeLedger:
    """Scripted DeviceTimeLedger: each snapshot() pops the next doc."""

    def __init__(self, snaps):
        self._snaps = list(snaps)

    def snapshot(self):
        return self._snaps.pop(0) if len(self._snaps) > 1 else self._snaps[0]


def _snap(device_s, launches, mfu=None, utilization=0.0):
    return {
        "device_seconds": device_s,
        "launches": launches,
        "window": {"utilization": utilization, "mfu": mfu or {}},
    }


# -- tick windowing -----------------------------------------------------------


def test_first_tick_has_no_delta_baseline():
    h = MetricHistory(
        ledger=_FakeLedger([_snap({"m|default": 1.0}, {"m": 5},
                                  mfu={"m": 0.02}, utilization=0.4)]),
        interval_s=1.0,
    )
    e = h.tick(now=0.0)
    assert e["interval_s"] == 0.0
    assert e["utilization"] == pytest.approx(0.4)
    m = e["models"]["m|default"]
    # rates need two snapshots; the window gauges export immediately
    assert m["launches_per_s"] == 0.0
    assert m["device_s_per_s"] == 0.0
    assert m["mfu"] == pytest.approx(0.02)


def test_tick_diffs_consecutive_snapshots_into_rates():
    h = MetricHistory(
        ledger=_FakeLedger([
            _snap({"m|default": 1.0}, {"m": 5}),
            _snap({"m|default": 1.5}, {"m": 15}, mfu={"m": 0.05},
                  utilization=0.25),
        ]),
        interval_s=1.0,
    )
    h.tick(now=0.0)
    e = h.tick(now=10.0)
    assert e["interval_s"] == pytest.approx(10.0)
    m = e["models"]["m|default"]
    assert m["launches_per_s"] == pytest.approx(1.0)   # 10 launches / 10 s
    assert m["device_s_per_s"] == pytest.approx(0.05)  # 0.5 s / 10 s
    assert m["mfu"] == pytest.approx(0.05)
    assert e["utilization"] == pytest.approx(0.25)


def test_tick_without_ledger_is_a_noop():
    h = MetricHistory(ledger=None)
    assert h.tick() is None
    assert h.stats()["ticks"] == 0


def test_ring_is_bounded_by_capacity():
    h = MetricHistory(
        ledger=_FakeLedger([_snap({"m|default": 1.0}, {"m": 1})]),
        interval_s=1.0, capacity=2,
    )
    for i in range(5):
        h.tick(now=float(i))
    st = h.stats()
    assert st["ticks"] == 5
    assert st["buffered"] == 2
    assert len(h.snapshots()) == 2
    assert len(h.snapshots(1)) == 1


# -- persistence --------------------------------------------------------------


def test_persist_load_restore_round_trip(tmp_path):
    src = MetricHistory(
        ledger=_FakeLedger([
            _snap({"m|default": 1.0}, {"m": 5}),
            _snap({"m|default": 2.0}, {"m": 9}, mfu={"m": 0.03}),
        ]),
        interval_s=1.0,
    )
    src.tick(now=0.0)
    src.tick(now=5.0)
    path = tmp_path / "history.json"
    assert src.persist(str(path)) == 2

    doc = MetricHistory.load(str(path))
    assert doc["interval_s"] == 1.0
    assert len(doc["snapshots"]) == 2

    dst = MetricHistory(interval_s=1.0)
    assert dst.restore(doc) == 2
    # the restored ring serves the same entries the source persisted
    assert dst.snapshots() == src.snapshots()
    assert dst.stats()["buffered"] == 2


def test_restore_keeps_newest_when_over_capacity():
    entries = [{"t": float(i), "interval_s": 1.0, "utilization": 0.0,
                "models": {}} for i in range(10)]
    h = MetricHistory(interval_s=1.0, capacity=3)
    assert h.restore({"snapshots": entries}) == 3
    assert [e["t"] for e in h.snapshots()] == [7.0, 8.0, 9.0]


# -- endpoint + server wiring -------------------------------------------------


def test_history_endpoint_serves_stats_and_snapshots():
    from triton_client_tpu.obs.http import TelemetryServer

    h = MetricHistory(
        ledger=_FakeLedger([_snap({"m|default": 1.0}, {"m": 2})]),
        interval_s=1.0,
    )
    for i in range(3):
        h.tick(now=float(i))
    srv = TelemetryServer(port=0, history=h)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        doc = json.load(urllib.request.urlopen(base + "/history", timeout=10))
        assert doc["stats"]["buffered"] == 3
        assert len(doc["snapshots"]) == 3
        doc = json.load(
            urllib.request.urlopen(base + "/history?n=1", timeout=10)
        )
        assert len(doc["snapshots"]) == 1
    finally:
        srv.close()


def test_history_endpoint_404_when_disabled():
    from triton_client_tpu.obs.http import TelemetryServer

    srv = TelemetryServer(port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/history", timeout=10
            )
        assert err.value.code == 404
    finally:
        srv.close()


def _double_repo(name="double"):
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository

    spec = ModelSpec(
        name=name,
        version="1",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
    )
    repo = ModelRepository()
    repo.register(spec, lambda inputs: {"y": np.asarray(inputs["x"]) * 2.0})
    return repo, spec


def test_server_drain_persists_history_and_restart_restores(tmp_path):
    pytest.importorskip("jax")
    pytest.importorskip("grpc")
    from triton_client_tpu.channel.base import InferRequest
    from triton_client_tpu.channel.grpc_channel import GRPCChannel
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
    from triton_client_tpu.runtime.server import InferenceServer

    path = tmp_path / "history.json"
    repo, spec = _double_repo()

    def build():
        chan = ContinuousBatchingChannel(
            TPUChannel(repo), max_batch=4
        )
        server = InferenceServer(
            repo, chan, address="127.0.0.1:0", metrics_port="auto",
            history_interval_s=3600.0,  # ticks only via drain in this test
            history_path=str(path),
        )
        server.start()
        return chan, server

    chan, server = build()
    try:
        assert server.history is not None
        client = GRPCChannel(f"127.0.0.1:{server.port}", timeout_s=30.0)
        x = np.ones((2, 4), np.float32)
        client.do_inference(InferRequest(spec.name, {"x": x}))
        client.close()
    finally:
        assert server.drain(timeout_s=10.0)
        chan.close()

    doc = json.loads(path.read_text())
    # drain took the final tick before persisting
    assert len(doc["snapshots"]) >= 1

    # a restarted server restores the persisted ring on construction
    chan2, server2 = build()
    try:
        assert server2.history.stats()["buffered"] >= 1
        base = f"http://127.0.0.1:{server2.metrics_port}"
        served = json.load(
            urllib.request.urlopen(base + "/history", timeout=10)
        )
        assert served["snapshots"] == doc["snapshots"]
    finally:
        server2.stop()
        chan2.close()


def test_background_thread_ticks_and_close_joins():
    h = MetricHistory(
        ledger=_FakeLedger([_snap({"m|default": 1.0}, {"m": 1})]),
        interval_s=0.5,
    )
    h.start()
    try:
        deadline = threading.Event()
        for _ in range(40):  # up to ~4 s for at least one tick
            if h.stats()["ticks"] >= 1:
                break
            deadline.wait(0.1)
        assert h.stats()["ticks"] >= 1
    finally:
        h.close()
    assert h._thread is None
