"""Streaming perception sessions (ISSUE 15): the SessionManager slot
pool, the server-side frame bracket, sequence-parameter plumbing,
session-affinity routing, and the replay/chaos acceptance drives.

The serving model in every end-to-end test is an ECHO detector — its
device fn returns the request's detections/valid tensors unchanged —
so the tracker's inputs are exactly what the replayer scripted and
track outputs are fully deterministic.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from triton_client_tpu.channel.base import InferRequest  # noqa: E402
from triton_client_tpu.ops.tracking import TrackerConfig  # noqa: E402
from triton_client_tpu.runtime.sessions import (  # noqa: E402
    SessionLimitError,
    SessionManager,
    id_base_for,
)

DET_DIM = 11
N_SLOTS = 6


# -- fixtures -----------------------------------------------------------------


def _detections(rows):
    det = np.zeros((N_SLOTS, DET_DIM), np.float32)
    valid = np.zeros((N_SLOTS,), bool)
    for i, (x, y) in enumerate(rows):
        det[i, 0], det[i, 1] = x, y
        det[i, 3:6] = (4.0, 2.0, 1.5)
        det[i, -2] = 0.9
        valid[i] = True
    return {"detections": det, "valid": valid}


def _req(sid, start=False, end=False, model="echo"):
    return InferRequest(
        model_name=model,
        inputs={},
        sequence_id=sid,
        sequence_start=start,
        sequence_end=end,
    )


def _manager(**kw):
    kw.setdefault("tracker", TrackerConfig(max_tracks=8))
    return SessionManager(**kw)


def _echo_repo(name="echo", sleep_s=0.0):
    from triton_client_tpu.config import ModelSpec, TensorSpec
    from triton_client_tpu.runtime.repository import ModelRepository

    spec = ModelSpec(
        name=name,
        version="1",
        inputs=(
            TensorSpec("detections", (-1, DET_DIM), "FP32"),
            TensorSpec("valid", (-1,), "BOOL"),
        ),
        outputs=(
            TensorSpec("detections", (-1, DET_DIM), "FP32"),
            TensorSpec("valid", (-1,), "BOOL"),
        ),
    )

    def infer(inputs):
        if sleep_s:
            time.sleep(sleep_s)
        return {
            "detections": inputs["detections"],
            "valid": inputs["valid"],
        }

    repo = ModelRepository()
    repo.register(spec, infer)
    return repo


def _server(max_sessions=8, ttl_s=60.0, id_namespace=0, sleep_s=0.0,
            **server_kw):
    """In-process server with an echo detector + attached sessions.
    Returns (server, manager); caller stops the server."""
    from triton_client_tpu.channel.tpu_channel import TPUChannel
    from triton_client_tpu.runtime.server import InferenceServer

    repo = _echo_repo(sleep_s=sleep_s)
    chan = TPUChannel(repo)
    manager = SessionManager(
        max_sessions=max_sessions,
        ttl_s=ttl_s,
        tracker=TrackerConfig(max_tracks=8),
        id_namespace=id_namespace,
    )
    chan.attach_sessions(manager)
    server = InferenceServer(
        repo, chan, address="127.0.0.1:0", metrics_port="auto", **server_kw
    )
    server.start()
    return server, manager


# -- SessionManager unit tests ------------------------------------------------


class TestSessionPool:
    def test_advance_creates_and_tracks(self):
        m = _manager()
        out = m.advance(_req("a", start=True), _detections([(0, 0), (5, 5)]))
        m.release("a")
        tids = np.asarray(out["det_track_ids"])
        assert tids[0] > 0 and tids[1] > 0 and tids[0] != tids[1]
        assert m.stats()["active_sessions"] == 1
        assert m.stats()["frames_total"] == 1

    def test_refcount_brackets_inflight(self):
        m = _manager()
        m.advance(_req("a", start=True), _detections([(0, 0)]))
        assert m.stats()["inflight_frames"] == 1
        m.release("a")
        assert m.stats()["inflight_frames"] == 0

    def test_end_frees_slot_after_last_release(self):
        m = _manager()
        m.advance(_req("a", start=True), _detections([(0, 0)]))
        m.advance(_req("a", end=True), _detections([(0.1, 0)]))
        # two frames in flight; the ended slot survives until both drop
        m.release("a")
        assert m.stats()["active_sessions"] == 1
        m.release("a")
        s = m.stats()
        assert s["active_sessions"] == 0
        assert s["ended_total"] == 1
        assert s["track_births_total"] == 1

    def test_restart_gets_fresh_epoch_disjoint_ids(self):
        m = _manager()
        out1 = m.advance(_req("a", start=True), _detections([(0, 0)]))
        m.release("a")
        tid1 = int(np.asarray(out1["det_track_ids"])[0])
        out2 = m.advance(_req("a", start=True), _detections([(0, 0)]))
        m.release("a")
        tid2 = int(np.asarray(out2["det_track_ids"])[0])
        assert tid1 != tid2  # same slot position, fresh epoch
        assert m.stats()["restarted_total"] == 1

    def test_ttl_reclaims_idle_session(self):
        now = [0.0]
        m = _manager(max_sessions=1, ttl_s=10.0, time_fn=lambda: now[0])
        m.advance(_req("a", start=True), _detections([(0, 0)]))
        m.release("a")
        now[0] = 11.0
        m.advance(_req("b", start=True), _detections([(1, 1)]))
        m.release("b")
        s = m.stats()
        assert s["active_sessions"] == 1
        assert s["expired_total"] == 1

    def test_lru_reclaims_oldest_idle(self):
        now = [0.0]
        m = _manager(max_sessions=2, ttl_s=100.0, time_fn=lambda: now[0])
        for i, sid in enumerate(("a", "b")):
            now[0] = float(i)
            m.advance(_req(sid, start=True), _detections([(i, i)]))
            m.release(sid)
        now[0] = 5.0
        m.advance(_req("c", start=True), _detections([(9, 9)]))
        m.release("c")
        s = m.stats()
        assert s["reclaimed_total"] == 1
        # "a" (least recently used) was the victim
        m.advance(_req("b"), _detections([(1, 1)]))
        m.release("b")
        assert m.stats()["restarted_total"] == 0

    def test_full_pool_of_inflight_sessions_sheds(self):
        m = _manager(max_sessions=1, ttl_s=0.0)
        m.advance(_req("a", start=True), _detections([(0, 0)]))
        # "a" still holds its in-flight ref: unreclaimable
        with pytest.raises(SessionLimitError):
            m.advance(_req("b", start=True), _detections([(1, 1)]))
        assert m.stats()["rejected_total"] == 1

    def test_ended_slot_reclaimed_before_ttl(self):
        m = _manager(max_sessions=1, ttl_s=1e9)
        m.advance(_req("a", start=True, end=True), _detections([(0, 0)]))
        m.release("a")
        m.advance(_req("b", start=True), _detections([(1, 1)]))
        m.release("b")
        assert m.stats()["active_sessions"] == 1

    def test_failed_step_drops_ref(self):
        m = _manager()
        bad = {"detections": np.zeros((5,), np.float32),  # 1-D: no det axis
               "valid": np.ones((5,), bool)}
        with pytest.raises(Exception):
            m.advance(_req("a", start=True), bad)
        assert m.stats()["inflight_frames"] == 0

    def test_2d_rows_narrow_velocity_window(self):
        # regression: the DEFAULT config carries CenterPoint's
        # velocity_cols=(7, 9); a 2D detector's 6-column rows must
        # narrow it to None instead of slicing a width-0 z_vel
        # (IndexError) — the live yolov5 sessions+temporal path
        m = SessionManager(max_sessions=4)  # default TrackerConfig
        det = np.zeros((4, 6), np.float32)
        det[0] = (10.0, 12.0, 20.0, 22.0, 0.9, 1.0)
        valid = np.array([True, False, False, False])
        out = m.advance(
            _req("v2d", start=True), {"detections": det, "valid": valid}
        )
        m.release("v2d")
        assert int(np.asarray(out["det_track_ids"])[0]) > 0
        coasted = m.coast(_req("v2d"))
        m.release("v2d")
        assert coasted is not None
        assert np.asarray(coasted["tracks"]).shape[-1] == 6

    def test_model_without_detections_passes_through(self):
        m = _manager()
        out = m.advance(_req("a", start=True), {"y": np.zeros(3)})
        m.release("a")
        assert set(out) == {"y"}

    def test_namespace_epoch_id_layout(self):
        base = id_base_for(3, 7)
        assert base == (3 << 27) | (7 << 16)
        assert id_base_for(15, 2047) > 0  # stays in int32 positive range
        assert id_base_for(16, 0) == id_base_for(0, 0)  # namespace masks
        assert id_base_for(1, 2048) == id_base_for(1, 0)  # epoch wraps


class TestSessionGroups:
    def test_group_step_outputs_per_camera(self):
        m = _manager()
        single = _detections([(0, 0), (8, 8)])
        group = {
            "detections": np.stack([single["detections"]] * 2),
            "valid": np.stack([single["valid"]] * 2),
        }
        out = m.advance(_req("g", start=True), group)
        m.release("g")
        tids = np.asarray(out["det_track_ids"])
        assert tids.shape[0] == 2
        cam0 = set(tids[0][tids[0] > 0].tolist())
        cam1 = set(tids[1][tids[1] > 0].tolist())
        assert cam0 and cam1 and not (cam0 & cam1)

    def test_group_size_change_rejected(self):
        m = _manager()
        single = _detections([(0, 0)])
        g2 = {
            "detections": np.stack([single["detections"]] * 2),
            "valid": np.stack([single["valid"]] * 2),
        }
        g3 = {
            "detections": np.stack([single["detections"]] * 3),
            "valid": np.stack([single["valid"]] * 3),
        }
        m.advance(_req("g", start=True), g2)
        m.release("g")
        with pytest.raises(ValueError, match="group size"):
            m.advance(_req("g"), g3)
        assert m.stats()["inflight_frames"] == 0

    def test_batch_of_one_is_a_group(self):
        m = _manager()
        single = _detections([(0, 0)])
        g1 = {
            "detections": single["detections"][None],
            "valid": single["valid"][None],
        }
        out = m.advance(_req("g", start=True), g1)
        m.release("g")
        assert np.asarray(out["det_track_ids"]).shape[0] == 1


class TestDeviceResidency:
    def test_advance_steady_state_no_host_reads(self):
        """The frame bracket never reads device memory: after warmup,
        advance/release run clean under the transfer guard."""
        m = _manager()
        frame = {
            "detections": jax.device_put(
                _detections([(0, 0)])["detections"]
            ),
            "valid": jax.device_put(_detections([(0, 0)])["valid"]),
        }
        m.advance(_req("a", start=True), frame)
        m.release("a")
        with jax.transfer_guard_device_to_host("disallow"):
            for _ in range(5):
                m.advance(_req("a"), frame)
                m.release("a")
        assert m.stats()["frames_total"] == 6  # stats AFTER the guard


# -- server end-to-end --------------------------------------------------------


class TestServerSessions:
    def test_sequence_round_trip_tracks_across_frames(self):
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        server, manager = _server()
        try:
            client = GRPCChannel(f"127.0.0.1:{server.port}")
            try:
                tids = []
                for k in range(4):
                    frame = _detections([(0.2 * k, 0.0)])
                    resp = client.do_inference(
                        InferRequest(
                            "echo",
                            frame,
                            sequence_id="cam-0",
                            sequence_start=(k == 0),
                            sequence_end=(k == 3),
                        )
                    )
                    assert "det_track_ids" in resp.outputs
                    tids.append(int(resp.outputs["det_track_ids"][0]))
                # one object, one stable id across the whole stream
                assert len(set(tids)) == 1 and tids[0] > 0
                s = manager.stats()
                assert s["frames_total"] == 4
                assert s["ended_total"] == 1
                assert s["inflight_frames"] == 0
            finally:
                client.close()
        finally:
            server.stop()

    def test_stateless_requests_untouched(self):
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        server, manager = _server()
        try:
            client = GRPCChannel(f"127.0.0.1:{server.port}")
            try:
                resp = client.do_inference(
                    InferRequest("echo", _detections([(0, 0)]))
                )
                assert "det_track_ids" not in resp.outputs
                assert manager.stats()["active_sessions"] == 0
            finally:
                client.close()
        finally:
            server.stop()

    def test_session_pool_full_is_resource_exhausted(self):
        # the only unreclaimable pool state is every slot IN FLIGHT:
        # pin stream "a"'s ref open on the shared manager (exactly what
        # an executing launch holds), then knock over the wire as "b" —
        # the SessionLimitError raised inside launch must surface as
        # non-retryable RESOURCE_EXHAUSTED, same contract as admission
        import grpc

        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        server, manager = _server(max_sessions=1, ttl_s=1e9)
        try:
            manager.advance(_req("a", start=True), _detections([(0, 0)]))
            client = GRPCChannel(f"127.0.0.1:{server.port}", retries=0)
            try:
                with pytest.raises(grpc.RpcError) as exc:
                    client.do_inference(
                        InferRequest(
                            "echo", _detections([(1, 1)]), sequence_id="b",
                            sequence_start=True,
                        )
                    )
                assert exc.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
                manager.release("a")
                # ref dropped: the same knock now succeeds (LRU reclaim)
                resp = client.do_inference(
                    InferRequest(
                        "echo", _detections([(1, 1)]), sequence_id="b",
                        sequence_start=True,
                    )
                )
                assert "det_track_ids" in resp.outputs
            finally:
                client.close()
        finally:
            server.stop()

    def test_collector_exports_session_plane(self):
        import urllib.request

        server, _ = _server()
        try:
            from triton_client_tpu.channel.grpc_channel import GRPCChannel

            client = GRPCChannel(f"127.0.0.1:{server.port}")
            try:
                client.do_inference(
                    InferRequest(
                        "echo", _detections([(0, 0)]), sequence_id="a",
                        sequence_start=True,
                    )
                )
            finally:
                client.close()
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.metrics_port}/metrics", timeout=5
            ).read().decode()
            assert "tpu_serving_sessions_active 1.0" in body
            assert "tpu_serving_session_frames_total 1.0" in body
            assert 'tpu_serving_sessions_total{event="created"} 1.0' in body
        finally:
            server.stop()


# -- session-affinity routing -------------------------------------------------


class TestFrontMemoUnderSessions:
    @pytest.mark.parametrize("shm", [False, True], ids=["wire", "shm"])
    def test_interleaved_sessions_answer_as_a_cold_server_does(self, shm):
        """Two sessions interleaved through one server, whose front memo
        is warm from the second frame on, answer array for array and id
        for id what a server does that sees each session alone; the
        counters say so (``/snapshot`` -> ``front_end``)."""
        import json
        import urllib.request

        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        def frames(sid):
            shift = 3.0 if sid == "cam-b" else 0.0
            return [
                InferRequest(
                    "echo", _detections([(0.2 * k + shift, shift)]),
                    request_id=f"{sid}-{k}", sequence_id=sid,
                    sequence_start=(k == 0), sequence_end=(k == 5),
                )
                for k in range(6)
            ]

        def serve(order):
            server, _ = _server()
            clients = {}
            try:
                got = {}
                for req in order:
                    client = clients.get(req.sequence_id)
                    if client is None:
                        client = clients[req.sequence_id] = GRPCChannel(
                            f"127.0.0.1:{server.port}", use_shared_memory=shm
                        )
                    resp = client.do_inference(req)
                    assert resp.request_id == req.request_id
                    got[req.request_id] = {
                        k: np.array(v) for k, v in resp.outputs.items()
                    }
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.metrics_port}/snapshot"
                ) as r:
                    return got, json.load(r)["front_end"]
            finally:
                for client in clients.values():
                    client.close()
                server.stop()

        a, b = frames("cam-a"), frames("cam-b")
        together, front = serve([r for pair in zip(a, b) for r in pair])
        alone = {**serve(a)[0], **serve(b)[0]}
        assert together.keys() == alone.keys()
        for request_id, outputs in alone.items():
            assert outputs.keys() == together[request_id].keys()
            for name, arr in outputs.items():
                other = together[request_id][name]
                if name.endswith("track_ids"):
                    # a track id carries its session's slot above bit 16,
                    # and the second of two live sessions sits in another
                    arr = np.where(arr > 0, arr & 0xFFFF, arr)
                    other = np.where(other > 0, other & 0xFFFF, other)
                np.testing.assert_array_equal(other, arr)
        assert front["handler_requests"] == 12
        assert front["handler_cpu_s"] > 0.0
        assert front["front_memo_hits"] + front["front_memo_misses"] == 12
        # a session's descriptors are new at its first frame, and once
        # more where the client has learned the answer's windows
        assert front["front_memo_hits"] >= 8


class TestAffinityRouting:
    def test_rendezvous_is_deterministic_and_spread(self):
        from triton_client_tpu.runtime.router import _rendezvous_score

        eps = [f"host{i}:8001" for i in range(3)]
        homes = {}
        for s in range(60):
            sid = f"stream-{s}"
            pick = max(eps, key=lambda e: (_rendezvous_score(sid, e), e))
            assert pick == max(
                eps, key=lambda e: (_rendezvous_score(sid, e), e)
            )
            homes.setdefault(pick, []).append(sid)
        # every replica owns a share of the streams
        assert len(homes) == 3

    def test_minimal_disruption_on_replica_loss(self):
        from triton_client_tpu.runtime.router import _rendezvous_score

        eps = [f"host{i}:8001" for i in range(3)]
        sids = [f"stream-{s}" for s in range(60)]

        def home(sid, pool):
            return max(pool, key=lambda e: (_rendezvous_score(sid, e), e))

        before = {sid: home(sid, eps) for sid in sids}
        survivors = eps[:2]
        for sid in sids:
            after = home(sid, survivors)
            if before[sid] in survivors:
                assert after == before[sid]  # unaffected streams stay put


# -- replay + chaos acceptance drives ----------------------------------------


@pytest.mark.slow
def test_replay_streams_sustained_and_consistent():
    """Multi-stream replay against one server: every stream sustains
    its pace, tracker outputs stay consistent (no ID churn on clean
    synthetic motion), and per-stream device-seconds appear under the
    ledger's stream tenant axis."""
    from triton_client_tpu.utils.loadgen import run_streams, synthetic_stream

    server, manager = _server(max_sessions=16)
    try:
        res = run_streams(
            f"127.0.0.1:{server.port}",
            "echo",
            n_streams=4,
            source=lambda i: synthetic_stream(
                n_frames=12, fps=40.0, n_objects=3, seed=i
            ),
            deadline_s=30.0,
        )
        assert res.frames_ok == res.frames_sent == 4 * 12
        assert res.goodput == 1.0
        assert res.aliases == 0
        for s in res.streams:
            assert s.sustained_fps > 0
        m = manager.stats()
        assert m["frames_total"] == 48
        assert m["ended_total"] == 4
        # per-stream device time on the ledger tenant axis
        ledger = server.device_time.device_seconds()
        stream_tenants = {
            k.split("|", 1)[1] for k in ledger if "|stream:" in k
        }
        assert len(stream_tenants) == 4
    finally:
        server.stop()


@pytest.mark.slow
def test_chaos_affinity_failover_rehomes_every_stream():
    """The acceptance chaos drive: N streams over a 2-replica router,
    one replica killed mid-run. Every surviving stream re-homes onto
    the survivor (explicit handoff, session restarted), goodput stays
    >=90%, and track ids never alias — distinct replica namespaces and
    fresh epochs on every re-home."""
    from triton_client_tpu.runtime.router import FrontDoorRouter
    from triton_client_tpu.utils.loadgen import run_streams, synthetic_stream

    s1, _m1 = _server(max_sessions=16, id_namespace=1)
    s2, _m2 = _server(max_sessions=16, id_namespace=2)
    router = FrontDoorRouter(
        [f"127.0.0.1:{s1.port}", f"127.0.0.1:{s2.port}"],
        models=("echo",), probe_interval_s=0.25, probe_timeout_s=1.0,
        timeout_s=10.0,
    )
    n_streams, n_frames = 6, 30
    killed = []

    def chaos():
        time.sleep(1.0)
        s1.stop()
        killed.append(True)

    ct = threading.Thread(target=chaos)
    try:
        ct.start()
        res = run_streams(
            router,
            "echo",
            n_streams=n_streams,
            source=lambda i: synthetic_stream(
                n_frames=n_frames, fps=10.0, n_objects=3, seed=i
            ),
            deadline_s=60.0,
        )
        ct.join(timeout=20.0)
        assert killed
        # >=90% goodput: the kill costs at most a frame per stream
        assert res.goodput >= 0.9, res.summary()
        # every stream kept flowing after the kill (re-homed, and its
        # session RESTARTED: switches recorded, never aliases)
        for s in res.streams:
            assert s.frames_ok >= 0.9 * n_frames, (s.stream_id, s.frames_ok)
            assert s.aliases == 0
        stats = router.stats()
        assert stats["affinity_routed"] >= n_streams * n_frames * 0.9
        # streams homed on the dead replica were explicitly handed off
        assert stats["affinity_handoffs"] >= 1
        # namespace disjointness: ids from the two replicas never collide
        ns = {
            tid >> 27
            for s in res.streams
            for tid in s.track_map
        }
        assert ns <= {1, 2} and len(ns) == 2
        all_ids = [tid for s in res.streams for tid in s.track_map]
        assert len(all_ids) == len(set(all_ids))  # no cross-stream alias
    finally:
        router.close()
        s2.stop()
        try:
            s1.stop()
        except Exception:
            pass
