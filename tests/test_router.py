"""Replicated front door: health-aware routing, outlier ejection,
hedged requests, retry budgets (the replication ring).

Covers the PR's acceptance contract:
  * ``ReplicaSet`` health machinery — active probing flips replicas in
    and out of rotation, passive outlier ejection holds a replica down
    for an exponentially growing window, p2c picks the less-loaded
    candidate, and the panic ladder never fails a request on the floor;
  * ``FrontDoorRouter`` retry discipline — UNAVAILABLE fails over to
    another replica and spends a retry-budget token, a drain failover
    is free (orchestrated, not a fault), RESOURCE_EXHAUSTED is NEVER
    retried (shedding must not amplify load), and a failure storm
    drives the budget to its observable floor without amplification;
  * hedging — launched only past the router's own latency quantile,
    capped by the hedge budget, first winner wins, and hedged outputs
    are bitwise identical to unhedged ones;
  * the ``replica_down`` fault point — flag-class injection that makes
    a live server answer not-ready and refuse work with UNAVAILABLE
    (no drain marker), exactly what a router should eject on;
  * GRPCChannel deadline discipline — the retry ladder fails fast with
    a client-local DEADLINE_EXCEEDED instead of sleeping past the
    caller's budget, and per-attempt wire timeouts are capped by the
    remaining deadline;
  * the dispatcher stall watchdog — a wedged dispatch loop is visible
    in stats() within the threshold and clears on recovery;
  * the chaos acceptance run — open loop against 3 in-process
    replicas, one killed and one drained mid-run: zero lost responses,
    goodput recovers to >=90% of steady state after the probe
    interval, hedge traffic stays inside its budget.
"""

import os
import threading
import time

import numpy as np
import pytest

from triton_client_tpu.runtime import faults
from triton_client_tpu.runtime.faults import (
    FaultPlan,
    FaultRule,
    install_fault_plan,
)
from triton_client_tpu.runtime.router import (
    FrontDoorRouter,
    ReplicaSet,
    RetryBudget,
    RouterCollector,
)

jax = pytest.importorskip("jax")

# the chaos CI shard pins this (ci.sh: TPU_FAULT_SEED=7) so the whole
# suite's fault timeline is one reproducible artifact
SEED = int(os.environ.get("TPU_FAULT_SEED", "7"))

X = np.arange(8, dtype=np.float32).reshape(2, 4)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test starts and ends with no process-wide fault plan."""
    prev = install_fault_plan(None)
    yield
    install_fault_plan(prev)


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
            time.sleep(sleep_s)
        return {"y": np.asarray(inputs["x"]) * 2.0}

    repo = ModelRepository()
    repo.register(spec, infer)
    return repo, spec


def _stack(repo, **server_kw):
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


def _infer(chan, model="double", x=X, **kw):
    from triton_client_tpu.channel.base import InferRequest

    return chan.do_inference(InferRequest(model, {"x": x}, **kw))


import grpc  # noqa: E402 — after the jax importorskip gate


class _FakeRpcError(grpc.RpcError):
    """Wire-shaped failure: a real grpc.RpcError subclass answering
    code()/details() with the named grpc.StatusCode, so both the
    channel's retry ladder and the router's classifier treat it
    exactly like a server-sent status."""

    def __init__(self, name, details=""):
        super().__init__(details or name)
        self._code = getattr(grpc.StatusCode, name)
        self._details = details

    def code(self):
        return self._code

    def details(self):
        return self._details


class _FakeChannel:
    """Replica stand-in for router unit tests. ``script(endpoint,
    request)`` returns a response or raises; futures are lazy, so the
    router's state machine runs synchronously and deterministically."""

    def __init__(self, endpoint, script, ready=True):
        self.endpoint = endpoint
        self.script = script
        self.ready = ready
        self.closed = False

    def do_inference_async(self, request):
        from triton_client_tpu.channel.base import InferFuture

        return InferFuture(lambda: self.script(self.endpoint, request))

    def do_inference(self, request):
        return self.do_inference_async(request).result()

    def server_ready(self, timeout_s=None):
        return self.ready

    def model_ready(self, model_name, model_version="", timeout_s=None):
        return self.ready

    def close(self):
        self.closed = True


def _ok_response(request):
    from triton_client_tpu.channel.base import InferResponse

    return InferResponse(
        model_name=request.model_name,
        model_version="1",
        outputs={"y": np.asarray(request.inputs["x"]) * 2.0},
        request_id=request.request_id,
    )


def _router(endpoints, script, **kw):
    kw.setdefault("probe_interval_s", 0.0)  # no background thread
    return FrontDoorRouter(
        list(endpoints),
        channel_factory=lambda ep: _FakeChannel(ep, script),
        **kw,
    )


# -- RetryBudget unit contract ------------------------------------------------


class TestRetryBudget:
    def test_spend_floor_and_deposit(self):
        b = RetryBudget(ratio=0.5, cap=10.0, initial=1.0)
        assert b.try_spend() is True  # the initial token
        assert b.tokens == 0.0
        assert b.try_spend() is False  # at the floor
        assert b.floor_hits == 1
        b.deposit()
        b.deposit()  # 2 x 0.5 = one token accrued
        assert b.try_spend() is True
        assert b.spent == 2

    def test_cap_bounds_banked_burst(self):
        b = RetryBudget(ratio=1.0, cap=2.0, initial=0.0)
        for _ in range(100):
            b.deposit()
        assert b.tokens == 2.0  # a quiet period cannot bank a storm


# -- ReplicaSet unit contract -------------------------------------------------


class TestReplicaSet:
    def _set(self, n=2, ready=True, **kw):
        kw.setdefault("probe_interval_s", 0.0)
        return ReplicaSet(
            [f"r{i}" for i in range(n)],
            channel_factory=lambda ep: _FakeChannel(
                ep, lambda _e, _r: None, ready=ready
            ),
            **kw,
        )

    def test_p2c_prefers_less_loaded(self):
        rs = self._set(2)
        a, b = rs.replicas
        a.inflight = 5  # b is strictly less loaded: p2c must pick it
        for _ in range(8):
            pick = rs.pick()
            assert pick is b
            rs.release(pick)

    def test_pick_excludes_and_counts_inflight(self):
        rs = self._set(2)
        a, b = rs.replicas
        pick = rs.pick(exclude=[a])
        assert pick is b and b.inflight == 1
        rs.release(pick)
        assert b.inflight == 0

    def test_ejection_threshold_and_exponential_hold(self):
        rs = self._set(
            2, eject_threshold=3, base_ejection_s=100.0,
            max_ejection_s=1000.0,
        )
        rep = rs.replicas[0]
        for _ in range(2):
            rs.record_failure(rep, connection_class=True)
        assert not rep.ejected(time.perf_counter())  # 2/3: still in
        rs.record_failure(rep, connection_class=True)
        now = time.perf_counter()
        assert rep.ejected(now)
        assert rep.ejected_until == pytest.approx(now + 100.0, abs=5.0)
        assert rs.ejections_total == 1
        # second ejection holds twice as long
        rep.ejected_until = 0.0
        for _ in range(3):
            rs.record_failure(rep, connection_class=True)
        assert rep.ejected_until == pytest.approx(
            time.perf_counter() + 200.0, abs=5.0
        )

    def test_non_connection_failures_never_eject(self):
        rs = self._set(1, eject_threshold=1)
        rep = rs.replicas[0]
        for _ in range(10):
            rs.record_failure(rep, connection_class=False)
        assert not rep.ejected(time.perf_counter())
        assert rep.failures == 10

    def test_probe_flips_rotation_and_clears_passive_state(self):
        rs = self._set(1, ready=False)
        rep = rs.replicas[0]
        assert rep.probe_ready  # optimistic before the first probe
        rs.probe_once()
        assert not rep.probe_ready
        assert rs.available_count() == 0
        rep.channel.ready = True
        rep.draining = True
        rep.consecutive_failures = 2
        rs.probe_once()
        # an affirmative probe supersedes stale passive signals
        assert rep.probe_ready and not rep.draining
        assert rep.consecutive_failures == 0
        assert rs.available_count() == 1

    def test_panic_ladder_always_picks(self):
        rs = self._set(2, eject_threshold=1, base_ejection_s=100.0)
        for rep in rs.replicas:
            rs.record_failure(rep, connection_class=True)
        assert rs.available_count() == 0
        assert rs.pick() is not None  # zero-lost-responses contract

    def test_close_closes_channels(self):
        rs = self._set(2)
        rs.close()
        assert all(r.channel.closed for r in rs.replicas)


# -- FrontDoorRouter retry discipline -----------------------------------------


class TestRouterRetries:
    def test_unavailable_fails_over_and_spends_budget(self):
        calls = []

        def script(ep, request):
            calls.append(ep)
            if len(calls) == 1:
                raise _FakeRpcError("UNAVAILABLE", "connection refused")
            return _ok_response(request)

        r = _router(["a", "b"], script)
        try:
            resp = _infer(r)
            np.testing.assert_array_equal(resp.outputs["y"], X * 2.0)
            s = r.stats()
            assert s["failovers"] == 1 and s["retries_spent"] == 1
            assert s["drain_failovers"] == 0 and s["errors_total"] == 0
            assert calls[0] != calls[1]  # the retry went elsewhere
        finally:
            r.close()

    def test_drain_failover_is_free_and_pulls_replica(self):
        calls = []

        def script(ep, request):
            calls.append(ep)
            if len(calls) == 1:
                raise _FakeRpcError("UNAVAILABLE", "server draining")
            return _ok_response(request)

        r = _router(["a", "b"], script)
        try:
            resp = _infer(r)
            np.testing.assert_array_equal(resp.outputs["y"], X * 2.0)
            s = r.stats()
            assert s["drain_failovers"] == 1 and s["failovers"] == 1
            assert s["retries_spent"] == 0  # a drain is not a fault
            drained = [
                rep for rep in r.snapshot()["replicas"] if rep["draining"]
            ]
            assert [d["endpoint"] for d in drained] == [calls[0]]
        finally:
            r.close()

    def test_resource_exhausted_never_retried(self):
        calls = []

        def script(ep, request):
            calls.append(ep)
            raise _FakeRpcError("RESOURCE_EXHAUSTED", "queue full")

        r = _router(["a", "b"], script)
        try:
            with pytest.raises(_FakeRpcError):
                _infer(r)
            assert len(calls) == 1  # shedding must not amplify load
            s = r.stats()
            assert s["errors_total"] == 1 and s["failovers"] == 0
        finally:
            r.close()

    def test_failure_storm_hits_budget_floor_without_amplification(self):
        calls = []

        def script(ep, request):
            calls.append(ep)
            raise _FakeRpcError("UNAVAILABLE", "connection refused")

        # ratio 0 keeps the bucket at its initial 3 tokens: the storm
        # must drain them and then STOP retrying
        r = _router(
            ["a", "b"], script, retry_budget_ratio=0.0, max_attempts=10,
            eject_threshold=1000,
        )
        try:
            for _ in range(3):
                with pytest.raises(_FakeRpcError):
                    _infer(r)
            s = r.stats()
            assert s["retry_budget_floor_hits"] >= 1
            assert s["retry_budget_tokens"] == 0.0  # observable floor
            assert s["retries_spent"] == 3
            # 3 requests, 3 budgeted retries total: 6 attempts on the
            # wire, not 3 x max_attempts — no amplification
            assert len(calls) == 6
            assert s["errors_total"] == 3
        finally:
            r.close()

    def test_max_attempts_caps_failover_chain(self):
        calls = []

        def script(ep, request):
            calls.append(ep)
            raise _FakeRpcError("UNAVAILABLE", "connection refused")

        r = _router(
            ["a", "b", "c"], script, max_attempts=2, retry_budget_cap=100.0,
            eject_threshold=1000,
        )
        try:
            with pytest.raises(_FakeRpcError):
                _infer(r)
            assert len(calls) == 2  # primary + one failover, capped
        finally:
            r.close()

    def test_ejection_via_router_failures(self):
        calls = []

        def script(ep, request):
            calls.append(ep)
            if ep == "a":
                raise _FakeRpcError("UNAVAILABLE", "connection refused")
            return _ok_response(request)

        r = _router(
            ["a", "b"], script, eject_threshold=2, base_ejection_s=60.0,
            retry_budget_cap=100.0, retry_budget_ratio=1.0,
        )
        try:
            # p2c primaries are random: drive requests until a's streak
            # reaches the threshold (failovers land on b throughout)
            for _ in range(64):
                _infer(r)
                if r.stats()["ejections_total"] >= 1:
                    break
            assert r.stats()["ejections_total"] >= 1
            snap = {
                rep["endpoint"]: rep for rep in r.snapshot()["replicas"]
            }
            assert snap["a"]["ejected"] is True
            # with a ejected, traffic goes straight to b
            calls.clear()
            _infer(r)
            _infer(r)
            assert calls == ["b", "b"]
        finally:
            r.close()

    def test_deadline_class_never_retried(self):
        calls = []

        def script(ep, request):
            calls.append(ep)
            raise _FakeRpcError("DEADLINE_EXCEEDED", "budget spent")

        r = _router(["a", "b"], script)
        try:
            with pytest.raises(_FakeRpcError):
                _infer(r)
            assert len(calls) == 1  # nobody is waiting: no failover
            s = r.stats()
            assert s["errors_total"] == 1 and s["failovers"] == 0
        finally:
            r.close()


# -- hedging ------------------------------------------------------------------


class TestHedging:
    def test_no_hedge_below_min_samples(self):
        r = _router(["a", "b"], lambda ep, req: _ok_response(req))
        try:
            assert r._hedge_delay_s() is None
            for _ in range(5):
                _infer(r)
            assert r.stats()["hedges_launched"] == 0
        finally:
            r.close()

    def test_hedge_delay_tracks_quantile(self):
        r = _router(
            ["a", "b"], lambda ep, req: _ok_response(req),
            hedge_min_samples=10,
        )
        try:
            for _ in range(20):
                r._latency.observe(0.04)
            delay = r._hedge_delay_s()
            assert delay is not None and 0.02 <= delay <= 0.06
        finally:
            r.close()

    def test_hedge_budget_denies_past_fraction(self):
        r = _router(
            ["a", "b"], lambda ep, req: _ok_response(req),
            hedge_budget_fraction=0.05,
        )
        try:
            # floor population is 20: one hedge allowed, second denied
            assert r._hedge_allowed() is True
            r._hedges_launched = 1
            assert r._hedge_allowed() is False
            assert r.stats()["hedges_denied"] == 1
        finally:
            r.close()


# -- GRPCChannel deadline discipline (satellite) ------------------------------


class TestChannelDeadline:
    def _channel(self, **kw):
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        kw.setdefault("timeout_s", 30.0)
        return GRPCChannel("127.0.0.1:1", **kw)  # never actually dialed

    def test_expired_deadline_fails_fast_without_wire_touch(self):
        import grpc

        from triton_client_tpu.channel.grpc_channel import (
            DeadlineExceededRpcError,
        )

        chan = self._channel(retries=3)
        attempts = []

        def method(request, timeout=None):
            attempts.append(timeout)
            raise AssertionError("must not reach the wire")

        with pytest.raises(DeadlineExceededRpcError) as ei:
            chan._call(method, None, deadline_s=time.perf_counter() - 1.0)
        assert ei.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
        assert attempts == []

    def test_backoff_never_outlives_deadline(self):
        import grpc

        from triton_client_tpu.channel.grpc_channel import (
            DeadlineExceededRpcError,
        )

        # backoff sleep (>= 0.5s after jitter) exceeds the 0.2s budget:
        # the ladder must fail fast instead of sleeping past it
        chan = self._channel(retries=3, backoff_s=1.0)
        attempts = []

        def method(request, timeout=None):
            attempts.append(timeout)
            raise _FakeRpcError("UNAVAILABLE", "connection refused")

        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceededRpcError):
            chan._call(
                method, None,
                retryable=(grpc.StatusCode.UNAVAILABLE,),
                deadline_s=t0 + 0.2,
            )
        wall = time.perf_counter() - t0
        assert wall < 0.2, wall  # no sleep was taken
        assert len(attempts) == 1  # one attempt, then fail-fast

    def test_per_attempt_timeout_capped_by_remaining(self):
        chan = self._channel(timeout_s=30.0, retries=0)
        seen = []

        def method(request, timeout=None):
            seen.append(timeout)
            return "ok"

        assert (
            chan._call(method, None, deadline_s=time.perf_counter() + 0.5)
            == "ok"
        )
        assert seen[0] <= 0.5

    def test_async_expired_deadline_surfaces_at_result(self):
        import grpc

        from triton_client_tpu.channel.base import InferRequest

        chan = self._channel(retries=0)
        fut = chan.do_inference_async(
            InferRequest(
                "double", {"x": X}, deadline_s=time.perf_counter() - 1.0
            )
        )
        with pytest.raises(grpc.RpcError) as ei:
            fut.result()
        assert ei.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED


# -- dispatcher stall watchdog (satellite) ------------------------------------


class _EchoInner:
    """Minimal inner channel: instant doubled echo."""

    def register_channel(self):
        pass

    def do_inference_async(self, request):
        from triton_client_tpu.channel.base import InferFuture

        return InferFuture(lambda: _ok_response(request))

    def do_inference(self, request):
        return self.do_inference_async(request).result()

    def stats(self):
        return {}

    def close(self):
        pass


class TestDispatcherWatchdog:
    def test_stall_is_visible_and_clears_on_recovery(self):
        from triton_client_tpu.runtime.continuous import (
            ContinuousBatchingChannel,
        )

        chan = ContinuousBatchingChannel(
            _EchoInner(), max_batch=1, pipeline_depth=1
        )
        chan.stall_threshold_s = 0.2
        try:
            assert chan.stats()["dispatcher_stalled"] == 0
            install_fault_plan(
                FaultPlan(
                    [FaultRule(point="batcher_stall", latency_s=1.0, count=1)],
                    seed=SEED,
                )
            )
            done = {}

            def call():
                done["resp"] = _infer(chan)

            t = threading.Thread(target=call)
            t.start()
            time.sleep(0.6)  # the stall is holding dispatch right now
            s = chan.stats()
            assert s["dispatcher_last_progress_age_s"] >= 0.2
            assert s["dispatcher_stalled"] == 1
            t.join(timeout=10.0)
            np.testing.assert_array_equal(done["resp"].outputs["y"], X * 2.0)
            deadline = time.perf_counter() + 5.0
            while time.perf_counter() < deadline:
                if chan.stats()["dispatcher_stalled"] == 0:
                    break
                time.sleep(0.05)
            assert chan.stats()["dispatcher_stalled"] == 0
        finally:
            chan.close()

    def test_watchdog_gauges_ride_the_collector(self):
        import urllib.request

        repo, _ = _repo()
        chan, server = _stack(repo)
        try:
            scrape = urllib.request.urlopen(
                f"http://127.0.0.1:{server.metrics_port}/metrics", timeout=5
            ).read().decode()
            assert "tpu_serving_dispatcher_stalled 0.0" in scrape
            assert "tpu_serving_dispatcher_last_progress_seconds" in scrape
        finally:
            server.stop()


# -- replica_down fault + route tool (live) -----------------------------------


class TestReplicaDownFault:
    def test_probe_flag_flips_readiness(self):
        from triton_client_tpu.channel.grpc_channel import GRPCChannel

        repo, _ = _repo()
        chan, server = _stack(repo, replica_of="cell0/r1")
        try:
            client = GRPCChannel(f"127.0.0.1:{server.port}", retries=0)
            try:
                assert client.server_ready() is True
                install_fault_plan(
                    FaultPlan(
                        [FaultRule(point="replica_down", model="cell0/r1",
                                   count=1)],
                        seed=SEED,
                    )
                )
                assert client.server_ready() is False  # consumes the flag
                assert client.server_ready() is True  # window over
            finally:
                client.close()
        finally:
            server.stop()

    def test_issue_refuses_unavailable_without_drain_marker(self):
        import grpc

        repo, _ = _repo()
        chan, server = _stack(repo, replica_of="cell0/r1")
        try:
            client = None
            from triton_client_tpu.channel.grpc_channel import GRPCChannel

            client = GRPCChannel(f"127.0.0.1:{server.port}", retries=0)
            try:
                install_fault_plan(
                    FaultPlan(
                        [FaultRule(point="replica_down", model="cell0/r1",
                                   count=1)],
                        seed=SEED,
                    )
                )
                with pytest.raises(grpc.RpcError) as ei:
                    _infer(client)
                assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
                # ejection-class to routers: NOT a drain
                assert "draining" not in (ei.value.details() or "")
                resp = _infer(client)  # window over: same server serves
                np.testing.assert_array_equal(resp.outputs["y"], X * 2.0)
            finally:
                client.close()
        finally:
            server.stop()

    def test_route_tool_reports_rotation(self, capsys):
        from triton_client_tpu.cli.tools import route

        repo, _ = _repo()
        chan, server = _stack(repo, replica_of="cell0/r1")
        ep = f"127.0.0.1:{server.port}"
        try:
            route([ep, "-m", "double", "--timeout", "5.0"])
            out = capsys.readouterr().out
            assert "IN-ROTATION" in out
            assert "replica_of=cell0/r1" in out
            assert "1/1 in rotation" in out
        finally:
            server.stop()
        with pytest.raises(SystemExit) as ei:
            route([ep, "--timeout", "0.5"])
        assert ei.value.code == 1
        assert "DEAD" in capsys.readouterr().out


# -- live router over real replicas -------------------------------------------


class TestRouterLive:
    def test_hedged_outputs_bitwise_identical_to_unhedged(self):
        repo, _ = _repo(sleep_s=0.15)
        stacks = [_stack(repo) for _ in range(2)]
        endpoints = [f"127.0.0.1:{s.port}" for _c, s in stacks]
        try:
            plain = FrontDoorRouter(
                endpoints, probe_interval_s=0.0, hedge_min_samples=10**9
            )
            try:
                reference = _infer(plain).outputs["y"]
                assert plain.stats()["hedges_launched"] == 0
            finally:
                plain.close()

            hedged = FrontDoorRouter(
                endpoints, probe_interval_s=0.0, hedge_min_samples=10,
                hedge_budget_fraction=1.0,
            )
            try:
                for _ in range(20):  # prime the quantile far below the
                    hedged._latency.observe(0.01)  # 0.15s service time
                resp = _infer(hedged)
                s = hedged.stats()
                assert s["hedges_launched"] == 1
                assert s["hedges_won"] + s["hedges_lost"] == 1
                np.testing.assert_array_equal(resp.outputs["y"], reference)
                assert s["errors_total"] == 0
            finally:
                hedged.close()
        finally:
            for _c, server in stacks:
                server.stop()

    def test_drain_during_hedged_request_no_lost_response(self):
        """Satellite regression: InferenceServer.drain() fired while a
        hedged request has attempts in flight on BOTH replicas — the
        request resolves exactly once, nothing is lost, and the drained
        server finishes its in-flight work."""
        repo, _ = _repo(sleep_s=0.4)
        stacks = [_stack(repo) for _ in range(2)]
        endpoints = [f"127.0.0.1:{s.port}" for _c, s in stacks]
        try:
            r = FrontDoorRouter(
                endpoints, probe_interval_s=0.0, hedge_min_samples=10,
                hedge_budget_fraction=1.0,
            )
            try:
                for _ in range(20):
                    r._latency.observe(0.02)
                results = []

                def call():
                    results.append(_infer(r))

                t = threading.Thread(target=call)
                t.start()
                time.sleep(0.2)  # primary AND hedge are both in flight
                assert r.stats()["hedges_launched"] == 1
                drained = {}
                dt = threading.Thread(
                    target=lambda: drained.update(
                        ok=stacks[0][1].drain(timeout_s=10.0)
                    )
                )
                dt.start()
                t.join(timeout=10.0)
                dt.join(timeout=15.0)
                assert len(results) == 1  # exactly one resolution
                np.testing.assert_array_equal(
                    results[0].outputs["y"], X * 2.0
                )
                assert drained["ok"] is True
                s = r.stats()
                assert s["requests_total"] == 1 and s["errors_total"] == 0
            finally:
                r.close()
        finally:
            for _c, server in stacks:
                server.stop()

    def test_collector_exports_router_families(self):
        pytest.importorskip("prometheus_client")
        repo, _ = _repo()
        chan, server = _stack(repo)
        try:
            r = FrontDoorRouter(
                [f"127.0.0.1:{server.port}"], probe_interval_s=0.0
            )
            try:
                _infer(r)
                fams = {m.name: m for m in RouterCollector(r).collect()}
                # prometheus strips the _total suffix from counter names
                assert fams["tpu_router_requests"].samples[0].value == 1.0
                assert "tpu_router_retry_budget_tokens" in fams
                avail = fams["tpu_router_replica_available"].samples
                assert avail[0].labels["endpoint"].startswith("127.0.0.1:")
                assert avail[0].value == 1.0
            finally:
                r.close()
        finally:
            server.stop()


# -- the chaos acceptance run -------------------------------------------------


@pytest.mark.slow
def test_chaos_replica_kill_and_drain_keeps_goodput():
    """Open loop against 3 in-process replicas; mid-run one replica is
    KILLED and another DRAINED. Acceptance: zero lost responses (every
    scheduled request completes or surfaces an error), goodput after
    the probe interval recovers to >=90% of steady state, and hedge
    traffic stays inside its budget."""
    from triton_client_tpu.utils.loadgen import run_open_loop

    slo_ms = 1000.0
    repo, _ = _repo()
    stacks = [_stack(repo) for _ in range(3)]
    endpoints = [f"127.0.0.1:{s.port}" for _c, s in stacks]
    router = FrontDoorRouter(
        endpoints, models=("double",), probe_interval_s=0.25,
        probe_timeout_s=1.0, timeout_s=10.0, eject_threshold=2,
        base_ejection_s=0.5,
    )
    try:
        steady = run_open_loop(
            router, [("double", {"x": X})], rate_qps=30.0, duration_s=1.5,
            seed=SEED, deadline_s=10.0,
        )
        assert steady.completed == steady.scheduled, steady.errors
        steady_goodput = steady.goodput_qps(slo_ms)
        assert steady_goodput > 0

        # chaos window: kill one replica and drain another mid-run
        def chaos():
            time.sleep(0.8)
            stacks[0][1].stop()  # killed: UNAVAILABLE / dead socket
            stacks[1][1].drain(timeout_s=10.0)  # orchestrated drain

        ct = threading.Thread(target=chaos)
        ct.start()
        chaotic = run_open_loop(
            router, [("double", {"x": X})], rate_qps=30.0, duration_s=3.0,
            seed=SEED + 1, deadline_s=10.0, warm=False,
        )
        ct.join(timeout=20.0)
        # zero lost responses: every scheduled request is accounted for
        assert chaotic.completed + len(chaotic.errors) == chaotic.scheduled
        # the vast majority completed (failovers absorbed the kill)
        assert chaotic.completed >= 0.9 * chaotic.scheduled, (
            chaotic.completed, chaotic.scheduled, chaotic.errors[:5]
        )

        # recovery: past the probe interval the fleet is one replica;
        # goodput must be back to >=90% of steady state
        time.sleep(2 * 0.25 + 0.1)
        snap = {r["endpoint"]: r for r in router.snapshot()["replicas"]}
        assert not snap[endpoints[2]]["draining"]
        recovered = run_open_loop(
            router, [("double", {"x": X})], rate_qps=30.0, duration_s=1.5,
            seed=SEED + 2, deadline_s=10.0, warm=False,
        )
        assert recovered.completed == recovered.scheduled, (
            recovered.errors[:5]
        )
        assert recovered.goodput_qps(slo_ms) >= 0.9 * steady_goodput

        s = router.stats()
        # hedge traffic bounded by the budget over the whole run: every
        # launch satisfied hedges+1 <= fraction * max(requests, 20) at
        # the time it fired, and requests only grow
        assert s["hedges_launched"] <= 0.05 * max(s["requests_total"], 20)
        assert s["requests_total"] == (
            steady.scheduled + chaotic.scheduled + recovered.scheduled
            + 1  # the steady window's warm request
        )
    finally:
        router.close()
        for _c, server in stacks:
            try:
                server.stop()
            except Exception:
                pass
