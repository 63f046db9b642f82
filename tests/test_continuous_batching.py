"""Continuous batching + ragged execution (ISSUE 8 tentpole).

Four contract planes of ``ContinuousBatchingChannel``:

  * **EDF admission** — with the single execution slot held, queued
    requests launch earliest-deadline-first (ties: higher priority,
    then arrival), not FIFO;
  * **dense bitwise parity** — the continuous scheduler's dense path
    produces byte-identical outputs to the legacy window
    ``BatchingChannel`` (and to the eager model), per request;
  * **packed ragged parity** — variable-row requests packed into one
    segment-table batch match their solo (true-size) execution, on the
    single-device channel and shard-major across the 8-device mesh;
  * **the padding tax** — under a seeded open-loop mixed drive the
    served pad fraction stays under the 5% acceptance bar (the window
    batcher's static buckets padded up to a third of device rows).
"""

import concurrent.futures
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from triton_client_tpu.channel import InferRequest, TPUChannel
from triton_client_tpu.channel.sharded_channel import ShardedTPUChannel
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.parallel.mesh import MeshConfig
from triton_client_tpu.parallel.ragged_kernels import segment_reduce
from triton_client_tpu.runtime import ModelRepository
from triton_client_tpu.runtime.batching import BatchingChannel
from triton_client_tpu.runtime.continuous import (
    ContinuousBatchingChannel,
    LiveBuckets,
)

_W = np.linspace(-1.0, 1.0, 16, dtype=np.float32).reshape(4, 4)


def _dense_compute(inputs):
    x = inputs["x"]
    return {"y": jnp.tanh(x @ jnp.asarray(_W)) + 0.5 * x}


def _dense_spec(name="dense"):
    return ModelSpec(
        name=name,
        version="1",
        inputs=(TensorSpec("x", (-1, 4), "FP32"),),
        outputs=(TensorSpec("y", (-1, 4), "FP32"),),
    )


def _dense_infer_fn(inputs):
    return {k: np.asarray(v) for k, v in _dense_compute(inputs).items()}


# -- ragged pool model: per-cloud tanh-projection + segment-sum, with a
#    per-segment bias so the sharded path must keep bias rows next to
#    their segments. Solo contract: points (n, 4) + bias (1, 4) ->
#    pooled (4,).

def _ragged_fn(inputs, segment_ids, num_segments):
    feat = jnp.tanh(inputs["points"] @ jnp.asarray(_W))
    pooled = segment_reduce(feat, segment_ids, num_segments, "sum")
    return {"pooled": pooled + jnp.squeeze(inputs["bias"], axis=1)}


def _pool_infer_fn(inputs):
    pooled = jnp.sum(
        jnp.tanh(jnp.asarray(inputs["points"]) @ jnp.asarray(_W)), axis=0
    )
    return {"pooled": np.asarray(pooled + jnp.asarray(inputs["bias"])[0])}


def _pool_spec(name="pool"):
    return ModelSpec(
        name=name,
        version="1",
        inputs=(
            TensorSpec("points", (-1, 4), "FP32"),
            TensorSpec("bias", (1, 4), "FP32"),
        ),
        outputs=(TensorSpec("pooled", (4,), "FP32"),),
        extra={"ragged_inputs": ["points"]},
    )


def _expected_pool(points, bias):
    return np.tanh(points @ _W).sum(axis=0) + bias[0]


def _cloud(seed, n):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal((n, 4)).astype(np.float32),
        rng.standard_normal((1, 4)).astype(np.float32),
    )


@pytest.fixture(scope="module")
def pool_repo():
    r = ModelRepository()
    r.register(_pool_spec(), _pool_infer_fn, ragged_fn=_ragged_fn)
    return r


# -- LiveBuckets -----------------------------------------------------------


def test_live_buckets_learns_frequent_sizes():
    lb = LiveBuckets(multiple=1, warmup=32)
    assert lb.target(6) == 8  # static pow2 fallback before warmup
    for _ in range(48):
        lb.observe(6)
    assert 6 in lb.table
    assert lb.target(6) == 6  # the recurring size pads to itself
    assert lb.target(5) == 6  # smaller totals ride the learned bucket
    assert lb.target(7) == 8  # above every learned size: static table


def test_live_buckets_respects_shard_multiple():
    lb = LiveBuckets(multiple=4, warmup=32)
    for _ in range(48):
        lb.observe(6)
    # every learned bucket must stay divisible by the data axis
    assert all(s % 4 == 0 for s in lb.table)
    assert lb.target(6) == 8


# -- EDF admission ---------------------------------------------------------


class _RecordingInner:
    """Duck-typed inner channel: records launch order; the FIRST call
    blocks on a gate so the single execution slot stays held while the
    test scrambles the ready queue."""

    batch_multiple = 1

    def __init__(self):
        self.order = []
        self.first_started = threading.Event()
        self.gate = threading.Event()

    def get_metadata(self, name, version=""):
        raise KeyError(name)  # no spec: requests take the dense path

    def do_inference_async(self, request):
        self.order.append(request.request_id)
        if len(self.order) == 1:
            self.first_started.set()
            assert self.gate.wait(timeout=30.0)
        from triton_client_tpu.channel.base import InferResponse

        fut = concurrent.futures.Future()
        fut.set_result(
            InferResponse(
                model_name=request.model_name,
                outputs={},
                request_id=request.request_id,
            )
        )
        return fut


def test_edf_ordering_under_held_slot():
    inner = _RecordingInner()
    chan = ContinuousBatchingChannel(
        inner,
        max_batch=1,
        pipeline_depth=1,
        max_merge=1,  # every request dispatches alone: pure ordering
        pad_to_buckets=False,
        live_buckets=False,
    )
    threads = []

    def submit(rid, deadline, priority=0):
        t = threading.Thread(
            target=chan.do_inference,
            args=(
                InferRequest(
                    "m",
                    {"x": np.zeros((1, 4), np.float32)},
                    request_id=rid,
                    deadline_s=deadline,
                    priority=priority,
                ),
            ),
            daemon=True,
        )
        t.start()
        threads.append(t)

    try:
        submit("blocker", None)
        assert inner.first_started.wait(timeout=30.0)
        # enqueue in scrambled order; wait for each insert so arrival
        # order is deterministic (it breaks the final tie)
        plan = [
            ("late", None, 0),
            ("d5-lo", 5.0, 0),
            ("d1", 1.0, 0),
            ("d5-hi", 5.0, 7),
            ("d05", 0.5, 0),
        ]
        for k, (rid, dl, pr) in enumerate(plan, start=1):
            submit(rid, dl, pr)
            deadline = time.time() + 10.0
            while time.time() < deadline:
                with chan._ready_cv:
                    if len(chan._ready) >= k:
                        break
                time.sleep(0.005)
            else:
                pytest.fail(f"request {rid} never reached the ready set")
        inner.gate.set()
        for t in threads:
            t.join(timeout=30.0)
            assert not t.is_alive()
    finally:
        inner.gate.set()
        chan.close()
    assert inner.order == ["blocker", "d05", "d1", "d5-hi", "d5-lo", "late"]


def test_window_knobs_accepted_and_ignored():
    inner = _RecordingInner()
    inner.gate.set()
    chan = ContinuousBatchingChannel(
        inner, timeout_us=5000, merge_hold_us=9999, use_native=True
    )
    try:
        assert chan._merge_hold_s == 0  # EDF head is never held
        assert chan._impl is None and chan._py is None  # no window thread
        s = chan.stats()
        assert s["scheduler"] == "continuous"
        assert s["pad_fraction"] == 0.0
    finally:
        chan.close()


# -- dense bitwise parity --------------------------------------------------


def test_dense_path_bitwise_matches_window_batcher():
    frames = {
        i: np.random.default_rng(i).standard_normal((2, 4)).astype(np.float32)
        for i in range(16)
    }

    def serve(make_batcher):
        repo = ModelRepository()
        repo.register(_dense_spec(), _dense_infer_fn, device_fn=_dense_compute)
        chan = make_batcher(TPUChannel(repo, MeshConfig(data=-1, model=1)))
        out = {}
        try:
            def call(i):
                resp = chan.do_inference(
                    InferRequest("dense", {"x": frames[i]})
                )
                out[i] = resp.outputs["y"]

            threads = [
                threading.Thread(target=call, args=(i,), daemon=True)
                for i in frames
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
                assert not t.is_alive()
        finally:
            chan.close()
        return out

    window = serve(
        lambda inner: BatchingChannel(
            inner, max_batch=8, timeout_us=2000, use_native=False,
            pad_to_buckets=True,
        )
    )
    continuous = serve(
        lambda inner: ContinuousBatchingChannel(
            inner, max_batch=8, pad_to_buckets=True
        )
    )
    for i, x in frames.items():
        direct = _dense_infer_fn({"x": x})["y"]
        np.testing.assert_array_equal(continuous[i], window[i])
        np.testing.assert_array_equal(continuous[i], direct)


# -- packed ragged parity --------------------------------------------------


def _ragged_group_case(chan_factory, sizes, rtol):
    """White-box determinism: hand one multi-member group to
    ``_run_ragged_group`` and check every member against its solo
    (true-size) result — no scheduler timing involved."""
    clouds = {i: _cloud(100 + i, n) for i, n in enumerate(sizes)}
    cont = chan_factory()
    try:
        futs = {i: concurrent.futures.Future() for i in clouds}
        group = [
            (
                None,
                InferRequest(
                    "pool", {"points": pts, "bias": bias}, request_id=str(i)
                ),
                futs[i],
            )
            for i, (pts, bias) in clouds.items()
        ]
        cont._run_ragged_group(group)
        for i, (pts, bias) in clouds.items():
            got = futs[i].result(timeout=60.0).outputs["pooled"]
            np.testing.assert_allclose(
                got, _expected_pool(pts, bias), rtol=rtol, atol=1e-5
            )
        s = cont.stats()
        assert s["ragged_batches"] == 1
        assert s["ragged_segments"] == len(sizes)
        assert s["ragged_rows"] == sum(sizes)
    finally:
        cont.close()


def test_ragged_group_matches_solo(pool_repo):
    _ragged_group_case(
        lambda: ContinuousBatchingChannel(
            TPUChannel(pool_repo, MeshConfig(data=-1, model=1))
        ),
        sizes=(3, 11, 8, 40, 5),
        rtol=1e-5,
    )


def test_ragged_group_matches_solo_sharded(pool_repo):
    _ragged_group_case(
        lambda: ContinuousBatchingChannel(
            ShardedTPUChannel(pool_repo, MeshConfig(data=-1, model=1))
        ),
        sizes=(5, 1, 1, 1, 4, 4, 17, 9),
        rtol=1e-5,
    )


def test_ragged_requests_pack_end_to_end(pool_repo):
    """Threaded e2e: concurrent variable-size requests through the full
    scheduler. Every response must match solo; with the single slot
    serialized (depth 1) the burst must pack at least once."""
    chan = ContinuousBatchingChannel(
        TPUChannel(pool_repo, MeshConfig(data=-1, model=1)),
        max_batch=8,
        pipeline_depth=1,
    )
    sizes = [3, 11, 8, 40, 5, 16, 7, 9, 24, 1]
    clouds = {i: _cloud(i, n) for i, n in enumerate(sizes)}
    out = {}
    barrier = threading.Barrier(len(clouds))

    def call(i):
        pts, bias = clouds[i]
        barrier.wait(timeout=30.0)
        resp = chan.do_inference(
            InferRequest("pool", {"points": pts, "bias": bias})
        )
        out[i] = resp.outputs["pooled"]

    try:
        threads = [
            threading.Thread(target=call, args=(i,), daemon=True)
            for i in clouds
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
            assert not t.is_alive()
        stats = chan.stats()
    finally:
        chan.close()
    for i, (pts, bias) in clouds.items():
        np.testing.assert_allclose(
            out[i], _expected_pool(pts, bias), rtol=1e-5, atol=1e-5
        )
    # the burst arrived while the first launch held the slot, so the
    # scheduler had to form at least one packed batch
    assert stats["ragged_batches"] >= 1
    assert stats["ragged_segments"] + 0 <= len(sizes)
    assert stats["ragged_rows"] <= sum(sizes)


# -- padding tax under open-loop drive (acceptance: < 5%) ------------------


@pytest.mark.slow
def test_pad_fraction_under_open_loop_drive(pool_repo):
    """Seeded open-loop mixed drive over the real gRPC server: 16-deep
    resolver pool, two cloud sizes. Ragged packing must keep the served
    pad fraction under the 5% acceptance bar (sizes are sublane-aligned
    and max_merge=4 keeps totals inside the zero-slack row buckets, so
    the only padding the scheduler COULD add is dense-bucket pad — the
    tax this PR removes)."""
    from triton_client_tpu.runtime.server import InferenceServer
    from triton_client_tpu.utils.loadgen import run_open_loop

    chan = ContinuousBatchingChannel(
        TPUChannel(pool_repo, MeshConfig(data=-1, model=1)),
        max_batch=4,
        max_merge=4,
        pipeline_depth=2,
    )
    server = InferenceServer(
        pool_repo, chan, address="127.0.0.1:0", max_workers=24
    )
    server.start()
    try:
        p16, b16 = _cloud(1, 16)
        p32, b32 = _cloud(2, 32)
        scenarios = [
            ("pool", {"points": p16, "bias": b16}),
            ("pool", {"points": p32, "bias": b32}),
        ]
        # warm both layouts outside the window (first ragged launch
        # compiles)
        res = run_open_loop(
            f"127.0.0.1:{server.port}",
            scenarios,
            rate_qps=60.0,
            duration_s=4.0,
            seed=7,
            deadline_s=120.0,
            resolvers=16,
        )
        stats = chan.stats()
    finally:
        server.stop()
        chan.close()
    assert not res.errors, res.errors[:3]
    assert res.completed == res.scheduled
    assert stats["ragged_batches"] >= 1
    # the acceptance bar: < 5% of shipped device rows were padding
    assert stats["pad_fraction"] < 0.05, stats
    # occupancy accounting stays coherent for the telemetry plane
    assert stats["ragged_rows"] >= stats["ragged_segments"]
    assert stats["ragged_pad_rows"] == 0


@pytest.mark.slow
def test_dense_occupancy_accounting_under_drive():
    """Closed-ish dense drive: the merge-occupancy ledger must cover
    every dispatch and the live-bucket fold must keep pad accounting
    consistent (padded_by_model sums to padded_frames)."""
    from triton_client_tpu.runtime.server import InferenceServer
    from triton_client_tpu.utils.loadgen import run_open_loop

    repo = ModelRepository()
    repo.register(_dense_spec(), _dense_infer_fn, device_fn=_dense_compute)
    chan = ContinuousBatchingChannel(
        TPUChannel(repo, MeshConfig(data=-1, model=1)),
        max_batch=8,
        pipeline_depth=2,
    )
    server = InferenceServer(repo, chan, address="127.0.0.1:0", max_workers=24)
    server.start()
    try:
        x = np.random.default_rng(0).standard_normal((2, 4)).astype(np.float32)
        res = run_open_loop(
            f"127.0.0.1:{server.port}",
            [("dense", {"x": x})],
            rate_qps=80.0,
            duration_s=4.0,
            seed=11,
            deadline_s=120.0,
            resolvers=16,
        )
        stats = chan.stats()
    finally:
        server.stop()
        chan.close()
    assert not res.errors, res.errors[:3]
    assert stats["merges"] >= 1
    occ = stats["merge_occupancy"]
    assert sum(occ.values()) == stats["merges"]
    assert sum(k * v for k, v in occ.items()) == stats["merged_frames"]
    assert sum(stats["padded_by_model"].values()) == stats["padded_frames"]
    assert 0.0 <= stats["pad_fraction"] < 1.0


def test_ragged_names_cache_fill_is_locked_and_converges():
    """Regression (TPL602): ``_ragged_inputs_cache`` used to be filled
    check-then-act with no lock, from the caller's RPC thread AND the
    dispatcher/executor threads. All fillers must now insert under
    ``_ragged_cache_lock`` and converge on one value, with the metadata
    RPC kept outside the lock."""

    calls = []
    gate = threading.Event()

    class _Spec:
        extra = {"ragged_inputs": ("points",)}

    class _Inner:
        batch_multiple = 1

        def get_metadata(self, name, version=""):
            calls.append(threading.current_thread().name)
            assert gate.wait(timeout=30.0)
            return _Spec()

        def do_inference_async(self, request):
            raise AssertionError("no inference in this test")

        def close(self):
            pass

    chan = ContinuousBatchingChannel(
        _Inner(), max_batch=1, pipeline_depth=1, live_buckets=False
    )
    try:
        lock = chan._ragged_cache_lock

        class _LockChecked(dict):
            def __setitem__(self, key, value):
                assert lock.locked(), "cache mutated without the lock"
                dict.__setitem__(self, key, value)

            def setdefault(self, key, default=None):
                assert lock.locked(), "cache mutated without the lock"
                return dict.setdefault(self, key, default)

        chan._ragged_inputs_cache = _LockChecked()

        results = []
        workers = [
            threading.Thread(
                target=lambda: results.append(chan._ragged_names("m", "1"))
            )
            for _ in range(8)
        ]
        for t in workers:
            t.start()
        # every worker misses the empty cache and blocks inside the
        # metadata RPC — the exact multi-filler window of the bug —
        # then the gate opens and all 8 race to insert
        for _ in range(200):
            if len(calls) == len(workers):
                break
            time.sleep(0.01)
        assert len(calls) == len(workers)
        assert not lock.locked(), "metadata RPC must run outside the lock"
        gate.set()
        for t in workers:
            t.join(timeout=30.0)
        assert results == [frozenset({"points"})] * len(workers)
        # the cache is warm: no further metadata calls
        assert chan._ragged_names("m", "1") == frozenset({"points"})
        assert len(calls) == len(workers)
    finally:
        gate.set()
        chan.close()
