"""Overlapped dispatch engine (round 6): stage/launch/readback seams.

Covers the four contract points of the overlapped TPUChannel path:

  * staged (device_fn) launches are bitwise identical to the SAME
    jitted program run directly (the channel's own launcher, no slot,
    no future), and carry the wire-contract output dtypes of the
    host-only infer_fn path (another executable: equal to rounding);
  * input donation cannot corrupt a request whose buffers are re-read
    after launch (host arrays are never donated; outputs of batch N are
    computed before batch N+1 can reuse N's staged HBM);
  * pipeline_depth=1 degrades to the strictly serial legacy behavior;
  * the lazy InferFuture resolves exactly once, and the staging-slot
    occupancy counters account for every launch.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_client_tpu.channel import InferRequest, TPUChannel
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.parallel.mesh import MeshConfig
from triton_client_tpu.runtime import ModelRepository

_W = np.linspace(-1.0, 1.0, 16, dtype=np.float32).reshape(4, 4)


def _compute(inputs):
    x = inputs["x"]
    y = jnp.tanh(x @ jnp.asarray(_W)) + 0.5 * x
    # int32 on device (x64 disabled); the spec declares INT64 on the
    # wire, so the channel must cast at the host boundary.
    cls = jnp.argmax(y, axis=-1).astype(jnp.int32)
    return {"y": y, "cls": cls}


def _spec(name):
    return ModelSpec(
        name=name,
        version="1",
        inputs=(TensorSpec("x", (-1, 4), "FP32", donatable=True),),
        outputs=(
            TensorSpec("y", (-1, 4), "FP32"),
            TensorSpec("cls", (-1,), "INT64"),
        ),
    )


def _eager_infer_fn():
    fn = jax.jit(_compute)

    def infer(inputs):
        out = fn(inputs)
        return {
            "y": np.asarray(out["y"]),
            "cls": np.asarray(out["cls"], dtype=np.int64),
        }

    return infer


@pytest.fixture(scope="module")
def repo():
    r = ModelRepository()
    # same computation registered twice: with a device_fn (staged
    # launch path) and host-only (legacy eager path)
    r.register(_spec("staged"), _eager_infer_fn(), device_fn=_compute)
    r.register(_spec("eager"), _eager_infer_fn())
    return r


def _req(model, arr):
    return InferRequest(model, {"x": arr})


def _frame(seed, batch=8):
    return np.random.default_rng(seed).standard_normal((batch, 4)).astype(np.float32)


def _direct(chan, x):
    """The program the staged path launches, run directly: the channel's
    own launcher on inputs placed as the channel places them, with no
    staging slot, no future and no deferred readback in between. The
    same executable on the same placement, so bitwise equality is a fair
    demand of the staged path (a corrupted donated buffer or a reordered
    slot would break it); another executable of the same function (a
    single-device ``jax.jit``, the host-only path) differs by an ulp."""
    model = chan.served_model("staged")
    launcher, donate_names, _ = chan._launcher(model)
    placed, _ = chan._place_inputs(model, _req("staged", x))
    out = launcher(
        {k: v for k, v in placed.items() if k in donate_names},
        {k: v for k, v in placed.items() if k not in donate_names},
    )
    return {
        "y": np.asarray(out["y"]),
        "cls": np.asarray(out["cls"], dtype=np.int64),
    }


def test_staged_matches_eager_bitwise(repo):
    chan = TPUChannel(repo, MeshConfig(data=-1, model=1), pipeline_depth=2)
    for seed in range(4):
        x = _frame(seed)
        staged = chan.do_inference(_req("staged", x))
        eager = chan.do_inference(_req("eager", x))
        direct = _direct(chan, x)
        for k in ("y", "cls"):
            np.testing.assert_array_equal(staged.outputs[k], direct[k])
            assert staged.outputs[k].dtype == eager.outputs[k].dtype
        # the host-only path runs another executable of the function
        np.testing.assert_allclose(
            staged.outputs["y"], eager.outputs["y"], rtol=1e-5, atol=1e-6
        )
    assert staged.outputs["cls"].dtype == np.int64  # wire contract
    assert chan.stats()["donated_launches"] > 0


def test_donation_does_not_corrupt_rereads(repo):
    chan = TPUChannel(repo, MeshConfig(data=-1, model=1), pipeline_depth=2)
    xa, xb = _frame(1), _frame(2)
    ref_a, ref_b = _direct(chan, xa), _direct(chan, xb)
    fut_a = chan.do_inference_async(_req("staged", xa))
    # host buffer is untouched by launch — staging device_puts a copy
    np.testing.assert_array_equal(xa, _frame(1))
    # batch B launches while A is unresolved; with donation on, B's
    # launch is exactly the point where A's staged HBM may be reused
    fut_b = chan.do_inference_async(_req("staged", xb))
    np.testing.assert_array_equal(xa, _frame(1))
    resp_a = fut_a.result()  # re-read A's outputs after B launched
    resp_b = fut_b.result()
    for k in ("y", "cls"):
        np.testing.assert_array_equal(resp_a.outputs[k], ref_a[k])
    np.testing.assert_array_equal(resp_b.outputs["y"], ref_b["y"])
    # the request's host arrays survive the whole round-trip
    np.testing.assert_array_equal(xa, _frame(1))
    np.testing.assert_array_equal(xb, _frame(2))


def test_depth_one_is_serial(repo):
    chan = TPUChannel(repo, MeshConfig(data=-1, model=1), pipeline_depth=1)
    refs = [_direct(chan, _frame(s)) for s in range(3)]
    futs = [chan.do_inference_async(_req("staged", _frame(s))) for s in range(3)]
    stats = chan.stats()
    # never more than one launched batch in flight: staging request N+1
    # blocked on request N's execution
    assert set(stats["slot_occupancy"]) == {1}
    assert stats["slot_occupancy"][1] == 3
    assert stats["stage_slot_waits"] >= 1
    for s, fut in enumerate(futs):
        np.testing.assert_array_equal(fut.result().outputs["y"], refs[s]["y"])
    assert chan.stats()["inflight"] == 0


def test_depth_knob_blocks_staging(repo):
    # with the deepest slot held by an unresolved future, a depth-2
    # channel admits exactly one more stage before blocking
    chan = TPUChannel(repo, MeshConfig(data=-1, model=1), pipeline_depth=2)
    f1 = chan.do_inference_async(_req("staged", _frame(0)))
    f2 = chan.do_inference_async(_req("staged", _frame(1)))
    assert chan.stats()["inflight"] <= 2
    done = threading.Event()
    f3 = []

    def third():
        f3.append(chan.do_inference_async(_req("staged", _frame(2))))
        done.set()

    t = threading.Thread(target=third, daemon=True)
    t.start()
    # the third stage proceeds once slot-acquisition retires the oldest
    # executed batch — on CPU execution finishes quickly, so this is a
    # liveness check, not a strict ordering one
    assert done.wait(timeout=30.0)
    t.join(timeout=30.0)
    for fut in (f1, f2, f3[0]):
        assert fut.result().outputs["y"].shape == (8, 4)


def test_future_resolves_exactly_once(repo):
    chan = TPUChannel(repo, MeshConfig(data=-1, model=1), pipeline_depth=2)
    fut = chan.do_inference_async(_req("staged", _frame(7)))
    r1 = fut.result()
    assert chan.stats()["inflight"] == 0
    r2 = fut.result()
    assert r1 is r2  # memoized: readback + slot retirement ran once
    stats = chan.stats()
    assert stats["launched"] == 1
    assert sum(stats["slot_occupancy"].values()) == stats["launched"]


def test_occupancy_accounts_for_every_launch(repo):
    chan = TPUChannel(repo, MeshConfig(data=-1, model=1), pipeline_depth=2)
    futs = [chan.do_inference_async(_req("staged", _frame(s))) for s in range(6)]
    for fut in futs:
        fut.result()
    stats = chan.stats()
    assert stats["launched"] == 6
    assert sum(stats["slot_occupancy"].values()) == 6
    assert max(stats["slot_occupancy"]) <= 2  # never beyond pipeline_depth
    assert stats["inflight"] == 0 and stats["staged"] == 6


def test_dispatch_errors_deferred_to_result(repo):
    chan = TPUChannel(repo, MeshConfig(data=-1, model=1), pipeline_depth=2)
    fut = chan.do_inference_async(InferRequest("staged", {}))
    with pytest.raises(ValueError, match="requires input"):
        fut.result()
    # a failed stage must not leak its slot
    assert chan.stats()["inflight"] == 0
    resp = chan.do_inference(_req("staged", _frame(3)))
    assert resp.outputs["y"].shape == (8, 4)
