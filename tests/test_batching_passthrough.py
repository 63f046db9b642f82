"""A lone request that needs no pad rows reaches the device channel as
it came (PR 27): ``ContinuousBatchingChannel`` hands the inner channel
the request's OWN arrays when a formed group has one member and its pad
is 0, and builds a new buffer (``np.concatenate``, span
``batch_merge``) for everything else, as before.

Cases of one parametrised test over a recording inner channel: a real
``TPUChannel`` on the CPU that notes the arrays it was handed, so the
outputs can be held bitwise against the same rows sent to the device
channel in a private copy (the parent's path: one part concatenated
into a new buffer).
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from triton_client_tpu.channel import InferRequest, TPUChannel
from triton_client_tpu.channel.base import BaseChannel
from triton_client_tpu.channel.kserve.codec import deserialize_tensor
from triton_client_tpu.config import ModelSpec, TensorSpec
from triton_client_tpu.obs.trace import RequestTrace
from triton_client_tpu.runtime import ModelRepository
from triton_client_tpu.runtime.admission import DeadlineExpiredError
from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
from triton_client_tpu.runtime.shared_memory import (
    SharedMemoryRegion,
    SystemSharedMemoryRegistry,
)

_W = np.linspace(-1.0, 1.0, 16, dtype=np.float32).reshape(4, 4)
_ROW_BYTES = 4 * 4
_WAIT_S = 20.0


def _infer_fn(inputs):
    x = inputs["x"]
    return {"y": np.asarray(jnp.tanh(x @ jnp.asarray(_W)) + 0.5 * x)}


@pytest.fixture(scope="module")
def device_channel():
    repo = ModelRepository()
    repo.register(
        ModelSpec(
            name="dense",
            version="1",
            inputs=(TensorSpec("x", (-1, 4), "FP32"),),
            outputs=(TensorSpec("y", (-1, 4), "FP32"),),
        ),
        _infer_fn,
    )
    return TPUChannel(repo)


class _Recording(BaseChannel):
    """Notes what it is handed, then passes it on. ``gate`` (when set)
    holds a launch inside ``do_inference_async``, the way a device
    channel holds a batcher slot until the transfer is enqueued."""

    def __init__(self, inner):
        self._inner = inner
        self.seen = []  # the "x" array of every request, as handed over
        self.gate = None
        self.entered = threading.Event()

    def register_channel(self):
        pass

    def fetch_channel(self):
        return self

    def get_metadata(self, model_name, model_version=""):
        return self._inner.get_metadata(model_name, model_version)

    def do_inference(self, request):
        return self.do_inference_async(request).result()

    def do_inference_async(self, request):
        self.seen.append(request.inputs["x"])
        self.entered.set()
        if self.gate is not None:
            assert self.gate.wait(_WAIT_S)
        return self._inner.do_inference_async(request)


def _rows(n, seed=0):
    return np.random.default_rng(seed).standard_normal((n, 4)).astype(np.float32)


def _request(x, **kw):
    return InferRequest(
        model_name="dense", model_version="1", inputs={"x": x},
        trace=RequestTrace(1), **kw,
    )


def _direct(device_channel, x):
    """The parent's path for a lone request: the same rows, copied into
    a new buffer, straight into the device channel."""
    return device_channel.do_inference(
        InferRequest(model_name="dense", model_version="1",
                     inputs={"x": np.concatenate([x])})
    ).outputs["y"]


def _span_names(request):
    return [s.name for s in request.trace.spans]


def _in_thread(fn):
    box = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:  # handed back to the test thread
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box


def _joined(t, box):
    t.join(_WAIT_S)
    assert not t.is_alive()
    if "error" in box:
        raise box["error"]
    return box["value"]


def _lone_full(chan, rec, dev):
    x = _rows(8)
    kept = x.copy()
    req = _request(x)
    y = chan.do_inference(req).outputs["y"]
    assert len(rec.seen) == 1 and rec.seen[0] is x  # the very buffer
    assert "batch_merge" not in _span_names(req)
    assert {"batch_queue", "merge_wait", "batch_respond"} <= set(_span_names(req))
    np.testing.assert_array_equal(x, kept)  # read, never written
    np.testing.assert_array_equal(y, _direct(dev, x))
    s = chan.stats()
    assert (s["passthrough_groups"], s["merged_bytes"], s["padded_frames"]) == (1, 0, 0)
    assert s["merge_occupancy"] == {8: 1}  # the pad table still saw it


def _lone_needs_pad(chan, rec, dev):
    x = _rows(5)
    req = _request(x)
    y = chan.do_inference(req).outputs["y"]
    (seen,) = rec.seen
    assert seen.shape == (8, 4) and not np.shares_memory(seen, x)
    np.testing.assert_array_equal(seen[:5], x)
    assert _span_names(req).count("batch_merge") == 1
    assert y.shape == (5, 4)
    np.testing.assert_array_equal(y, _direct(dev, seen)[:5])
    s = chan.stats()
    assert (s["passthrough_groups"], s["merged_bytes"], s["padded_frames"]) == (
        0, 8 * _ROW_BYTES, 3
    )


def _two_members(chan, rec, dev):
    # a held launch occupies the one slot while two requests queue up:
    # they leave as one group
    rec.gate = threading.Event()
    blocker = _in_thread(lambda: chan.do_inference(_request(_rows(8, seed=9))))
    assert rec.entered.wait(_WAIT_S)
    a, b = _rows(4, seed=1), _rows(4, seed=2)
    reqs = [_request(a), _request(b)]
    waiting = [_in_thread(lambda r=r: chan.do_inference(r)) for r in reqs]
    deadline = time.monotonic() + _WAIT_S
    while chan.stats()["ready_depth"] < 2:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    rec.gate.set()
    _joined(*blocker)
    ya, yb = (_joined(*w).outputs["y"] for w in waiting)
    assert len(rec.seen) == 2
    merged = rec.seen[1]
    assert merged.shape == (8, 4)
    assert not np.shares_memory(merged, a) and not np.shares_memory(merged, b)
    for r in reqs:
        assert _span_names(r).count("batch_merge") == 1
    both = _direct(dev, merged)
    np.testing.assert_array_equal(ya, both[:4])
    np.testing.assert_array_equal(yb, both[4:])
    s = chan.stats()
    # the blocker passed through; the pair was copied
    assert (s["passthrough_groups"], s["merged_bytes"]) == (1, 8 * _ROW_BYTES)


def _lone_over_max_merge(chan, rec, dev):
    x = _rows(12)  # wider than max_merge 8: no bucket fits, pad is 0
    req = _request(x)
    y = chan.do_inference(req).outputs["y"]
    assert len(rec.seen) == 1 and rec.seen[0] is x
    assert "batch_merge" not in _span_names(req)
    np.testing.assert_array_equal(y, _direct(dev, x))
    s = chan.stats()
    assert (s["passthrough_groups"], s["merged_bytes"]) == (1, 0)


def _expired_lone(chan, rec, dev):
    req = _request(_rows(8), deadline_s=time.perf_counter() - 1.0)
    with pytest.raises(DeadlineExpiredError):
        chan.do_inference(req)
    assert rec.seen == []
    s = chan.stats()
    assert s["passthrough_groups"] == 0
    assert s["shed"] == {"dense|0|merge": 1}


def _shm_view(chan, rec, dev):
    x = _rows(8, seed=3)
    key = f"tct_test_passthrough_{time.time_ns()}"
    caller = SharedMemoryRegion.create(key, x.nbytes)
    registry = SystemSharedMemoryRegistry()
    try:
        caller.write(x)
        registry.register("frames", key, 0, x.nbytes)
        # what runtime/server.py's parse gives the channel: a view of
        # the server's own mapping of the caller's region
        view = deserialize_tensor(registry.read("frames", 0, x.nbytes), "FP32", x.shape)
        view.flags.writeable = False  # nothing on the way may write to it
        rec.gate = threading.Event()
        req = _request(view)
        pending = _in_thread(lambda: chan.do_inference(req))
        assert rec.entered.wait(_WAIT_S)
        del view
        # the caller goes away while its request is in flight: the
        # mapping has to outlive the registration
        registry.unregister("frames")
        assert registry.status() == {}
        rec.gate.set()
        y = _joined(*pending).outputs["y"]
        (seen,) = rec.seen
        assert seen is req.inputs["x"] and not seen.flags.writeable
        assert "batch_merge" not in _span_names(req)
        np.testing.assert_array_equal(seen, x)
        np.testing.assert_array_equal(y, _direct(dev, x))
        assert chan.stats()["passthrough_groups"] == 1
    finally:
        registry.unregister_all()
        caller.close()


CASES = {
    "lone_full_request_is_the_callers_buffer": (_lone_full, {}),
    "lone_request_under_a_bucket_is_padded_and_copied": (_lone_needs_pad, {}),
    "two_members_are_concatenated": (_two_members, {"pipeline_depth": 1}),
    "lone_request_over_max_merge_passes_through": (_lone_over_max_merge, {}),
    "expired_lone_request_is_shed": (_expired_lone, {"shed_expired": True}),
    "shm_view_survives_unregister_in_flight": (_shm_view, {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_lone_request_without_pad_is_not_copied(case, device_channel):
    check, options = CASES[case]
    rec = _Recording(device_channel)
    chan = ContinuousBatchingChannel(rec, max_batch=8, **options)
    try:
        check(chan, rec, device_channel)
    finally:
        if rec.gate is not None:
            rec.gate.set()
        chan.close()
