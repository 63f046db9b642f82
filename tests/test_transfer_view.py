"""The form in which a launch's host arrays cross to the device (PR 34).

``channel/staged.py``: ``transfer_view`` (a FREE view of the caller's
buffer whose two minor dimensions are whole tiles, or the array itself),
``DenseStaged`` (the staged array with what undoes the view, static in
the launched program) and the launcher body's first operation, the
inverse. Held here on the CPU:

  * the rule, case by case, reads only the array and never copies;
  * a 2D entry served through ``TPUChannel`` answers bit for bit what
    the same launcher answers on wire-shaped arrays, donation on and off;
  * ``staged_bytes`` / ``staged_dense_bytes`` count what they should and
    a token model's launches stage nothing dense;
  * the traced ``h2d`` span still closes and carries the request's bytes;
  * the benchmark's reader of the counters.
"""

import importlib
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from triton_client_tpu.channel import InferRequest, TPUChannel  # noqa: E402
from triton_client_tpu.channel.staged import (  # noqa: E402
    DenseStaged,
    _batch_rows,
    put_staged,
    transfer_view,
)
from triton_client_tpu.config import ModelSpec, TensorSpec  # noqa: E402
from triton_client_tpu.obs.trace import RequestTrace  # noqa: E402
from triton_client_tpu.parallel.mesh import MeshConfig  # noqa: E402
from triton_client_tpu.runtime import ModelRepository  # noqa: E402

# -- the rule -------------------------------------------------------------------


def _misaligned(shape):
    """uint16 elements at an odd byte address: a view numpy itself calls
    unaligned."""
    raw = np.zeros(int(np.prod(shape)) * 2 + 1, np.uint8)
    return raw[1:].view(np.uint16).reshape(shape)


_BF16 = jnp.bfloat16.dtype

# (id, array, shape of the view, or None where the array goes as it is);
# four-byte elements are not packed into sublane words and cross as fast
# in the wire shape (the probe, PERF.md PR 34): float32 frames go as they are
_CASES = [
    ("uint8-b8-512", lambda: np.zeros((8, 512, 512, 3), np.uint8), (8, 6144, 128)),
    ("float32-b8-512", lambda: np.zeros((8, 512, 512, 3), np.float32), None),
    ("uint8-b3-64", lambda: np.zeros((3, 64, 64, 3), np.uint8), (3, 96, 128)),
    ("float32-b3-64", lambda: np.zeros((3, 64, 64, 3), np.float32), None),
    ("uint8-lone-frame", lambda: np.zeros((1, 512, 512, 3), np.uint8), (1, 6144, 128)),
    ("float32-lone-frame", lambda: np.zeros((1, 512, 512, 3), np.float32), None),
    ("bfloat16-b4-64", lambda: np.zeros((4, 64, 64, 3), _BF16), (4, 96, 128)),
    ("int8-codes-b2-64", lambda: np.zeros((2, 64, 64, 3), np.int8), (2, 96, 128)),
    ("points", lambda: np.zeros((20000, 4), np.float32), None),
    ("prompt", lambda: np.zeros((1, 4096), np.int32), None),
    ("step-row", lambda: np.zeros((7,), np.int32), None),
    ("scalar", lambda: np.zeros((), np.float32), None),
    ("non-contiguous", lambda: np.zeros((8, 64, 128, 3), np.uint8)[:, :, ::2], None),
    ("misaligned", lambda: _misaligned((2, 64, 64, 3)), None),
    ("uint16-b2-64", lambda: np.zeros((2, 64, 64, 3), np.uint16), (2, 96, 128)),
    ("whole-tiles-bfloat16", lambda: np.zeros((2, 16, 128), _BF16), None),
    ("whole-tiles-uint8", lambda: np.zeros((2, 3, 32, 256), np.uint8), None),
    ("no-whole-number-of-tiles", lambda: np.zeros((2, 30, 30, 3), np.uint8), None),
    ("eight-byte-elements", lambda: np.zeros((2, 64, 64, 3), np.float64), None),
    ("empty-batch", lambda: np.zeros((0, 64, 64, 3), np.uint8), None),
]


@pytest.mark.parametrize("make,want", [c[1:] for c in _CASES], ids=[c[0] for c in _CASES])
def test_the_rule_reads_only_the_array_and_never_copies(make, want):
    arr = make()
    view = transfer_view(arr)
    if want is None:
        assert view is arr
        return
    assert view.shape == want and view.dtype == arr.dtype
    assert np.shares_memory(view, arr) and view.base is not None
    # the batch axis is never merged; the minor dimensions are whole tiles
    sublanes = 8 * (4 // arr.dtype.itemsize)
    assert view.shape[0] == arr.shape[0]
    assert view.shape[-1] == 128 and view.shape[-2] % sublanes == 0
    # the same bytes in the same order
    arr.reshape(-1)[-1] = 1
    assert view.reshape(-1)[-1] == 1


def test_the_staged_array_undoes_its_view_inside_a_program():
    frames = np.random.default_rng(0).integers(0, 256, (3, 64, 64, 3)).astype(np.uint8)
    staged = put_staged(frames)
    assert isinstance(staged, DenseStaged) and staged.data.shape == (3, 96, 128)
    # shape, ndim and dtype are the wire array's: the launch's rows are counted from them
    assert (staged.shape, staged.ndim, staged.dtype) == (frames.shape, 4, frames.dtype)
    assert staged.nbytes == frames.nbytes and _batch_rows({"images": staged}) == 3
    back = jax.jit(lambda d: d.wire())
    np.testing.assert_array_equal(np.asarray(back(staged)), frames)
    # the trailing shape is static: another one is another program, the same one is not
    traced = back._cache_size()
    back(put_staged(frames[:2]))  # rows differ: a new shape, as for any array
    back(put_staged(frames))
    assert back._cache_size() == traced + 1
    # an array the rule leaves alone is placed as it came
    points = np.zeros((100, 4), np.float32)
    assert isinstance(put_staged(points), jax.Array)


# -- a 2D entry through the channel ------------------------------------------------


@pytest.fixture(scope="module")
def yolo_repo():
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline

    pipe, spec, _ = build_yolov5_pipeline(variant="n", num_classes=2, input_hw=(64, 64))
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn(), device_fn=pipe.device_fn())
    return repo, spec.name


@pytest.fixture(scope="module", params=[True, False], ids=["donate", "keep"])
def yolo_channel(request, yolo_repo):
    repo, name = yolo_repo
    chan = TPUChannel(
        repo, MeshConfig(data=1, model=1), devices=jax.devices()[:1], donate=request.param
    )
    return chan, name


def _frames(seed, batch):
    return np.random.default_rng(seed).integers(0, 256, (batch, 64, 64, 3)).astype(np.uint8)


@pytest.mark.parametrize("batch", [1, 3, 8])
def test_a_2d_entry_answers_what_the_wire_shape_launcher_answers(yolo_channel, batch):
    chan, name = yolo_channel
    frames = _frames(batch, batch)
    before = chan.stats()
    staged = chan.stage(InferRequest(name, {"images": frames}))
    placed = staged.device_inputs["images"]
    assert isinstance(placed, DenseStaged) and placed.data.shape == (batch, 96, 128)
    got = chan.launch(staged).result().outputs
    after = chan.stats()
    assert after["staged_bytes"] - before["staged_bytes"] == frames.nbytes
    assert after["staged_dense_bytes"] - before["staged_dense_bytes"] == frames.nbytes
    # the same launcher on the wire-shaped array: the body passes it through untouched
    model = chan.served_model(name)
    launcher, donate_names, _ = chan._launcher(model)
    wire = {"images": jax.device_put(frames, jax.devices()[0])}
    want = launcher(
        {k: v for k, v in wire.items() if k in donate_names},
        {k: v for k, v in wire.items() if k not in donate_names},
    )
    assert set(got) == {k for k in want if not k.startswith("__")}
    for k in got:
        np.testing.assert_array_equal(got[k], np.asarray(want[k]))
    # a caller's buffer is never donated: its frames are what they were
    np.testing.assert_array_equal(frames, _frames(batch, batch))


def test_the_traced_h2d_span_closes_and_carries_the_requests_bytes(yolo_channel):
    chan, name = yolo_channel
    frames = _frames(5, 3)
    tr = RequestTrace(1)
    chan.do_inference(InferRequest(name, {"images": frames}, trace=tr))
    spans = {s.name: s for s in tr.spans}
    assert spans["h2d"].attrs["bytes"] == frames.nbytes and spans["h2d"].attrs["rows"] == 3
    assert spans["h2d"].attrs["launch_id"] == spans["launch"].attrs["launch_id"]
    assert spans["slot_wait"].t1 == spans["h2d"].t0 <= spans["stage"].t1 <= spans["h2d"].t1


# -- who is left alone --------------------------------------------------------------


def _token_like_repo():
    """A launcher over what a token model stages: ids and rows of int32."""
    spec = ModelSpec(
        name="ids", version="1",
        inputs=(TensorSpec("tokens", (-1, -1), "INT32"), TensorSpec("rows", (-1,), "INT32")),
        outputs=(TensorSpec("total", (-1,), "INT32"),),
    )
    fn = lambda inputs: {"total": inputs["tokens"].sum(axis=1) + inputs["rows"][: inputs["tokens"].shape[0]]}
    repo = ModelRepository()
    repo.register(spec, lambda inputs: {k: np.asarray(v) for k, v in fn(inputs).items()}, device_fn=fn)
    return repo


def test_a_token_models_launches_stage_nothing_dense():
    chan = TPUChannel(_token_like_repo(), MeshConfig(data=1, model=1), devices=jax.devices()[:1])
    prompt = np.arange(4096, dtype=np.int32)[None]
    steps = np.arange(7, dtype=np.int32)
    out = chan.do_inference(InferRequest("ids", {"tokens": prompt, "rows": steps}))
    assert out.outputs["total"][0] == prompt.sum()
    out = chan.do_inference(InferRequest("ids", {"tokens": steps[:, None], "rows": steps}))
    np.testing.assert_array_equal(out.outputs["total"], 2 * steps)
    stats = chan.stats()
    assert stats["staged_dense_bytes"] == 0
    assert stats["staged_bytes"] == prompt.nbytes + 3 * steps.nbytes


def test_a_host_boundary_model_is_handed_wire_shaped_arrays():
    spec = ModelSpec(
        name="host", version="1",
        inputs=(TensorSpec("images", (-1, 64, 64, 3), "UINT8"),),
        outputs=(TensorSpec("mean", (-1,), "FP32"),),
    )
    seen = {}

    def infer(inputs):
        seen["shape"] = inputs["images"].shape
        return {"mean": np.asarray(inputs["images"]).reshape(len(inputs["images"]), -1).mean(axis=1)}

    repo = ModelRepository()
    repo.register(spec, infer)  # no device_fn: nothing could undo a view
    chan = TPUChannel(repo, MeshConfig(data=1, model=1), devices=jax.devices()[:1])
    frames = _frames(9, 2)
    out = chan.do_inference(InferRequest("host", {"images": frames}))
    assert seen["shape"] == frames.shape and chan.stats()["staged_dense_bytes"] == 0
    np.testing.assert_allclose(out.outputs["mean"], frames.reshape(2, -1).mean(axis=1), rtol=1e-6)


# -- the benchmark's reader ---------------------------------------------------------


@pytest.mark.parametrize("before,after,want", [
    ({}, {"staged": 4}, None),  # a program without the counters: the parent
    ({"staged_bytes": 10, "staged_dense_bytes": 10}, {"staged_bytes": 10, "staged_dense_bytes": 10}, None),
    ({"staged_bytes": 100, "staged_dense_bytes": 100}, {"staged_bytes": 500, "staged_dense_bytes": 500}, 100.0),
    ({"staged_bytes": 100, "staged_dense_bytes": 0}, {"staged_bytes": 300, "staged_dense_bytes": 50}, 25.0),
    ({}, {"staged_bytes": 64, "staged_dense_bytes": 0}, 0.0),
], ids=["no-counter", "no-launch", "all-dense", "a-quarter", "token-model"])
def test_the_reader_of_the_counters(before, after, want):
    reader = importlib.import_module("benchmarks.layer_metrics.staged_dense_share")
    ctx = {"snapshot_before": {"channel": before}, "snapshot_after": {"channel": after}}
    assert reader.read(ctx) == want
    assert reader.read({}) is None
