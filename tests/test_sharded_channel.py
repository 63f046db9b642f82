"""Mesh-sharded serving channel (round 7): data-parallel dispatch.

The contract under test (channel/sharded_channel.py): one
ShardedTPUChannel serving a whole mesh must be *observationally
identical* to the single-device TPUChannel — bitwise-equal outputs,
same wire dtypes, same error surfaces — while splitting batchable
requests over the data axis. Runs on the 8 virtual CPU devices that
conftest.py provisions.

  * yolov5n (max_batch_size=8, batch-leading NHWC input): sharded for
    full, uneven, and single-row batches — pad rows are replicated real
    rows sliced back off, so padding can never leak into answers;
  * pointpillars (max_batch_size=1: the dynamic leading dim is a point
    count, not a batch): runs fully replicated, same answers;
  * the batcher stacks in front unchanged and sizes its merge
    groups off ``batch_multiple`` so batcher padding and shard padding
    agree;
  * stats/gauges surface data_axis_size and mesh_devices for the
    collector.
"""

import threading

import jax
import numpy as np
import pytest

from triton_client_tpu.channel import (
    InferRequest,
    ShardedTPUChannel,
    TPUChannel,
)
from triton_client_tpu.channel.staged import DenseStaged
from triton_client_tpu.parallel.mesh import MeshConfig
from triton_client_tpu.runtime import ModelRepository
from triton_client_tpu.runtime.continuous import ContinuousBatchingChannel
from triton_client_tpu.runtime.padding import bucket_for


def _single_device_channel(repo, **kw):
    """The parity reference: same engine, one device, no sharding."""
    return TPUChannel(
        repo, MeshConfig(data=1, model=1), devices=jax.devices()[:1], **kw
    )


# -- yolov5n: the batch-sharded path --------------------------------------


@pytest.fixture(scope="module")
def yolo_repo():
    from triton_client_tpu.pipelines.detect2d import build_yolov5_pipeline

    pipe, spec, _ = build_yolov5_pipeline(
        variant="n", num_classes=2, input_hw=(64, 64)
    )
    assert spec.max_batch_size > 1  # precondition for sharding
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn(), device_fn=pipe.device_fn())
    return repo


def _frames(seed, batch):
    return (
        np.random.default_rng(seed)
        .integers(0, 255, (batch, 64, 64, 3))
        .astype(np.float32)
    )


# module-scoped channels: every fresh channel re-jits its launchers,
# and on the 1-core CI host compile time IS this file's budget — tests
# that only read answers share one channel pair; tests that assert
# counters build their own
@pytest.fixture(scope="module")
def yolo_sharded(yolo_repo):
    return ShardedTPUChannel(yolo_repo, MeshConfig(data=-1, model=1))


@pytest.fixture(scope="module")
def yolo_single(yolo_repo):
    return _single_device_channel(yolo_repo)


@pytest.mark.parametrize("batch", [8, 3, 1, 16])
def test_sharded_yolo_bitwise_matches_single_device(
    yolo_sharded, yolo_single, batch
):
    sharded, single = yolo_sharded, yolo_single
    assert sharded.batch_multiple == len(jax.devices())
    x = _frames(batch, batch)
    a = sharded.do_inference(InferRequest("yolov5n", {"images": x}))
    b = single.do_inference(InferRequest("yolov5n", {"images": x}))
    for k in ("detections", "valid"):
        np.testing.assert_array_equal(a.outputs[k], b.outputs[k])
        assert a.outputs[k].dtype == b.outputs[k].dtype
    # pad rows (uneven batches round up to the device multiple) must be
    # sliced off before the response
    assert a.outputs["detections"].shape[0] == batch
    assert a.outputs["valid"].shape[0] == batch


@pytest.mark.parametrize("dtype", [np.float32, np.uint8])
def test_sharded_inputs_actually_shard(yolo_sharded, dtype):
    n_dev = yolo_sharded.batch_multiple
    staged = yolo_sharded.stage(
        InferRequest("yolov5n", {"images": _frames(0, n_dev).astype(dtype)})
    )
    placed = staged.device_inputs["images"]
    if dtype is np.uint8:
        # byte frames cross in their transfer form (staged.transfer_view):
        # the device array is the one leaf, its rows split as the wire's would
        assert isinstance(placed, DenseStaged) and placed.shape[0] == n_dev
        placed = placed.data
    # one row-shard per device, all devices addressed
    assert len(placed.sharding.device_set) == n_dev
    assert placed.addressable_shards[0].data.shape[0] == 1
    yolo_sharded.launch(staged).result()


@pytest.mark.parametrize("batch", [8, 3])
def test_sharded_byte_frames_cross_dense_and_match_single_device(
    yolo_sharded, yolo_single, batch
):
    """The sharded launcher undoes the transfer view as the single-device
    one does: same answers, pad rows and all."""
    x = _frames(batch, batch).astype(np.uint8)
    dense = yolo_sharded.stats()["staged_dense_bytes"]
    a = yolo_sharded.do_inference(InferRequest("yolov5n", {"images": x}))
    b = yolo_single.do_inference(InferRequest("yolov5n", {"images": x}))
    for k in ("detections", "valid"):
        np.testing.assert_array_equal(a.outputs[k], b.outputs[k])
    padded = bucket_for(batch, yolo_sharded.batch_multiple)
    assert yolo_sharded.stats()["staged_dense_bytes"] - dense == padded * x[0].nbytes


def test_uneven_batch_pads_to_device_multiple(yolo_sharded):
    n_dev = yolo_sharded.batch_multiple
    staged = yolo_sharded.stage(
        InferRequest("yolov5n", {"images": _frames(1, 3)})
    )
    padded = staged.device_inputs["images"].shape[0]
    assert padded == bucket_for(3, n_dev)
    assert padded % n_dev == 0
    resp = yolo_sharded.launch(staged).result()
    assert resp.outputs["detections"].shape[0] == 3  # pad sliced off


def test_sharded_overlap_and_donation_counters(yolo_repo, yolo_single):
    sharded = ShardedTPUChannel(
        yolo_repo, MeshConfig(data=-1, model=1), pipeline_depth=2
    )
    futs = [
        sharded.do_inference_async(
            InferRequest("yolov5n", {"images": _frames(s, 8)})
        )
        for s in range(4)
    ]
    single = yolo_single
    for s, fut in enumerate(futs):
        ref = single.do_inference(
            InferRequest("yolov5n", {"images": _frames(s, 8)})
        )
        got = fut.result()
        np.testing.assert_array_equal(
            got.outputs["detections"], ref.outputs["detections"]
        )
    stats = sharded.stats()
    assert stats["launched"] == 4
    assert stats["donated_launches"] == 4  # images is spec-donatable
    assert stats["inflight"] == 0
    assert stats["data_axis_size"] == len(jax.devices())
    assert stats["mesh_devices"] == len(jax.devices())


def test_sharded_validation_matches_single_device(yolo_sharded):
    with pytest.raises(ValueError, match="requires input"):
        yolo_sharded.do_inference(InferRequest("yolov5n", {}))
    assert yolo_sharded.stats()["inflight"] == 0  # failed stage leaks no slot


# -- pointpillars: the replicated fallback --------------------------------


@pytest.fixture(scope="module")
def pillars_repo():
    from triton_client_tpu.models.pointpillars import PointPillarsConfig
    from triton_client_tpu.ops.voxelize import VoxelConfig
    from triton_client_tpu.pipelines.detect3d import (
        Detect3DConfig,
        build_pointpillars_pipeline,
    )

    model_cfg = PointPillarsConfig(
        voxel=VoxelConfig(max_voxels=128, max_points_per_voxel=8),
        vfe_filters=8,
        backbone_layers=(1,),
        backbone_strides=(2,),
        backbone_filters=(8,),
        upsample_strides=(1,),
        upsample_filters=(8,),
    )
    cfg = Detect3DConfig(point_buckets=(512,), max_det=16, pre_max=32)
    pipe, spec, _ = build_pointpillars_pipeline(
        jax.random.PRNGKey(0), model_cfg=model_cfg, config=cfg
    )
    assert spec.max_batch_size <= 1  # precondition for the fallback
    repo = ModelRepository()
    repo.register(spec, pipe.infer_fn(), device_fn=pipe.device_fn())
    return repo


def _cloud(seed, n=300):
    rng = np.random.default_rng(seed)
    return rng.uniform(0, 30, (n, 4)).astype(np.float32)


@pytest.fixture(scope="module")
def pillars_sharded(pillars_repo):
    return ShardedTPUChannel(pillars_repo, MeshConfig(data=-1, model=1))


def test_unshardable_model_runs_replicated(pillars_repo, pillars_sharded):
    """max_batch_size<=1: the dynamic leading dim is a point count —
    splitting it over devices would change answers, so the channel must
    serve it fully replicated with single-device numerics."""
    sharded = pillars_sharded
    single = _single_device_channel(pillars_repo)
    name = "pointpillars"
    for seed in (0, 1):
        req = {
            "points": _cloud(seed),
            "num_points": np.int32(300),
        }
        a = sharded.do_inference(InferRequest(name, dict(req)))
        b = single.do_inference(InferRequest(name, dict(req)))
        for k in a.outputs:
            np.testing.assert_array_equal(a.outputs[k], b.outputs[k])


def test_unshardable_inputs_not_row_split(pillars_sharded):
    sharded = pillars_sharded
    name = "pointpillars"
    staged = sharded.stage(
        InferRequest(
            name, {"points": _cloud(2), "num_points": np.int32(300)}
        )
    )
    placed = staged.device_inputs["points"]
    # replicated: every device holds the FULL point cloud
    assert placed.addressable_shards[0].data.shape[0] == placed.shape[0]
    sharded.launch(staged).result()


# -- the batcher stacks in front ------------------------------------------


def test_batcher_reads_batch_multiple(yolo_repo):
    inner = ShardedTPUChannel(yolo_repo, MeshConfig(data=-1, model=1))
    chan = ContinuousBatchingChannel(inner, max_batch=4)
    try:
        n_dev = inner.batch_multiple
        stats = chan.stats()
        assert stats["batch_multiple"] == n_dev
        # merge window defaults to max_batch x data_axis so the batcher
        # can actually fill the mesh
        assert chan._max_merge == 4 * n_dev
    finally:
        chan.close()


def test_batched_sharded_stack_bitwise(yolo_repo, yolo_single):
    inner = ShardedTPUChannel(yolo_repo, MeshConfig(data=-1, model=1))
    chan = ContinuousBatchingChannel(inner, max_batch=4)
    single = yolo_single
    try:
        results = {}
        errors = []

        def one(seed):
            try:
                x = _frames(seed, 2)
                results[seed] = chan.do_inference(
                    InferRequest("yolov5n", {"images": x})
                )
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=one, args=(s,)) for s in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert not errors
        assert len(results) == 6
        for seed, resp in results.items():
            ref = single.do_inference(
                InferRequest("yolov5n", {"images": _frames(seed, 2)})
            )
            np.testing.assert_array_equal(
                resp.outputs["detections"], ref.outputs["detections"]
            )
            np.testing.assert_array_equal(
                resp.outputs["valid"], ref.outputs["valid"]
            )
        assert chan.stats()["merges"] >= 1
    finally:
        chan.close()
