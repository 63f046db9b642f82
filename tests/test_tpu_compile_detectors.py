"""Ask the TPU's compiler before the chip is asked: the detectors.

Every Pallas kernel on the detectors' default TPU route, at its real
serving shapes, and three whole serving launchers (yolov5 b8, fused
SECOND, yolov5 over a four-chip mesh), compiled by the installed libtpu
for a DESCRIBED v5e (no device attached) — what Mosaic or XLA:TPU would
refuse on the chip, it refuses here, at no chip time. Interpret mode
cannot show this: ``sorted_segment_mean_pallas`` passed every
interpret-mode test and still failed to lower ("cannot statically
prove that index in dimension 1 is a multiple of 128"), and the mesh
launcher failed with "Mosaic kernels cannot be automatically
partitioned".

A compile that passes is not a chip run: nothing executes here, so
these tests say nothing about results or times (``chip_smoke.py`` does).
The described chip and the rules that keep it to one worker's fixture are
in ``tests/tpu_compile_support.py``. A kernel PR adds its compile case to
the file of its family: detectors' kernels and launchers in
``test_tpu_compile_detectors.py``, the latent-attention families (A.X-K1,
DeepSeek-V3.2, Ling) in ``test_tpu_compile_latent.py``, the grouped-query
families (SDAR, SmallThinker) in ``test_tpu_compile_gqa.py``.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_compile_support import compile_text as _compile, one_chip, topo, tpu_route  # noqa: E402,F401

N_POINTS = 131072  # the largest served point bucket


def test_described_chip_is_a_v5e(topo):
    from triton_client_tpu.obs.roofline import DEVICE_PEAKS

    # the kind the peak table is keyed by is the kind the compiler
    # targets here and the chip reports there
    assert topo.devices[0].device_kind in DEVICE_PEAKS


@pytest.mark.parametrize("num_slots", [40000, 16000])
@pytest.mark.parametrize("pipeline", ["grid", "manual"])
def test_voxel_segment_mean_lowers(one_chip, pipeline, num_slots):
    """Both pipelining forms at the KITTI SECOND (40k) and PointPillars
    (16k) voxel budgets over the 131072-point bucket. The whole
    (8, v_out) accumulator is VMEM-resident: ~1.3 MB at 40k slots."""
    from triton_client_tpu.ops.pallas_voxel import sorted_segment_mean_pallas

    text = _compile(
        sorted_segment_mean_pallas, one_chip,
        ((8, N_POINTS), jnp.float32), ((N_POINTS,), jnp.int32),
        num_slots=num_slots, pipeline=pipeline,
    )
    assert "tpu_custom_call" in text


def _decode_nms_2d_text(one_chip, batch):
    from triton_client_tpu.ops.pallas_decode import fused_decode_nms_2d

    k = 1024  # Detect2DConfig.max_nms
    return _compile(
        lambda b, s, c, v: fused_decode_nms_2d(b, s, c, v, max_det=300),
        one_chip,
        ((batch, k, 4), jnp.float32), ((batch, k), jnp.float32),
        ((batch, k), jnp.int32), ((batch, k), jnp.bool_),
    )


def test_decode_nms_2d_lowers_b8(one_chip):
    """One whole group: eight frames, one a sublane, one grid step."""
    assert "tpu_custom_call" in _decode_nms_2d_text(one_chip, 8)


def test_decode_nms_2d_lowers_b11(one_chip):
    """A second group padded with five frames that hold no candidate."""
    assert "tpu_custom_call" in _decode_nms_2d_text(one_chip, 11)


def test_decode_tail_3d_lowers_b2(one_chip):
    from triton_client_tpu.ops.pallas_decode import (
        fused_residual_decode,
        fused_suppress_pack_3d,
    )

    k = 256  # Detect3DConfig.pre_max

    def tail(deltas, anchors, dir_bin, scores, labels):
        boxes = jax.vmap(
            lambda d, a, b: fused_residual_decode(
                d, a, b, num_dir_bins=2, dir_offset=0.78539
            )
        )(deltas, anchors, dir_bin)
        return jax.vmap(
            lambda b, s, l: fused_suppress_pack_3d(b, s, l, max_det=128)
        )(boxes, scores, labels)

    text = _compile(
        tail, one_chip,
        ((2, k, 7), jnp.float32), ((2, k, 7), jnp.float32),
        ((2, k), jnp.int32), ((2, k), jnp.float32), ((2, k), jnp.int32),
    )
    assert text.count("tpu_custom_call") >= 2


def test_segment_sum_lowers(one_chip):
    from triton_client_tpu.parallel.ragged_kernels import segment_sum_pallas

    text = _compile(
        lambda v, ids: segment_sum_pallas(v, ids, num_segments=8),
        one_chip, ((1024, 64), jnp.float32), ((1024,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_nms_pallas_lowers(one_chip):
    from triton_client_tpu.ops.pallas_nms import nms_pallas

    text = _compile(
        nms_pallas, one_chip,
        ((1024, 4), jnp.float32), ((1024,), jnp.float32), max_det=300,
    )
    assert "tpu_custom_call" in text


def _registered(model_dir):
    from triton_client_tpu.runtime.disk_repository import build_model
    from triton_client_tpu.runtime.repository import ModelRepository

    model = build_model(model_dir)
    repo = ModelRepository()
    repo.register(model.spec, model.infer_fn, device_fn=model.device_fn)
    return repo, repo.get(model.spec.name, model.spec.version)


def _compile_launcher(channel, model, structs):
    """Compile the serving channel's OWN launcher for a model (the jit
    the channel caches: donation split, named module, shardings) at
    the given argument structs."""
    launcher, donate, _ = channel._make_launcher(model)
    return launcher.lower(
        {k: v for k, v in structs.items() if k in donate},
        {k: v for k, v in structs.items() if k not in donate},
    ).compile()


def _launcher_text(model_dir, inputs, one_chip):
    from triton_client_tpu.channel.tpu_channel import TPUChannel

    repo, model = _registered(model_dir)
    structs = {
        name: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for name, (shape, dtype) in inputs.items()
    }
    return model, _compile_launcher(TPUChannel(repo), model, structs).as_text()


def test_yolov5_b8_launcher_compiles(one_chip, tpu_route):
    """examples/yolov5_crop as served: 512x512, b8, the fused
    decode+NMS tail inside the real program."""
    model, text = _launcher_text(
        "examples/yolov5_crop",
        {"images": ((8, 512, 512, 3), jnp.float32)},
        one_chip,
    )
    assert model.spec.extra["fused_stages"] == ["decode_nms"]
    assert "tpu_custom_call" in text


def test_yolov5_mesh_launcher_compiles_for_four_chips(topo, tpu_route):
    """``serve --mesh data=4``: the sharded channel's launcher over the
    four described chips. Under plain SPMD partitioning this fails with
    "Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map" — the channel runs a fused body per
    shard. Each device then holds 2 of the 8 frames, the kernel, and
    no collective."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from triton_client_tpu.channel.sharded_channel import ShardedTPUChannel
    from triton_client_tpu.parallel.mesh import MeshConfig

    repo, model = _registered("examples/yolov5_crop")
    channel = ShardedTPUChannel(
        repo, mesh_config=MeshConfig(data=4), devices=list(topo.devices)
    )
    frames = jax.ShapeDtypeStruct(
        (8, 512, 512, 3), jnp.float32,
        sharding=NamedSharding(channel.fetch_channel(), P("data")),
    )
    compiled = _compile_launcher(channel, model, {"images": frames})
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not any(
        op in text for op in ("all-reduce", "all-gather", "all-to-all")
    )
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device == 2 * 512 * 512 * 3 * 4


def test_second_fused_launcher_compiles(one_chip, tpu_route):
    """examples/second_iou at the 131072-point bucket with BOTH fused
    stages — the launcher that held the kernel Mosaic refused — so
    VMEM and HBM limits are met at full width with the kernels inside
    the real program."""
    model, text = _launcher_text(
        "examples/second_iou",
        {
            "points": ((N_POINTS, 4), jnp.float32),
            "num_points": ((), jnp.int32),
        },
        one_chip,
    )
    assert model.spec.extra["fused_stages"] == [
        "voxelize_scatter", "decode_nms",
    ]
    # voxelize_scatter + residual decode + suppress/pack
    assert text.count("tpu_custom_call") >= 3
