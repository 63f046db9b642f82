"""Mesh construction and canonical shardings.

Axes:
  data   — batch / camera-stream data parallelism (a multi-camera
           ensemble maps cameras here)
  model  — tensor parallelism for wide layers (conv channel sharding,
           voxel-axis sharding for the 3D stack)
  seq    — sequence/context parallelism: the point/pillar/BEV-token
           axis for long point clouds (the reference's scale axis is
           MAX_NUMBER_OF_VOXELS=40000, data/kitti_dataset.yaml:66-70;
           a full KITTI BEV canvas is 432x496 ≈ 214k tokens).

On a single host this is `jax.devices()` reshaped; on multi-host the
same code runs under `jax.distributed` with DCN-attached hosts, with
the data axis laid out across hosts (DCN) and model across the
intra-slice ICI ring, so heavy collectives stay on ICI.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    data: int = -1  # -1: all remaining devices
    model: int = 1
    seq: int = 1
    pipe: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int, int, int]:
        model = max(1, self.model)
        seq = max(1, self.seq)
        pipe = max(1, self.pipe)
        rest = model * seq * pipe
        data = self.data if self.data > 0 else n_devices // rest
        if data * rest != n_devices:
            raise ValueError(
                f"mesh {data}x{model}x{seq}x{pipe} != {n_devices} devices"
            )
        return data, model, seq, pipe


def make_mesh(config: MeshConfig | None = None, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    if config.data > 0:
        # Fully explicit mesh: claim only the devices it names, so e.g.
        # --mesh data=4 works on an 8-device host (first 4 devices) —
        # loudly, so a mis-sized training config can't silently run at
        # partial throughput.
        want = (
            config.data
            * max(1, config.model) * max(1, config.seq) * max(1, config.pipe)
        )
        if want < len(devices):
            import logging

            logging.getLogger(__name__).warning(
                "mesh %s uses %d of %d available devices",
                config, want, len(devices),
            )
            devices = devices[:want]
    data, model, seq, pipe = config.resolve(len(devices))
    arr = np.asarray(devices).reshape(data, model, seq, pipe)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS, SEQ_AXIS, PIPE_AXIS))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over the data axis, replicate rest."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_axis_size(mesh: Mesh) -> int:
    """Width of the data axis — the serving channel's batch multiple."""
    return int(mesh.shape[DATA_AXIS])


def serving_shardings(mesh: Mesh) -> tuple[NamedSharding, NamedSharding]:
    """The two shardings the serving path ever uses: ``(batch, params)``
    — batch-leading request arrays split over ``data``, everything else
    (params, scalars, non-batched inputs) replicated on every device.
    One helper so the channel and the jit ``in_shardings`` can't
    disagree about placement."""
    return batch_sharding(mesh), replicated(mesh)


def replicate_params(tree, mesh: Mesh):
    """Place a param pytree once onto the mesh, replicated on every
    device. Serving's replicate-params / shard-batch shape: params are
    uploaded a single time at model registration, then every sharded
    launch reads the local copy — no per-request weight movement."""
    return jax.device_put(tree, replicated(mesh))
