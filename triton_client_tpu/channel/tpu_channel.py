"""TPUChannel: the in-process dispatch channel.

This is the framework's answer to the reference's GRPCChannel
(communicator/channel/grpc_channel.py): instead of serializing ~3 MB of
image bytes into a protobuf and blocking on a remote GPU server
(SURVEY.md section 3.1), do_inference is a function call — inputs are
device_put onto the mesh with the batch axis sharded over `data`, the
jit-compiled model runs, and outputs come back as numpy only at the
driver boundary.

"register_channel" claims the device mesh (the analogue of dialing the
endpoint); "get_metadata" reads the local repository (the analogue of
the two startup RPCs, grpc_channel.py:39-54).

The overlapped stage/launch/lazy-readback protocol introduced in
round 6 now lives in :mod:`triton_client_tpu.channel.staged`
(``StagedChannel``), shared with the mesh-sharded serving channel
(round 9). This subclass keeps the single-executable placement policy:

  * dtype policy (round 4): narrow inputs upload as-is (pipelines widen
    on device), wider stray dtypes cast down to the wire contract;
  * per-array sharding heuristic: shard batch-leading arrays over the
    ``data`` axis when the batch divides, otherwise replicate;
  * transfer form (PR 34): an array crosses as ``staged.transfer_view``
    gives it (whole tiles, a free view) and the launcher's body undoes
    the view first thing;
  * launcher: cached ``jax.jit(fn, donate_argnums=(0,))`` whose first
    arg carries the spec-marked ``donatable`` inputs, so consecutive
    batches reuse the same HBM input buffers.
"""

from __future__ import annotations

import jax
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec

from triton_client_tpu.channel.staged import (  # noqa: F401 — re-exported
    ParamLauncher,
    StagedChannel,
    StagedRequest,
    _Inflight,
    cast_wire_input,
    put_staged,
)
from triton_client_tpu.config import config_dtypes
from triton_client_tpu.obs.roofline import name_launcher
from triton_client_tpu.parallel.mesh import batch_sharding


class TPUChannel(StagedChannel):
    """Single-executable serving channel (see module docstring)."""

    def _place_inputs(self, model, request):
        sharding = batch_sharding(self._mesh)
        # a host-boundary model (no device_fn) is handed the staged arrays
        # themselves: nothing there could undo a transfer view
        put = put_staged if model.device_fn is not None else jax.device_put
        device_inputs = {}
        for name, arr in request.inputs.items():
            # Shard batch-leading arrays over the data axis when the
            # batch divides; otherwise replicate (single-frame path).
            # round-4 dtype policy (see staged.cast_wire_input: never
            # widen on the host, cast stray wider dtypes down)
            arr = cast_wire_input(model, name, np.asarray(arr))
            use = (
                sharding
                if arr.ndim > 0
                and arr.shape[0] % self._mesh.shape["data"] == 0
                else NamedSharding(self._mesh, PartitionSpec())
            )
            device_inputs[name] = put(arr, use)
        return device_inputs, None

    def _make_launcher(self, model):
        """Cached ``jax.jit(fn, donate_argnums=(0,))`` whose first arg
        carries the spec-marked donatable inputs — consecutive batches
        then reuse the same HBM input buffers."""
        donate_names = (
            frozenset(model.spec.donatable_inputs()) if self._donate else frozenset()
        )
        device_fn = self._device_body(model)
        out_dtype = {
            t.name: config_dtypes().get(t.dtype) for t in model.spec.outputs
        }
        if model.params is not None:
            # weights (and a declared device state) as launcher
            # arguments: staged.ParamLauncher
            return ParamLauncher(model, device_fn), donate_names, out_dtype
        # the launcher carries the model's name so its HLO module is
        # jit_mdl_<name>_<version> — profiler op events then attribute
        # back to the model by module name (obs/opstats.py)
        launcher = jax.jit(
            name_launcher(
                lambda donated, kept: device_fn({**donated, **kept}), model
            ),
            donate_argnums=(0,),
        )
        return launcher, donate_names, out_dtype
