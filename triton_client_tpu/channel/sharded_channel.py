"""ShardedTPUChannel: one server saturating a whole mesh.

The replicate-params / shard-batch serving shape used by TPU LLM
serving stacks (PAPERS.md — Ragged Paged Attention, Gemma-on-TPU),
applied to the perception stack: one model, all devices of a
``parallel/mesh.py`` mesh, one executable per padded batch bucket.

  * **params** are placed ONCE with ``replicated(mesh)`` sharding — at
    launcher build for an explicit ``RegisteredModel.params`` tree, or
    implicitly by XLA for the closure-captured weights every in-tree
    pipeline carries (replication happens at first trace per bucket,
    then every launch reads the local HBM copy).
  * **batches** are padded to the shared bucket table
    (:mod:`triton_client_tpu.runtime.padding` — ``bucket_for`` keeps
    each padded size divisible by the data-axis width) and split over
    the ``data`` axis via ``jax.device_put(arr, batch_sharding(mesh))``,
    so each device runs batch/N rows of the SAME program — per-request
    numerics are bitwise identical to the single-device channel because
    data parallelism never changes a row's compute and pad rows
    replicate a real row before being sliced back off.
  * **dispatch** keeps PR 1's staged/launch/lazy-readback overlap via
    the shared :class:`~triton_client_tpu.channel.staged.StagedChannel`
    engine: staging slots are per MESH (one admission window over all
    devices), so batch N+1's host->device scatter overlaps batch N's
    mesh-wide execution. The launcher is a cached
    ``jax.jit(..., in_shardings=(batch_sharding, None),
    donate_argnums=...)`` so consecutive padded batches reuse the same
    per-device HBM input shards.

``ContinuousBatchingChannel`` stacks in front unchanged through the ``inner``
channel interface and reads :attr:`batch_multiple` (the data-axis
width) to size merge groups up to ``max_batch x data_axis`` and align
its pad buckets, so batcher padding and shard padding never disagree.

Models whose spec declares ``max_batch_size <= 1`` (pointpillars: the
leading ``points`` dim is a point-count bucket, not a batch) cannot be
row-split; they run fully replicated on the mesh — same answers,
no speedup — so one server can still serve a mixed model set.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from triton_client_tpu.channel.staged import (
    SEGMENT_IDS_KEY,
    StagedChannel,
    cast_wire_input,
    put_staged,
)
from triton_client_tpu.obs.roofline import name_launcher
from triton_client_tpu.parallel.mesh import (
    DATA_AXIS,
    data_axis_size,
    replicate_params,
    serving_shardings,
)
from triton_client_tpu.parallel.ragged_kernels import (
    ShardedRaggedLayout,
    shard_segment_ids,
    unshard_segments,
)
from triton_client_tpu.runtime.padding import bucket_for, pad_batch, unpad_rows


class ShardedTPUChannel(StagedChannel):
    """Data-parallel serving channel over every device of the mesh."""

    # -- placement ------------------------------------------------------------

    @property
    def batch_multiple(self) -> int:
        """The data-axis width: the batcher sizes merge groups and pad
        buckets off this so a merged batch always splits evenly."""
        return data_axis_size(self._mesh)

    def _batched_names(self, model) -> frozenset[str]:
        """Inputs carrying the request batch on their leading dim.

        Triton's own convention: a model is batchable iff its spec
        declares ``max_batch_size > 1``, and then every input whose
        leading dim is dynamic (-1) is batch-leading. Models at the
        default ``max_batch_size=1`` have NO batch inputs here — their
        dynamic leading dims mean something else (pointpillars' point
        count) and splitting them over devices would change answers."""
        if model.spec.max_batch_size <= 1:
            return frozenset()
        return frozenset(
            t.name for t in model.spec.inputs if t.shape and t.shape[0] == -1
        )

    def _place_inputs(self, model, request):
        batch_s, repl_s = serving_shardings(self._mesh)
        multiple = self.batch_multiple
        batched = self._batched_names(model)
        # the request batch: leading dim of the first declared batched
        # input (spec order, so every request of a model agrees)
        n = None
        for t in model.spec.inputs:
            if t.name in batched and t.name in request.inputs:
                n = int(np.asarray(request.inputs[t.name]).shape[0])
                break
        target = bucket_for(n, multiple) if n is not None else None
        # the transfer form of staged.transfer_view (its batch axis is
        # never merged, so the rows still split over the data axis); a
        # host-boundary model is handed the staged arrays as they are
        put = put_staged if model.device_fn is not None else jax.device_put
        device_inputs = {}
        for name, arr in request.inputs.items():
            arr = cast_wire_input(model, name, np.asarray(arr))
            if (
                n is not None
                and name in batched
                and arr.ndim > 0
                and arr.shape[0] == n
            ):
                # pad rows replicate a real row (bitwise-safe; see
                # runtime/padding.py), then split rows over the data
                # axis — the only H2D path that scatters
                device_inputs[name] = put(pad_batch(arr, target), batch_s)
            else:
                device_inputs[name] = put(arr, repl_s)
        # meta: (real rows, padded rows) so resolve can slice the pad
        # back off before the host copy pays for it
        meta = (n, target) if n is not None and target != n else None
        return device_inputs, meta

    def _place_ragged(self, model, request):
        """Packed-ragged placement over the mesh: the continuous
        batcher packed this request SHARD-MAJOR (``request.ragged`` is
        a :class:`ShardedRaggedLayout` built at ``batch_multiple``
        shards — every input's leading dim is ``n_shards * per_shard``),
        so one batch-sharded ``device_put`` hands each device exactly
        its contiguous segment group. Segment ids are shard-LOCAL: no
        segment straddles a device, so the launched body needs no
        cross-device collectives."""
        sl = request.ragged
        batch_s, repl_s = serving_shardings(self._mesh)
        w = sl.n_shards
        device_inputs = {}
        for name, arr in request.inputs.items():
            arr = cast_wire_input(model, name, np.asarray(arr))
            use = (
                batch_s
                if arr.ndim > 0 and arr.shape[0] % w == 0
                else repl_s
            )
            device_inputs[name] = jax.device_put(arr, use)
        device_inputs[SEGMENT_IDS_KEY] = jax.device_put(
            shard_segment_ids(sl), batch_s
        )
        return device_inputs, sl

    # -- launch ---------------------------------------------------------------

    def _make_ragged_launcher(self, model, num_segments: int):
        """Sharded ragged launcher: reshape every shard-major input to
        ``(n_shards, per_shard, ...)`` and ``vmap`` the model's
        segment-aware body over the shard axis — under the batch
        sharding each device then runs ONLY its own shard's segments
        (the shard-local ids keep every reduce device-local, the SPMD
        partitioner never inserts a collective). ``num_segments`` is
        the per-shard capacity (:attr:`ShardedRaggedLayout.seg_pad`)."""
        from triton_client_tpu.config import config_dtypes

        batch_s, _ = serving_shardings(self._mesh)
        w = data_axis_size(self._mesh)
        ragged_fn = model.ragged_fn

        # named distinctly from the dense `launcher`: this jit does NOT
        # donate, and tpulint's donor index pools jit-bound names
        # module-wide
        def ragged_launcher(device_inputs):
            inputs = dict(device_inputs)
            ids = inputs.pop(SEGMENT_IDS_KEY).reshape(w, -1)
            sharded = {
                k: v.reshape(w, v.shape[0] // w, *v.shape[1:])
                for k, v in inputs.items()
            }
            out = jax.vmap(
                lambda inp, i: ragged_fn(inp, i, num_segments)
            )(sharded, ids)
            return {
                k: v.reshape(w * v.shape[1], *v.shape[2:])
                for k, v in out.items()
            }

        # stamped with the model's launcher name (runtime only — the
        # local binding above keeps lint's donor index unambiguous) so
        # profiler op events attribute by HLO module (obs/opstats.py)
        ragged_launcher = jax.jit(name_launcher(ragged_launcher, model))

        out_dtype = {
            t.name: config_dtypes().get(t.dtype) for t in model.spec.outputs
        }
        return ragged_launcher, out_dtype

    def _make_launcher(self, model):
        """Cached sharded launcher: donated arg carries the batched
        donatable inputs with an explicit ``in_shardings`` batch
        sharding (so XLA reuses the per-device input shards across
        consecutive padded batches), everything else propagates its
        device_put placement. An explicit ``model.params`` tree is
        replicated onto the mesh ONCE here and closed over as a
        committed jit argument — including int8 ``QuantizedParam``
        leaves (runtime/precision.py registered pytree nodes): the
        policy quantized the tree at registration, so the SMALL tree is
        what ships to every device."""
        from triton_client_tpu.config import config_dtypes

        batch_s, repl_s = serving_shardings(self._mesh)
        batched = self._batched_names(model)
        donate_names = (
            frozenset(model.spec.donatable_inputs()) & batched
            if self._donate
            else frozenset()
        )
        device_fn = self._device_body(model)
        out_dtype = {
            t.name: config_dtypes().get(t.dtype) for t in model.spec.outputs
        }
        if model.params is not None:
            placed = replicate_params(model.params, self._mesh)
            if self._lifecycle is not None:
                # refine the lifecycle manager's HBM accounting with the
                # measured per-device bytes of the placed tree (.nbytes
                # is sharding metadata — no host sync)
                nbytes = sum(
                    int(x.nbytes)
                    for x in jax.tree_util.tree_leaves(placed)
                    if hasattr(x, "nbytes")
                )
                self._lifecycle.note_cost(
                    model.spec.name, model.spec.version, nbytes
                )
            jitted = jax.jit(
                name_launcher(
                    lambda params, batched, rest: device_fn(
                        {**batched, **rest}, params
                    ),
                    model,
                ),
                in_shardings=(repl_s, batch_s, None),
                donate_argnums=(1,),
            )
            outer = lambda d, k: jitted(placed, d, k)  # noqa: E731
            # cost-measurement seam (obs/roofline.py): the channel's
            # measured flops/bytes capture lowers the launcher with the
            # first launch's args — forward to the underlying jit with
            # the closed-over params in place (lowering only traces;
            # nothing is donated, hence the distinct parameter names)
            outer.lower = lambda db, kb: jitted.lower(placed, db, kb)
            return outer, donate_names, out_dtype
        def body(donated, kept):
            return device_fn({**donated, **kept})

        if batched and model.spec.extra.get("fused_stages"):
            # A Pallas fusion inside the body: the SPMD partitioner
            # refuses it ("Mosaic kernels cannot be automatically
            # partitioned. Please wrap the call in a shard_map"), so
            # run the body per shard — each device executes the
            # single-device program on its own rows, which is what the
            # data axis means here anyway. Every output of a model
            # with batched inputs is batch-leading (the slice-back in
            # _host_outputs relies on the same fact).
            mesh, whole = self._mesh, body

            def spec(names):
                return {
                    k: P(DATA_AXIS) if k in batched else P() for k in names
                }

            def body(donated, kept):
                return jax.shard_map(
                    whole, mesh=mesh, in_specs=(spec(donated), spec(kept)),
                    out_specs=P(DATA_AXIS), check_vma=False,
                )(donated, kept)

        launcher = jax.jit(
            name_launcher(body, model),
            in_shardings=(batch_s, None),
            donate_argnums=(0,),
        )
        return launcher, donate_names, out_dtype

    # -- readback -------------------------------------------------------------

    def _host_outputs(self, outputs, out_dtype, meta) -> dict:
        """Slice pad rows off batch-leading outputs (lazy device slice —
        the host copy only ever pays for real rows), then the base
        wire-dtype readback."""
        if isinstance(meta, ShardedRaggedLayout):
            # gather real segments per shard back into request order
            # (lazy per-shard slices; dead seg_pad slots never copy)
            outputs = {
                k: unshard_segments(v, meta)
                if getattr(v, "ndim", 0) >= 1
                and v.shape[0] == meta.n_shards * meta.seg_pad
                else v
                for k, v in outputs.items()
            }
            return StagedChannel._host_outputs(self, outputs, out_dtype, None)
        if meta is not None:
            n, target = meta
            outputs = {
                k: unpad_rows(v, n)
                if getattr(v, "ndim", 0) >= 1 and v.shape[0] == target
                else v
                for k, v in outputs.items()
            }
        return super()._host_outputs(outputs, out_dtype, meta)
