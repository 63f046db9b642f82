"""Ragged (packed) batch execution: segment tables + TPU segment kernels.

The padding tax the dense batcher pays is worst exactly where batching
helps most: variable-size inputs. A 3D scan's point count swings 2-10x
between frames (the reference's MAX_NUMBER_OF_VOXELS ceiling exists
because of it), so padding every member of a merged batch to the
widest member — or the whole merge to a power-of-two bucket — ships
mostly dead rows. *Ragged Paged Attention* (PAPERS.md) shows the TPU
answer: concatenate the real rows back to back and carry a row-offset /
segment-id table alongside, so one launched program processes every
request at its true size.

This module is that mechanism for the serving stack:

  * :class:`RaggedLayout` — the row-offset/segment-id table that rides
    with a packed batch (built once on the host by the scheduler,
    shipped to the device as one int32 vector);
  * :func:`pack_rows` — concatenate per-request row blocks into one
    packed array, padded to a bucketed row count so the compiled-shape
    set stays log-bounded (pad rows belong to a dead segment and are
    dropped by construction);
  * :func:`segment_reduce` — the segment-aware reduction every ragged
    model body leans on: a Pallas TPU kernel (one-hot x values matmul,
    the MXU-friendly formulation) with an XLA ``segment_sum`` fallback
    for hosts without the Pallas toolchain;
  * :func:`partition_segments` / :func:`shard_pack` — contiguous,
    row-balanced partition of a packed batch over a mesh data axis, so
    the sharded channel splits ragged work without a segment ever
    straddling two devices (no cross-device collectives in the body).

Bitwise/accuracy contract: packing never changes a row's values, and a
segment's rows stay contiguous and in request order — a ragged model
body that reduces per segment sees exactly the arrays a solo request
would (modulo the reduction's own reassociation, which `segment_reduce`
keeps in row order).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from triton_client_tpu.runtime.padding import bucket

_LANES = 128
_SUBLANES = 8


def _round_up(n: int, m: int) -> int:
    return ((max(1, n) + m - 1) // m) * m


def ragged_row_bucket(n: int) -> int:
    """Padded row count for a packed batch: 8 steps per power-of-two
    octave, sublane-aligned. The classic pow2 table wastes up to 50% on
    the big row counts ragged batching exists for (a 5000-point merge
    would pad to 8192); this table bounds the pad at 12.5% while the
    compiled-shape set stays log-bounded (<= 8 shapes per octave — jit
    retraces per packed shape, so the table IS the executable budget).
    Lane alignment is NOT needed here: the segment kernels pad to tile
    boundaries internally."""
    n = max(1, n)
    step = max(_SUBLANES, bucket(n) // 8)
    return _round_up(n, step)


def kernel_block_rows(n: int, block: int) -> int:
    """Padded row count for a fused Pallas kernel launch over a packed
    cloud/batch: the learned ragged bucket, rounded up to the kernel's
    point-block multiple.

    ``block`` must be a power of two >= ``_SUBLANES`` — that guarantee
    is what keeps the two tables compatible: ``ragged_row_bucket``'s
    step is ``max(8, bucket(n) // 8)``, itself a power of two, so for
    every bucket >= ``8 * block`` the step is already a ``block``
    multiple and the tables coincide exactly (asserted by
    :func:`assert_block_divides_buckets`); below that the round-up
    costs at most ``block - 1`` extra rows while the compiled-shape set
    stays a subset of the bucket table's."""
    if block < _SUBLANES or block & (block - 1):
        raise ValueError(
            f"kernel block must be a power of two >= {_SUBLANES}, got {block}"
        )
    return _round_up(ragged_row_bucket(n), block)


def assert_block_divides_buckets(block: int, max_rows: int = 1 << 22) -> None:
    """Assert the fused-kernel block size divides every learned bucket
    in its regime (bucket >= 8 * block) — the invariant that lets a
    channel reuse one packed array for BOTH the segment kernels (bucket
    shapes) and a fused kernel launch (block-multiple shapes) without a
    re-pad in between. Raises AssertionError naming the first violator."""
    if block < _SUBLANES or block & (block - 1):
        raise ValueError(
            f"kernel block must be a power of two >= {_SUBLANES}, got {block}"
        )
    floor = 8 * block
    n = floor
    while n <= max_rows:
        b = ragged_row_bucket(n)
        if b >= floor:
            assert b % block == 0, (
                f"ragged_row_bucket({n}) = {b} is not a multiple of the "
                f"fused kernel block {block}"
            )
        n += max(1, b // 16)  # sample densely enough to hit every step


@dataclasses.dataclass(frozen=True)
class RaggedLayout:
    """Row-offset/segment-id table for one packed ragged batch.

    ``sizes[i]`` is request *i*'s row count; ``offsets`` is the
    exclusive prefix sum (length ``n_segments + 1``); ``padded_rows``
    is the bucketed row count every packed array is padded to (pad rows
    carry segment id ``n_segments`` — one past the last real segment,
    so every reduction drops them); ``seg_bucket`` is the bucketed
    segment count the launched program is traced for — the ONLY part of
    the layout that keys the launcher cache, so the executable set is
    log-bounded in both rows (jit's own shape cache over ``padded_rows``
    buckets) and segments (our cache over ``seg_bucket``)."""

    sizes: tuple[int, ...]

    @property
    def n_segments(self) -> int:
        return len(self.sizes)

    @property
    def total(self) -> int:
        return int(sum(self.sizes))

    @functools.cached_property
    def offsets(self) -> np.ndarray:
        return np.concatenate(
            [[0], np.cumsum(np.asarray(self.sizes, np.int64))]
        ).astype(np.int32)

    @property
    def padded_rows(self) -> int:
        return ragged_row_bucket(self.total)

    @property
    def seg_bucket(self) -> int:
        """Static segment capacity the launched program is traced for."""
        return bucket(self.n_segments)

    @property
    def launch_segments(self) -> int:
        """The static ``num_segments`` the channel's ragged launcher is
        built (and cache-keyed) at — the uniform name both layout kinds
        expose to ``StagedChannel.launch``."""
        return self.seg_bucket

    @functools.cached_property
    def segment_ids(self) -> np.ndarray:
        """(padded_rows,) int32 — pad rows get id ``n_segments`` (out
        of range for a ``num_segments``-sized reduce, so they vanish)."""
        ids = np.full(self.padded_rows, self.n_segments, np.int32)
        ids[: self.total] = np.repeat(
            np.arange(self.n_segments, dtype=np.int32),
            np.asarray(self.sizes, np.int64),
        )
        return ids

    @property
    def pad_rows(self) -> int:
        return self.padded_rows - self.total


def pack_rows(parts: list[np.ndarray], layout: RaggedLayout) -> np.ndarray:
    """Concatenate per-request row blocks into one packed array padded
    to ``layout.padded_rows``. Pad rows replicate the last real row
    (never zeros: a copied row cannot steer a model down a numerically
    different path — the same rule as ``runtime/padding.pad_rows``) and
    belong to the dead segment, so their outputs are never read."""
    if [int(p.shape[0]) for p in parts] != list(layout.sizes):
        raise ValueError(
            f"pack_rows: part sizes {[p.shape[0] for p in parts]} != "
            f"layout sizes {list(layout.sizes)}"
        )
    packed = np.concatenate([np.asarray(p) for p in parts])
    pad = layout.padded_rows - packed.shape[0]
    if pad > 0:
        fill = (
            np.repeat(packed[-1:], pad, axis=0)
            if packed.shape[0]
            else np.zeros((pad, *packed.shape[1:]), packed.dtype)
        )
        packed = np.concatenate([packed, fill])
    return packed


# -- segment-aware reduction (the ragged model-body primitive) -----------------


def _segment_sum_kernel(values_ref, ids_ref, out_ref, *, num_segments):
    """One-hot x values matmul: ``out[s, f] = sum_r [ids[r]==s] * v[r, f]``.

    The MXU formulation of segment-sum — the gather/scatter-free shape
    *Ragged Paged Attention* uses for its row bookkeeping: build the
    (S, R) one-hot selector from a 2D iota compare (TPU has no 1D
    iota), then one ``jnp.dot`` keeps the whole reduction on the
    systolic array. Pad rows carry an out-of-range id, so their one-hot
    row is all zeros and they contribute nothing.
    """
    import jax
    import jax.numpy as jnp

    r = ids_ref.shape[1]
    seg = jax.lax.broadcasted_iota(jnp.int32, (num_segments, r), 0)
    onehot = (seg == ids_ref[0:1, :]).astype(jnp.float32)
    # HIGHEST: the default MXU pass rounds the f32 values to bf16
    # (measured on a v5e: sums of 128 unit-range rows off by 0.03)
    out_ref[:] = jnp.dot(
        onehot, values_ref[:], precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def segment_sum_pallas(values, segment_ids, num_segments: int, interpret: bool = False):
    """Pallas TPU segment-sum: ``values`` (R, F) f32, ``segment_ids``
    (R,) int32 -> (num_segments, F) f32. Out-of-range ids (the packing
    pad convention) are dropped. ``interpret=True`` runs the same
    kernel on CPU for tests."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, f = values.shape
    r_pad = _round_up(r, _LANES)
    f_pad = _round_up(f, _LANES)
    s_pad = _round_up(num_segments, _SUBLANES)

    v = jnp.zeros((r_pad, f_pad), jnp.float32)
    v = v.at[:r, :f].set(values.astype(jnp.float32))
    ids = jnp.full((1, r_pad), num_segments, jnp.int32)
    ids = ids.at[0, :r].set(segment_ids.astype(jnp.int32))

    out = pl.pallas_call(
        functools.partial(_segment_sum_kernel, num_segments=s_pad),
        out_shape=jax.ShapeDtypeStruct((s_pad, f_pad), jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(v, ids)
    return out[:num_segments, :f]


def segment_reduce(values, segment_ids, num_segments: int, op: str = "sum"):
    """Segment-aware reduction routed to the best backend: the Pallas
    kernel on TPU (sum/mean — the MXU shapes), XLA's ``segment_*`` ops
    elsewhere and for max/min. ``values`` (R, F) or (R,); out has
    leading dim ``num_segments``. The one primitive every in-tree
    ragged model body is written against, so the backend choice lives
    in exactly one place."""
    import jax
    import jax.numpy as jnp

    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    if op in ("sum", "mean") and _use_pallas(v):
        out = segment_sum_pallas(v, segment_ids, num_segments)
        if op == "mean":
            ones = jnp.ones((v.shape[0], 1), jnp.float32)
            counts = segment_sum_pallas(ones, segment_ids, num_segments)
            out = out / jnp.maximum(counts, 1.0)
    else:
        seg = jax.ops.segment_sum if op in ("sum", "mean") else (
            jax.ops.segment_max if op == "max" else jax.ops.segment_min
        )
        out = seg(v, segment_ids, num_segments=num_segments)
        if op == "mean":
            counts = jax.ops.segment_sum(
                jnp.ones((v.shape[0],), jnp.float32),
                segment_ids,
                num_segments=num_segments,
            )
            out = out / jnp.maximum(counts[:, None], 1.0)
        if op in ("max", "min"):
            # XLA fills empty segments with the dtype identity
            # (-inf/+inf for floats); zero them so dead pad segments
            # can't leak infinities into a downstream stack
            counts = jax.ops.segment_sum(
                jnp.ones((v.shape[0],), jnp.int32),
                segment_ids,
                num_segments=num_segments,
            )
            out = jnp.where(counts[:, None] > 0, out, 0.0)
    return out[:, 0] if squeeze else out


def _use_pallas(values) -> bool:
    """Pallas only on a real TPU backend with a VMEM-fitting working
    set; everywhere else the XLA segment ops are faster than interpret
    mode and numerically identical in row order. A backend that
    cannot initialise raises here — it is not read as "no TPU"."""
    import jax

    return jax.default_backend() == "tpu" and segment_reduce_vmem_fits(
        values.shape[0], values.shape[1]
    )


def segment_reduce_vmem_fits(
    rows: int, features: int, budget_bytes: int = 12 << 20
) -> bool:
    """Whether the one-hot matmul's VMEM working set fits comfortably
    (values + one-hot + out, f32)."""
    r = _round_up(rows, _LANES)
    f = _round_up(features, _LANES)
    s = _SUBLANES  # lower bound; the one-hot dominates via r anyway
    return (r * f + s * r + s * f) * 4 < budget_bytes


# -- data-axis sharding of a packed batch --------------------------------------


def partition_segments(sizes, n_shards: int) -> list[list[int]]:
    """Contiguous, row-balanced partition of segments over ``n_shards``.

    Greedy walk: each shard takes segments until it reaches the ideal
    rows-per-shard for the REMAINING work (re-computed per shard so one
    huge leading segment can't starve the tail). Contiguity is the
    point — a segment never straddles two shards, so the sharded body
    needs no cross-device collectives and per-request outputs reassemble
    by concatenation. Returns ``n_shards`` lists of segment indices
    (possibly empty on a narrow batch)."""
    sizes = [int(s) for s in sizes]
    groups: list[list[int]] = [[] for _ in range(max(1, int(n_shards)))]
    i = 0
    for w in range(len(groups)):
        left = len(groups) - w
        remaining_rows = sum(sizes[i:])
        target = remaining_rows / left if left else 0
        rows = 0
        # every shard after this one must still be able to take at
        # least one segment
        max_take = len(sizes) - i - (left - 1)
        while i < len(sizes) and (not groups[w] or len(groups[w]) < max_take):
            if groups[w] and rows + sizes[i] > target and rows > 0:
                break
            groups[w].append(i)
            rows += sizes[i]
            i += 1
    return groups


@dataclasses.dataclass(frozen=True)
class ShardedRaggedLayout:
    """Per-shard layout for one packed batch split over the data axis.

    Each shard holds ``rows_pad`` rows and ``seg_pad`` segment slots
    (both maxima over shards, bucketed) so every shard runs the SAME
    program shape; ``groups`` maps shard-local segments back to request
    order for reassembly."""

    base: RaggedLayout
    n_shards: int
    groups: tuple[tuple[int, ...], ...]
    rows_pad: int
    seg_pad: int

    @property
    def counts(self) -> tuple[int, ...]:
        """Real segments per shard."""
        return tuple(len(g) for g in self.groups)

    @property
    def launch_segments(self) -> int:
        """Per-SHARD static segment capacity (see
        :attr:`RaggedLayout.launch_segments`)."""
        return self.seg_pad

    @property
    def n_segments(self) -> int:
        return self.base.n_segments


def shard_layout(layout: RaggedLayout, n_shards: int) -> ShardedRaggedLayout:
    groups = partition_segments(layout.sizes, n_shards)
    rows = [sum(layout.sizes[i] for i in g) for g in groups]
    segs = [len(g) for g in groups]
    return ShardedRaggedLayout(
        base=layout,
        n_shards=max(1, int(n_shards)),
        groups=tuple(tuple(g) for g in groups),
        rows_pad=ragged_row_bucket(max(rows + [1])),
        seg_pad=bucket(max(segs + [1])),
    )


def shard_pack_rows(
    parts: list[np.ndarray], sl: ShardedRaggedLayout
) -> np.ndarray:
    """Pack per-request row blocks as ``(n_shards * rows_pad, ...)`` —
    shard-major, so a batch sharding over the leading dim gives each
    device its contiguous segment group. Pad rows replicate the shard's
    last real row (or zero-fill an empty shard) under dead segment
    ids."""
    sizes = sl.base.sizes
    if [int(p.shape[0]) for p in parts] != list(sizes):
        raise ValueError("shard_pack_rows: parts do not match layout sizes")
    trailing = parts[0].shape[1:]
    dtype = parts[0].dtype
    out = np.zeros((sl.n_shards, sl.rows_pad, *trailing), dtype)
    for w, g in enumerate(sl.groups):
        o = 0
        for i in g:
            p = np.asarray(parts[i])
            out[w, o : o + p.shape[0]] = p
            o += p.shape[0]
        if o and o < sl.rows_pad:
            out[w, o:] = out[w, o - 1]
    return out.reshape(sl.n_shards * sl.rows_pad, *trailing)


def shard_segment_ids(sl: ShardedRaggedLayout) -> np.ndarray:
    """Shard-LOCAL segment ids, ``(n_shards * rows_pad,)`` int32 —
    each shard's ids live in ``[0, seg_pad)`` with pad rows at the dead
    id ``seg_pad`` (out of range for the per-shard reduce)."""
    ids = np.full((sl.n_shards, sl.rows_pad), sl.seg_pad, np.int32)
    for w, g in enumerate(sl.groups):
        o = 0
        for local, i in enumerate(g):
            n = sl.base.sizes[i]
            ids[w, o : o + n] = local
            o += n
    return ids.reshape(-1)


def shard_stack_segments(
    parts: list[np.ndarray], sl: ShardedRaggedLayout
) -> np.ndarray:
    """Stack per-request (non-ragged) arrays as
    ``(n_shards * seg_pad, ...)`` shard-major, matching the output
    layout of a sharded ragged launch. Dead slots replicate the shard's
    last real entry."""
    trailing = np.asarray(parts[0]).shape
    out = np.zeros((sl.n_shards, sl.seg_pad, *trailing), np.asarray(parts[0]).dtype)
    for w, g in enumerate(sl.groups):
        for local, i in enumerate(g):
            out[w, local] = np.asarray(parts[i])
        if g and len(g) < sl.seg_pad:
            out[w, len(g):] = out[w, len(g) - 1]
    return out.reshape(sl.n_shards * sl.seg_pad, *trailing)


def unshard_segments(arr, sl: ShardedRaggedLayout):
    """Gather the real per-request rows back out of a
    ``(n_shards * seg_pad, ...)`` sharded ragged output, in request
    order. Lazy slices per shard — on device arrays the host copy pays
    only for real segments."""
    out = []
    for w, g in enumerate(sl.groups):
        if g:
            base = w * sl.seg_pad
            out.append(arr[base : base + len(g)])
    if not out:
        return arr[:0]
    return np.concatenate([np.asarray(a) for a in out])
