"""Batch padding/bucketing helpers — the ONE copy of the bucket table.

Both batch producers pad to a bounded set of device batch sizes so the
inner executable cache stays small (the role Triton's
preferred_batch_size plays): the micro-batcher
(``ContinuousBatchingChannel._run_dense_merge``) pads merged request
groups, and the mesh-sharded serving channel (``channel/sharded_channel.py``) pads each
request batch so it splits evenly over the mesh's ``data`` axis. Before
this module each carried its own ``_bucket`` — two tables that could
drift apart and double XLA's compiled-shape set. Now:

  * :func:`bucket`      — the classic next-power-of-two table;
  * :func:`bucket_for`  — the mesh-aware table: smallest padded size
    that is both bucketed AND divisible by the data-axis width, so one
    table serves single-device and sharded channels (for the common
    power-of-two meshes the two tables coincide at sizes >= the axis);
  * :func:`pad_rows` / :func:`unpad_rows` — the padding policy itself.
    Pad rows REPLICATE a real row rather than zero-filling: zeros can
    steer a model down numerically different paths (different NMS
    survivors, different argmax ties), a copied row cannot, which is
    what keeps padded launches bitwise identical after the slice-back.
"""

from __future__ import annotations

import numpy as np


def bucket(n: int) -> int:
    """Smallest power of two >= n (the padded device batch size)."""
    b = 1
    while b < n:
        b *= 2
    return b


def bucket_for(n: int, multiple: int = 1) -> int:
    """Smallest bucketed batch size >= n that divides evenly into
    ``multiple`` shards (the mesh data-axis width).

    ``multiple=1`` is exactly :func:`bucket`. For ``multiple=m`` the
    padded size is the smallest multiple of ``m`` that covers
    ``bucket(n)`` — i.e. round to the classic power-of-two table first,
    then up to the next axis multiple. The size set stays log2-bounded
    (one entry per power of two), every entry splits evenly over the
    axis — required before ``jax.device_put`` with a batch sharding can
    place the array at all — and for power-of-two meshes the table
    coincides with :func:`bucket` at every size >= m, so stacking the
    batcher's padding in front of a sharded channel never double-pads.

    Non-power-of-two axes (a data=6 mesh of paired trays) used to go
    through ``m * bucket(ceil(n/m))``, which jumps past valid sizes:
    13 rows on 6 shards padded to 24 when 18 (= 6 * ceil(16/6)) already
    covers the classic bucket — an extra 46% of pad work for nothing.
    """
    if multiple <= 1:
        return bucket(n)
    if n <= multiple:
        # one row per shard is the floor: a 1-row request on a 6-wide
        # mesh still ships 6 rows
        return multiple
    b = bucket(n)
    return multiple * -(-b // multiple)  # ceil to the next axis multiple


def pad_rows(parts: list[np.ndarray], pad: int) -> list[np.ndarray]:
    """Append ``pad`` replicated rows (copies of the first non-empty
    part's first row) to a list of batch fragments about to be
    concatenated.

    Replicating from a 0-row fragment would contribute ``0`` pad rows
    (``empty[:1]`` is empty) and the concatenated batch silently
    under-pads — a shape-mismatch launch downstream. An all-empty
    fragment list has no real row to copy, so it zero-fills."""
    if pad <= 0:
        return parts
    for p in parts:
        if p.shape[0]:
            return list(parts) + [np.repeat(p[:1], pad, axis=0)]
    return list(parts) + [
        np.zeros((pad, *parts[0].shape[1:]), parts[0].dtype)
    ]


def pad_batch(arr: np.ndarray, target: int) -> np.ndarray:
    """Pad one batch-leading array up to ``target`` rows by replicating
    its first row (no-op when already at target)."""
    if arr.shape[0] >= target:
        return arr
    return np.concatenate(pad_rows([arr], target - arr.shape[0]))


def unpad_rows(arr, total: int):
    """Slice the real ``total`` rows back off a padded batch output.

    Works on numpy and on device arrays (a lazy slice — for a sharded
    device output the host copy that follows only ever pays for the
    real rows)."""
    if arr.ndim >= 1 and arr.shape[0] > total:
        return arr[:total]
    return arr
