"""The learned selection of a sparse attention: index scores over a
session's cached index keys, and the threshold that keeps each query's
``top_k`` best positions.

For a query at position ``t`` with index queries ``q [Hi, Di]`` and head
weights ``w [Hi]``, and the cached index key ``k[s] [Di]`` of every
position ``s <= t``::

    I[t, s] = sum_j w[j] * ReLU(q[j] . k[s])        float32

and attention reads the ``min(t + 1, top_k)`` positions with the
largest ``I[t, s]``. Two launch kinds compute it (models/axk1.py):

  * :func:`step_scores` — one query a session (a step launch): each
    session's slot of index keys read in place, one after another;
  * :func:`extend_scores` — thousands of queries of ONE session (an
    extend launch) against its slot: a Pallas kernel on a TPU (a block
    of queries against a block of keys, the heads one after another in
    VMEM, so the per-head scores ``[T, Hi, S]`` never reach HBM; key
    blocks past the block's last query are not computed), the same sum
    in plain XLA elsewhere.

Both give ``[rows, S]`` float32 with minus infinity at every position
after the query's own. :func:`kth_largest` turns a row of scores into
the threshold ``tau`` (the ``k``-th largest value, exactly, by bisection
over the bits of the float32 pattern: 32 counting passes, no sort), and
attention keeps the positions with ``I >= tau``: positions that tie
with the ``k``-th are all kept. Products read bfloat16 and accumulate
in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QUERY_TILE = 256  # queries a kernel block holds
KEY_TILE = 1024  # keys a kernel block holds
XLA_QUERY_BLOCK = 256
XLA_KEY_BLOCK = 1024


def _weighted_relu(q, w, keys):
    """``q [R, Hi, Di]``, ``w [R, Hi]``, ``keys [S, Di]`` -> ``[R, S]`` float32."""
    s = jnp.einsum("rjd,sd->rjs", q, keys, preferred_element_type=jnp.float32)
    return jnp.sum(w[:, :, None] * jax.nn.relu(s), axis=1)


def step_scores(q, w, index_keys, layer, slots, positions):
    """``q [B, Hi, Di]`` bfloat16, ``w [B, Hi]`` float32 (one token a
    session), ``index_keys [layers, slots, S, Di]`` the whole index
    cache, ``slots``/``positions [B]``. Returns ``[B, S]``."""
    s_len, dim = index_keys.shape[2:]
    key_pos = jnp.arange(s_len)

    def one(args):
        q_row, w_row, slot, pos = args
        keys = jax.lax.dynamic_slice(index_keys, (layer, slot, 0, 0), (1, 1, s_len, dim))[0, 0]
        scores = _weighted_relu(q_row[None], w_row[None], keys)[0]
        return jnp.where(key_pos <= pos, scores, -jnp.inf)

    return jax.lax.map(one, (q, w, slots, positions))


def _extend_scores_xla(q, w, keys, positions):
    t, s_len = q.shape[0], keys.shape[0]
    qb, kb = math.gcd(t, XLA_QUERY_BLOCK), math.gcd(s_len, XLA_KEY_BLOCK)

    def block(args):
        qq, ww, pos = args

        def key_block(j, out):
            lo = j * kb
            scores = _weighted_relu(qq, ww, jax.lax.dynamic_slice_in_dim(keys, lo, kb))
            keep = (lo + jnp.arange(kb))[None, :] <= pos[:, None]
            return jax.lax.dynamic_update_slice_in_dim(out, jnp.where(keep, scores, -jnp.inf), lo, axis=1)

        blocks = jnp.minimum(pos[-1] // kb + 1, s_len // kb)
        return jax.lax.fori_loop(0, blocks, key_block, jnp.full((qb, s_len), -jnp.inf, jnp.float32))

    split = lambda a: a.reshape(t // qb, qb, *a.shape[1:])
    return jax.lax.map(block, (split(q), split(w), split(positions))).reshape(t, s_len)


def _scores_kernel(start_ref, q_ref, w_ref, k_ref, out_ref, *, heads, dim, tq, tk):
    i, j = pl.program_id(0), pl.program_id(1)
    first = start_ref[0] + i * tq  # the block's first query's position; they ascend by one

    @pl.when(j * tk <= first + tq - 1)
    def _():
        keys = k_ref[...]
        acc = jnp.zeros((tq, tk), jnp.float32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[:, h * dim : (h + 1) * dim], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = acc + w_ref[:, h : h + 1] * jnp.maximum(s, 0.0)
        q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        k_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        out_ref[...] = jnp.where(k_pos <= q_pos, acc, -jnp.inf)

    @pl.when(j * tk > first + tq - 1)
    def _():
        out_ref[...] = jnp.full((tq, tk), -jnp.inf, jnp.float32)


def _extend_scores_pallas(q, w, keys, start, interpret=False):
    t, heads, dim = q.shape
    s_len = keys.shape[0]
    tq, tk = math.gcd(t, QUERY_TILE), math.gcd(s_len, KEY_TILE)
    assert t % tq == 0 and s_len % tk == 0, "the tiles divide the launch and the slot"
    kernel = functools.partial(_scores_kernel, heads=heads, dim=dim, tq=tq, tk=tk)
    # a key block past the query block's last position is not computed: name the
    # last one that is instead, so that the pipeline does not fetch it anew
    last = lambda i, start: (start[0] + (i + 1) * tq - 1) // tk
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t // tq, s_len // tk),
            in_specs=[
                pl.BlockSpec((tq, heads * dim), lambda i, j, start: (i, 0)),
                pl.BlockSpec((tq, heads), lambda i, j, start: (i, 0)),
                pl.BlockSpec((tk, dim), lambda i, j, start: (jnp.minimum(j, last(i, start)), 0)),
            ],
            out_specs=pl.BlockSpec((tq, tk), lambda i, j, start: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, s_len), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=64 << 20
        ),
        interpret=interpret,
        name="lm_index_scores",
    )(start, q.reshape(t, heads * dim), w, keys)


def kernel_fits(t: int, s_len: int, heads: int, dim: int) -> bool:
    """Whether the Pallas kernel takes these shapes: whole lane tiles a
    head and a key block, whole sublane tiles a query block."""
    return dim % 128 == 0 and math.gcd(s_len, KEY_TILE) % 128 == 0 and math.gcd(t, QUERY_TILE) % 8 == 0


def extend_scores(q, w, keys, positions, kernel=None):
    """``q [T, Hi, Di]`` bfloat16, ``w [T, Hi]`` float32 (T new tokens
    of one session at ``positions [T]``, ascending by one), ``keys [S,
    Di]`` its slot of index keys, the new tokens' already written.
    Returns ``[T, S]``. ``kernel``: the Pallas kernel (default: on a TPU,
    where the shapes are whole tiles) or plain XLA."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu" and kernel_fits(q.shape[0], keys.shape[0], *q.shape[1:])
    if kernel:
        return _extend_scores_pallas(q, w, keys, positions[:1])
    return _extend_scores_xla(q, w, keys, positions)


ROW_TILE = 32  # rows of scores the threshold kernel holds in VMEM


def _key(x):
    """float32 -> int32 that orders as the floats do; the same map takes a key's bits back."""
    bits = x if x.dtype == jnp.int32 else jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _largest_key(enough, shape):
    """The largest int32 key ``tau`` for which ``enough(tau)`` holds (a
    count of keys at or above ``tau`` that reaches ``k``): the sign
    first, which int32 orders the other way, then 31 bits from the top."""
    lowest = jnp.full(shape, jnp.iinfo(jnp.int32).min, jnp.int32)
    tau = jnp.where(enough(jnp.zeros(shape, jnp.int32)), 0, lowest)

    def bit(i, tau):
        trial = tau | (jnp.int32(1) << (30 - i))
        return jnp.where(enough(trial), trial, tau)

    return jax.lax.fori_loop(0, 31, bit, tau)


def _kth_kernel(scores_ref, k_ref, out_ref, key_ref):
    """``ROW_TILE`` whole rows in VMEM: their sortable keys once, then 32
    counting passes that never leave it."""
    key_ref[...] = _key(scores_ref[...])
    k = k_ref[...]
    enough = lambda trial: jnp.sum((key_ref[...] >= trial).astype(jnp.int32), axis=1, keepdims=True) >= k
    out_ref[...] = jax.lax.bitcast_convert_type(_key(_largest_key(enough, k.shape)), jnp.float32)


def _kth_largest_pallas(scores, k, interpret=False):
    r, s_len = scores.shape
    tr = math.gcd(r, ROW_TILE)
    assert r % tr == 0, "the tile divides the rows"
    return pl.pallas_call(
        _kth_kernel,
        grid=(r // tr,),
        in_specs=[pl.BlockSpec((tr, s_len), lambda i: (i, 0)), pl.BlockSpec((tr, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tr, s_len), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="lm_index_select",
    )(scores, k[:, None].astype(jnp.int32))[:, 0]


def kth_kernel_fits(r: int, s_len: int) -> bool:
    return s_len % 128 == 0 and math.gcd(r, ROW_TILE) % 8 == 0


def kth_largest(scores, k, last=None, kernel=None):
    """``scores [R, S]`` float32, ``k [R]`` int32 (1 <= k <= the row's
    finite entries): each row's ``k``-th largest value ``[R]``, the
    largest ``tau`` with ``count(scores >= tau) >= k``. ``last``: the
    largest position any row can see (a traced scalar): the rows are
    then counted a key block at a time and the blocks past it, which
    hold minus infinity, are not read; without it a row is counted
    whole (a step launch's few rows: 32 passes of one small program,
    where a loop over 34 blocks in each took 3.7 ms a layer, my chip
    run, PR 35). ``kernel``: for an extend launch's rows (``last``
    given), the Pallas kernel that holds whole rows in VMEM through all
    32 passes (default: on a TPU, where the shapes are whole tiles)."""
    r, s_len = scores.shape
    if kernel is None:
        kernel = last is not None and jax.default_backend() == "tpu" and kth_kernel_fits(r, s_len)
    if kernel:
        return _kth_largest_pallas(scores, k)
    kb = s_len if last is None else math.gcd(s_len, XLA_KEY_BLOCK)
    blocks = 1 if last is None else jnp.minimum(last // kb + 1, s_len // kb)

    def enough(tau):
        def add(j, n):
            block = _key(jax.lax.dynamic_slice_in_dim(scores, j * kb, kb, axis=1))
            return n + jnp.sum(block >= tau[:, None], axis=1, dtype=jnp.int32)

        return jax.lax.fori_loop(0, blocks, add, jnp.zeros((r,), jnp.int32)) >= k

    return jax.lax.bitcast_convert_type(_key(_largest_key(enough, (r,))), jnp.float32)
