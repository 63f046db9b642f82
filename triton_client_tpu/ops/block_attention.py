"""Grouped-query attention over a per-head key/value slot cache under a
BLOCK mask: causal between blocks of ``block`` positions, bidirectional
inside one (the attention of a model that generates by diffusion over
blocks, models/sdar.py). A query at position ``p`` reads every key at a
position ``s`` with ``s // block <= p // block``, that is, up to the end
of its own block: the causal mask with the query's position rounded up.

The cache is a dict of two arrays, keys ``k`` and values ``v``, each
``[layers, slots, slot_len, kv_heads * head_dim]`` bfloat16: a position
is ONE row of whole 128-lane tiles (512 values at 4 heads of 128), the
layout of models/axk1.py's latent rows, which every launch program and a
fresh array agree on without being told. (With the heads on an axis of
their own, ``[..., kv_heads, slot_len, head_dim]``, the chip's compiler
laid the array out with the heads minor for the scatter that writes a
block's rows, and copied all 4 GB at both ends of every launch: my AOT
compile, PR 39.) Query head ``j`` reads key/value head ``j // (heads /
kv_heads)``. Two forms, one a launch kind:

  * :func:`prefill_attention`: many new tokens of ONE session, already
    written to its slot: tiles of queries against the blocks of keys up
    to their last visible position, with a running softmax, in ONE Pallas
    kernel (``lm_extend_attention``) that reads a head's key blocks out
    of the slot's rows as they lie;
  * :func:`block_attention`: ONE block of each of several sessions
    against what their slots hold BEFORE the block plus the block itself,
    which need not be in the cache (a denoising pass writes nothing).
    Every slot of the layer is attended to IN PLACE, in one batched
    product over the rows as they lie: the queries of key/value head
    ``g`` are zero outside that head's 128 lanes of a row (a
    block-diagonal query, four times the multiply-adds of a product a
    head, in a launch that is bound by the bytes it reads), so nothing
    of the cache is sliced, transposed or copied. The launch's rows are
    scattered to their slots' places (a few KB); a slot without a row
    attends with a zero query and is thrown away. Reading the launch's
    slots one after another was a dynamic slice, a softmax and two
    products a session a layer, 768 small programs a launch at 16
    sessions and 48 layers; gathering them into one array copies each
    slot first.

With ``block`` 1 the block mask is the causal one, and models/smallthinker.py
takes the same row layout and :func:`prefill_attention` for its FULL
layers. Its WINDOW layers (a position reads the ``window`` latest
positions, its own among them) keep a session's rows in a RING: position
``p`` lies at row ``p % rows`` of a slot of ``rows`` < ``slot_len`` rows
(:func:`write_ring`), which positions, never contents, give a meaning to:
a row whose position is out of a query's sight is masked, whatever an
earlier lap or an earlier session left in it.

  * :func:`prefill_attention` with ``window``: a tile's key blocks
    start at the FIRST block some query of it may see, and block ``j``
    (absolute) is found at row ``j * KEY_BLOCK % rows``; a ring of
    ``window`` + the launch's tokens - 1 rows or more still holds every
    key the launch may see after the launch has written its own;
  * :func:`step_attention`: ONE token of each of several sessions,
    already written, against its slot's rows IN PLACE, ring or not, in
    :func:`block_attention`'s one batched product over the rows as they
    lie (no copy of a slot per session per layer: gathering the launch's
    slots copied each first, PR 39): row ``r`` of a slot at position ``p``
    holds position ``p - (p - r) % rows``, in sight if that is not
    negative and inside the window.

Scores, softmax and the mask are float32; products read bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import latent_attention

QUERY_BLOCK = 512
KEY_BLOCK = 1024


def write_rows(kv, layer, slots, where, k, v):
    """``k``, ``v`` ``[R, n, G, d]`` into the cache's layer ``layer`` at
    positions ``where [R, n]`` of ``slots [R]``; a position past the
    slot is dropped (a pad token, a row that writes nothing)."""
    rows = lambda a: a.reshape(*a.shape[:2], -1).astype(kv["k"].dtype)
    put = lambda cache, new: cache.at[layer, slots[:, None], where].set(rows(new), mode="drop")
    return {"k": put(kv["k"], k), "v": put(kv["v"], v)}


def write_span(kv, layer, slot, start, k, v):
    """``k``, ``v`` ``[n, G, d]`` of ONE session into its slot from
    position ``start`` on, as one contiguous update; returns the cache
    and the slot's rows ``(keys [S, G * d], values [S, G * d])`` as they
    now are. Pad tokens at the span's end are written too: positions
    past a session's length are read by nobody before the request that
    appends them has written them. The update goes through a copy of the
    slot widened by the span, so that a padded span that runs past the
    slot's end is cut there (a plain ``dynamic_update_slice`` would move
    its start back instead)."""

    def put(cache, new):
        new = new.reshape(new.shape[0], -1).astype(cache.dtype)
        s_len, width = cache.shape[2:]
        rows = jax.lax.dynamic_slice(cache, (layer, slot, 0, 0), (1, 1, s_len, width))[0, 0]
        wide = jnp.concatenate([rows, jnp.zeros_like(new)])
        rows = jax.lax.dynamic_update_slice(wide, new, (start, 0))[:s_len]
        return jax.lax.dynamic_update_slice(cache, rows[None, None], (layer, slot, 0, 0)), rows

    keys, key_rows = put(kv["k"], k)
    values, value_rows = put(kv["v"], v)
    return {"k": keys, "v": values}, (key_rows, value_rows)


def write_ring(kv, layer, slot, start, k, v):
    """:func:`write_span` into a slot that is a RING: position ``p`` goes
    to row ``p % rows``, and a span that runs past the ring's last row
    goes on at its first (a span holds at most ``rows`` tokens). Pad
    tokens at the span's end are written too: the rows they take held
    positions that are out of every later query's sight."""

    def put(cache, new):
        new = new.reshape(new.shape[0], -1).astype(cache.dtype)
        n, (ring, width) = new.shape[0], cache.shape[2:]
        assert n <= ring, "a span fits the ring"
        rows = jax.lax.dynamic_slice(cache, (layer, slot, 0, 0), (1, 1, ring, width))[0, 0]
        at = start % ring
        wide = jax.lax.dynamic_update_slice(jnp.concatenate([rows, jnp.zeros_like(new)]), new, (at, 0))
        # the rows that ran past the ring's end are its head's
        head = jnp.where((jnp.arange(n) < at + n - ring)[:, None], wide[ring:], wide[:n])
        rows = jax.lax.dynamic_update_slice(wide[:ring], head, (0, 0))
        return jax.lax.dynamic_update_slice(cache, rows[None, None], (layer, slot, 0, 0)), rows

    keys, key_rows = put(kv["k"], k)
    values, value_rows = put(kv["v"], v)
    return {"k": keys, "v": values}, (key_rows, value_rows)


def _extend_kernel(first_ref, last_ref, start_ref, q_ref, k_ref, v_ref, o_ref, top_ref, total_ref, acc_ref,
                   *, scale, block, window, kb, heads, d):
    """One grid step: one key/value head, one tile of queries (the
    ``heads`` query heads of its group, each ``[qb, d]``, side by side in
    a row of ``q_ref``) against one block of ``kb`` keys; the running
    softmax of every head sits in scratch while the tile's key blocks
    (the last grid axis) go by. ``first_ref``/``last_ref``: the first
    and last absolute key block some query of tile ``i`` sees,
    ``start_ref`` its first query's position (they ascend by one). A
    step past the last block does nothing, and has fetched nothing
    (:func:`prefill_attention`'s index map). Only a block that straddles
    the tile's limit or its window's floor is masked: the blocks between
    are in every query's sight whole."""
    i, j = pl.program_id(1), pl.program_id(2)
    qb = q_ref.shape[0]
    first, last, p0 = first_ref[i], last_ref[i], start_ref[i]

    @pl.when(j == 0)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, -1e30, jnp.float32)  # finite: a row with no key in sight yet
        total_ref[...] = jnp.zeros(total_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    lo = (first + j) * kb  # the block's first key's absolute position
    limit_of = lambda p: p if block == 1 else (p // block + 1) * block - 1
    whole = lo + kb - 1 <= limit_of(p0)  # the first query's limit is the tile's least
    if window:
        whole &= lo >= p0 + qb - window  # the last query's floor is the tile's highest

    to_exponent = scale * math.log2(math.e)

    def take(masked):
        keys, values = k_ref[...], v_ref[...]
        if masked:  # one mask a step: every head of the group shares it
            pos = p0 + jax.lax.broadcasted_iota(jnp.int32, (qb, 1), 0)
            key = lo + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
            keep = key <= limit_of(pos)
            if window:
                keep &= key >= pos - (window - 1)
        product = lambda h: jax.lax.dot_general(
            q_ref[:, h * d:(h + 1) * d], keys, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ahead = product(0)
        for h in range(heads):
            # the next head's product is issued before this head's softmax, which the matrix unit then runs beside:
            # in the heads' own order a layer took 3.55 ms where it takes 3.13 (2,048 tokens on 13k: PR 50)
            scores, ahead = ahead, product(h + 1) if h + 1 < heads else None
            if masked:
                scores = jnp.where(keep, scores, -jnp.inf)
            # the scale rides in the exponent's own multiply (2 ** (x log2 e)): the scores are not passed over for it
            top = top_ref[h]  # in units of the exponent of 2
            new_top = jnp.maximum(top, scores.max(axis=1, keepdims=True) * to_exponent)
            w = jnp.exp2(scores * to_exponent - new_top)
            shrink = jnp.exp2(top - new_top)
            total_ref[h] = total_ref[h] * shrink + w.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * shrink + jnp.dot(w.astype(values.dtype), values, preferred_element_type=jnp.float32)
            top_ref[h] = new_top

    in_sight = first + j <= last
    pl.when(in_sight & whole)(lambda: take(False))
    pl.when(in_sight & jnp.logical_not(whole))(lambda: take(True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for h in range(heads):
            o_ref[:, h * d:(h + 1) * d] = (acc_ref[h] / total_ref[h]).astype(o_ref.dtype)


def prefill_attention(q, rows, positions, block: int, scale: float, window: int = 0):
    """``q [n, H, d]`` (n new tokens of one session, ``positions [n]``
    ascending by one), ``rows`` its slot's ``(keys, values)`` ``[S, G *
    d]`` with the new tokens written. Returns ``[n, H * d]``.

    One Pallas kernel (``lm_extend_attention``, under a window
    ``lm_extend_attention_window``; interpreted where there is no TPU)
    whose grid is key/value head x tile of :data:`QUERY_BLOCK` queries x
    block of :data:`KEY_BLOCK` keys, the key blocks last and in turn: a
    head's ``d`` lanes are a column block of a row, so a key block ``[kb,
    d]`` is read straight out of ``rows`` (no split of the slot by head),
    it serves the ``H / G`` query heads of its group one after another,
    and scores, mask, maximum, exponential and sum stay in the chip's
    fast memory. For each tile only the key blocks from the first to the
    last some query of it sees (a prompt of 512 tokens reads 512 keys,
    not the slot's 2,048): the steps past them compute nothing and name
    the block already held, so they fetch nothing. With ``window`` a
    query at ``p`` reads positions ``p - window + 1`` to ``p``, ``rows``
    are a ring (:func:`write_ring`) and absolute key block ``j`` lies at
    row ``j * kb % S``.

    The running softmax in plain XLA that this replaced (a ``lax.map``
    over query blocks around a ``fori_loop`` over key blocks, both 512)
    was no slow path: XLA fuses a key block's mask and softmax into its
    two products, and alone it took 36-63 us a (tile, key block) step of
    19 us of products: 4.1 ms a full layer and 1.6 a window layer for
    2,048 tokens on 13k of context, 18 of an extend launch's 134 ms at 7k
    (not the 60 that ISSUE 50 reckoned). The kernel takes 3.1 and 1.4 ms
    there, 54 us a step of 512 x 1,024 whose products are 38, and is the
    faster at every served rung (PERF.md section 6, PR 50;
    ``perf/profile_extend_attention.py`` keeps the loop). What is left
    over the products is the scores' way through fast memory between
    the maximum and the exponential (a quarter of a step: without the
    maximum it ran in 2.8 ms for 3.8), not arithmetic: the exponential is
    free beside it."""
    n, h, d = q.shape
    s_len, g = rows[0].shape[0], rows[0].shape[1] // d
    r = h // g
    qb, kb = min(n, QUERY_BLOCK), math.gcd(s_len, KEY_BLOCK)
    assert n % qb == 0, "the launch's tokens fill whole query blocks"
    tiles, blocks = n // qb, s_len // kb
    starts, ends = (positions.reshape(tiles, qb)[:, at].astype(jnp.int32) for at in (0, -1))
    last = ((ends // block + 1) * block - 1) // kb
    if window:
        first = jnp.maximum(starts - (window - 1), 0) // kb
        # the most key blocks a tile's sight touches (its first and last may be the same rows of a small ring,
        # each masked down to the positions it stands for)
        steps = (window + qb + block - 3) // kb + 2
    else:
        # key 0 is visible to every query, so the first block leaves no row empty (under a window a row
        # whose first blocks are all out of its sight keeps the state's finite floor until one is not)
        first, last, steps = jnp.zeros_like(starts), jnp.clip(last, 0, blocks - 1), blocks
    # the tile's last block, named again for the steps after it so that the pipeline does not fetch them
    at = lambda i, j, first, last: (first[i] + jnp.minimum(j, last[i] - first[i])) % blocks
    tile = pl.BlockSpec((qb, r * d), lambda head, i, j, *_: (i, head))
    key_block = pl.BlockSpec((kb, d), lambda head, i, j, first, last, _: (at(i, j, first, last), head))
    return pl.pallas_call(
        functools.partial(_extend_kernel, scale=scale, block=block, window=window, kb=kb, heads=r, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(g, tiles, steps),
            in_specs=[tile, key_block, key_block],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((r, qb, 1), jnp.float32), pltpu.VMEM((r, qb, 1), jnp.float32),
                pltpu.VMEM((r, qb, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, h * d), rows[1].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=64 << 20
        ),
        interpret=not latent_attention.on_chip(),
        name="lm_extend_attention_window" if window else "lm_extend_attention",  # the trace tells a launch's two apart
    )(first, last, starts, q.reshape(n, h * d), *rows)


def block_attention(q, k, v, kv, layer, slots, positions, scale: float):
    """``q [R, B, H, d]``, ``k``, ``v`` ``[R, B, G, d]``: one block of B
    positions of each of R sessions, in slots ``slots [R]`` (a pad row's
    is the slot COUNT: it is dropped), the block's first position
    ``positions [R]`` = the positions cached before it. Each row reads
    the ``positions[r]`` cached positions of its slot and its own block
    (whether or not the launch has written it). Returns ``[R, B, H * d]``."""
    rows, b, h, d = q.shape
    g = k.shape[2]
    r = h // g
    n_slots, s_len = kv["k"].shape[1:3]
    dtype = kv["k"].dtype
    layer_of = lambda cache: jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)  # [slots, S, G * d]
    # the queries of key/value head g, rows (g, head in group, position in block), zero outside g's lanes
    qs = jnp.moveaxis(q.reshape(rows, b, g, r, d), 1, 3)  # [R, G, r, B, d]
    qs = (qs[:, :, :, :, None, :] * jnp.eye(g, dtype=q.dtype)[:, None, None, :, None]).reshape(rows, g * r * b, g * d)
    place = lambda a: jnp.zeros((n_slots, *a.shape[1:]), a.dtype).at[slots].set(a, mode="drop")
    own = lambda a: place(a.reshape(rows, b, g * d).astype(dtype))
    qs, own_k, own_v, cached = place(qs), own(k), own(v), place(positions)
    scores = jnp.einsum("sqc,skc->sqk", qs, layer_of(kv["k"]), preferred_element_type=jnp.float32) * scale
    scores = jnp.where(jnp.arange(s_len) < cached[:, None, None], scores, -jnp.inf)
    mine = jnp.einsum("sqc,skc->sqk", qs, own_k, preferred_element_type=jnp.float32) * scale
    top = jnp.maximum(scores.max(axis=-1), mine.max(axis=-1))[..., None]
    w, w_mine = jnp.exp(scores - top), jnp.exp(mine - top)
    total = w.sum(axis=-1) + w_mine.sum(axis=-1)
    out = jnp.einsum(
        "sqk,skc->sqc", w.astype(dtype), layer_of(kv["v"]), preferred_element_type=jnp.float32
    ) + jnp.einsum("sqk,skc->sqc", w_mine.astype(dtype), own_v, preferred_element_type=jnp.float32)
    out = (out / total[..., None])[jnp.minimum(slots, n_slots - 1)]  # [R, G * r * B, G * d]
    # a row of key/value head g keeps that head's lanes of its output
    out = jnp.einsum("rgqhd,gh->rgqd", out.reshape(rows, g, r * b, g, d), jnp.eye(g, dtype=out.dtype))
    return jnp.moveaxis(out.reshape(rows, g, r, b, d), 3, 1).reshape(rows, b, h * d).astype(dtype)


def step_attention(q, kv, layer, slots, positions, scale: float, window: int = 0):
    """``q [R, H, d]``: ONE token of each of R sessions at ``positions
    [R]``, its key and value already written to row ``positions[r] %
    rows`` of slot ``slots[r]`` (a pad row's slot is the slot COUNT: it
    is dropped). Each row reads its slot's rows in place, a ring or a
    whole slot alike: row ``s`` holds the position ``age`` = ``(p - s) %
    rows`` back from ``p``, in sight if that position is not negative
    and, with ``window``, among the latest ``window``. Returns ``[R, H * d]``."""
    rows, h, d = q.shape
    n_slots, s_len, width = kv["k"].shape[1:]
    g = width // d
    r = h // g
    dtype = kv["k"].dtype
    layer_of = lambda cache: jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)  # [slots, S, G * d]
    # the queries of key/value head g, rows (g, head in group), zero outside g's lanes (block_attention)
    qs = q.reshape(rows, g, r, 1, d) * jnp.eye(g, dtype=q.dtype)[:, None, :, None]  # [R, G, r, G, d]
    place = lambda a: jnp.zeros((n_slots, *a.shape[1:]), a.dtype).at[slots].set(a, mode="drop")
    qs, at = place(qs.reshape(rows, h, width)), place(positions)
    age = (at[:, None] - jnp.arange(s_len)[None, :]) % s_len  # [slots, S]
    keep = age <= at[:, None]
    if window:
        keep &= age < window
    scores = jnp.einsum("sqc,skc->sqk", qs, layer_of(kv["k"]), preferred_element_type=jnp.float32) * scale
    w = jax.nn.softmax(jnp.where(keep[:, None, :], scores, -jnp.inf), axis=-1)  # a row's own position is in sight
    out = jnp.einsum("sqk,skc->sqc", w.astype(dtype), layer_of(kv["v"]), preferred_element_type=jnp.float32)
    out = out[jnp.minimum(slots, n_slots - 1)]  # [R, H, G * d]
    # a row of key/value head g keeps that head's lanes of its output
    out = jnp.einsum("rgqhd,gh->rgqd", out.reshape(rows, g, r, g, d), jnp.eye(g, dtype=out.dtype))
    return out.reshape(rows, h * d).astype(dtype)
