"""Multi-head latent attention over a slot cache.

The cache holds, for every position of every session slot, the
normalised latent ``c`` (``kv_rank`` values) and the ONE rotated key
head ``kr`` (``rope`` values) side by side, then a zero tail up to whole
128-lane tiles: ``[slots, slot_len, row]`` bfloat16 a layer, nothing per
head. Two forms compute
the same attention and the launch's shape picks one (models/axk1.py):

  * :func:`absorbed_attention` — one new token a session (a step
    launch): ``kv_b`` is folded into the query and the output, so every
    head attends over the 576-wide cache rows themselves; a long slot is
    read in place by one Pallas kernel, a block of positions at a time
    and only as far as the session's position, a short one whole;
  * :func:`expanded_attention` — many new tokens of ONE session (an
    extend launch): the slot's latents are expanded to per-head keys
    and values once a launch, and blocks of queries go against the
    blocks of keys at or before them with a running softmax.

Either form takes a ``select`` ``(index_scores, tau)`` (ops/sparse_index.py:
one row of float32 index scores a query over the slot's positions and
the row's threshold): a query then reads only the positions whose index
score is at least its threshold, the learned sparse attention of
``family: deepseek_v32``. The selection is a mask over the same dense
products; without it the code is what it was. A slot of more than
:data:`SEGMENT_ROWS` positions is expanded a segment at a time. On a TPU
the selected extend launch runs each segment through one Pallas kernel
(:func:`_selected_segment`: eight heads a grid step share the step's
block of the mask, scores and softmax stay in VMEM); the same blocks in
plain XLA, whose scores ``[H, queries, keys]`` pass through HBM, took
six times as long at 128 heads (my chip run, PR 35).

Scores, softmax and the mask are float32; products read bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QUERY_BLOCK = 512
KEY_BLOCK = 256
#: the most positions whose per-head keys and values exist at once in an
#: extend launch: a slot of 34,048 positions expanded whole is 2.2 GB at
#: 128 heads, beside 10 GB of weights and cache
SEGMENT_ROWS = 8192


# The most positions of a slot that a step launch's row reads at a time. The sweep (my chip run, PR 49,
# perf/profile_latent_step.py: the kernel alone over the layers that attend, ms at 3 / 4 sessions of 8 rows, 11 of 16
# for A.X-K1; the whole-slot form it replaces read 3.19, 6.96 and 2.17 there and more inside a launch):
#   slot 62,720 x 32 heads x 2 layers:  256 1.66/1.85, 640 1.20/1.43, 896 1.16/1.34, 1,280 1.19/1.27, 1,792 1.19/1.24,
#                                       4,480 1.25/1.28, 6,272 1.20/1.35, 8,960 1.21/1.39, 12,544 1.36/1.52
#   slot 34,048 x 128 heads x 6 layers: 256 2.96/3.34, 896 2.18/2.41, 1,792 2.04/2.14, 2,432 2.01/2.23,
#                                       4,864 2.20/2.39, 17,024 5.13/5.23
#   slot 4,352 x 64 heads x 6 layers:   256 1.38, 2,176 1.32, 4,352 (whole) 1.41 (served by the whole-slot form: below)
# A short block fetches little past a row's position and pays a grid step (0.35 us) for every block of the slot, read
# or not; a long one the reverse. The kernel runs at 300-380 GB/s of rows whatever the block: every row passes the
# matrix unit twice (scores, values), which bounds it near 530.
STEP_BLOCK = 2560


def step_block(slot_len: int) -> int:
    """Positions a step launch's row reads at a time, from the slot's
    shape alone: the slot whole where it has at most
    :data:`SEGMENT_ROWS` positions (as the extend form takes it), else
    its largest part of whole 128-lane tiles that divides it and has at
    most :data:`STEP_BLOCK` (a slot that no such part divides: whole)."""
    fits = [n for n in range(128, STEP_BLOCK + 1, 128) if slot_len % n == 0]
    return slot_len if slot_len <= SEGMENT_ROWS or not fits else max(fits)


def on_chip() -> bool:
    """The one probe :func:`absorbed_attention` asks: its kernel is
    compiled for a TPU and interpreted elsewhere
    (tests/test_tpu_compile.py steers it: the compiler is asked here,
    where the backend says cpu)."""
    return jax.default_backend() == "tpu"


def _decode_kernel(layer_ref, slots_ref, pos_ref, q_ref, kv_ref, *refs, scale, block, rank, selected):
    """One grid step: one row of the launch against one block of its
    slot's positions, every head at once; the running softmax sits in
    scratch while the row's blocks (the last grid axis) go by. A block
    past the row's position is not computed (and, its index clamped to
    the row's last, not fetched)."""
    sel_ref, tau_ref = refs[:2] if selected else (None, None)
    out_ref, top_ref, total_ref, acc_ref = refs[-4:]
    b, j = pl.program_id(0), pl.program_id(1)
    pos, lo = pos_ref[b], j * block

    @pl.when(j == 0)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, -1e30, jnp.float32)
        total_ref[...] = jnp.zeros(total_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(edge: bool):
        """``edge``: the block holds the row's position, so some of it lies past it."""
        rows = kv_ref[...]  # [block, row]
        scores = jax.lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        keep = lo + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) <= pos if edge else None
        if selected:
            chosen = sel_ref[...] >= tau_ref[...]
            keep = chosen if keep is None else keep & chosen
        if keep is not None:
            scores = jnp.where(keep, scores, -jnp.inf)
        values = rows[:, :rank]
        if edge:
            # what a slot holds past the row's position is not the row's: it must not reach the sum even times zero
            seen = lo + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) <= pos
            values = jnp.where(seen, values, jnp.zeros_like(values))
        top = top_ref[...]
        new_top = jnp.maximum(top, jnp.max(scores, axis=1, keepdims=True))
        # the weights as the values' product reads them, and their sum over those: what is divided in the end is
        # then a mean of the values under weights that add up to one, whatever their rounding
        w = jnp.exp(scores - new_top).astype(values.dtype)
        shrink = jnp.exp(top - new_top)
        total_ref[...] = total_ref[...] * shrink + jnp.sum(w.astype(jnp.float32), axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * shrink + jnp.dot(w, values, preferred_element_type=jnp.float32)
        top_ref[...] = new_top

    pl.when(lo + block - 1 <= pos)(lambda: attend(False))
    pl.when((lo <= pos) & (pos < lo + block - 1))(lambda: attend(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = (acc_ref[...] / total_ref[...]).astype(out_ref.dtype)


def _whole_slot_attention(q, kv, layer, slots, positions, rank, scale, select):
    """:func:`absorbed_attention` for a slot taken whole: the sessions
    go one after another (``lax.map``), each slices its slot out of the
    cache (5.6 MB at the served 4,352 positions), scores every position
    and masks afterwards. ``q [B, H, row]``; returns ``[B, H, rank]``."""
    key_pos = jnp.arange(kv.shape[2])

    def one(args):
        q_row, slot, pos, *picked = args  # [H, row]
        rows = jax.lax.dynamic_slice(kv, (layer, slot, 0, 0), (1, 1, kv.shape[2], kv.shape[3]))[0, 0]
        scores = jnp.einsum("hc,sc->hs", q_row, rows, preferred_element_type=jnp.float32) * scale
        if picked:
            index_scores, tau = picked
            scores = jnp.where(index_scores >= tau, scores, -jnp.inf)
        w = jax.nn.softmax(jnp.where(key_pos <= pos, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hs,sc->hc", w.astype(rows.dtype), rows[:, :rank])

    return jax.lax.map(one, (q, slots, positions, *(select or ())))


def absorbed_attention(q_nope, q_rope, kv, layer, slots, positions, kv_b, scale, nope, select=None):
    """``q_nope [B, H, nope]``, ``q_rope [B, H, rope]`` (one token a
    session), ``kv [layers, slots, S, rank + rope]`` the whole cache,
    ``slots``/``positions [B]`` of the sessions and their new tokens,
    ``kv_b [rank, H, nope + v]``, ``select`` the sessions' index scores
    ``[B, S]`` and thresholds ``[B]`` or None. Returns ``[B, H, v]``
    bfloat16.

    A slot of more than :data:`SEGMENT_ROWS` positions goes through one
    Pallas kernel (``lm_latent_decode``; interpreted where there is no
    TPU) whose grid is rows x the slot's blocks of :func:`step_block`
    positions: each row reads its slot IN PLACE, a block at a time up
    to the block that holds its position, with a running softmax over
    the blocks, so nothing of the shape ``[S, row]`` is ever written out
    and a row fetches no block past its position (a pad row, at position
    0, fetches one). One whole slot sliced out a row wrote 80 MB out
    before anything was multiplied (3.9 of a step launch's 14.9 ms at
    62,720 positions: PERF.md section 6, PR 49). A shorter slot is taken
    whole (:func:`_whole_slot_attention`): the kernel gives the served
    slot of 4,352 positions 0.8 ms a launch back, and its one cell LOST
    1.1-2.6% of throughput by it in four pairs of four, because its
    batcher answers a shorter launch with more and smaller launches
    (PERF.md section 6, PR 49; ROADMAP A14): the kernel waits there on
    the batcher, as PR 45's form of the experts does. A gather of the B
    slots into one array copied them first, row by row, and took most of
    a step launch (8.2 ms for 8 slots of one layer: my chip run, PR 29)."""
    rank = kv_b.shape[0]
    b, h = q_nope.shape[:2]
    s_len, row = kv.shape[2:]
    block = step_block(s_len)
    assert s_len % block == 0, "the block divides the slot"
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, kv_b[..., :nope])
    tail = row - rank - q_rope.shape[-1]  # a cache row's zero tail
    q = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((*q_rope.shape[:-1], tail), q_rope.dtype)], axis=-1
    ).astype(kv.dtype)
    if block == s_len:
        out_lat = _whole_slot_attention(q, kv, layer, slots, positions, rank, scale, select)
        return jnp.einsum("bhc,chd->bhd", out_lat, kv_b[..., nope:])
    # the row's last block, named again for the blocks after it so that the pipeline does not fetch them
    at = lambda j, pos: jnp.minimum(j, pos // block)
    in_specs = [
        pl.BlockSpec((None, h, row), lambda i, j, layer, slots, pos: (i, 0, 0)),
        pl.BlockSpec((None, None, block, row), lambda i, j, layer, slots, pos: (layer[0], slots[i], at(j, pos[i]), 0)),
    ]
    operands = [q, kv]
    if select is not None:
        index_scores, tau = select
        in_specs += [
            pl.BlockSpec((None, 1, block), lambda i, j, layer, slots, pos: (i, 0, at(j, pos[i]))),
            pl.BlockSpec((None, 1, 1), lambda i, j, layer, slots, pos: (i, 0, 0)),
        ]
        operands += [index_scores[:, None, :], tau.astype(jnp.float32)[:, None, None]]
    out_lat = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block=block, rank=rank, selected=select is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, s_len // block),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, h, rank), lambda i, j, layer, slots, pos: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32), pltpu.VMEM((h, 1), jnp.float32), pltpu.VMEM((h, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), kv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=32 << 20
        ),
        interpret=not on_chip(),
        name="lm_latent_decode",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
        jnp.clip(positions, 0, s_len - 1).astype(jnp.int32), *operands,
    )
    return jnp.einsum("bhc,chd->bhd", out_lat, kv_b[..., nope:])


def _segment_rows(s_len: int, kb: int) -> int:
    """The slot whole where it has at most :data:`SEGMENT_ROWS`
    positions, else its largest part of whole key blocks that divides it
    and has at most that many."""
    blocks = s_len // kb
    fits = [n for n in range(1, blocks + 1) if blocks % n == 0 and n * kb <= SEGMENT_ROWS]
    return s_len if s_len <= SEGMENT_ROWS else max(fits) * kb


HEAD_TILE = 8  # heads a grid step of the selected kernel holds: they share its block of the mask
SELECTED_QUERY_TILE = 256  # ... and queries ...
# ... and at most this many keys, in whole lane tiles that divide the segment: 2,432 of the served slot's
# 4,864. A layer of a 4,096-token launch on 16k took 158 ms at 512 x 256, 189 at 256 x 256, 102 at
# 128 x 2,432, 95 at 512 x 2,432, 81.5 at 256 x 2,432 and 80.4 at 256 x 4,864 (my chip run, PR 35)
SELECTED_KEY_TILE = 2560


def _key_tile(seg: int) -> int:
    fits = [n for n in range(128, min(seg, SELECTED_KEY_TILE) + 1, 128) if seg % n == 0]
    return max(fits) if fits else seg


def _selected_kernel(meta_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, sel_ref, tau_ref,
                     top_in, total_in, acc_in, top_ref, total_ref, acc_ref, *, scale, tq, tk):
    """One grid step: ``HEAD_TILE`` heads, ``tq`` queries, ``tk`` keys of
    the segment; the running softmax sits in the output blocks, which
    stay in VMEM while the key blocks (the last grid axis) go by."""
    i, j = pl.program_id(1), pl.program_id(2)
    start, base = meta_ref[0], meta_ref[1]  # the first query's position; the segment's first key's

    @pl.when(j == 0)
    def _():
        top_ref[...] = top_in[...]
        total_ref[...] = total_in[...]
        acc_ref[...] = acc_in[...]

    @pl.when(base + j * tk <= start + (i + 1) * tq - 1)  # else: every key lies after every query
    def _():
        keep = sel_ref[...] >= tau_ref[...]  # [tq, tk]: minus infinity after the query, so causal too
        kr = kr_ref[...]
        dims = (((1,), (1,)), ((), ()))
        for g in range(qn_ref.shape[0]):
            scores = (
                jax.lax.dot_general(qn_ref[g], kn_ref[g], dims, preferred_element_type=jnp.float32)
                + jax.lax.dot_general(qr_ref[g], kr, dims, preferred_element_type=jnp.float32)
            ) * scale
            scores = jnp.where(keep, scores, -jnp.inf)
            top = top_ref[g]
            new_top = jnp.maximum(top, jnp.max(scores, axis=1, keepdims=True))
            w = jnp.exp(scores - new_top)
            shrink = jnp.exp(top - new_top)
            total_ref[g] = total_ref[g] * shrink + jnp.sum(w, axis=1, keepdims=True)
            acc_ref[g] = acc_ref[g] * shrink + jnp.dot(
                w.astype(v_ref.dtype), v_ref[g], preferred_element_type=jnp.float32
            )
            top_ref[g] = new_top


def _selected_segment(state, q_nope, q_rope, k_nope, kr, v, index_scores, tau, start, base, scale,
                      interpret=False):
    """One expanded segment into every query's running softmax.
    ``state`` ``(top [H, T, 1], total [H, T, 1], acc [H, T, v])``
    float32, ``q_nope [H, T, nope]``, ``q_rope [H, T, rope]``, ``k_nope
    [H, seg, nope]``, ``kr [seg, rope]``, ``v [H, seg, v]``,
    ``index_scores [T, S]``, ``tau [T, 1]``; ``start`` the first
    query's position (they ascend by one), ``base`` the segment's first
    key's. Returns the state."""
    h, t, _ = q_nope.shape
    seg = k_nope.shape[1]
    g, tq, tk = math.gcd(h, HEAD_TILE), math.gcd(t, SELECTED_QUERY_TILE), _key_tile(seg)
    assert h % g == 0 and t % tq == 0 and seg % tk == 0, "the tiles divide heads, queries and the segment"
    # a key block after the query block's last position is not computed: name the last one that is
    last = lambda i, meta: jnp.clip((meta[0] + (i + 1) * tq - 1 - meta[1]) // tk, 0, seg // tk - 1)
    heads = lambda width, rows: pl.BlockSpec((g, rows, width), lambda a, i, j, meta: (a, i, 0))
    keys = lambda width: pl.BlockSpec((g, tk, width), lambda a, i, j, meta: (a, jnp.minimum(j, last(i, meta)), 0))
    state_specs = [heads(1, tq), heads(1, tq), heads(v.shape[-1], tq)]
    return pl.pallas_call(
        functools.partial(_selected_kernel, scale=scale, tq=tq, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h // g, t // tq, seg // tk),
            in_specs=[
                heads(q_nope.shape[-1], tq), heads(q_rope.shape[-1], tq),
                keys(k_nope.shape[-1]),
                pl.BlockSpec((tk, kr.shape[-1]), lambda a, i, j, meta: (jnp.minimum(j, last(i, meta)), 0)),
                keys(v.shape[-1]),
                pl.BlockSpec((tq, tk), lambda a, i, j, meta: (i, meta[1] // tk + jnp.minimum(j, last(i, meta)))),
                pl.BlockSpec((tq, 1), lambda a, i, j, meta: (i, 0)),
                *state_specs,
            ],
            out_specs=state_specs,
        ),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in state],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=96 << 20
        ),
        interpret=interpret,
        name="lm_sparse_attention",
    )(jnp.stack([start, base]).astype(jnp.int32), q_nope, q_rope, k_nope, kr, v, index_scores, tau, *state)


def selected_kernel_fits(t: int, s_len: int, nope: int, vd: int) -> bool:
    """Whether the Pallas kernel takes these shapes: whole lane tiles a
    head's keys and values and a block of the mask."""
    kb = math.gcd(s_len, KEY_BLOCK)
    return nope % 128 == 0 and vd % 128 == 0 and kb % 128 == 0 and math.gcd(t, QUERY_BLOCK) % 8 == 0


def _selected_attention(q_nope, q_rope, kv_rows, positions, kv_b, scale, nope, select, interpret=False):
    """:func:`expanded_attention` under a selection, a segment at a time
    through :func:`_selected_segment`."""
    rank = kv_b.shape[0]
    t, h = q_nope.shape[:2]
    s_len, rope = kv_rows.shape[0], q_rope.shape[-1]
    vd = kv_b.shape[-1] - nope
    seg = _segment_rows(s_len, math.gcd(s_len, KEY_BLOCK))
    index_scores, tau = select
    qn, qr = jnp.moveaxis(q_nope, 0, 1), jnp.moveaxis(q_rope, 0, 1)

    def segment(g, state):
        rows = jax.lax.dynamic_slice_in_dim(kv_rows, g * seg, seg)
        kv = jnp.einsum("sc,chd->hsd", rows[:, :rank], kv_b)
        return tuple(_selected_segment(
            state, qn, qr, kv[..., :nope], rows[:, rank : rank + rope], kv[..., nope:],
            index_scores, tau[:, None], positions[0], g * seg, scale, interpret,
        ))

    state = (
        jnp.full((h, t, 1), -1e30, jnp.float32),
        jnp.zeros((h, t, 1), jnp.float32),
        jnp.zeros((h, t, vd), jnp.float32),
    )
    segments = jnp.minimum(positions[-1] // seg + 1, s_len // seg)
    _, total, acc = jax.lax.fori_loop(0, segments, segment, state)
    return jnp.moveaxis(acc / total, 0, 1).astype(kv_rows.dtype)


def expanded_attention(q_nope, q_rope, kv_rows, positions, kv_b, scale, nope, select=None, kernel=None):
    """``q_nope [T, H, nope]``, ``q_rope [T, H, rope]`` (T new tokens of
    one session), ``kv_rows [S, rank + rope]`` (its slot, the new
    tokens already written), ``positions [T]`` ascending, ``select`` the
    queries' index scores ``[T, S]`` and thresholds ``[T]`` or None.
    Returns ``[T, H, v]``.

    Blocks of queries against blocks of keys with a running softmax, and
    for each block of queries only the key blocks up to its last
    position (a loop whose length the positions decide): a prompt of
    1,024 tokens reads 1,024 keys, not the slot's 4,352, and the causal
    half is skipped. One pass over scores ``[H, queries, S]`` in float32
    took 20 ms a layer at 2,048 tokens, ten times the products' time (my
    chip run, PR 29). A long slot goes a segment at a time: the
    segment's latents are expanded, every block of queries takes its
    key blocks in, and the running softmax of all queries waits in
    memory for the next segment. ``kernel``: under a selection, the
    Pallas kernel (default: on a TPU, where the shapes are whole tiles)
    or these blocks in plain XLA."""
    rank = kv_b.shape[0]
    t, h = q_nope.shape[:2]
    s_len = kv_rows.shape[0]
    rope = q_rope.shape[-1]
    vd = kv_b.shape[-1] - nope
    if kernel is None:
        kernel = (select is not None and jax.default_backend() == "tpu"
                  and selected_kernel_fits(t, s_len, nope, vd))
    if kernel:
        return _selected_attention(q_nope, q_rope, kv_rows, positions, kv_b, scale, nope, select)
    qb = min(t, QUERY_BLOCK)
    kb = math.gcd(s_len, KEY_BLOCK)
    seg = _segment_rows(s_len, kb)
    split = lambda a: a.reshape(t // qb, qb, *a.shape[1:])
    queries = (split(q_nope), split(q_rope), split(positions), *map(split, select or ()))

    def expand(rows):
        kv = jnp.einsum("sc,chd->shd", rows[:, :rank], kv_b)
        return kv[..., :nope], kv[..., nope:], rows[:, rank : rank + rope]

    def fresh():
        return (
            jnp.full((h, qb), -1e30, jnp.float32),
            jnp.zeros((h, qb), jnp.float32),
            jnp.zeros((h, qb, vd), jnp.float32),
        )

    def attend(expanded, base, state, qn, qr, pos, picked):
        """One block of queries ``[qb, H, .]`` takes in the key blocks of
        the expanded rows (the slot's from ``base`` on) at or before its
        last position."""
        k_nope, v, kr = expanded

        def keys(j, carry):
            top, total, acc = carry
            lo = j * kb
            kn = jax.lax.dynamic_slice_in_dim(k_nope, lo, kb)
            scores = (
                jnp.einsum("thd,shd->hts", qn, kn, preferred_element_type=jnp.float32)
                + jnp.einsum(
                    "thr,sr->hts", qr, jax.lax.dynamic_slice_in_dim(kr, lo, kb),
                    preferred_element_type=jnp.float32,
                )
            ) * scale
            keep = (base + lo + jnp.arange(kb))[None, None, :] <= pos[None, :, None]
            if picked:
                index_scores, tau = picked  # [qb, S], [qb]
                chosen = jax.lax.dynamic_slice_in_dim(index_scores, base + lo, kb, axis=1)
                keep = keep & (chosen >= tau[:, None])[None]
            scores = jnp.where(keep, scores, -jnp.inf)
            new_top = jnp.maximum(top, scores.max(axis=-1))
            w = jnp.exp(scores - new_top[..., None])
            shrink = jnp.exp(top - new_top)
            acc = acc * shrink[..., None] + jnp.einsum(
                "hts,shd->htd", w.astype(v.dtype),
                jax.lax.dynamic_slice_in_dim(v, lo, kb),
                preferred_element_type=jnp.float32,
            )
            return new_top, total * shrink + w.sum(axis=-1), acc

        # key 0 is at or before every query, so the first block leaves no row empty
        blocks = jnp.clip((pos[-1] - base) // kb + 1, 0, k_nope.shape[0] // kb)
        return jax.lax.fori_loop(0, blocks, keys, state)

    if seg == s_len:
        expanded = expand(kv_rows)

        def block(args):
            qn, qr, pos, *picked = args
            _, total, acc = attend(expanded, 0, fresh(), qn, qr, pos, picked)
            return jnp.moveaxis(acc / total[..., None], 0, 1).astype(kv_rows.dtype)

        return jax.lax.map(block, queries).reshape(t, h, -1)

    def segment(g, state):
        expanded = expand(jax.lax.dynamic_slice_in_dim(kv_rows, g * seg, seg))

        def block(args):
            carried, qn, qr, pos, *picked = args
            return attend(expanded, g * seg, carried, qn, qr, pos, picked)

        return jax.lax.map(block, (state, *queries))

    state = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (t // qb, *a.shape)), fresh()
    )
    segments = jnp.minimum(positions[-1] // seg + 1, s_len // seg)
    _, total, acc = jax.lax.fori_loop(0, segments, segment, state)
    out = jnp.moveaxis(acc / total[..., None], 1, 2).astype(kv_rows.dtype)
    return out.reshape(t, h, -1)
