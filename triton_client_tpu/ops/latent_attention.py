"""Multi-head latent attention over a slot cache.

The cache holds, for every position of every session slot, the
normalised latent ``c`` (``kv_rank`` values) and the ONE rotated key
head ``kr`` (``rope`` values) side by side, then a zero tail up to whole
128-lane tiles: ``[slots, slot_len, row]`` bfloat16 a layer, nothing per
head. Two forms compute
the same attention and the launch's shape picks one (models/axk1.py):

  * :func:`absorbed_attention` — one new token a session (a step
    launch): ``kv_b`` is folded into the query and the output, so every
    head attends over the 576-wide cache rows themselves and a launch
    reads each session's slot once;
  * :func:`expanded_attention` — many new tokens of ONE session (an
    extend launch): the slot's latents are expanded to per-head keys
    and values once a launch, and blocks of queries go against the
    blocks of keys at or before them with a running softmax.

Scores, softmax and the mask are float32; products read bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
KEY_BLOCK = 256


def _masked_softmax(scores, key_pos, query_pos):
    """Softmax over keys at positions <= the query's own."""
    keep = key_pos <= query_pos[..., None]
    return jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)


def absorbed_attention(q_nope, q_rope, kv, layer, slots, positions, kv_b, scale, nope):
    """``q_nope [B, H, nope]``, ``q_rope [B, H, rope]`` (one token a
    session), ``kv [layers, slots, S, rank + rope]`` the whole cache,
    ``slots``/``positions [B]`` of the sessions and their new tokens,
    ``kv_b [rank, H, nope + v]``. Returns ``[B, H, v]`` bfloat16.

    The sessions go one after another (``lax.map``): each reads its own
    slot in place with one dynamic slice, 5 MB at the served size. A
    gather of the B slots into one array copied them first, row by row,
    and took most of a step launch (8.2 ms for 8 slots of one layer: my
    chip run, PR 29)."""
    rank = kv_b.shape[0]
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, kv_b[..., :nope])
    tail = kv.shape[-1] - rank - q_rope.shape[-1]  # a cache row's zero tail
    q = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((*q_rope.shape[:-1], tail), q_rope.dtype)], axis=-1
    ).astype(kv.dtype)
    key_pos = jnp.arange(kv.shape[2])

    def one(args):
        q_row, slot, pos = args  # [H, rank + rope]
        rows = jax.lax.dynamic_slice(
            kv, (layer, slot, 0, 0), (1, 1, kv.shape[2], kv.shape[3])
        )[0, 0]
        scores = jnp.einsum(
            "hc,sc->hs", q_row, rows, preferred_element_type=jnp.float32
        ) * scale
        w = _masked_softmax(scores, key_pos, pos[None])
        return jnp.einsum("hs,sc->hc", w.astype(rows.dtype), rows[:, :rank])

    out_lat = jax.lax.map(one, (q, slots, positions))
    return jnp.einsum("bhc,chd->bhd", out_lat, kv_b[..., nope:])


def expanded_attention(q_nope, q_rope, kv_rows, positions, kv_b, scale, nope):
    """``q_nope [T, H, nope]``, ``q_rope [T, H, rope]`` (T new tokens of
    one session), ``kv_rows [S, rank + rope]`` (its slot, the new
    tokens already written), ``positions [T]`` ascending. Returns
    ``[T, H, v]``.

    Blocks of queries against blocks of keys with a running softmax, and
    for each block of queries only the key blocks up to its last
    position (a loop whose length the positions decide): a prompt of
    1,024 tokens reads 1,024 keys, not the slot's 4,352, and the causal
    half is skipped. One pass over scores ``[H, queries, S]`` in float32
    took 20 ms a layer at 2,048 tokens, ten times the products' time (my
    chip run, PR 29)."""
    rank = kv_b.shape[0]
    t, h = q_nope.shape[:2]
    s_len = kv_rows.shape[0]
    kv = jnp.einsum("sc,chd->shd", kv_rows[:, :rank], kv_b)
    rope = q_rope.shape[-1]
    k_nope, v, kr = kv[..., :nope], kv[..., nope:], kv_rows[:, rank : rank + rope]
    qb = min(t, QUERY_BLOCK)
    kb = math.gcd(s_len, KEY_BLOCK)

    def block(args):
        qn, qr, pos = args  # [qb, H, .], [qb]

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
            keep = (lo + jnp.arange(kb))[None, None, :] <= pos[None, :, None]
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
        blocks = jnp.minimum(pos[-1] // kb + 1, s_len // kb)
        top, total, acc = jax.lax.fori_loop(
            0, blocks, keys,
            (
                jnp.full((h, qb), -1e30, jnp.float32),
                jnp.zeros((h, qb), jnp.float32),
                jnp.zeros((h, qb, v.shape[-1]), jnp.float32),
            ),
        )
        return jnp.moveaxis(acc / total[..., None], 0, 1).astype(v.dtype)

    split = lambda a: a.reshape(t // qb, qb, *a.shape[1:])
    out = jax.lax.map(block, (split(q_nope), split(q_rope), split(positions)))
    return out.reshape(t, h, -1)
