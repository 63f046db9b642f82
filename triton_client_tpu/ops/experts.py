"""The routed-expert layer for a chip that holds a share of the experts.

The router scores every token over ALL experts of the model; this chip
holds ``experts_here`` of them from ``expert_offset`` on and computes
their part of the result for the tokens routed to them. Tokens routed
elsewhere add nothing here (their experts' chips add it in the
deployment; on one chip nothing stands in for them), and no token is
dropped whatever the imbalance: the token-slots routed here are sorted
by expert and go through a grouped product (``jax.lax.ragged_dot``, one
group an expert) in chunks of :data:`CHUNK_ROWS` rows, as many chunks
as the launch's routing needs. A launch of a few tokens (a step launch)
that is given the LAYERS' stacks and the layer's place in them reads
each held expert that some valid row of it chose, once, from its place
in the stack, runs all rows through it and weights by the gates: an
expert no row chose is not read. Given a layer's own experts it runs
every one of them over every row.

An expert is a GATED product, ``act(x W_gate) * (x W_up)`` through
``W_down``, and ``act`` is the layer's (``activation`` of
:func:`routed_experts`): SiLU, the default, for ``family: axk1``,
``deepseek_v32``, ``sdar_moe`` and ``bailing_hybrid`` (SwiGLU); ReLU for
``family: smallthinker`` (its ReLU-gated experts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK_ROWS = 1024
#: launches of at most this many tokens (a step launch) take plain
#: products instead: such a launch is bound by reading experts' weights,
#: and a plain product reads them in place where the grouped one has a
#: layer's experts copied out of the layers' stack first (5.3 ms a matrix
#: a launch: my chip run, PR 29). Given the stacks, the launch loops over
#: the held experts its rows chose (3.9 of 64 a layer at four rows of
#: examples/ling3_ep8), each sliced at (layer, expert) inside its product
DENSE_TOKENS = 64


def route(x, router, top_k: int, scale: float, normalise: bool = True,
          bias=None, n_group: int = 1, topk_group: int = 1, softmax: bool = False):
    """Sigmoid scores in float32 over all experts (``softmax``: a
    softmax over them, the Qwen3-MoE router of ``family: sdar_moe``): the
    ``top_k`` largest and their gates ``scale * s_i / sum s_j``. With ``bias``
    (``topk_method: noaux_tc``) the choice is made by ``s + bias`` and
    only inside the ``topk_group`` best of ``n_group`` groups of experts
    (a group's score: the sum of its two largest ``s + bias``); the
    gates are still the chosen experts' ``s``."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,  # a TPU's float32 product is bfloat16 passes unless told
    )
    scores = jax.nn.softmax(logits, axis=-1) if softmax else jax.nn.sigmoid(logits)
    if bias is None:
        top, idx = jax.lax.top_k(scores, top_k)
    else:
        t, e = scores.shape
        biased = scores + bias
        group = jnp.sum(jax.lax.top_k(biased.reshape(t, n_group, e // n_group), 2)[0], axis=-1)
        kept = group >= jax.lax.top_k(group, topk_group)[0][:, -1:]
        biased = jnp.where(jnp.repeat(kept, e // n_group, axis=-1), biased, -jnp.inf)
        idx = jax.lax.top_k(biased, top_k)[1]
        top = jnp.take_along_axis(scores, idx, axis=-1)
    if normalise:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * scale


def _gated_grouped(rows, experts, sizes, activation):
    """The gated product of ``rows``, sorted by expert, one group an expert."""
    dot = lambda a, w: jax.lax.ragged_dot(a, w, sizes)
    act = activation(dot(rows, experts["gate"])) * dot(rows, experts["up"])
    return jax.lax.ragged_dot(
        act, experts["down"], sizes, preferred_element_type=jnp.float32
    )


def routed_experts(x, valid, idx, gates, experts, expert_offset: int, chunk_rows: int = CHUNK_ROWS, layer=None,
                   activation=jax.nn.silu):
    """``x [T, D]`` bfloat16, ``valid [T]`` (pad tokens route nowhere),
    ``idx``/``gates [T, k]`` from :func:`route`, ``experts`` the held
    experts' ``gate``/``up [E, D, F]`` and ``down [E, F, D]``; with
    ``layer`` (an index, traced or not) they are the LAYERS' stacks
    ``[L, E, D, F]`` / ``[L, E, F, D]`` and this layer's experts are read
    from their place in them, which is what lets a launch of at most
    ``DENSE_TOKENS`` tokens read the chosen experts alone. Returns the held experts' sum ``[T, D]``
    float32 and the rows each expert saw ``[E]`` int32. ``chunk_rows``:
    the token-slots one grouped product takes; the launch runs as many
    as the rows routed HERE fill, so a launch whose expected rows equal
    ``chunk_rows`` takes one pass or two as the seed's router falls
    (models/axk1.py ``expert_chunk_rows``). ``activation``: what the gate's
    product passes through (module docstring)."""
    t, k = idx.shape
    held = experts["gate"].shape[-3]
    local = idx - expert_offset
    here = (local >= 0) & (local < held) & valid[:, None]
    if t <= DENSE_TOKENS:
        # [T, E]: the token's gate for each held expert, 0 where it was not chosen
        weight = jnp.sum(
            jnp.where(
                here[:, :, None] & (local[:, :, None] == jnp.arange(held)),
                gates[:, :, None], 0.0,
            ),
            axis=1,
        )
        if layer is None:
            # a layer's own experts: every one of them over every row. The products read the layer in place
            # (its slice of a scan fuses into them); a loop over single experts would have the slice written
            # out first and cost twice this (29.7 ms against 13.2 at 12 x 64 experts: my chip run, PR 45)
            act = activation(jnp.einsum("td,edf->etf", x, experts["gate"])) * jnp.einsum(
                "td,edf->etf", x, experts["up"]
            )
            y = jnp.einsum("etf,efd->etd", act, experts["down"]).astype(jnp.float32)
            rows = jnp.sum(weight > 0, axis=0, dtype=jnp.int32)
            return jnp.einsum("etd,te->td", y, weight), rows
        rows = jnp.sum(weight > 0, axis=0, dtype=jnp.int32)
        chosen = jnp.argsort(rows == 0, stable=True).astype(jnp.int32)  # the experts some row chose, ascending, first

        def add_expert(i, acc):
            e = chosen[i]
            # one expert's matrix at (layer, e) of the stack, sliced inside the product that reads it
            at = lambda a: jax.lax.dynamic_slice(a, (layer, e, 0, 0), (1, 1, *a.shape[2:]))[0, 0]
            act = activation(x @ at(experts["gate"])) * (x @ at(experts["up"]))
            y = (act @ at(experts["down"])).astype(jnp.float32)
            return acc + y * jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)

        acc = jnp.zeros((t, x.shape[1]), jnp.float32)
        return jax.lax.fori_loop(0, jnp.sum(rows > 0, dtype=jnp.int32), add_expert, acc), rows
    if layer is not None:
        experts = {name: jax.lax.dynamic_index_in_dim(a, layer, 0, False) for name, a in experts.items()}
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    token = (order // k).astype(jnp.int32)
    gate = jnp.where(key[order] < held, gates.reshape(-1)[order], 0.0)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32
    )
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    chunk = min(t * k, chunk_rows)
    assert (t * k) % chunk == 0, "token-slots must fill whole chunks"

    def add_chunk(i, acc):
        lo = i * chunk
        rows = jax.lax.dynamic_slice(token, (lo,), (chunk,))
        g = jax.lax.dynamic_slice(gate, (lo,), (chunk,))
        # the part of each expert's run of rows that falls in this chunk
        in_chunk = jnp.clip(ends, lo, lo + chunk) - jnp.clip(starts, lo, lo + chunk)
        y = _gated_grouped(x[rows], experts, in_chunk, activation)
        # rows past the last group are not the product's to define
        return acc.at[rows].add(jnp.where(g[:, None] > 0, y * g[:, None], 0.0))

    acc = jnp.zeros((t, x.shape[1]), jnp.float32)
    if t * k <= chunk:
        return add_chunk(0, acc), sizes
    chunks = (ends[-1] + chunk - 1) // chunk
    return jax.lax.fori_loop(0, chunks, add_chunk, acc), sizes
