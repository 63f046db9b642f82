"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): a linear
attention whose per-head state ``S`` (``d_k x d_v``, float32) forgets by
a PER-CHANNEL decay and learns by the delta rule. With ``a_t = exp(g_t)``
(``g_t <= 0``, one log decay a key channel), ``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

A session's state is what it has read so far: it does not grow with the
context, and the next token reads it whole. It is held VALUE-major,
``[..., d_v, d_k]``: the decay then scales lanes. Before the recurrence
``q``, ``k`` and ``v`` pass a causal depthwise convolution of width
:data:`CONV_WIDTH` over positions (:func:`short_conv`), whose last three
input rows are part of the session's state too. Two forms of the
recurrence, one a launch kind (models/ling.py):

  * :func:`step`: one token of each of several sessions. Row by row the
    slot's state is read in place, moved by one rank-one update and
    written back; a pad row writes back what it read, so it and every
    slot that is not in the launch are bit-identical afterwards.
  * :func:`extend`: many tokens of ONE session, chunkwise-parallel from
    the slot's state: :data:`CHUNK` positions at a time, the state
    carried from chunk to chunk. NOT a scan a token. Its core is one
    Pallas kernel, ``lm_kda_chunk`` (grid over heads and chunks, a
    head's state resident in VMEM across its chunks); where the head
    size is no whole lane tile, or off the chip, the same chunk
    (:func:`_chunk`) runs as plain XLA under a scan over chunks.

The chunk. With ``G_i`` the cumulative log decay inside the chunk and
``u_i`` the delta rule's corrected value (``S_i = diag(a_i) S_{i-1} +
k_i u_i^T``), the chunk's ``U`` solves ``(I + A) U = beta (V - (K exp
G) S_0)``, ``A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` for ``j <
i``; then ``O = (Q exp G) S_0 + B U`` with ``B_ij`` the same sum over
``q_i`` for ``j <= i``, and ``S_C = diag(exp G_C) S_0 + (K exp(G_C -
G))^T U``. ``exp(G_i - G_j)`` is at most 1 but its factors are not: a
log decay is at least ``kda_lower_bound`` (-5) a token, so the chunk is
taken in sub-chunks of :data:`SUB` positions inside which ``G`` moves by
at most 80 and ``exp(-G)`` is finite in float32. Rows of sub-chunk ``a``
carry ``exp(G_i - base_a)`` (``base_a``: ``G`` at the sub-chunk's
boundary; at most 1), columns ``exp(base_a - G_j)``: at most 1 for a
column of an earlier sub-chunk (the decay relative to the boundary), at
most ``e^80`` inside the sub-chunk, where the product is the difference
``G_i - G_j <= 0``. ``I + A`` is inverted by products, never by 64
dependent vector steps: its diagonal 16-blocks ``D`` by ``(I - D)(I +
D^2)(I + D^4)(I + D^8)`` (``D^16 = 0``), the rest by the same identity
over the four blocks. Decay sums, the solve's values and the state are
float32; what the matrix unit multiplies is bfloat16.

Pad positions (a turn that is no multiple of the chunk, a launch padded
to its shape) carry ``beta = 0`` and ``g = 0``: they change nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CONV_WIDTH = 4  # positions the short convolution reads: the token's own and the three before it
CHUNK = 64  # positions a chunk of the extend form holds
SUB = 16  # ... in sub-chunks of this many: 16 x |kda_lower_bound| = 80, and exp(80) is finite in float32
_SAFE = 80.0


def short_conv(tail, rows, weight):
    """The causal depthwise convolution over positions, then SiLU.
    ``tail [..., W - 1, C]`` the rows before the launch, ``rows [..., n,
    C]`` the launch's, ``weight [W, C]`` (its last row multiplies the
    token's own position). Returns ``[..., n, C]`` float32 and
    ``tail + rows`` (what the next tail is cut from)."""
    full = jnp.concatenate([tail.astype(rows.dtype), rows], axis=-2)
    n = rows.shape[-2]
    y = sum(
        full[..., w : w + n, :].astype(jnp.float32) * weight[w].astype(jnp.float32)
        for w in range(weight.shape[0])
    )
    return jax.nn.silu(y), full


def _dot(a, b, contract=((1,), (0,))):
    """A product as the matrix unit takes it: bfloat16 in, float32 out."""
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), (contract, ((), ())),
        preferred_element_type=jnp.float32,
    )


_NT = ((1,), (1,))  # a @ b.T


def _chunk(q, k, kb, vb, G, s):
    """One chunk of one head. ``q``, ``k``, ``kb`` (= beta k), ``vb``
    (= beta v) ``[CHUNK, d]`` float32, ``G [CHUNK, d]`` the chunk's own
    cumulative log decay (inclusive), ``s [d_v, d_k]`` the state before
    it. Returns the outputs ``[CHUNK, d_v]`` and the state after it."""
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    a_rows, b_rows = [], []
    for lo in range(0, c, SUB):
        base = G[lo - 1 : lo] if lo else jnp.zeros_like(G[:1])
        mine = jnp.exp(G[lo : lo + SUB] - base)  # at most 1
        # columns of later sub-chunks are masked below; clamped so that they stay finite until then
        theirs = k * jnp.exp(jnp.minimum(base - G, _SAFE))
        both = _dot(jnp.concatenate([kb[lo : lo + SUB] * mine, q[lo : lo + SUB] * mine]), theirs, _NT)
        a_rows.append(both[:SUB])
        b_rows.append(both[SUB:])
    a = jnp.where(col < row, jnp.concatenate(a_rows), 0.0)
    b = jnp.where(col <= row, jnp.concatenate(b_rows), 0.0)
    # (I + A)^-1 = (I + N)^-1 (I + D)^-1, D the diagonal blocks of A, N = (I + D)^-1 (A - D)
    eye = (row == col).astype(jnp.float32)
    diagonal = (row // SUB) == (col // SUB)
    power = jnp.where(diagonal, -a, 0.0)
    inverse = eye + power
    for _ in range(SUB.bit_length() - 2):  # (I - D)(I + D^2)(I + D^4)(I + D^8)
        power = _dot(power, power)
        inverse = inverse + _dot(inverse, power)
    n = _dot(inverse, jnp.where(diagonal, 0.0, a))
    rest = (eye - n) + _dot(eye - n, _dot(n, n))  # (I - N)(I + N^2): N^4 = 0
    decay = jnp.exp(G)
    u = _dot(rest, _dot(inverse, vb - _dot(kb * decay, s, _NT)))
    out = _dot(q * decay, s, _NT) + _dot(b, u)
    last = G[c - 1 :]
    s = s * jnp.exp(last) + _dot(u.T, k * jnp.exp(last - G))
    return out, s


def _chunk_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, s0_ref, o_ref, s_ref):
    """One grid step: one head's chunk. The state's output block stays
    in VMEM while the head's chunks (the last grid axis) go by."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    out, s = _chunk(q_ref[...], k_ref[...], kb_ref[...], vb_ref[...], g_ref[...], s_ref[0])
    o_ref[...] = out
    s_ref[0] = s


def kernel_fits(d: int) -> bool:
    """Whether ``lm_kda_chunk`` takes this head size: a head's values are whole lane tiles."""
    return d % 128 == 0


def on_chip() -> bool:
    """The one probe :func:`chunk_scan` asks (tests/test_tpu_compile.py
    steers it: the compiler is asked here, where the backend says cpu)."""
    return jax.default_backend() == "tpu"


def chunk_scan(q, k, kb, vb, G, s0, kernel=None, interpret=False):
    """The chunks of every head. ``q``, ``k``, ``kb``, ``vb``, ``G``
    ``[T, H * d]`` float32 (a head's values side by side; ``T`` whole
    chunks; ``G`` cumulative inside each chunk), ``s0 [H, d, d]``.
    Returns the outputs ``[T, H * d]`` and the last state ``[H, d, d]``.
    ``kernel``: the Pallas kernel (default: on a TPU, where
    :func:`kernel_fits`) or the same chunk in plain XLA."""
    t, width = q.shape
    h = s0.shape[0]
    d = width // h
    assert t % CHUNK == 0 and width == h * d, "whole chunks of whole heads (extend pads a turn to them)"
    if kernel is None:
        kernel = on_chip() and kernel_fits(d)
    if not kernel:
        heads = lambda a: jnp.moveaxis(a.reshape(t // CHUNK, CHUNK, h, d), 2, 1)  # [chunks, H, CHUNK, d]

        def one(s, xs):
            out, s = jax.vmap(_chunk)(*xs, s)
            return s, out

        s, out = jax.lax.scan(one, s0, tuple(map(heads, (q, k, kb, vb, G))))
        return jnp.moveaxis(out, 1, 2).reshape(t, width), s
    rows = pl.BlockSpec((CHUNK, d), lambda head, i: (i, head))
    state = pl.BlockSpec((1, d, d), lambda head, i: (head, 0, 0))
    return pl.pallas_call(
        _chunk_kernel,
        grid=(h, t // CHUNK),
        in_specs=[rows] * 5 + [state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((t, width), jnp.float32), jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lm_kda_chunk",
    )(q, k, kb, vb, G, s0)


def extend(q, k, v, g, beta, s0, kernel=None, interpret=False):
    """Many tokens of ONE session. ``q``, ``k``, ``v``, ``g`` ``[T, H,
    d]`` float32 (``q`` scaled, ``q`` and ``k`` normalised; ``g`` the
    log decay a key channel), ``beta [T, H]``, ``s0 [H, d, d]`` the
    session's state (value-major). Pad positions carry ``beta = 0`` and
    ``g = 0``. Returns ``o [T, H, d]`` float32 and the state after the
    last position."""
    t, h, d = q.shape
    pad = -t % CHUNK
    flat = lambda a: jnp.pad(a.reshape(t, h * d), ((0, pad), (0, 0)))
    b = beta[..., None]
    # the decay sums, float32: cumulative inside each chunk
    G = jnp.cumsum(flat(g).reshape(-1, CHUNK, h * d), axis=1).reshape(-1, h * d)
    with jax.named_scope("lm_kda_chunk"):
        out, s = chunk_scan(flat(q), flat(k), flat(k * b), flat(v * b), G, s0, kernel, interpret)
    return out[:t].reshape(t, h, d), s


def step(q, k, v, g, beta, valid, fresh, state, layer, slots):
    """One token of each of ``R`` sessions. ``q``, ``k``, ``v``, ``g``
    ``[R, H, d]`` float32, ``beta [R, H]``, ``valid [R]`` (a pad row is
    not), ``fresh [R]`` (the row starts its session: it reads a zero
    state, whatever a former session left in the slot), ``state [layers,
    slots, H, d, d]`` the whole state, ``slots [R]``. Returns ``o [R, H,
    d]`` float32 and the state, in which row ``r`` moved ``state[layer,
    slots[r]]`` by one token; a pad row wrote back what it read."""
    h, d = state.shape[2], state.shape[-1]

    def one(r, carry):
        state, out = carry
        row = lambda a: jax.lax.dynamic_index_in_dim(a, r, 0, keepdims=False)
        at = (layer, row(slots), 0, 0, 0)
        old = jax.lax.dynamic_slice(state, at, (1, 1, h, d, d))[0, 0]
        kr, bt = row(k), row(beta)[:, None]
        s = jnp.where(row(fresh), 0.0, old) * jnp.exp(row(g))[:, None, :]
        # plain sums, float32: a step is bound by the bytes of the state, not by these
        seen = jnp.sum(s * kr[:, None, :], axis=-1)  # what the decayed state answers to this key: [H, d_v]
        s = s + (bt * (row(v) - seen))[:, :, None] * kr[:, None, :]
        o = jnp.sum(s * row(q)[:, None, :], axis=-1)
        s = jnp.where(row(valid), s, old)
        return jax.lax.dynamic_update_slice(state, s[None, None], at), out.at[r].set(o)

    state, out = jax.lax.fori_loop(0, q.shape[0], one, (state, jnp.zeros_like(v)))
    return out, state


def log_decay(f, a_log, dt_bias, lower_bound: float):
    """The per-channel log decay: ``lower_bound * sigmoid(exp(A_log_h) *
    (f + dt_bias))``, in ``[lower_bound, 0)``. ``f [..., H, d]``,
    ``a_log [H]``, ``dt_bias [H, d]``."""
    return lower_bound * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * (f.astype(jnp.float32) + dt_bias))
