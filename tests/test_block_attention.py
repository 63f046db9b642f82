"""``ops/block_attention.py::prefill_attention`` (the Pallas kernel
``lm_extend_attention``, interpreted here) against a plain float32
masked softmax over the positions a slot's rows hold: the block mask and
the causal one, a window over a ring that wraps, one query tile and
several, a first prompt and a context that is not a multiple of the key
block, pad tokens at a launch's tail, groups of 7 and of 8 query heads."""

from __future__ import annotations

import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from triton_client_tpu.ops import block_attention  # noqa: E402

D = 128  # a head's values: one lane tile, as both served families have


def _slot(seed: int, s_len: int, g: int, start: int, n: int, ring: bool):
    """A slot's key and value rows holding positions ``0 .. start + n -
    1`` (a ring: the latest ``s_len`` of them, position ``p`` at row ``p
    % s_len``; the other rows an earlier session's) and, for the
    reference, every position's key and value."""
    rng = np.random.default_rng(seed)
    total = start + n
    k, v = (rng.standard_normal((total, g * D)).astype(np.float32) for _ in range(2))
    k, v = (np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)) for a in (k, v))
    rows_k, rows_v = (rng.standard_normal((s_len, g * D)).astype(np.float32) for _ in range(2))  # an earlier session's
    for p in (range(max(0, total - s_len), total) if ring else range(min(total, s_len))):
        rows_k[p % s_len], rows_v[p % s_len] = k[p], v[p]
    return (jnp.asarray(rows_k, jnp.bfloat16), jnp.asarray(rows_v, jnp.bfloat16)), k, v


def _plain(q, k, v, positions, block, scale, window, g):
    """Every query against every position written, float32, one softmax."""
    n, h, _ = q.shape
    r = h // g
    s = np.arange(k.shape[0])
    out = np.zeros((n, h, D), np.float32)
    for t, p in enumerate(positions):
        keep = s // block <= p // block
        if window:
            keep &= s >= p - window + 1
        for j in range(h):
            head = slice((j // r) * D, (j // r + 1) * D)
            scores = np.where(keep, (k[:, head] @ q[t, j]) * scale, -np.inf)
            w = np.exp(scores - scores.max())
            out[t, j] = (w / w.sum()) @ v[:, head]
    return out.reshape(n, h * D)


CASES = {
    # name: (H, G, block, window, slot rows, query block, key block, context, new tokens, of which real)
    "first-prompt-one-tile-r8-block4": (8, 1, 4, 0, 64, 32, 16, 0, 32, 32),
    "block4-context-off-the-key-block-r8": (16, 2, 4, 0, 128, 16, 16, 36, 48, 48),
    "block4-pad-tail-past-the-slot": (8, 1, 4, 0, 64, 16, 16, 40, 32, 20),
    "causal-first-prompt-r7": (7, 1, 1, 0, 96, 16, 16, 0, 48, 48),
    "causal-context-off-the-key-block-r7": (14, 2, 1, 0, 128, 16, 16, 37, 32, 32),
    "causal-pad-tail-r7": (7, 1, 1, 0, 96, 32, 16, 21, 64, 41),
    "causal-one-tile-of-a-short-launch": (7, 1, 1, 0, 64, 32, 16, 9, 16, 16),
    "window-first-prompt-r7": (7, 1, 1, 24, 48, 16, 16, 0, 16, 16),
    "window-ring-wraps-r7": (14, 2, 1, 24, 48, 8, 8, 100, 24, 24),
    "window-span-ends-on-the-ring-head": (7, 1, 1, 24, 48, 8, 8, 72, 24, 24),  # positions 72..95: rows 24..47
    "window-span-runs-over-the-ring-end": (7, 1, 1, 24, 48, 8, 8, 85, 24, 17),  # rows 37..47 then 0..12, pads after
    "window-sight-laps-a-small-ring": (7, 1, 1, 32, 96, 64, 32, 62, 64, 64),  # 4 key blocks of sight, 3 of ring
    "window-key-block-not-the-ring-divisor": (8, 1, 1, 20, 40, 8, 16, 53, 16, 16),  # gcd(40, 16): blocks of 8
}


@pytest.mark.parametrize("name", CASES)
def test_the_kernel_gives_the_plain_softmax(monkeypatch, name):
    h, g, block, window, s_len, qb, kb, context, n, real = CASES[name]
    monkeypatch.setattr(block_attention, "QUERY_BLOCK", qb)
    monkeypatch.setattr(block_attention, "KEY_BLOCK", kb)
    rows, k, v = _slot(list(CASES).index(name), s_len, g, context, n, ring=bool(window))
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((n, h, D)), jnp.bfloat16)
    positions = context + np.arange(n)
    scale = D**-0.5
    got = jax.jit(block_attention.prefill_attention, static_argnums=(3, 4, 5))(
        q, rows, jnp.asarray(positions, jnp.int32), block, scale, window)
    assert got.shape == (n, h * D) and got.dtype == jnp.bfloat16
    # what the slot holds of a pad token past its end is nothing: the reference sees the positions written
    held = min(context + n, s_len) if not window else context + n
    want = _plain(np.asarray(q.astype(jnp.float32))[:real], k[:held], v[:held], positions[:real], block, scale, window, g)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32))[:real], want, atol=2e-2, rtol=2e-2)
    assert np.isfinite(np.asarray(got.astype(jnp.float32))).all()  # the pad rows too


def test_tiles_come_from_the_shapes():
    """The served slots are whole key blocks (the ring of 6,144 rows 6,
    SDAR's slot 2, the full layers' 16); a launch shorter than a query
    block is one tile."""
    assert all(math.gcd(rows, block_attention.KEY_BLOCK) == 1024 for rows in (6144, 2048, 16384))
    rows = (jnp.zeros((64, 2 * D), jnp.bfloat16),) * 2
    out = block_attention.prefill_attention(jnp.ones((16, 14, D), jnp.bfloat16), rows, jnp.arange(16), 1, 1.0)
    assert out.shape == (16, 14 * D) and not np.asarray(out.astype(jnp.float32)).any()  # zero values in, zeros out
