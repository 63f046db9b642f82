"""Rotary position embeddings with YaRN's blended frequencies.

The long-context scaling of DeepSeek-V3's published code, which A.X-K1
(models/axk1.py) carries in its ``rope_scaling`` block: a channel that
turns more than ``beta_fast`` times over the original context keeps its
published frequency, one that turns fewer than ``beta_slow`` times has
it divided by ``factor``, a linear ramp between. Pairs are adjacent
channels ``(2i, 2i+1)``.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    dim: int = 64
    theta: float = 10000.0
    factor: float = 32.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def attention_mscale(cfg: YarnConfig) -> float:
    """What the softmax scale is multiplied by, squared, under YaRN."""
    return yarn_mscale(cfg.factor, cfg.mscale_all_dim)


def yarn_inv_freq(cfg: YarnConfig) -> jnp.ndarray:
    """``[dim / 2]`` float32 inverse frequencies."""

    def correction_dim(rotations: float) -> float:
        return (
            cfg.dim
            * math.log(cfg.original_max_position / (rotations * 2 * math.pi))
            / (2 * math.log(cfg.theta))
        )

    low = max(math.floor(correction_dim(cfg.beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.beta_slow)), cfg.dim - 1)
    published = 1.0 / cfg.theta ** (
        jnp.arange(0, cfg.dim, 2, dtype=jnp.float32) / cfg.dim
    )
    ramp = jnp.clip(
        (jnp.arange(cfg.dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 0.001),
        0.0,
        1.0,
    )
    return published / cfg.factor * ramp + published * (1.0 - ramp)


def rope_tables(positions, cfg: YarnConfig):
    """cos and sin ``[..., dim / 2]`` float32 for integer ``positions``."""
    angle = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    scale = yarn_mscale(cfg.factor, cfg.mscale) / attention_mscale(cfg)
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def apply_rope(x, cos, sin):
    """Rotate the adjacent pairs of ``x [..., dim]`` (float32 in and
    out); ``cos``/``sin`` broadcast against ``x[..., ::2]``."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [a * cos - b * sin, a * sin + b * cos], axis=-1
    ).reshape(x.shape)
