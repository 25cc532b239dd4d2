"""Rotary position embeddings with YaRN frequency scaling.

``yarn_inv_freq`` gives the per-pair angular frequencies: pair ``i`` of a
``dim``-wide rotary slice turns at ``theta^(-2i/dim)`` radians a position;
YaRN (Peng et al. 2023, the form the DeepSeek-V3 line's configs name with
``beta_fast`` / ``beta_slow``) keeps the pairs that turn more than
``beta_fast`` times within the original context as they are, divides those
that turn fewer than ``beta_slow`` times by ``factor`` (position
interpolation), and blends linearly in between, by pair index.

``apply_interleaved`` rotates ADJACENT pairs ``(x[2i], x[2i+1])``: the
layout of a projection whose checkpoint is stored ``rope_interleave``. A
dot product of two vectors rotated this way equals that of the
de-interleaved half-split form other stacks use, so nothing is permuted.

``apply_half_split`` is that other form (``x * cos + rotate_half(x) *
sin``): pair ``i`` is ``(x[i], x[i + dim/2])``, the layout of a checkpoint
stored without ``rope_interleave``.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def _correction_dim(rotations: float, dim: int, theta: float,
                    original_max: int) -> float:
    """The (fractional) pair index that turns ``rotations`` times in
    ``original_max`` positions."""
    return (dim * math.log(original_max / (rotations * 2.0 * math.pi))
            / (2.0 * math.log(theta)))


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """[dim // 2] float32 frequencies (radians a position)."""
    base = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if factor == 1.0:
        return base
    low = max(math.floor(_correction_dim(beta_fast, dim, theta,
                                         original_max)), 0)
    high = min(math.ceil(_correction_dim(beta_slow, dim, theta,
                                         original_max)), dim - 1)
    span = max(high - low, 1e-3)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / span,
                    0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: ``0.1 * mscale * ln(factor) + 1``
    (1 when nothing is scaled)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def apply_interleaved(x, positions, inv_freq):
    """Rotate ``x[..., 2i:2i+2]`` by ``positions * inv_freq[i]``.
    ``x``: [..., S, ..., dim] with ``positions`` broadcastable to
    ``x.shape[:-1]``; the math runs in float32 and the result keeps
    ``x``'s dtype."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    pairs = xf.reshape(xf.shape[:-1] + (xf.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(xf.shape).astype(x.dtype)


def apply_half_split(x, positions, inv_freq):
    """Rotate the pairs ``(x[..., i], x[..., i + dim/2])`` by
    ``positions * inv_freq[i]``: ``x * cos + rotate_half(x) * sin`` with
    ``rotate_half(x) = [-x2 | x1]``. Shapes and dtypes as
    :func:`apply_interleaved`."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    half = xf.shape[-1] // 2
    a, b = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.astype(x.dtype)
