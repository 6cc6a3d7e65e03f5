"""Rotary position embedding on raw arrays, interleaved pairs.

The reference framework has no rotary embedding.  Channel pairs ``(x[2i],
x[2i+1])`` of a ``d``-wide vector at position ``t`` are rotated by the angle
``t * theta^(-2i/d)`` (RoFormer, arXiv:2104.09864, section 3.4; the
interleaved layout, ``rope_interleave``):

    y[2i]   = x[2i] cos - x[2i+1] sin
    y[2i+1] = x[2i+1] cos + x[2i] sin

Angles, sines and cosines in float32; the result in ``x``'s dtype.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["rotary_interleaved"]


def rotary_interleaved(x, theta: float):
    """``x`` (batch, seq, heads, d), positions 0 .. seq-1, ``d`` even."""
    seq, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)
