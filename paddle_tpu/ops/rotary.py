"""Rotary position embedding of part of a head.

`apply_rotary(x, pos, rotary_dim, theta)` rotates the first `rotary_dim`
channels of every head of x [N, H, D] by the row's position pos [N] and
leaves the rest as they are.  Pair i turns by pos * theta^(-2i /
rotary_dim), for i below rotary_dim / 2.  The rotated channels are paired
by halves (rotate-half: channel i with channel i + rotary_dim / 2), or,
with `interleaved`, as neighbours (channel 2i with channel 2i + 1: the
layout of a checkpoint whose config says `rope_interleave`).  Plain XLA, in
float32 whatever x's dtype (a position of 10^5 times a frequency has no
room in bf16); it runs under `jax.named_scope("rotary")`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def apply_rotary(x, pos, rotary_dim, theta, interleaved=False):
    """x [N, H, D] -> the same shape and dtype; pos [N] int (a padding
    row's -1 turns it backwards by one step: its output is discarded)."""
    half = rotary_dim // 2
    if half * 2 != rotary_dim or rotary_dim > x.shape[-1]:
        raise ValueError(f"rotary_dim {rotary_dim} is not an even part of "
                         f"a head of {x.shape[-1]}")
    with jax.named_scope("rotary"):
        f32 = jnp.float32
        freq = theta ** (-jnp.arange(half, dtype=f32) * 2.0 / rotary_dim)
        angle = pos.astype(f32)[:, None, None] * freq           # [N, 1, h]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        xf = x.astype(f32)
        if interleaved:
            a, b = xf[..., 0:rotary_dim:2], xf[..., 1:rotary_dim:2]
            turned = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                               axis=-1).reshape(xf.shape[:-1] + (rotary_dim,))
            return jnp.concatenate([turned, xf[..., rotary_dim:]],
                                   axis=-1).astype(x.dtype)
        a, b = xf[..., :half], xf[..., half:rotary_dim]
        return jnp.concatenate(
            [a * cos - b * sin, b * cos + a * sin, xf[..., rotary_dim:]],
            axis=-1).astype(x.dtype)
