"""Compressed convolutional attention (CCA): what happens to q and k
between their projections and the attention itself, on rows [N, C] whose
earlier rows the caller supplies (from a packed stream, or from the conv
tails a sequence left in the state store: `nn/decode_blocks.py`).

C = (Hq + Hkv) * D channels: the compressed queries q~ (Hq heads of D)
beside the compressed keys k~ (Hkv heads of D).  In order:

  `depthwise`  c'_t = sum_j w0[j] * c_{t-j} + b0, one tap a channel;
  `per_head`   c''_t = sum_j W1[j] c'_{t-j} + b1, W1[j] block-diagonal
               over the Hq + Hkv heads (a [D, D] matrix a head and tap);
  `qk_mean`    m^q_h = (q~_h + k~_{h // G}) / 2, m^k_g = the mean of m^q
               over the G query heads of K/V head g; q = q'' + m^q,
               k = k'' + m^k;
  `qk_norm`    q_h <- sqrt(D) q_h / |q_h|, k_g <- exp(tau_g) sqrt(D) k_g /
               |k_g| (|x| = sqrt(sum x^2 + 1e-6)).

All plain XLA, float32 inside; the two convolutions run under
`jax.named_scope("cca_conv")`.  Rotary positions (`ops/rotary.py`) and the
paged attention (`ops/attention.py`) follow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6
F32 = jnp.float32


def depthwise(w, b, taps):
    """taps[j] [N, C] is the input j positions back (taps[0] the row's
    own); w [K, C], b [C].  -> [N, C] float32."""
    with jax.named_scope("cca_conv"):
        w = w.astype(F32)
        return sum(w[j] * t.astype(F32) for j, t in enumerate(taps)) \
            + b.astype(F32)


def per_head(w, b, taps):
    """taps[j] [N, C] as `depthwise`'s; w [K, heads, D, D] (in, out), b
    [C].  A matmul a head and tap, accumulated in float32.  -> [N, C]."""
    with jax.named_scope("cca_conv"):
        _k, heads, d, _d = w.shape
        n = taps[0].shape[0]
        y = sum(jnp.einsum("nhd,hde->nhe", t.reshape(n, heads, d), w[j],
                           preferred_element_type=F32)
                for j, t in enumerate(taps))
        return y.reshape(n, heads * d) + b.astype(F32)


def qk_mean(c, c2, heads, kv_heads, head_dim):
    """c = [q~ | k~] and c2 = [q'' | k''], both [N, C] -> (q [N, Hq, D],
    k [N, Hkv, D]) float32 with the q-k means added."""
    n, group = c.shape[0], heads // kv_heads
    cq = heads * head_dim
    q0 = c[:, :cq].astype(F32).reshape(n, kv_heads, group, head_dim)
    k0 = c[:, cq:].astype(F32).reshape(n, kv_heads, 1, head_dim)
    mq = (q0 + k0) / 2
    q = c2[:, :cq].astype(F32).reshape(mq.shape) + mq
    k = c2[:, cq:].astype(F32).reshape(n, kv_heads, head_dim) \
        + jnp.mean(mq, axis=2)
    return q.reshape(n, heads, head_dim), k


def qk_norm(q, k, tau):
    """Per head: q to length sqrt(D), k to exp(tau_g) sqrt(D); tau [Hkv]."""
    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                 + NORM_EPS)

    root = q.shape[-1] ** 0.5
    return unit(q) * root, \
        unit(k) * (jnp.exp(tau.astype(F32))[None, :, None] * root)
