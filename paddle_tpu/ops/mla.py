"""Multi-head latent attention (MLA) against a paged pool of latents.

A pool row is one token's `[RMSNorm(c) | k_pe]`, `lora + pe` values wide,
shared by every query head.  Both ops here take the ABSORBED query, the
key half of W_kvb folded into it (`q_lat = [q_nope W_kb^T | q_pe]`, as
wide as a pool row), and return the probabilities' sum of latents
(`o_lat`, `lora` wide): the value half of W_kvb is the caller's to apply
after.  So a step reads latents only, once for all heads.

  mla_prefill_attention  a token-packed stream in tiles of `tile` tokens,
      each tile wholly one sequence's; an online softmax over the table's
      columns, one pool block of every tile a step (XLA).
  mla_decode_attention   one token a row; on the TPU the `mla_decode`
      Pallas kernel walks each row's blocks where they lie, elsewhere the
      blocks are gathered.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import attention as _attention

NEG_INF = -1e30


def mla_prefill_attention(q_lat, pool, layer, tables, tile_row, pos, *,
                          scale, tile, lora):
    """q_lat [T, H, W] (W = pool row width); pool [L, N, BS, W]; tables
    [P, M]; tile_row [T // tile] the table row of each tile; pos [T] the
    absolute position of each token (-1: padding, attends nothing).
    Token t attends pool positions [0, pos[t]] of its row's blocks, its
    own latent among them (written before the call).  Returns
    o_lat [T, H, lora] in q_lat's dtype."""
    t_len, h, w = q_lat.shape
    n, bs = t_len // tile, pool.shape[2]
    q = q_lat.reshape(n, tile * h, w)
    qpos = jnp.repeat(pos.reshape(n, tile), h, axis=1)        # [n, tile*H]
    rows = tables[tile_row]                                   # [n, M]

    def column(m, carry):
        m_i, l_i, acc = carry
        blk = pool[layer, rows[:, m]]                         # [n, BS, W]
        s = jnp.einsum("nqc,nkc->nqk", q, blk,
                       preferred_element_type=jnp.float32) * scale
        kpos = m * bs + jnp.arange(bs)
        s = jnp.where(kpos[None, None, :] <= qpos[..., None], s, NEG_INF)
        m_new = jnp.maximum(m_i, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_i - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "nqk,nkc->nqc", p.astype(blk.dtype), blk[..., :lora],
            preferred_element_type=jnp.float32)
        return m_new, l_i * alpha + p.sum(-1), acc

    init = (jnp.full((n, tile * h), NEG_INF, jnp.float32),
            jnp.zeros((n, tile * h), jnp.float32),
            jnp.zeros((n, tile * h, lora), jnp.float32))
    # as many columns as the deepest token of this stream reaches, however
    # wide the table: the width is the engine's horizon, one program for all
    columns = jnp.clip(jnp.max(pos) // bs + 1, 0, tables.shape[1])
    m_i, l_i, acc = jax.lax.fori_loop(0, columns, column, init)
    # a padding token's row is all NEG_INF: uniform garbage nobody reads
    o = acc / jnp.maximum(l_i, 1e-30)[..., None]
    return o.reshape(t_len, h, lora).astype(q_lat.dtype)


def mla_decode_attention(q_lat, pool, layer, tables, ctx, *, scale, lora):
    """q_lat [B, H, W]; pool [L, N, BS, W]; tables [B, M]; ctx [B] the
    number of pool positions each row attends (its own latent included).
    Returns o_lat [B, H, lora] in q_lat's dtype."""
    if _attention._on_tpu():
        from .pallas.mla_decode import mla_decode_kernel

        return mla_decode_kernel(q_lat, pool, layer, tables, ctx,
                                 scale=scale, lora=lora)
    b, m = tables.shape
    lat = pool[layer, tables].reshape(b, m * pool.shape[2], -1)
    s = jnp.einsum("bhc,bkc->bhk", q_lat, lat,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(lat.shape[1])[None, :] < ctx[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p.astype(lat.dtype), lat[..., :lora],
                      preferred_element_type=jnp.float32
                      ).astype(q_lat.dtype)
