"""`mla_decode`: one decode token a row against the paged latent pool.

The pool is [L, N, BS, W]: a row is one token's `[RMSNorm(c) | k_pe]`
(W = lora + pe wide), shared by all heads.  The query arrives absorbed
(`[q_nope W_kb^T | q_pe]`, W wide), so a pool block is scored for every
head with one [H, W] x [W, BS] dot and summed with one [H, BS] x
[BS, lora] dot: the kernel reads each latent once and nothing else.

Grid (row, table columns / FAN).  A grid step takes FAN consecutive table
entries of its row as FAN operands over the same pool (a block is 128
rows; an empty step still costs its fixed third of a microsecond, and a
table is as wide as the longest sequence the engine admits).  Steps past
a row's context name the block before them, which Pallas does not fetch
again, and compute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import named_pallas_call

NEG_INF = -1e30
FAN = 4


def _kernel(layer_ref, tables_ref, ctx_ref, q_ref, *refs, scale, lora, fan,
            steps):
    del layer_ref, tables_ref
    blocks, (o_ref, acc_ref, m_ref, l_ref) = refs[:fan], refs[fan:]
    bi, mi = pl.program_id(0), pl.program_id(1)
    bs = blocks[0].shape[0]
    ctx = ctx_ref[bi]

    @pl.when(mi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    for f in range(fan):
        first = (mi * fan + f) * bs        # pool position of the block

        @pl.when(first < ctx)
        def _block(f=f, first=first):
            blk = blocks[f][...]                              # [BS, W]
            s = jax.lax.dot_general(
                q_ref[...], blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [H, BS]
            col = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < ctx, s, NEG_INF)
            m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
                p.astype(blk.dtype), blk[:, :lora],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [H, lora]
            m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[:] = jnp.broadcast_to(
                l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                l_ref.shape)

    @pl.when(mi == steps - 1)
    def _flush():
        o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:, 0:1], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "lora", "interpret"))
def mla_decode_kernel(q_lat, pool, layer, tables, ctx, *, scale, lora,
                      interpret=False):
    """q_lat [B, H, W]; pool [L, N, BS, W]; `layer` an int or traced
    scalar; tables [B, M] int32; ctx [B] int32 positions attended (0: an
    idle row, flushes zeros).  Returns o_lat [B, H, lora]."""
    b, h, w = q_lat.shape
    bs = pool.shape[2]
    fan = FAN
    m = -(-tables.shape[1] // fan) * fan
    tables = jnp.pad(tables.astype(jnp.int32),
                     ((0, 0), (0, m - tables.shape[1])))
    steps = m // fan
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    ctx = ctx.astype(jnp.int32)

    def block_spec(f):
        def index(bi, mi, ly, tb, cx):
            # past the context: the last block that was needed, again
            last = jnp.maximum(cx[bi] - 1, 0) // bs
            return (ly[0], tb[bi, jnp.minimum(mi * fan + f, last)], 0, 0)

        return pl.BlockSpec((None, None, bs, w), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, steps),
        in_specs=[pl.BlockSpec((None, h, w),
                               lambda bi, mi, ly, tb, cx: (bi, 0, 0))]
        + [block_spec(f) for f in range(fan)],
        out_specs=pl.BlockSpec((None, h, lora),
                               lambda bi, mi, ly, tb, cx: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, lora), jnp.float32),
                        pltpu.VMEM((h, 128), jnp.float32),
                        pltpu.VMEM((h, 128), jnp.float32)],
    )
    return named_pallas_call(
        "mla_decode",
        functools.partial(_kernel, scale=float(scale), lora=lora, fan=fan,
                          steps=steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lora), q_lat.dtype),
        interpret=interpret,
    )(layer, tables, ctx, q_lat, *([pool] * fan))
