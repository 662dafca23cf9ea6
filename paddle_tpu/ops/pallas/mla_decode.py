"""`mla_decode`: one decode token a row against the paged latent pool.

The pool is [L, N, BS, W]: a row is one token's `[RMSNorm(c) | k_pe]`
(W = lora + pe wide), shared by all heads.  The query arrives absorbed
(`[q_nope W_kb^T | q_pe]`, W wide), so a pool block is scored for every
head with one [H, W] x [W, BS] dot and summed with one [H, BS] x
[BS, lora] dot: the kernel reads each latent once and nothing else.

The grid is the launch's work list (`unified_attention.decode_work_list`,
the paged decode kernel's), its length read on the device: a step takes
FAN consecutive live blocks of one row, so a row of n blocks takes
ceil(n / FAN) steps and an idle row one, which flushes zeros; no step lies
past a row's context.  The step's FAN score tiles go side by side into ONE
[H, FAN*BS] tile under one mask and one online-softmax update, and each
block's first `lora` lanes take one value dot.  A latent block is K and V
at once, so a step reads each of its blocks once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import named_pallas_call
from .unified_attention import _live_blocks, decode_work_list

NEG_INF = -1e30
#: Latent blocks of one row a grid step takes: 8 x 160 KB (128 rows of 640
#: bf16) = 1.25 MB a step.  From scripts/decode_tile_bench.py's sweep on
#: the v5e (PERF.md section 6): at 1, 2, 4, 8 a step a launch read
#: 1,759, 1,211, 909, 806 us over the Kanana cell's contexts (64 rows, a
#: table 152 wide) and 1,116, 796, 624, 591 us over the Kimi cell's (128
#: rows, 76 wide); 8 costs 32% more than 4 where every row is one block.
#: This kernel's own, not `decode_fan`'s rule, which counts K and V as two
#: operands and would give a latent block 1.
FAN = 8


def _kernel(layer_ref, ctx_ref, rows_ref, firsts_ref, blocks_ref, q_ref,
            *refs, scale, lora, fan, width):
    del layer_ref, blocks_ref
    blocks, (o_ref, acc_ref, m_ref, l_ref) = refs[:fan], refs[fan:]
    # grid step i of the launch's work list: blocks `first` ..
    # `first + fan - 1` of row `bi`
    i = pl.program_id(0)
    bi, first = rows_ref[i], firsts_ref[i]
    bs = blocks[0].shape[0]
    ctx = ctx_ref[bi]

    @pl.when(first == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(first * bs < ctx)
    def _compute():
        lat = [b[...] for b in blocks]                        # [BS, W] each
        # ONE [H, fan*BS] tile, one mask (a slot past the row's blocks
        # holds its last block again), one online-softmax update
        s = jnp.concatenate([jax.lax.dot_general(
            q_ref[...], blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) for blk in lat],
            axis=1) * scale
        col = first * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < jnp.minimum(ctx, width * bs), s, NEG_INF)
        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        pv = 0.0
        for f, blk in enumerate(lat):
            pv = pv + jax.lax.dot_general(
                p[:, f * bs:(f + 1) * bs].astype(blk.dtype), blk[:, :lora],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [H, lora]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)

    @pl.when(first + fan >= _live_blocks(ctx, bs, width))  # the row's last
    def _flush():
        o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:, 0:1], 1e-30)
                    ).astype(o_ref.dtype)


def mla_decode_kernel(q_lat, pool, layer, tables, ctx, *, scale, lora,
                      interpret=False):
    """q_lat [B, H, W]; pool [L, N, BS, W]; `layer` an int or traced
    scalar; tables [B, M] int32; ctx [B] int32 positions attended (0: an
    idle row, flushes zeros).  Returns o_lat [B, H, lora].  FAN is read
    here, at each call, and keys the compiled kernel."""
    return _mla_decode(q_lat, pool, layer, tables, ctx, scale=float(scale),
                       lora=lora, interpret=interpret, fan=FAN)


@functools.partial(jax.jit,
                   static_argnames=("scale", "lora", "interpret", "fan"))
def _mla_decode(q_lat, pool, layer, tables, ctx, *, scale, lora, interpret,
                fan):
    b, h, w = q_lat.shape
    bs = pool.shape[2]
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    ctx = ctx.astype(jnp.int32)
    rows, firsts, blocks, steps = decode_work_list(ctx, tables, bs, fan)

    def row(lanes):
        return pl.BlockSpec((None, h, lanes),
                            lambda i, ly, cx, rw, fs, bl: (rw[i], 0, 0))

    def slot(f):   # the step's f-th block, named by the work list
        return pl.BlockSpec(
            (None, None, bs, w),
            lambda i, ly, cx, rw, fs, bl: (ly[0], bl[i * fan + f], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, contexts and the work list steer the DMA
        num_scalar_prefetch=5,
        grid=(steps,),
        in_specs=[row(w)] + [slot(f) for f in range(fan)],
        out_specs=row(lora),
        scratch_shapes=[pltpu.VMEM((h, lora), jnp.float32),
                        pltpu.VMEM((h, 128), jnp.float32),
                        pltpu.VMEM((h, 128), jnp.float32)],
    )
    return named_pallas_call(
        "mla_decode",
        functools.partial(_kernel, scale=scale, lora=lora, fan=fan,
                          width=tables.shape[1]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lora), q_lat.dtype),
        interpret=interpret,
    )(layer, ctx, rows, firsts, blocks, q_lat, *([pool] * fan))
