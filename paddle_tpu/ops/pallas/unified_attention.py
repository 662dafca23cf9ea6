"""Unified ragged paged attention — the Pallas TPU kernel over the
block pool, block-table driven (one-kernel serving round, r16).

It holds:

  * the STREAM kernel (`unified_ragged_attention_kernel`) — segment-
    causal attention for a token-packed multi-sequence stream where
    every token attends its OWN sequence's paged-cache positions
    [0, pos].  That one mask generalizes every query shape the serving
    round produces: a prefill chunk (n tokens at positions
    start..start+n-1), a plain decode row (1 token at its write
    position) and a speculative verify region ([last_token,
    draft_1..k]) are all just ragged segments of the same stream, so a
    scheduler round mixing all three is ONE launch of this kernel;
  * the DECODE entry (`paged_decode_attention_kernel`) — the
    one-token-per-sequence call of the standalone `step`/offline
    paths: the same kernel body at a DECODE_TILE-row query tile.  (A
    separate (B, M)-grid decode body passed interpret mode for twenty
    PRs and was refused by the chip's compiler — Mosaic lowers no
    head-batched dot whose left operand is a bare [H, Dh] — so it is
    gone; PR 21.)

Shared machinery:

  * `kv_operand_specs` — the scalar-prefetched block-index BlockSpec
    construction: the k/v (and int8 scale) index maps read
    `tables[row, m]` from a prefetched table, so the pipeline DMAs
    exactly the pool blocks each query's sequence names and never
    materializes the [.., M*BS, ...] gather copy the XLA fallback
    builds.  Scale tiles ride the SAME prefetched index as their
    codes.
  * `_load_kv` — the int8-KV dequant (quantized-serving round): pools
    may be `QuantizedKV` (codes [N, BS, H, Dh] int8 + per-vector
    scales [N, BS, H]); dequantization happens HERE on the
    VMEM-resident block in flight, so a bf16 copy of the cache never
    exists in HBM.

Layout (matches inference/kv_cache.py):
    q:        [T, H, Dh] stream / [B, H, Dh] decode
    k_blocks: [N, BS, H, Dh]             one layer's pool
    tables:   [B, M] int32               block ids, 0-padded (trash)
    tile_seg: [T // QT] int32            slot row of each query tile
    tile_pos: [T // QT] int32            abs cache position of each
                                         tile's first token; -1 = pad
    ctx_lens: [B] int32                  decode: tokens visible per row

Stream packing contract: the scheduler aligns every segment's packed
region to the QT=128 query tile, so ONE tile never mixes segments —
that keeps the grid a plain (num_q_tiles, M) with the per-tile segment
and start position scalar-prefetched.  KV blocks past a tile's causal
horizon (and pad tiles) still occupy grid steps but are predicated
off — raggedness saves the gather traffic and the compute, not the
grid iterations.

Segment-causal masking contract (normative for every implementation of
stream attention, not just this kernel): a query row carrying
(seg, pos) attends exactly the keys of ITS segment at positions
0 <= kpos <= pos — resident paged-cache positions and fresh stream
rows alike — and pad rows (pos == -1) attend nothing.  The XLA
fallback (`ops.attention.ragged_prefill_attention`) and the
sequence-parallel seams (`serving_dist.sp_attention` ring/ulysses,
where per-row seg/pos metadata must SURVIVE block rotation so
cross-shard causality stays exact) implement this same contract and
are parity-tested against each other.

Per (tile, kv-block) step the score tile is [H, QT, BS] from a
head-batched dot over Dh; online-softmax state (m, l, acc) rides VMEM
scratch across the M dimension exactly like flash_attention.py, with
the extra QT query axis on the lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import named_pallas_call

NEG_INF = -1e30
Q_TILE = 128     # stream query-tile (and packing alignment) size
DECODE_TILE = 8  # query tile of a one-token decode row: one f32 sublane
                 # group, the smallest tile Mosaic lowers the dots for


def supported_shapes(head_dim, block_size, num_heads, total_tokens=None):
    """Shape gate for the compiled TPU kernel (interpret mode takes
    any): head_dim lane-sized, block_size a lane multiple, heads
    sublane-aligned; a packed stream additionally needs its length
    query-tile aligned."""
    ok = (head_dim in (32, 64, 128, 256) and block_size % 128 == 0
          and num_heads % 8 == 0)
    if total_tokens is not None:
        ok = ok and total_tokens % Q_TILE == 0
    return ok


def is_quantized(kv):
    """Duck-typed inference.kv_quant.QuantizedKV check (no import — the
    kernel layer must not pull the inference package)."""
    return hasattr(kv, "codes") and hasattr(kv, "scales")


def kv_operand_specs(BS, H, Dh, quant, block_id):
    """The scalar-prefetched block-index construction the kernel
    steers its DMA pipeline with:
    `block_id(*grid_and_prefetch_refs) -> pool block` feeds the k/v
    BlockSpec index maps, and for int8 pools the per-vector scale tiles
    ride the SAME index as their codes.  Returns the in_specs list for
    (k[, ks], v[, vs])."""
    kv = pl.BlockSpec((1, BS, H, Dh),
                      lambda *a: (block_id(*a), 0, 0, 0))
    if not quant:
        return [kv, kv]
    sc = pl.BlockSpec((1, BS, H), lambda *a: (block_id(*a), 0, 0))
    return [kv, sc, kv, sc]


def kv_operands(k_blocks, v_blocks):
    """(quant, operand tuple) for a dense or QuantizedKV pool pair —
    the argument-flattening half of `kv_operand_specs`."""
    if is_quantized(k_blocks):
        return True, (k_blocks.codes, k_blocks.scales,
                      v_blocks.codes, v_blocks.scales)
    return False, (k_blocks, v_blocks)


def _load_kv(ref, sref, dt):
    """One pool block from VMEM, dequantized in place when the pool is
    int8 (codes * per-vector scales, elementwise).  The convert
    happens on the ONE block in flight; no bf16 cache copy ever exists
    in HBM.  The product is taken in f32: Mosaic refuses the
    [BS, H] -> [BS, H, 1] shape cast of a bf16 scale tile, and the
    v5e's VPU has no bf16 arithmetic to lose."""
    x = ref[0]
    if sref is None:
        return x
    return (x.astype(jnp.float32)
            * sref[0].astype(jnp.float32)[..., None]).astype(dt)


# ---- stream kernel (prefill chunks / decode rows / verify regions) ----

def _stream_kernel(tile_seg_ref, tile_pos_ref, tables_ref, q_ref,
                   *refs, scale, nm, qt, quant, tile_base):
    if quant:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    qi = pl.program_id(0)
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # abs position of the tile's first query (-1 pad); tile_base shifts
    # a shard-local grid into the GLOBAL prefetch arrays (sp shards)
    q0 = tile_pos_ref[qi + tile_base]
    bs = k_ref.shape[1]

    # a kv block matters iff it starts at or before the tile's LAST
    # query's causal horizon; pad tiles (q0 < 0) skip every block
    @pl.when((q0 >= 0) & (mi * bs <= q0 + qt - 1))
    def _compute():
        q = q_ref[:]  # [H, QT, Dh] — input dtype feeds the MXU full-rate
        k = _load_kv(k_ref, ks_ref, q.dtype)  # [BS, H, Dh]
        v = _load_kv(v_ref, vs_ref, q.dtype)
        # s[h, i, j] = sum_d q[h, i, d] * k[j, h, d]: batch over heads
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale  # [H, QT, BS]
        row = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        col = mi * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(col <= row, s, NEG_INF)  # segment-causal by abs pos
        m_prev = m_ref[:]                       # [H, QT]
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[:, :, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=2)
        # o[h, i, d] += sum_j p[h, i, j] * v[j, h, d]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)  # [H, QT, Dh]
        acc_ref[:] = acc_ref[:] * alpha[:, :, None] + pv
        m_ref[:] = m_new

    @pl.when(mi == nm - 1)
    def _flush():
        l = jnp.maximum(l_ref[:], 1e-30)  # pad tiles flush zeros
        o_ref[:] = (acc_ref[:] / l[:, :, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "q_tile", "interpret",
                                    "tile_base"))
def unified_ragged_attention_kernel(q, k_blocks, v_blocks, tables,
                                    tile_seg, tile_pos, *, scale=None,
                                    q_tile=None, interpret=False,
                                    tile_base=0):
    """Pallas segment-causal stream attention: ONE launch scores a
    token-packed stream mixing prefill chunks, plain decode rows and
    speculative verify regions (see module docstring for the layout
    and packing contract); returns [T, H, Dh] in q's dtype.
    k_blocks/v_blocks may be `QuantizedKV` (codes [N, BS, H, Dh] int8,
    scales [N, BS, H]) — the scale tiles ride the same
    scalar-prefetched block index as their codes and dequant happens
    in VMEM (`_load_kv`).  q_tile defaults to the production
    Q_TILE=128 (interpret-mode tests shrink it to exercise tiny
    shapes).

    tile_base (long-context round): static tile offset into the
    scalar-prefetched tile_seg/tile_pos arrays — a SEQUENCE-PARALLEL
    shard holding tiles [base, base + T_local/QT) of a global packed
    stream passes its LOCAL q slice with the GLOBAL prefetch arrays
    and tile_base=base, and the block-index maps (`tb[ts[qi+base], m]`)
    DMA exactly the pool blocks the shard's own tiles name.  0 (the
    default) is the exact pre-round single-stream kernel."""
    quant, operands = kv_operands(k_blocks, v_blocks)
    qt = Q_TILE if q_tile is None else int(q_tile)
    tile_base = int(tile_base)
    T, H, Dh = q.shape
    _, BS, _, _ = operands[0].shape
    M = tables.shape[1]
    if T % qt:
        raise ValueError(f"packed length {T} not a multiple of the "
                         f"query tile {qt}")
    NQ = T // qt
    if tile_base < 0 or tile_base + NQ > tile_seg.shape[0]:
        raise ValueError(
            f"tile_base {tile_base} + local tiles {NQ} exceeds the "
            f"global tile arrays ({tile_seg.shape[0]} tiles)")
    scale = (Dh ** -0.5) if scale is None else float(scale)

    qh = q.transpose(1, 0, 2)  # [H, T, Dh]: heads ride the sublane axis
    q_spec = pl.BlockSpec((H, qt, Dh),
                          lambda qi, m, ts, tp, tb: (0, qi, 0))
    in_specs = [q_spec] + kv_operand_specs(
        BS, H, Dh, quant,
        lambda qi, m, ts, tp, tb: tb[ts[qi + tile_base], m])
    kernel = functools.partial(_stream_kernel, scale=scale, nm=M,
                               qt=qt, quant=quant, tile_base=tile_base)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tile_seg, tile_pos, tables steer the DMA
        grid=(NQ, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((H, qt, Dh),
                               lambda qi, m, ts, tp, tb: (0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, qt, Dh), jnp.float32),
            pltpu.VMEM((H, qt), jnp.float32),
            pltpu.VMEM((H, qt), jnp.float32),
        ],
    )
    # decode rows ride DECODE_TILE tiles, every other caller (chunk
    # prefill, verify, the unified round's mixed stream) the wide ones:
    # two names, so a device trace tells the two loads apart
    out = named_pallas_call(
        "paged_attn_decode" if qt == DECODE_TILE else "paged_attn_prefill",
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, T, Dh), q.dtype),
        interpret=interpret,
    )(tile_seg.astype(jnp.int32), tile_pos.astype(jnp.int32),
      tables.astype(jnp.int32), qh, *operands)
    return out.transpose(1, 0, 2)


# ---- decode (one token per sequence) --------------------------------

def paged_decode_attention_kernel(q, k_blocks, v_blocks, tables, ctx_lens,
                                  *, scale=None, interpret=False):
    """Ragged paged decode attention: q [B, H, Dh], one token per
    sequence attending cache positions [0, ctx_len).  A decode row is a
    one-token segment of the stream, so this IS the stream kernel at a
    DECODE_TILE-row query tile: row b sits at stream row b*DECODE_TILE
    with pos = ctx_len - 1 and the tile's other rows are zero padding
    whose output is dropped (ctx_len == 0 makes a pad tile, which
    flushes zeros).  Returns [B, H, Dh] in q's dtype."""
    B = q.shape[0]
    stream = jnp.pad(q[:, None], ((0, 0), (0, DECODE_TILE - 1),
                                  (0, 0), (0, 0)))
    out = unified_ragged_attention_kernel(
        stream.reshape((B * DECODE_TILE,) + q.shape[1:]), k_blocks,
        v_blocks, tables, jnp.arange(B, dtype=jnp.int32),
        ctx_lens.astype(jnp.int32) - 1, scale=scale, q_tile=DECODE_TILE,
        interpret=interpret)
    return out[::DECODE_TILE]
