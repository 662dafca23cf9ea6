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
  * the DECODE kernel (`paged_decode_attention_kernel`) — the
    one-token-per-sequence call of the standalone `step`/offline
    paths, with a body of its own (PR 27).  A decode row is one query
    a head against a block all heads share, so the row's H heads are
    the H rows of ONE block-diagonal query tile [H, H*Dh] (row h: head
    h's query in head h's lanes, zeros elsewhere), and a grid step is
    one score dot over the whole [BS, H*Dh] block as it lies, one
    online-softmax update on the [H, BS] tile (every row has the same
    horizon, ctx_len) and one value dot; the block diagonal of the
    accumulator is the output row.  Its grid is ONE axis over the
    launch's live (row, block) pairs (PR 31): `decode_work_list`
    turns the contexts into a flat list of each step's row and table
    column, scalar-prefetched beside the tables, and the grid's bound,
    how many of them the launch has, is read on the device, so one
    compiled program serves every mix of contexts and a step is never
    spent past a row's context (a row of context 0 keeps one step, in
    which it writes its zeros).  (Until PR 31 the grid was rows x
    table width: 150 of a launch's 256 steps in the GPT-2 serve cell
    and 3 of 4 in the ZAYA cell did nothing but cost their fixed part,
    PERF.md section 6.  Until PR 27 the decode entry was the stream
    kernel at an 8-row tile once a head.  A (B, M)-grid body whose dots
    were batched over heads with a bare [H, Dh] left operand never
    lowered and went in PR 21.)

Shared machinery:

  * `kv_operand_specs` — the scalar-prefetched block-index BlockSpec
    construction: the k/v (and int8 scale) index maps read
    `(layer[0], tables[row, m])` from prefetched scalars (the decode
    kernel's row and m themselves from its prefetched work list), so the
    pipeline DMAs exactly the pool blocks each query's sequence names,
    out of the WHOLE layer stack, and never materializes the
    [.., M*BS, ...] gather copy the XLA fallback builds nor a slice of
    one layer.  Scale tiles ride the SAME prefetched index as their
    codes.
  * the int8-KV dequant (quantized-serving round): pools may be
    `QuantizedKV` (codes [L, N, BS, H*Dh] int8 + per-vector scales
    [L, N, BS, H]); dequantization happens on the VMEM-resident
    block, so a bf16 copy of the cache never exists in HBM.  The
    stream kernel scales each head's [BS, Dh] slice (`_load_heads`);
    the decode kernel puts the codes into its dots and folds the
    scales into the [H, BS] score and weight tiles, as the XLA path
    folds them (`_scales_by_head`).

Grouped heads (PR 30): the pool may hold fewer K/V heads than q has
query heads (`Hkv = pool width / Dh`, `group = H / Hkv`): query heads
g*group .. (g+1)*group - 1 attend K/V head g.  The stream kernel scores
each of them against its K/V head's lanes; the decode kernel's query tile
is [H, Hkv*Dh], row h in the lanes of K/V head h // group, and its q and
output are then [H, Dh] tiles.  With Hkv = H (group 1) both are the
kernels they were.

Layout (matches inference/kv_cache.py; H query heads, Hkv <= H in a pool):
    q:        [T, H, Dh] stream / [B, H, Dh] decode
    k_blocks: [L, N, BS, H*Dh] + layer   the pool stack, every token's
                                         heads side by side on the
                                         lanes, and a layer index (an
                                         int or a traced scalar); or
                                         one layer's [N, BS, H, Dh]
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

The pool is read WHERE IT LIES (PR 25).  [L, N, BS, H*Dh] is
row-major on the device with no padding (a minor dimension of
[.., H, Dh] with Dh = 64 is padded to the 128 lanes, or re-laid with
BS on the lanes, and either way XLA re-laid the whole pool around every
program that handed this kernel a [BS, H, Dh] block: PERF.md section
6, PR 25).  A block is one contiguous [BS, H*Dh] tile; head h is its
lanes [h*Dh, (h+1)*Dh).

Stream kernel: per (tile, kv-block) step and head the score tile is
[QT, BS] from a dot over Dh, in an unrolled loop over the block's
128-lane tiles (two heads of 64 each); online-softmax state (m, l, acc)
rides VMEM scratch across the M dimension exactly like
flash_attention.py, one row of it per head.
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


def supported_shapes(head_dim, block_size, num_heads, total_tokens=None,
                     kv_heads=None):
    """Shape gate for the compiled TPU kernel (interpret mode takes
    any): head_dim lane-sized, block_size a lane multiple, query heads
    sublane-aligned and whole groups of the pool's `kv_heads` (None: as
    many as query heads), a pool row whole lane tiles; a packed stream
    additionally needs its length query-tile aligned."""
    kv_heads = num_heads if kv_heads is None else kv_heads
    ok = (head_dim in (32, 64, 128, 256) and block_size % 128 == 0
          and num_heads % 8 == 0 and kv_heads > 0
          and num_heads % kv_heads == 0
          and (kv_heads * head_dim) % 128 == 0)
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
    `block_id(*grid_and_prefetch_refs) -> (layer, pool block)` feeds
    the k/v BlockSpec index maps over the pool stack, and for int8
    pools the per-vector scale tiles ride the SAME index as their
    codes.  Returns the in_specs list for (k[, ks], v[, vs])."""
    kv = pl.BlockSpec((None, None, BS, H * Dh),
                      lambda *a: (*block_id(*a), 0, 0))
    if not quant:
        return [kv, kv]
    sc = pl.BlockSpec((None, None, BS, H),
                      lambda *a: (*block_id(*a), 0, 0))
    return [kv, sc, kv, sc]


def kv_operands(k_blocks, v_blocks, layer):
    """(quant, operand tuple) for a dense or QuantizedKV pool pair —
    the argument-flattening half of `kv_operand_specs`.  Every operand
    is a stack: blocks and codes [L, N, BS, H*Dh], scales
    [L, N, BS, H].  With layer None the pools are ONE layer's
    [N, BS, H, Dh] (scales [N, BS, H]), taken as a stack of one."""
    quant = is_quantized(k_blocks)
    operands = (k_blocks.codes, k_blocks.scales, v_blocks.codes,
                v_blocks.scales) if quant else (k_blocks, v_blocks)
    if layer is None:
        operands = tuple(
            a.reshape((1,) + a.shape[:2] + (-1,)) for a in operands)
    if any(a.ndim != 4 for a in operands):
        raise ValueError(
            "a pool stack is [L, N, BS, H*Dh] and takes layer=; one "
            "layer's pool is [N, BS, H, Dh] and takes none; got "
            f"{[a.shape for a in operands]} with layer={layer}")
    return quant, operands


def _kv_heads(pool, num_heads, head_dim):
    """K/V heads of a pool stack [L, N, BS, Hkv*Dh] that `num_heads` query
    heads of `head_dim` attend: whole groups of them share a K/V head."""
    hkv, rest = divmod(pool.shape[-1], head_dim)
    if rest or hkv == 0 or num_heads % hkv:
        raise ValueError(
            f"a pool row of {pool.shape[-1]} values is not K/V heads of "
            f"{head_dim} that {num_heads} query heads share in whole groups")
    return hkv


def _load_heads(ref, sref, g, per, dh, dt):
    """The `per` heads of lane group `g` of the pool block in VMEM, each
    [BS, Dh]: heads g*per .. g*per + per - 1 are the lanes
    [g*per*Dh, (g+1)*per*Dh) of the [BS, H*Dh] tile, one aligned lane
    tile (so `g` may be a loop's index), cut into heads by static slices.
    Dequantized when the pool is int8: codes * the head's per-vector
    scales, column g*per + i of the [BS, H] scale tile.  The convert
    happens on the ONE block in flight; no bf16 cache copy ever exists
    in HBM.  The product is taken in f32: the v5e's VPU has no bf16
    arithmetic to lose."""
    width = per * dh
    x = ref[:, pl.ds(pl.multiple_of(g * width, width), width)]
    heads = [x[:, i * dh:(i + 1) * dh] for i in range(per)]
    if sref is None:
        return heads
    sc = sref[...].astype(jnp.float32)                       # [BS, H]
    col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    return [(x_i.astype(jnp.float32) * jnp.sum(
        jnp.where(col == g * per + i, sc, 0.0), axis=1, keepdims=True)
             ).astype(dt) for i, x_i in enumerate(heads)]


# ---- stream kernel (prefill chunks / decode rows / verify regions) ----

def _stream_kernel(layer_ref, tile_seg_ref, tile_pos_ref, tables_ref, q_ref,
                   *refs, scale, nm, qt, quant, tile_base, group):
    if quant:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        ks_ref = vs_ref = None
    qi = pl.program_id(0)
    mi = pl.program_id(1)
    nh, _, dh = q_ref.shape
    nkv = nh // group
    # K/V heads side by side in one 128-lane tile of a pool row: the loop
    # below steps over such tiles, so its index may address the lanes
    per = max(1, min(nkv, 128 // dh))

    @pl.when(mi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # abs position of the tile's first query (-1 pad); tile_base shifts
    # a shard-local grid into the GLOBAL prefetch arrays (sp shards)
    q0 = tile_pos_ref[qi + tile_base]
    bs = k_ref.shape[0]

    # a kv block matters iff it starts at or before the tile's LAST
    # query's causal horizon; pad tiles (q0 < 0) skip every block
    @pl.when((q0 >= 0) & (mi * bs <= q0 + qt - 1))
    def _compute():
        row = q0 + jax.lax.broadcasted_iota(jnp.int32, (qt, bs), 0)
        col = mi * bs + jax.lax.broadcasted_iota(jnp.int32, (qt, bs), 1)
        live = col <= row  # segment-causal by abs pos

        def one_head(h, k, v):
            q = q_ref[h]  # [QT, Dh] — input dtype feeds the MXU full-rate
            # s[i, j] = sum_d q[i, d] * k[j, d]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [QT, BS]
            s = jnp.where(live, s, NEG_INF)
            m_prev = m_ref[h, :, 0:1]             # [QT, 1]
            l_prev = l_ref[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            # o[i, d] += sum_j p[i, j] * v[j, d]
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [QT, Dh]
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        def lane_group(g, carry):
            ks = _load_heads(k_ref, ks_ref, g, per, dh, q_ref.dtype)
            vs = _load_heads(v_ref, vs_ref, g, per, dh, q_ref.dtype)
            for i in range(per):        # a K/V head, then its query heads
                kv = g * per + i
                for r in range(group):
                    one_head(kv if group == 1 else kv * group + r,
                             ks[i], vs[i])
            return carry

        # one traced body, unrolled by the lowering: Python-unrolling the
        # heads cost every program 0.5 s of tracing (56 programs a warm
        # set-up), a rolled loop cost the kernel a fifth of its speed (the
        # scheduler overlaps the heads' dots only in straight-line code)
        jax.lax.fori_loop(0, nkv // per, lane_group, 0, unroll=True)

    @pl.when(mi == nm - 1)
    def _flush():
        l = jnp.maximum(l_ref[:, :, 0:1], 1e-30)  # pad tiles flush zeros
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "q_tile", "interpret",
                                    "tile_base"))
def unified_ragged_attention_kernel(q, k_blocks, v_blocks, tables,
                                    tile_seg, tile_pos, layer=None, *,
                                    scale=None, q_tile=None,
                                    interpret=False, tile_base=0):
    """Pallas segment-causal stream attention: ONE launch scores a
    token-packed stream mixing prefill chunks, plain decode rows and
    speculative verify regions (see module docstring for the layout
    and packing contract); returns [T, H, Dh] in q's dtype.
    k_blocks/v_blocks are the pool STACK [L, N, BS, H*Dh] with `layer`
    the layer to attend (an int or a traced int32 scalar: it is
    scalar-prefetched, so every layer's launch of a program shares one
    kernel body), or one layer's [N, BS, H, Dh] with layer=None.  They
    may be `QuantizedKV` (codes like the blocks, int8, and scales
    [L, N, BS, H] or [N, BS, H]) — the scale tiles ride the same
    scalar-prefetched index as their codes and dequant happens in VMEM
    (`_load_heads`).  q_tile defaults to the production Q_TILE=128
    (interpret-mode tests shrink it to exercise tiny shapes).

    tile_base (long-context round): static tile offset into the
    scalar-prefetched tile_seg/tile_pos arrays — a SEQUENCE-PARALLEL
    shard holding tiles [base, base + T_local/QT) of a global packed
    stream passes its LOCAL q slice with the GLOBAL prefetch arrays
    and tile_base=base, and the block-index maps (`tb[ts[qi+base], m]`)
    DMA exactly the pool blocks the shard's own tiles name.  0 (the
    default) is the exact pre-round single-stream kernel."""
    quant, operands = kv_operands(k_blocks, v_blocks, layer)
    layer = jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                    jnp.int32), (1,))
    qt = Q_TILE if q_tile is None else int(q_tile)
    tile_base = int(tile_base)
    T, H, Dh = q.shape
    BS = operands[0].shape[2]
    Hkv = _kv_heads(operands[0], H, Dh)
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
                          lambda qi, m, ly, ts, tp, tb: (0, qi, 0))
    in_specs = [q_spec] + kv_operand_specs(
        BS, Hkv, Dh, quant,
        lambda qi, m, ly, ts, tp, tb: (ly[0], tb[ts[qi + tile_base], m]))
    kernel = functools.partial(_stream_kernel, scale=scale, nm=M,
                               qt=qt, quant=quant, tile_base=tile_base,
                               group=H // Hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, tile_seg, tile_pos, tables steer the DMA
        num_scalar_prefetch=4,
        grid=(NQ, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((H, qt, Dh),
                               lambda qi, m, ly, ts, tp, tb: (0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, qt, Dh), jnp.float32),
            # m, l: one value a row, kept across a lane tile
            pltpu.VMEM((H, qt, 128), jnp.float32),
            pltpu.VMEM((H, qt, 128), jnp.float32),
        ],
    )
    # chunk prefill, verify and the unified round's mixed stream; the
    # decode kernel has its own name, so a device trace tells the two
    # loads apart
    out = named_pallas_call(
        "paged_attn_prefill",
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, T, Dh), q.dtype),
        interpret=interpret,
    )(layer, tile_seg.astype(jnp.int32), tile_pos.astype(jnp.int32),
      tables.astype(jnp.int32), qh, *operands)
    return out.transpose(1, 0, 2)


# ---- decode (one token per sequence) --------------------------------

def _own_lanes(nh, e, dh):
    """[H, Hkv*Dh] mask: row h owns the lanes of its K/V head, [g*Dh,
    (g+1)*Dh) with g = h // group (group = H / Hkv; head h's own lanes
    when every query head has a K/V head)."""
    group = nh * dh // e
    if group == 1:
        first = jax.lax.broadcasted_iota(jnp.int32, (nh, e), 0) * dh
        lane = jax.lax.broadcasted_iota(jnp.int32, (nh, e), 1)
        return (lane >= first) & (lane < first + dh)
    row = jax.lax.broadcasted_iota(jnp.int32, (nh, e), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (nh, e), 1)
    own = None       # one term a K/V head: no vector division to lower
    for g in range(e // dh):
        mine = ((row >= g * group) & (row < (g + 1) * group)
                & (lane >= g * dh) & (lane < (g + 1) * dh))
        own = mine if own is None else own | mine
    return own


def _scales_by_head(sref, nh):
    """An int8 block's scale tile [BS, H] as float32 [H, BS], the shape of
    the decode body's score tile: the product with an [H, H] identity,
    which is exact (one non-zero term a sum) and is the transposed form
    of dot Mosaic lowers for any width, where a [BS, H] tile narrower
    than the lanes has no transpose."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (nh, nh), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (nh, nh), 1))
    return jax.lax.dot_general(
        eye.astype(sref.dtype), sref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _live_blocks(ctx, bs, width):
    """Grid steps a row of context `ctx` takes: its live blocks (never more
    than its table is wide), and one where it has none, so that a pad row
    still flushes its zeros.  On [B] contexts where the work list is built
    and on one scalar in the body: the two agree by construction."""
    return jnp.clip(jax.lax.div(ctx + (bs - 1), bs), 1, width)


def decode_work_list(ctx_lens, block_size, width):
    """The decode launch's grid: its live (row, table column) pairs as two
    flat int32 lists of the static length B * width, rows ascending and a
    row's columns 0 .. n - 1 (n = `_live_blocks`), and how many of them
    the launch steps over.  Entries past that count are never read by a
    grid step; they stay inside the table.  A few integer ops on
    `ctx_lens` alone, the same for every layer of a program, which XLA
    keeps once (tests/test_tpu_aot_compile.py)."""
    n = _live_blocks(ctx_lens.astype(jnp.int32), block_size, width)   # [B]
    ends = jnp.cumsum(n)
    step = jnp.arange(n.shape[0] * width, dtype=jnp.int32)[:, None]
    done = ends[None, :] <= step          # [B * width, B]: rows behind us
    rows = jnp.sum(done, axis=1, dtype=jnp.int32)
    cols = step[:, 0] - jnp.sum(jnp.where(done, n[None, :], 0), axis=1,
                                dtype=jnp.int32)
    return (jnp.minimum(rows, n.shape[0] - 1), jnp.minimum(cols, width - 1),
            ends[-1])


def _decode_kernel(layer_ref, tables_ref, ctx_ref, rows_ref, cols_ref, q_ref,
                   *refs, scale, nm, dh, quant):
    del layer_ref, tables_ref
    if quant:
        (k_ref, ks_ref, v_ref, vs_ref, o_ref, qbd_ref, acc_ref, m_ref,
         l_ref) = refs
    else:
        k_ref, v_ref, o_ref, qbd_ref, acc_ref, m_ref, l_ref = refs
    # grid step i of the launch's work list: block `mi` of row `bi`
    bi = rows_ref[pl.program_id(0)]
    mi = cols_ref[pl.program_id(0)]
    nh, e = qbd_ref.shape
    bs = k_ref.shape[0]
    ctx = ctx_ref[bi]

    grouped = nh * dh != e     # fewer K/V heads than query heads

    @pl.when(mi == 0)
    def _init():
        # the row's heads as ONE block-diagonal query tile: row h is head
        # h's query in its K/V head's lanes and zero elsewhere, so a dot
        # over all Hkv*Dh lanes of a pool block is every head's score at
        # once (the zeros add exactly 0); selected in float32, the width
        # of the mask: the VPU has no bf16 select to lose
        if grouped:   # q is the [H, Dh] tile: once beside itself a K/V head
            q = jnp.concatenate([q_ref[...].astype(jnp.float32)]
                                * (e // dh), axis=1)
        else:         # q is the [1, H*Dh] row it is in memory
            q = jnp.broadcast_to(q_ref[...].astype(jnp.float32), (nh, e))
        qbd_ref[:] = jnp.where(_own_lanes(nh, e, dh), q,
                               0.0).astype(qbd_ref.dtype)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(mi * bs < ctx)
    def _compute():
        dt = qbd_ref.dtype
        # s[h, j] = sum_e qbd[h, e] * k[j, e]: the block as it lies
        s = jax.lax.dot_general(
            qbd_ref[...], k_ref[...].astype(dt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [H, BS]
        if quant:  # codes went into the dot; the scales fold in here
            s = s * _scales_by_head(ks_ref, nh)
        col = mi * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < ctx, s, NEG_INF)  # one horizon for every row
        m_prev = m_ref[:, 0:1]                                 # [H, 1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quant:
            p = p * _scales_by_head(vs_ref, nh)
        # pv[h, e] = sum_j p[h, j] * v[j, e]: head h's lanes of row h are
        # its output, the other lanes are other heads' values under head
        # h's weights and are dropped at the flush
        pv = jax.lax.dot_general(
            p.astype(dt), v_ref[...].astype(dt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [H, H*Dh]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(mi == _live_blocks(ctx, bs, nm) - 1)   # the row's last step
    def _flush():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)  # ctx 0 flushes zeros
        o = jnp.where(_own_lanes(nh, e, dh), acc_ref[:] / l, 0.0)
        if grouped:   # row h's output lies in its K/V head's lanes
            o_ref[:] = sum(o[:, g * dh:(g + 1) * dh]
                           for g in range(e // dh)).astype(o_ref.dtype)
        else:
            o_ref[:] = jnp.sum(o, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_kernel(q, k_blocks, v_blocks, tables, ctx_lens,
                                  layer=None, *, scale=None,
                                  interpret=False):
    """Ragged paged decode attention: q [B, H, Dh], one token per
    sequence attending cache positions [0, ctx_len) of the pool stack's
    `layer` (or of one layer's pool, layer=None; either may be
    `QuantizedKV`).  The grid is the launch's live (row, block) pairs
    (`decode_work_list`), its length read on the device: a step scores
    ALL heads of its row against the [BS, H*Dh] block with one dot,
    updates one online softmax on the [H, BS] tile and sums with one dot
    (`_decode_kernel`); a row's steps are consecutive, so the pipeline
    fetches the next row's first block behind this row's last.  q goes in
    and the output comes back as the lane-dense [1, H*Dh] row they are in
    memory (as the [H, Dh] tile where the pool holds fewer K/V heads than
    q has heads: a row of the query tile is then Hkv*Dh wide).
    ctx_len == 0 (a pad row) takes one step and returns zeros.  Returns
    [B, H, Dh] in q's dtype."""
    quant, operands = kv_operands(k_blocks, v_blocks, layer)
    layer = jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                    jnp.int32), (1,))
    B, H, Dh = q.shape
    Hkv = _kv_heads(operands[0], H, Dh)
    if quant and Hkv != H:
        raise ValueError("the decode kernel folds an int8 pool's scales "
                         "a query head: it takes no grouped heads")
    E = Hkv * Dh
    BS = operands[0].shape[2]
    M = tables.shape[1]
    scale = (Dh ** -0.5) if scale is None else float(scale)
    rows, cols, steps = decode_work_list(ctx_lens, BS, M)
    tile = (1, H * Dh) if Hkv == H else (H, Dh)
    row = pl.BlockSpec((None,) + tile,
                       lambda i, ly, tb, cx, rw, cl: (rw[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, tables and the work list steer the DMA
        num_scalar_prefetch=5,
        grid=(steps,),
        in_specs=[row] + kv_operand_specs(
            BS, Hkv, Dh, quant,
            lambda i, ly, tb, cx, rw, cl: (ly[0], tb[rw[i], cl[i]])),
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((H, E), q.dtype),       # the block-diagonal query
            pltpu.VMEM((H, E), jnp.float32),   # acc: row h, its own lanes
            # m, l: one value a head, kept across a lane tile
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    out = named_pallas_call(
        "paged_attn_decode",
        functools.partial(_decode_kernel, scale=scale, nm=M, dh=Dh,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B,) + tile, q.dtype),
        interpret=interpret,
    )(layer, tables.astype(jnp.int32), ctx_lens.astype(jnp.int32), rows,
      cols, q.reshape((B,) + tile), *operands)
    return out.reshape(B, H, Dh)
