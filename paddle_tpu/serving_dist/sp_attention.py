"""Memory-flat sequence-parallel attention for the sp packed-prefill trunk.

The r21 sp trunk made the packed prefill stream sequence-parallel but
kept one memory cliff: `nn/decode._sp_kv_gather` all-gathers the FULL
freshly-projected K/V stream onto every sp shard before the pool
scatter and attention, so peak live fresh-K/V bytes per shard are
O(chunk) — linear in chunk length, exactly the regime ring attention
exists for.  This module ports the two multichip training primitives
(`parallel/ring_attention.py`, `parallel/ulysses.py`) into the serving
trunk's RAGGED, PAGED contract:

* ring — each shard's fresh K/V stream slice is cut into fixed
  `block_tokens`-row sub-blocks that rotate around the `sp` axis via
  ppermute; every shard scatters each visiting block into its replica
  of the paged pool (so the sp-replicated pool converges bitwise, the
  r21 invariant) and folds it into an online-softmax accumulator.
  Peak cross-shard fresh-K/V per shard = held block + in-flight
  ppermute buffer = O(block_tokens), CONSTANT in chunk length.

* ulysses — one all-to-all per sub-block swaps sequence<->head
  sharding: each shard attends its own head slice over the full
  gathered sub-block (global row order reconstructed by index math).
  The pool scatter still rides the ring rotation (the replicated pool
  needs ALL mp-local heads per shard), fused into the same scan.
  Requires the mp-local head count divisible by sp.

Masking contract (the `ops/pallas/unified_attention.py` segment-causal
contract, which must survive rotation): every row of the packed stream
carries (seg, pos) metadata; a query at (qseg, qpos) attends exactly
keys with kseg == qseg and 0 <= kpos <= qpos.  Because seg/pos enter
the seam REPLICATED (specs P(None)), a visiting block's metadata is
recovered exactly from its origin shard index — global row r of ring
step s on shard j is (j - s) % n * T_local + c * block + r — so
cross-shard causality is exact, not approximate.  The fresh pass
covers positions [start_seg, qpos] (start_seg = the segment's first
position written THIS dispatch, computed by `segment_starts`); the
pool pass covers columns < start_seg against the already-resident
paged blocks with the same numerics as `ops.attention`'s XLA fallback
(scores f32, weights cast to model dtype, int8 scales folded
post-contraction).  The union is exactly [0, qpos] — the same key set
the all-gather path masks — so parity is token-for-token (the online
softmax reassociates the reduction; parity is asserted empirically on
the composed stack, the established sp policy).

Pad rows (pos == -1) are excluded from attention by the mask and their
K/V payload is ZEROED before rotation: all pads scatter into the
reserved trash block (0, 0), and different shards apply those writes
in different rotation orders — identical zero payloads keep the sp
pool replicas bitwise convergent regardless of order (the all-gather
path gets this for free because every shard applies the one gathered
stream in one order).
"""
from __future__ import annotations

import functools

from .config import SP_ATTENTION_MODES  # noqa: F401  (re-export)

#: Rotation sub-block length (tokens).  Fixed — NOT a function of chunk
#: length — so ring/ulysses peak cross-shard fresh-K/V bytes per shard
#: are constant across any chunk sweep (the memory-flatness bar).
#: Matches parallel/ring_attention._CHUNK.
DEFAULT_BLOCK_TOKENS = 512

NEG_INF = -1e30


def _sub_block(local_tokens, block_tokens):
    """Static sub-block length: `block_tokens` shrunk (power-of-two
    steps) until it divides the shard-local stream length."""
    bc = max(1, min(int(block_tokens), int(local_tokens)))
    while local_tokens % bc:
        bc //= 2
    return bc


def sp_attention_peak_bytes(mode, chunk_tokens, sp, tp, num_heads,
                            head_dim, kv_quant=False, itemsize=4,
                            scale_itemsize=4,
                            block_tokens=DEFAULT_BLOCK_TOKENS):
    """Peak CROSS-SHARD fresh-K/V bytes one sp shard materializes to
    attend a packed stream of `chunk_tokens` — the analytic accounting
    the flat-memory assertion and the `serving_sp_attention_bytes_peak`
    gauge report (host-side arithmetic, the r20 `dispatch_wire_bytes`
    discipline: CPU-degraded runs can't measure HBM, the formula is
    exact on any backend).

    Counted: bytes the attention MODE materializes beyond the shard's
    own T/sp stream slice — the all-gather output (full stream, k+v),
    or ring's held + in-flight rotating sub-blocks, or ulysses' a2a
    in/out buffers + the rotation-scatter window.  Not counted: the
    shard-local q/k/v projections and the paged pool itself, identical
    across modes (O(chunk/sp) and O(pool) respectively).

    allgather: 2 * chunk * (H/tp) * Dh * eff     (linear in chunk)
    ring:      4 * block * (H/tp) * Dh * eff     (constant)
    ulysses:   8 * block * (H/tp) * Dh * eff     (constant)
    eff = itemsize, or for int8 KV 1 + scale_itemsize/Dh.
    """
    if mode not in SP_ATTENTION_MODES:
        raise ValueError(f"sp_attention={mode!r} must be one of "
                         f"{SP_ATTENTION_MODES}")
    t = int(chunk_tokens)
    local_heads = max(1, int(num_heads) // max(1, int(tp)))
    eff = (1.0 + float(scale_itemsize) / float(head_dim)) if kv_quant \
        else float(itemsize)
    per_tok = local_heads * int(head_dim) * eff
    if mode == "allgather" or int(sp) <= 1:
        return int(round(2 * t * per_tok))
    bc = _sub_block(max(1, t // int(sp)), block_tokens)
    ring = 4 * bc * per_tok          # k+v, held + in-flight ppermute
    if mode == "ring":
        return int(round(ring))
    return int(round(2 * ring))      # ulysses: + a2a in/out buffers


def sp_attention_flat_bound(mode, tp, num_heads, head_dim,
                            kv_quant=False, itemsize=4,
                            scale_itemsize=4,
                            block_tokens=DEFAULT_BLOCK_TOKENS):
    """The chunk-length-INDEPENDENT ceiling on ring/ulysses peak bytes
    (the sub-block never exceeds `block_tokens` rows) — what the
    serving loop asserts every ring/ulysses dispatch stays under."""
    eff = (1.0 + float(scale_itemsize) / float(head_dim)) if kv_quant \
        else float(itemsize)
    per_tok = max(1, int(num_heads) // max(1, int(tp))) * int(head_dim) \
        * eff
    mult = 4 if mode == "ring" else 8
    return int(round(mult * int(block_tokens) * per_tok))


def segment_starts(seg, pos, num_segments):
    """Per-segment first position written THIS dispatch: starts[b] =
    min over the stream's valid rows of segment b of pos (a huge
    sentinel when a segment feeds no rows — its queries don't exist
    either).  Splits each query's key range exactly: pool columns
    < starts[qseg] (earlier dispatches), fresh rows in
    [starts[qseg], qpos].  Computed OUTSIDE the shard_map seam from the
    replicated stream metadata, so every shard agrees bitwise."""
    import jax.numpy as jnp

    big = jnp.int32(2 ** 30)
    p = jnp.where(pos >= 0, pos.astype(jnp.int32), big)
    return jnp.full((num_segments,), big, jnp.int32).at[seg].min(p)


def kv_get_layer(cache, i, num_heads):
    """One layer's pool out of the stack, with the heads named:
    [N, BS, H, Dh] from [L, N, BS, H*Dh] (dense, or the codes of a
    QuantizedKV, whose scales [N, BS, H] come along), for the seam
    below, which takes and returns a whole layer.  Every other trunk
    hands the attention ops the stack and `layer=`."""
    import jax

    from ..inference.kv_cache import split_heads

    return jax.tree.map(lambda a: split_heads(a[i], num_heads), cache)


def kv_set_layer(cache, i, new):
    """Functional single-layer write-back into the full pool stack —
    the inverse of `kv_get_layer`, for trunks whose attention seam
    updates a whole layer slice at once."""
    import jax

    return jax.tree.map(
        lambda a, n: a.at[i].set(n.reshape(a.shape[1:])), cache, new)


@functools.lru_cache(maxsize=16)
def build_sp_fresh_attention(mesh, mode, kv_quant, block_size, scale,
                             block_tokens=DEFAULT_BLOCK_TOKENS):
    """Build the shard_map seam that replaces `_sp_kv_gather` + the
    sp trunk's per-layer pool scatter + `ragged_prefill_attention`:

        attend(q, k, v, kc_i, vc_i, tables, seg, pos, starts)
            -> (o, kc_i, vc_i)

    q/k/v: [T, H_mp, Dh] fresh projections, token axis sp-sharded and
    head axis mp-sharded (the trunk's layout).  kc_i/vc_i: ONE layer's
    pool arrays ([N, BS, H_mp, Dh] dense, or int8 QuantizedKV),
    sp-replicated / mp-head-sharded, returned with this stream's rows
    scattered in on every sp replica.  tables/seg/pos/starts:
    replicated ragged metadata ([B, M], [T], [T], [B]).  o: [T, H_mp,
    Dh] attention output, token-sharded like q.

    Static args (cache key): mesh, mode ("ring"|"ulysses"), kv_quant,
    pool block_size, softmax scale, rotation sub-block length.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from jax.lax import axis_size as _axis_size
    from ..parallel.mesh import pvary as _pvary

    if mode not in ("ring", "ulysses"):
        raise ValueError(f"build_sp_fresh_attention: mode={mode!r} "
                         f"(allgather keeps the r21 seam)")
    BS = int(block_size)
    quant = bool(kv_quant)
    if quant:
        from ..inference.kv_quant import QuantizedKV, kv_encode

    def _vary(t):
        return jax.tree_util.tree_map(lambda x: _pvary(x, "sp"), t)

    # -- shared pieces (shapes generic over the head count Hq) --------

    def _pool_partial(qh, qseg, qpos, qcap, kc_i, vc_i, tables):
        """Unnormalized (o, m, l) of the queries against the
        ALREADY-RESIDENT pool columns (< qcap per query) — the exact
        numerics of ops.attention's XLA fallback (scores f32, weights
        cast to model dtype, int8 scales folded post-contraction),
        minus the final normalization, which happens after the fresh
        blocks merge in.  qh: [Hq, Tq, Dh]."""
        hq, tq, dh = qh.shape
        b, mmax = tables.shape
        c = mmax * BS
        if quant:
            k = kc_i.codes[tables].reshape(b, c, hq, dh)
            v = vc_i.codes[tables].reshape(b, c, hq, dh)
            ks = kc_i.scales[tables].reshape(b, c, hq).transpose(2, 0, 1)
            vs = vc_i.scales[tables].reshape(b, c, hq).transpose(2, 0, 1)
        else:
            k = kc_i[tables].reshape(b, c, hq, dh)
            v = vc_i[tables].reshape(b, c, hq, dh)
        k = k.transpose(2, 0, 1, 3).astype(qh.dtype)   # [Hq, B, C, Dh]
        v = v.transpose(2, 0, 1, 3).astype(qh.dtype)
        s = jnp.einsum("htd,hbcd->htbc", qh, k).astype(jnp.float32) \
            * scale
        if quant:
            s = s * ks[:, None].astype(jnp.float32)
        own = qseg[:, None] == jnp.arange(b)[None, :]          # [Tq, B]
        ok = jnp.arange(c)[None, :] < qcap[:, None]            # [Tq, C]
        mask = own[:, :, None] & ok[:, None, :]
        s = jnp.where(mask[None], s, NEG_INF)
        sf = s.reshape(hq, tq, b * c)
        m = sf.max(-1)                                         # [Hq, Tq]
        p = jnp.exp(sf - m[..., None])                         # f32
        l = p.sum(-1)
        w = p.reshape(hq, tq, b, c).astype(qh.dtype)
        if quant:
            w = w * vs[:, None].astype(qh.dtype)
        o = jnp.einsum("htbc,hbcd->htd", w, v).astype(jnp.float32)
        return o, m, l

    def _attend_block(qh, qseg, qpos, kb, vb, kseg, kpos, acc):
        """Fold one visiting fresh sub-block into the online-softmax
        accumulator (ring_attention's merge rule).  kb/vb: [Bc, Hq,
        Dh] (or int8 (codes, scales)); kseg/kpos: the block's global
        row metadata, recovered outside."""
        o, m, l = acc
        if quant:
            kcodes, ksc = kb
            vcodes, vsc = vb
            k = kcodes.transpose(1, 0, 2).astype(qh.dtype)
            v = vcodes.transpose(1, 0, 2).astype(qh.dtype)
            ksh = ksc.transpose(1, 0)                          # [Hq, Bc]
            vsh = vsc.transpose(1, 0)
        else:
            k = kb.transpose(1, 0, 2)                     # [Hq, Bc, Dh]
            v = vb.transpose(1, 0, 2)
        s = jnp.einsum("htd,hcd->htc", qh, k).astype(jnp.float32) \
            * scale
        if quant:
            s = s * ksh[:, None].astype(jnp.float32)
        mask = (qseg[:, None] == kseg[None, :]) \
            & (kpos[None, :] >= 0) \
            & (kpos[None, :] <= qpos[:, None])                # [Tq, Bc]
        s = jnp.where(mask[None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)       # exp(-1e30 - finite) == 0.0:
        p = jnp.exp(s - m_new[..., None])  # empty partials annihilate
        w = p.astype(qh.dtype)
        if quant:
            w = w * vsh[:, None].astype(qh.dtype)
        pv = jnp.einsum("htc,hcd->htd", w, v).astype(jnp.float32)
        o = o * alpha[..., None] + pv
        l = l * alpha + p.sum(-1)
        return o, m_new, l

    def _scatter(kc_i, vc_i, kb, vb, kseg, kpos, tables):
        """Scatter one visiting sub-block's rows into this shard's
        pool replica — the same (blk, off) arithmetic as the trunk's
        `kv_write`, pads routed to the reserved trash block 0 (their
        payload is pre-zeroed, so every rotation order converges)."""
        valid = kpos >= 0
        p0 = jnp.where(valid, kpos, 0)
        blk = jnp.where(valid, tables[kseg, p0 // BS], 0)
        off = p0 % BS
        if quant:
            kc_i = QuantizedKV(kc_i.codes.at[blk, off].set(kb[0]),
                               kc_i.scales.at[blk, off].set(kb[1]))
            vc_i = QuantizedKV(vc_i.codes.at[blk, off].set(vb[0]),
                               vc_i.scales.at[blk, off].set(vb[1]))
        else:
            kc_i = kc_i.at[blk, off].set(kb)
            vc_i = vc_i.at[blk, off].set(vb)
        return kc_i, vc_i

    def _fresh_payload(k, v, valid, scales_dtype):
        """Zero pad rows, encode once when quantized (per-row absmax —
        bit-identical to `kv_write`'s append encoding no matter how
        rows are batched or routed), cut into rotation sub-blocks."""
        kz = jnp.where(valid[:, None, None], k, 0)
        vz = jnp.where(valid[:, None, None], v, 0)
        if quant:
            kz = kv_encode(kz, scales_dtype)       # (codes, scales)
            vz = kv_encode(vz, scales_dtype)
        return kz, vz

    def _chunks(t, n_blocks, bc):
        return jax.tree_util.tree_map(
            lambda x: x.reshape((n_blocks, bc) + x.shape[1:]), t)

    def _rotate(t, n):
        perm = [(d, (d + 1) % n) for d in range(n)]
        return jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, "sp", perm), t)

    # -- mode bodies (run per-shard inside shard_map) ------------------

    def ring_body(q, k, v, kc_i, vc_i, tables, seg, pos, starts):
        n = _axis_size("sp")
        j = jax.lax.axis_index("sp")
        tl = q.shape[0]
        bc = _sub_block(tl, block_tokens)
        nb = tl // bc
        kc_i, vc_i, tables, seg, pos, starts = _vary(
            (kc_i, vc_i, tables, seg, pos, starts))
        qseg = jax.lax.dynamic_slice_in_dim(seg, j * tl, tl)
        qpos = jax.lax.dynamic_slice_in_dim(pos, j * tl, tl)
        qh = q.transpose(1, 0, 2)                      # [Hl, Tl, Dh]
        qcap = jnp.where(qpos >= 0,
                         jnp.minimum(starts[qseg], qpos + 1), 0)
        o, m, l = _pool_partial(qh, qseg, qpos, qcap, kc_i, vc_i,
                                tables)
        sdt = kc_i.scales.dtype if quant else None
        kz, vz = _fresh_payload(k, v, qpos >= 0, sdt)

        def outer(carry, xs):
            kc_i, vc_i, o, m, l = carry
            kb0, vb0, c = xs

            def inner(icarry, s):
                kc_i, vc_i, o, m, l, kb, vb = icarry
                src = (j - s) % n
                base = src * tl + c * bc
                kseg = jax.lax.dynamic_slice_in_dim(seg, base, bc)
                kpos = jax.lax.dynamic_slice_in_dim(pos, base, bc)
                kc_i, vc_i = _scatter(kc_i, vc_i, kb, vb, kseg, kpos,
                                      tables)
                o, m, l = _attend_block(qh, qseg, qpos, kb, vb, kseg,
                                        kpos, (o, m, l))
                kb, vb = _rotate((kb, vb), n)
                return (kc_i, vc_i, o, m, l, kb, vb), None

            (kc_i, vc_i, o, m, l, _, _), _ = jax.lax.scan(
                inner, (kc_i, vc_i, o, m, l, kb0, vb0),
                jnp.arange(n))
            return (kc_i, vc_i, o, m, l), None

        (kc_i, vc_i, o, m, l), _ = jax.lax.scan(
            outer, (kc_i, vc_i, o, m, l),
            (_chunks(kz, nb, bc), _chunks(vz, nb, bc), jnp.arange(nb)))
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return out.transpose(1, 0, 2), kc_i, vc_i

    def ulysses_body(q, k, v, kc_i, vc_i, tables, seg, pos, starts):
        n = _axis_size("sp")
        j = jax.lax.axis_index("sp")
        tl, hl, dh = q.shape
        hu = hl // n
        bc = _sub_block(tl, block_tokens)
        nb = tl // bc
        kc_i, vc_i, tables, seg, pos, starts = _vary(
            (kc_i, vc_i, tables, seg, pos, starts))
        # seq -> head: my head slice over the FULL packed stream, rows
        # in global order (sources concatenate in ring order)
        qg = jax.lax.all_to_all(q, "sp", split_axis=1, concat_axis=0,
                                tiled=True)               # [T, Hu, Dh]
        qh = qg.transpose(1, 0, 2)
        qcap = jnp.where(pos >= 0,
                         jnp.minimum(starts[seg], pos + 1), 0)
        h0 = j * hu
        if quant:
            kc_h = QuantizedKV(
                jax.lax.dynamic_slice_in_dim(kc_i.codes, h0, hu, 2),
                jax.lax.dynamic_slice_in_dim(kc_i.scales, h0, hu, 2))
            vc_h = QuantizedKV(
                jax.lax.dynamic_slice_in_dim(vc_i.codes, h0, hu, 2),
                jax.lax.dynamic_slice_in_dim(vc_i.scales, h0, hu, 2))
        else:
            kc_h = jax.lax.dynamic_slice_in_dim(kc_i, h0, hu, 2)
            vc_h = jax.lax.dynamic_slice_in_dim(vc_i, h0, hu, 2)
        o, m, l = _pool_partial(qh, seg, pos, qcap, kc_h, vc_h, tables)
        sdt = kc_i.scales.dtype if quant else None
        qpos_loc = jax.lax.dynamic_slice_in_dim(pos, j * tl, tl)
        kz, vz = _fresh_payload(k, v, qpos_loc >= 0, sdt)
        # global row index of gathered-sub-block row r: source shard
        # r // bc contributed its rows [c*bc, c*bc+bc)
        gbase = (jnp.arange(n)[:, None] * tl
                 + jnp.arange(bc)[None, :]).reshape(-1)

        def a2a(t):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.all_to_all(
                    x, "sp", split_axis=1, concat_axis=0, tiled=True),
                t)

        def outer(carry, xs):
            kc_i, vc_i, o, m, l = carry
            kb0, vb0, c = xs
            gidx = gbase + c * bc
            o, m, l = _attend_block(qh, seg, pos, a2a(kb0), a2a(vb0),
                                    seg[gidx], pos[gidx], (o, m, l))

            # the sp-replicated pool needs ALL mp-local heads on every
            # shard, which the head-sharded a2a view can't provide —
            # the scatter rides the ring rotation instead
            def inner(icarry, s):
                kc_i, vc_i, kb, vb = icarry
                src = (j - s) % n
                base = src * tl + c * bc
                kseg = jax.lax.dynamic_slice_in_dim(seg, base, bc)
                kpos = jax.lax.dynamic_slice_in_dim(pos, base, bc)
                kc_i, vc_i = _scatter(kc_i, vc_i, kb, vb, kseg, kpos,
                                      tables)
                kb, vb = _rotate((kb, vb), n)
                return (kc_i, vc_i, kb, vb), None

            (kc_i, vc_i, _, _), _ = jax.lax.scan(
                inner, (kc_i, vc_i, kb0, vb0), jnp.arange(n))
            return (kc_i, vc_i, o, m, l), None

        (kc_i, vc_i, o, m, l), _ = jax.lax.scan(
            outer, (kc_i, vc_i, o, m, l),
            (_chunks(kz, nb, bc), _chunks(vz, nb, bc), jnp.arange(nb)))
        on = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        # head -> seq: normalize FIRST so only o crosses back
        out = jax.lax.all_to_all(on.transpose(1, 0, 2), "sp",
                                 split_axis=0, concat_axis=1,
                                 tiled=True)              # [Tl, Hl, Dh]
        return out, kc_i, vc_i

    body = ring_body if mode == "ring" else ulysses_body
    stream = P("sp", "mp", None)
    if quant:
        from ..inference.kv_quant import QuantizedKV as _QKV

        pool = _QKV(P(None, None, "mp", None), P(None, None, "mp"))
    else:
        pool = P(None, None, "mp", None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(stream, stream, stream, pool, pool, P(None, None),
                  P(None), P(None), P(None)),
        out_specs=(stream, pool, pool),
        check_vma=False)
