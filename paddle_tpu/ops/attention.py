"""Attention ops.

Reference: the fused attention ops (paddle/fluid/operators/fused/ — north-star
names fused_attention_op) and python/paddle/nn/functional/transformer.py.
TPU-first: `scaled_dot_product_attention` dispatches to the Pallas
flash-attention kernel on TPU (MXU-tiled, online softmax, O(S) memory);
elsewhere it runs the plain einsum path, which XLA fuses well at small S.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec, get_abstract_mesh

from ._registry import defop, raw


def _on_tpu():
    return jax.default_backend() == "tpu"


def _manual_mesh(mesh):
    """The mesh a Pallas call has to be `shard_map`ped over, or None
    when the call already runs per device.  Mosaic kernels cannot be
    partitioned by GSPMD, so under a multi-device `jit` the kernel runs
    inside a shard_map with heads split over `mp` (and, in training,
    batch over `dp`); inside an enclosing shard_map the axes are manual
    already and the operands are the local shards."""
    if mesh is None or mesh.size == 1 \
            or get_abstract_mesh().manual_axes:
        return None
    return mesh


def paged_attention_path(head_dim, block_size, num_heads,
                         total_tokens=None, mesh=None, kv_heads=None):
    """Which implementation the paged-pool attention ops take for these
    shapes on this backend: "pallas", "pallas/shard_map" (the kernel
    per device, heads split over the mesh's mp axis) or "xla" (the
    gather path).  `kv_heads` are the pool's K/V heads where whole groups
    of the `num_heads` query heads share one (None: a K/V head a query
    head).  Chosen by the platform and the kernel's shape gate alone — a
    lowering error in the chosen path raises, it never selects another
    path."""
    from .pallas.unified_attention import supported_shapes

    if not _on_tpu():
        return "xla"
    kv_heads = num_heads if kv_heads is None else kv_heads
    mesh = _manual_mesh(mesh)
    mp = 1 if mesh is None else dict(mesh.shape).get("mp", 1)
    if num_heads % mp or kv_heads % mp or not supported_shapes(
            head_dim, block_size, num_heads // mp, total_tokens,
            kv_heads // mp):
        return "xla"
    return "pallas" if mesh is None else "pallas/shard_map"


def _paged_kernel(kernel, mesh, q, k_blocks, v_blocks, layer, *rest, **kw):
    """Run a paged-pool Pallas `kernel(q, k_blocks, v_blocks, *rest,
    layer)` on the pool stack [L, N, BS, H*Dh], which the kernel reads
    where it lies (or on one layer's [N, BS, H, Dh], layer None); with a
    mesh, per device under shard_map: q/out [T, H, Dh] and the pools
    split on heads over mp, the int32 steering arrays replicated."""
    mesh = _manual_mesh(mesh)
    fn = functools.partial(kernel, **kw)
    if mesh is None:
        return fn(q, k_blocks, v_blocks, *rest, layer)
    if layer is None:  # one layer's pool: a stack of one, as the kernel
        k_blocks, v_blocks = jax.tree.map(  # itself would take it
            lambda a: a.reshape((1,) + a.shape[:2] + (-1,)),
            (k_blocks, v_blocks))
        layer = 0
    layer = jnp.asarray(layer, jnp.int32)
    if dict(mesh.shape).get("dp", 1) > 1:
        # the engine shards blocks over dp and a launch may name any of
        # them: gather ONE layer's blocks over dp, never the stack
        k_blocks, v_blocks = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0),
            (k_blocks, v_blocks))
        layer = jnp.zeros((), jnp.int32)
    heads = PartitionSpec(None, "mp", None)
    # blocks / int8 codes [L, N, BS, H*Dh] and, for an int8 pool, the
    # per-vector scales [L, N, BS, H]: heads are the minor axis of both
    pool = PartitionSpec(None, None, None, "mp")
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(heads, pool, pool) + (PartitionSpec(),) * (len(rest) + 1),
        out_specs=heads, check_vma=False)(q, k_blocks, v_blocks, *rest,
                                          layer)


def _pool_gather(layer, block_tables):
    """blocks -> `blocks[block_tables]` of the stack's `layer`, as ONE
    gather from the stack (no slice of a layer's pool in between); of
    one layer's pool when layer is None.  [.., M, BS, H*Dh] from a
    stack, [.., M, BS, H, Dh] from one layer's pool: the callers
    reshape either to [.., M*BS, H, Dh]."""
    if layer is None:
        return lambda blocks: blocks[block_tables]
    return lambda blocks: blocks[layer, block_tables]


def _block_size(blocks, layer):
    """BS of a pool stack [L, N, BS, H*Dh] or (layer None) of one
    layer's pool [N, BS, H, Dh]."""
    return blocks.shape[1 if layer is None else 2]


def _pool_heads(blocks, layer, head_dim):
    """K/V heads of a pool stack [L, N, BS, Hkv*Dh] or (layer None) of
    one layer's pool [N, BS, Hkv, Dh]: as many as the query heads, or
    fewer, each shared by a whole group of them."""
    return blocks.shape[2] if layer is None \
        else blocks.shape[-1] // head_dim


def _per_query_head(kv, heads, axis):
    """K or V gathered with its Hkv heads on `axis`, each repeated for
    the group of query heads that share it (as it is when Hkv = heads)."""
    group = heads // kv.shape[axis]
    return kv if group == 1 else jnp.repeat(kv, group, axis=axis)


def _xla_attention(q, k, v, mask=None, scale=None, causal=False):
    # q: [B, H, Sq, D]; k/v: [B, H, Sk, D]
    scale = (q.shape[-1] ** -0.5) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(cm, s, -1e30)
    if mask is not None:
        s = s + mask
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    return out, w


def _flash_plan(head_dim, num_heads, q_len, k_len, batch, mesh,
                token_major):
    """(path, mesh to shard_map over | None, token-major operands go to the
    kernels as they are) for `flash_attention_path`'s arguments."""
    from .pallas.flash_attention import heads_per_block

    if not _on_tpu() or head_dim not in (32, 64, 128, 256) \
            or q_len < 128 or q_len % 128 or k_len % 128:
        return "xla", None, False
    mesh = _manual_mesh(mesh)
    if mesh is not None:
        # the canonical (dp, mp) axes of parallel/mesh.py, and whole
        # batches and heads per device; any other mesh takes the XLA
        # path, which GSPMD can partition
        if not {"dp", "mp"} <= set(mesh.axis_names) \
                or batch % mesh.shape["dp"] or num_heads % mesh.shape["mp"]:
            return "xla", None, False
        num_heads //= mesh.shape["mp"]
    # heads that do not fill the kernels' lane blocks (an odd count at
    # Dh = 64) go through the head-major border, which appends zero heads
    direct = token_major and num_heads % heads_per_block(head_dim) == 0
    if mesh is not None:
        return "pallas/shard_map", mesh, direct
    return ("pallas/token_major" if direct else "pallas"), None, direct


def flash_attention_path(head_dim, num_heads, q_len, k_len, batch=1,
                         mesh=None, token_major=False):
    """Which implementation the unmasked (or per-key-biased), dropout-free
    attention ops take for these shapes on this backend:
    "pallas/token_major" (the flash kernels on the projections' own
    [B, S, H*Dh] operands: `token_major_attention` when the heads fill the
    kernels' lane blocks), "pallas" (the same kernels behind the head-major
    border: `scaled_dot_product_attention`), "pallas/shard_map" (either, per
    device under `mesh`: batch over dp, heads over mp) or "xla".  Chosen by
    the platform, the shapes and the mesh alone — a lowering error in the
    chosen path raises, it never selects another path."""
    return _flash_plan(head_dim, num_heads, q_len, k_len, batch, mesh,
                       token_major)[0]


def _per_key_bias(attn_mask, batch, k_len):
    """A [B | 1, 1, 1, Sk] additive mask (the padding-mask form every
    BERT-class encoder builds) as the PER-KEY bias [B, Sk] the kernels
    stream natively; None for any other mask."""
    mask = raw(attn_mask)
    if mask is None or getattr(mask, "ndim", 0) != 4 \
            or mask.shape[1] != 1 or mask.shape[2] != 1 \
            or mask.shape[0] not in (1, batch) or mask.shape[-1] != k_len:
        return None
    return jnp.broadcast_to(mask[:, 0, 0, :], (batch, k_len))


def _flash_plan_for(attn_mask, key_bias, dropout_p, return_weights, *shape,
                    token_major=False):
    """`_flash_plan` of an op call: the XLA path unless the call is one the
    kernels can serve (no mask or a per-key bias, no dropout, no weights
    output)."""
    if (attn_mask is not None and key_bias is None) or dropout_p != 0.0 \
            or return_weights:
        return "xla", None, False
    from ..parallel.mesh import current_mesh
    return _flash_plan(*shape, current_mesh(), token_major)


@defop(stochastic=True)
def scaled_dot_product_attention(q, k, v, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, scale=None,
                                 return_weights=False, key=None):
    """q,k,v: [B, H, S, D] (head-major). Dispatches to flash attention when
    profitable (`flash_attention_path`); the weights output is only
    materialized when requested."""
    # The Pallas kernels stream K/V (fwd, dq) and Q/dO (dkv) blockwise over
    # an arbitrary grid dim with online-softmax state in VMEM scratch, so
    # per-step residency is a few blocks regardless of sequence length —
    # no VMEM-driven length cap. (The fused one-pass backward, which does
    # pin full Q/dO, self-gates on sq.)
    key_bias = _per_key_bias(attn_mask, q.shape[0], k.shape[-2])
    path, mesh, _ = _flash_plan_for(
        attn_mask, key_bias, dropout_p, return_weights, q.shape[-1],
        q.shape[1], q.shape[-2], k.shape[-2], q.shape[0])
    if path != "xla":
        from .pallas.flash_attention import (flash_attention,
                                             flash_attention_bias)
        # pallas_call abstractification rejects Tensor wrappers (JAX
        # dropped __jax_array__ support there), while plain jnp ops
        # accept them — unwrap, or the grad trace loses the kernel
        args = (raw(q), raw(k), raw(v))
        bh = PartitionSpec("dp", "mp", None, None)
        if key_bias is None:
            fn = functools.partial(flash_attention, causal=is_causal,
                                   scale=scale)
            specs = (bh,) * 3
        else:
            fn = functools.partial(flash_attention_bias, causal=is_causal,
                                   scale=scale)
            args += (key_bias,)
            specs = (bh,) * 3 + (PartitionSpec("dp", None),)
        if mesh is not None:  # batch over dp, heads over mp, per device
            fn = jax.shard_map(fn, mesh=mesh, in_specs=specs,
                               out_specs=specs[0], check_vma=False)
        return fn(*args), None
    out, w = _xla_attention(q, k, v, attn_mask, scale, is_causal)
    if dropout_p > 0.0:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, w.shape)
        w_d = jnp.where(keep, w / (1.0 - dropout_p), 0.0)
        out = jnp.einsum("bhqk,bhkd->bhqd", w_d, v)
    return out, (w if return_weights else None)


@defop(stochastic=True)
def token_major_attention(q, k=None, v=None, num_heads=1, attn_mask=None,
                          dropout_p=0.0, is_causal=False, scale=None,
                          key=None):
    """Attention on the projections' own layout: q [B, Sq, E] and k, v
    [B, Sk, E] with the `num_heads` heads side by side, as the projection
    GEMMs write them — or q the fused projection [B, S, 3E] (q, k, v side
    by side) with k = v = None. Returns [B, Sq, E], as the output
    projection reads it.

    Where `flash_attention_path(..., token_major=True)` says so, the flash
    kernels read and write these operands where they lie, and no relayout
    pass stands around them, forward or backward. Everywhere else the heads
    are split and `scaled_dot_product_attention` does the work."""
    q, k, v = raw(q), raw(k), raw(v)
    fused = k is None
    b, sq = q.shape[0], q.shape[1]
    e = q.shape[-1] // 3 if fused else q.shape[-1]
    sk = sq if fused else k.shape[1]
    hd = e // num_heads
    key_bias = _per_key_bias(attn_mask, b, sk)
    _, mesh, direct = _flash_plan_for(
        attn_mask, key_bias, dropout_p, False, hd, num_heads, sq, sk, b,
        token_major=True)
    if not direct:
        if fused:
            q, k, v = jnp.moveaxis(q.reshape(b, sq, 3, num_heads, hd), 2, 0)
        q, k, v = (x.reshape(b, -1, num_heads, hd).transpose(0, 2, 1, 3)
                   for x in (q, k, v))
        out, _ = scaled_dot_product_attention.__raw_fn__(
            q, k, v, attn_mask, dropout_p, is_causal, scale, key=key)
        return out.transpose(0, 2, 1, 3).reshape(b, sq, e)
    from .pallas.flash_attention import flash_attention_token_major
    if mesh is None:
        return flash_attention_token_major(q, k, v, key_bias, num_heads,
                                           is_causal, scale)
    # batch over dp, heads over mp, per device; of the fused projection
    # each device holds its heads' q, k and v
    heads = num_heads // mesh.shape["mp"]
    lanes = PartitionSpec("dp", None, "mp")
    if fused:
        args = (q.reshape(b, sq, 3, e),)
        specs = (PartitionSpec("dp", None, None, "mp"),)
    else:
        args, specs = (q, k, v), (lanes,) * 3
    if key_bias is not None:
        args += (key_bias,)
        specs += (PartitionSpec("dp", None),)

    def per_device(*xs):
        bias = xs[-1] if key_bias is not None else None
        if fused:
            return flash_attention_token_major(
                xs[0].reshape(xs[0].shape[:2] + (-1,)), None, None, bias,
                heads, is_causal, scale)
        return flash_attention_token_major(*xs[:3], bias, heads, is_causal,
                                           scale)

    return jax.shard_map(per_device, mesh=mesh, in_specs=specs,
                         out_specs=lanes, check_vma=False)(*args)


def _is_quantized_kv(kv):
    """Duck-typed inference.kv_quant.QuantizedKV check (no import — the
    ops layer must not pull the inference package at module scope)."""
    return hasattr(kv, "codes") and hasattr(kv, "scales")


def paged_decode_attention(q, k_blocks, v_blocks, block_tables, ctx_lens,
                           scale=None, mesh=None, layer=None):
    """Single-token decode attention over a PAGED KV cache (the
    gather-by-block-table read half of inference/kv_cache.py).

    q: [B, H, Dh] — one new token per sequence.
    k_blocks/v_blocks: the pool STACK [L, N, BS, H*Dh] (a token's
        heads side by side: `inference.kv_cache`) with `layer` the
        layer to attend — what every decode program passes: the Pallas
        kernel reads the stack where it lies and the XLA path gathers
        from it, so no layer's slice is ever materialized — or ONE
        layer's [N, BS, H, Dh] with layer=None; OR a `QuantizedKV`
        (int8 codes like the blocks, per-vector scales [L, N, BS, H]
        or [N, BS, H]) for an int8 pool — dequantization happens
        INSIDE the kernel/contraction (the scales fold into the score
        and output einsums), so no bf16 copy of the cache ever
        materializes in HBM.
    block_tables: [B, M] int32 — block ids per sequence, 0-padded.
    ctx_lens: [B] int32 — tokens (cache positions) visible to each query;
        everything at position >= ctx_len is masked by LENGTH, never by
        pad-token value.

    mesh: the device mesh of a sharded engine whose pool is split on
        heads over `mp` (see `_paged_kernel`); None on one device.

    Returns [B, H, Dh] in q's dtype. Dispatches to the Pallas decode
    kernel on TPU when shapes allow (`paged_attention_path`: head_dim
    lane-sized, block_size a lane multiple, per-device heads
    sublane-aligned); otherwise runs the XLA gather path, which
    materializes the [B, M*BS] gathered keys — correct everywhere, but
    it reads the padded table width, where the kernel's grid is the
    launch's live (row, block) pairs: it fetches a row's live blocks and
    nothing else, and names no pad block (a row of context 0 takes one
    step, in which it writes its zeros)."""
    quant = _is_quantized_kv(k_blocks)
    kcodes = k_blocks.codes if quant else k_blocks
    B, H, Dh = q.shape
    BS = _block_size(kcodes, layer)
    Hkv = _pool_heads(kcodes, layer, Dh)
    M = block_tables.shape[1]
    sc = (Dh ** -0.5) if scale is None else scale
    if paged_attention_path(Dh, BS, H, mesh=mesh, kv_heads=Hkv) != "xla":
        from .pallas.unified_attention import paged_decode_attention_kernel
        return _paged_kernel(paged_decode_attention_kernel, mesh, q,
                             k_blocks, v_blocks, layer, block_tables,
                             ctx_lens, scale=float(sc))
    gather = _pool_gather(layer, block_tables)
    if quant:
        # gather CODES + per-vector scales; the int8->dt convert fuses
        # into the einsum operand pipeline (the weight-dot ::w8c trick)
        # and the scale vector multiplies the SCORE/PROB tensors — the
        # cache is consumed as raw int8
        k = gather(kcodes).reshape(B, M * BS, H, Dh).transpose(0, 2, 1, 3)
        v = gather(v_blocks.codes).reshape(B, M * BS, H, Dh) \
            .transpose(0, 2, 1, 3)
        ks = jnp.transpose(gather(k_blocks.scales)
                           .reshape(B, M * BS, H), (0, 2, 1))  # [B,H,C]
        vs = jnp.transpose(gather(v_blocks.scales)
                           .reshape(B, M * BS, H), (0, 2, 1))
        s = jnp.einsum("bhd,bhsd->bhs", q, k.astype(q.dtype)) \
            .astype(jnp.float32) * ks.astype(jnp.float32) * sc
        valid = jnp.arange(M * BS)[None, :] < ctx_lens[:, None]
        s = jnp.where(valid[:, None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhs,bhsd->bhd", w * vs.astype(q.dtype),
                          v.astype(q.dtype))
    # XLA gather path: [B, M, BS, Hkv*Dh] -> [B, H, M*BS, Dh]
    k, v = (_per_query_head(
        gather(blocks).reshape(B, M * BS, Hkv, Dh).transpose(0, 2, 1, 3),
        H, 1) for blocks in (k_blocks, v_blocks))
    s = jnp.einsum("bhd,bhsd->bhs", q, k).astype(jnp.float32) * sc
    valid = jnp.arange(M * BS)[None, :] < ctx_lens[:, None]  # [B, M*BS]
    s = jnp.where(valid[:, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bhsd->bhd", w, v)


def ragged_prefill_attention(q, k_blocks, v_blocks, block_tables, seg, pos,
                             scale=None, allow_pallas=True, mesh=None,
                             layer=None):
    """Packed ragged prefill attention over a PAGED KV cache: every token
    of a token-packed multi-sequence stream attends its OWN sequence's
    cache positions [0, pos] — both the K/V this chunk just wrote and
    whatever earlier chunks of the same prompt left in the paged blocks,
    so chunked prefill carries no extra state.

    q: [T, H, Dh] — packed query stream (several prompt chunks).
    k_blocks/v_blocks: the pool stack [L, N, BS, H*Dh] and `layer`,
        or ONE layer's [N, BS, H, Dh] and layer=None, as in
        `paged_decode_attention`; OR `QuantizedKV` (int8 codes +
        per-vector scales) for an int8 pool — scales fold into the
        score/output contractions, the cache streams as raw int8.
    block_tables: [B, M] int32 — block ids per slot row, 0-padded.
    seg: [T] int32 — slot row (index into block_tables) of each token.
    pos: [T] int32 — absolute cache position of each token; -1 marks a
        packing-pad token (its output is garbage the caller discards).

    Returns [T, H, Dh] in q's dtype. On TPU with aligned shapes this
    dispatches to the Pallas kernel (ops/pallas/unified_attention.py),
    which additionally requires the PACKING CONTRACT: each segment's
    packed region starts at a multiple of Q_TILE=128, so one query tile
    never mixes segments.

    The (seg, pos) row metadata defines the segment-causal masking
    contract shared by the Pallas kernels and the sequence-parallel
    serving seams (`serving_dist.sp_attention` splits this exact key
    set into a resident-pool pass and a rotating fresh-block pass; see
    ops/pallas/unified_attention.py for the normative statement).

    The XLA fallback gathers ONE [B, M*BS, ...] copy per slot ROW
    (never per token — a [T, M*BS, ...] materialization measured 8x
    slower than the sequential prefill at bench shapes), scores every
    query against every row's cache HEAD-MAJOR (one transpose per
    call instead of a relayout inside every batched matmul — a
    measured 3.4x on the same shapes), and applies the row-AND-position
    mask before a joint softmax over all rows — exactly the per-row
    softmax, because only the query's own row has unmasked columns.

    mesh: as in `paged_decode_attention`.

    allow_pallas=False forces the XLA path even on TPU: the
    sequence-parallel packed trunk (long-context round) runs with
    sp-sharded queries under GSPMD, where a pallas_call is an opaque
    per-device program — the sp-local stream-kernel wiring (tile_base
    shard offsets, ops/pallas/unified_attention.py) is the ROADMAP
    follow-up."""
    quant = _is_quantized_kv(k_blocks)
    kcodes = k_blocks.codes if quant else k_blocks
    T, H, Dh = q.shape
    BS = _block_size(kcodes, layer)
    Hkv = _pool_heads(kcodes, layer, Dh)
    B, M = block_tables.shape
    sc = (Dh ** -0.5) if scale is None else scale
    if allow_pallas and paged_attention_path(Dh, BS, H, T, mesh,
                                             Hkv) != "xla":
        from .pallas.unified_attention import (
            Q_TILE, unified_ragged_attention_kernel)
        return _paged_kernel(unified_ragged_attention_kernel, mesh, q,
                             k_blocks, v_blocks, layer, block_tables,
                             seg[::Q_TILE], pos[::Q_TILE], scale=float(sc))
    # row-gather, head-major, joint-row softmax
    gather = _pool_gather(layer, block_tables)
    if quant:
        k = gather(kcodes).reshape(B, M * BS, H, Dh) \
            .transpose(2, 0, 1, 3).astype(q.dtype)        # [H, B, C, Dh]
        v = gather(v_blocks.codes).reshape(B, M * BS, H, Dh) \
            .transpose(2, 0, 1, 3).astype(q.dtype)
        ks = gather(k_blocks.scales).reshape(B, M * BS, H) \
            .transpose(2, 0, 1)                           # [H, B, C]
        vs = gather(v_blocks.scales).reshape(B, M * BS, H) \
            .transpose(2, 0, 1)
    else:
        k, v = (_per_query_head(
            gather(blocks).reshape(B, M * BS, Hkv, Dh)
            .transpose(2, 0, 1, 3), H, 0)                 # [H, B, C, Dh]
            for blocks in (k_blocks, v_blocks))
        ks = vs = None
    qh = q.transpose(1, 0, 2)                             # [H, T, Dh]
    s = jnp.einsum("htd,hbcd->htbc", qh, k).astype(jnp.float32) * sc
    if quant:  # per-KEY scale rides the score tensor post-contraction
        s = s * ks[:, None].astype(jnp.float32)
    own = seg[:, None] == jnp.arange(B)[None, :]          # [T, B]
    ok = jnp.arange(M * BS)[None, :] <= pos[:, None]      # [T, M*BS]
    mask = own[:, :, None] & ok[:, None, :]               # [T, B, M*BS]
    s = jnp.where(mask[None], s, -1e30)
    w = jax.nn.softmax(
        s.reshape(H, T, B * M * BS), axis=-1
    ).reshape(H, T, B, M * BS).astype(q.dtype)
    if quant:  # per-VALUE scale rides the prob tensor
        w = w * vs[:, None].astype(q.dtype)
    return jnp.einsum("htbc,hbcd->htd", w, v).transpose(1, 0, 2)


def unified_stream_attention(q, k_blocks, v_blocks, block_tables, seg,
                             pos, scale=None, mesh=None, layer=None):
    """Unified serving-round attention (one-kernel round, r16): score a
    single packed token stream containing MIXED prefill chunks, plain
    decode rows and speculative verify regions in one launch.

    The insight of the merge (Ragged Paged Attention direction) is
    that the segment-causal contract already generalizes all three row
    kinds: a prefill chunk is n stream tokens at positions
    start..start+n-1, a decode row is 1 token at its write position,
    and a verify region is [last_token, draft_1..k] — in every case
    token t attends exactly its own sequence's cache positions
    [0, pos[t]].  So the unified op IS `ragged_prefill_attention` on
    the round's combined stream: the Pallas stream kernel
    (ops/pallas/unified_attention.py) on TPU, the row-gathered
    head-major XLA fallback elsewhere.  This alias exists as the
    documented entry point of the unified decode program
    (`nn.decode` `unified_round`); the argument contract is exactly
    `ragged_prefill_attention`'s."""
    return ragged_prefill_attention(q, k_blocks, v_blocks, block_tables,
                                    seg, pos, scale=scale, mesh=mesh,
                                    layer=layer)


def verify_window_attention(q, k_blocks, v_blocks, block_tables, pos,
                            scale=None, mesh=None, layer=None):
    """Speculative-verification attention over a PAGED KV cache: a
    DENSE [P, W] window of queries per plan row (each row's last
    emitted token + its draft tokens, W pinned by the verify plan),
    every query attending its OWN row's cache positions [0, pos].

    q: [P, W, H, Dh]; k_blocks/v_blocks: the pool stack
    [L, N, BS, H*Dh] and `layer`, or one layer's [N, BS, H, Dh] and
    layer=None, as in `paged_decode_attention`, or `QuantizedKV`
    codes+scales for an int8 pool (scales fold into the
    contractions); block_tables: [P, M] int32 0-padded; pos:
    [P, W] int32
    absolute cache positions (-1 = region pad; its output is finite
    garbage no readout index touches).

    Semantically this is `ragged_prefill_attention` on the flattened
    [P*W] stream — and on TPU with aligned shapes it IS that call, so
    the verify dispatch rides the same Pallas segment-causal kernel as
    packed prefill. Off TPU the dense layout lets the fallback score
    each row's window against ONLY its own cache ([P, W, C] scores
    instead of the packed fallback's [P*W, P, C] cross-row
    materialization) — the verify dispatch runs every scheduler round,
    and the P-fold waste measurably capped the speculation speedup on
    CPU."""
    quant = _is_quantized_kv(k_blocks)
    kcodes = k_blocks.codes if quant else k_blocks
    P, W, H, Dh = q.shape
    BS = _block_size(kcodes, layer)
    M = block_tables.shape[1]
    sc = (Dh ** -0.5) if scale is None else scale
    if _on_tpu():
        seg = jnp.repeat(jnp.arange(P, dtype=jnp.int32), W)
        return ragged_prefill_attention(
            q.reshape(P * W, H, Dh), k_blocks, v_blocks, block_tables,
            seg, pos.reshape(-1), scale=sc, mesh=mesh,
            layer=layer).reshape(P, W, H, Dh)
    gather = _pool_gather(layer, block_tables)
    if quant:
        k = gather(kcodes).reshape(P, M * BS, H, Dh).astype(q.dtype)
        v = gather(v_blocks.codes).reshape(P, M * BS, H, Dh) \
            .astype(q.dtype)
        ks = gather(k_blocks.scales).reshape(P, M * BS, H) \
            .transpose(0, 2, 1)[:, :, None, :]            # [P, H, 1, C]
        vs = gather(v_blocks.scales).reshape(P, M * BS, H) \
            .transpose(0, 2, 1)[:, :, None, :]
    else:
        k = gather(k_blocks).reshape(P, M * BS, H, Dh)
        v = gather(v_blocks).reshape(P, M * BS, H, Dh)
        ks = vs = None
    s = jnp.einsum("pwhd,pchd->phwc", q, k).astype(jnp.float32) * sc
    if quant:
        s = s * ks.astype(jnp.float32)
    ok = jnp.arange(M * BS)[None, None, :] <= pos[:, :, None]
    s = jnp.where(ok[:, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if quant:
        w = w * vs.astype(q.dtype)
    return jnp.einsum("phwc,pchd->pwhd", w, v)


@defop()
def fused_multi_head_attention(x, qkv_weight, qkv_bias, out_weight, out_bias,
                               num_heads, attn_mask=None, dropout_p=0.0,
                               is_causal=False):
    """Fused QKV projection + attention + output projection (ref:
    fused_attention_op.cc). One einsum chain; XLA fuses the bias/reshape glue.

    x: [B, S, E]; qkv_weight: [E, 3E]; out_weight: [E, E].
    """
    b, s, e = x.shape
    d = e // num_heads
    qkv = jnp.einsum("bse,ef->bsf", x, qkv_weight)
    if qkv_bias is not None:
        qkv = qkv + qkv_bias
    qkv = qkv.reshape(b, s, 3, num_heads, d)
    q, k, v = (jnp.moveaxis(qkv[:, :, i], 2, 1) for i in range(3))
    out, _ = _xla_attention(q, k, v, attn_mask, None, is_causal)
    out = jnp.moveaxis(out, 1, 2).reshape(b, s, e)
    out = jnp.einsum("bse,ef->bsf", out, out_weight)
    if out_bias is not None:
        out = out + out_bias
    return out


@defop()
def fused_feedforward(x, w1, b1, w2, b2, activation="gelu"):
    """Fused FFN (ref: fused_feedforward_op) — XLA fuses act into the matmul."""
    h = jnp.einsum("bse,ef->bsf", x, w1)
    if b1 is not None:
        h = h + b1
    h = jax.nn.gelu(h) if activation == "gelu" else jax.nn.relu(h)
    out = jnp.einsum("bsf,fe->bse", h, w2)
    if b2 is not None:
        out = out + b2
    return out
