"""Flash attention — Pallas TPU kernel.

Replaces the reference's fused_attention CUDA op (north-star: "fused_attention
→ Pallas flash-attn"). Blockwise online-softmax: each grid step owns one Q
block in VMEM, streams K/V blocks from VMEM, and accumulates on the MXU in
f32 (inputs stay bf16 — the MXU multiplies bf16 natively and accumulates f32
via preferred_element_type; casting inputs to f32 would quarter the MXU rate
and double VMEM traffic). O(S) memory instead of the O(S²) score matrix.

Forward emits the per-row LSE so the backward (also Pallas) can recompute
probabilities blockwise without a second softmax pass — the standard
flash-attention training recipe (dq kernel + dkv kernel, delta = rowsum(dO·O)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import named_pallas_call

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _compiler_params(semantics):
    return {"compiler_params":
            pltpu.CompilerParams(dimension_semantics=semantics)}


LSE_LANES = 8  # lse/delta rows are broadcast over 8 sublanes to satisfy
               # the TPU (8, 128)-tile layout for non-vector shapes


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, nk,
                has_bias=False):
    if has_bias:
        bias_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        bias_ref = None
    # Streaming layout: grid = (b*h, nq, nk), K/V blocks arrive one per grid
    # step on the innermost ("arbitrary") dim — nothing larger than a block
    # is ever resident in VMEM, so sequence length is unbounded. Online
    # softmax state (acc, m, l) is carried in VMEM scratch across k steps.
    # q_ref: [bq, d]; k_ref/v_ref: [bk, d]; lse_ref: [bq, LSE_LANES].
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: tiles strictly above the diagonal contribute nothing; tiles
    # strictly below need no mask — only diagonal-straddling tiles pay for
    # the iota+select (at S=1024/b=512 that's 2 of every 3 executed tiles,
    # at long S a vanishing fraction)
    run = (ki * bk < (qi + 1) * bq) if causal else (ki >= 0)
    diag = ((ki + 1) * bk > qi * bq) if causal else False

    def _compute(apply_mask):
        q = q_ref[:]  # keep input dtype — bf16 feeds the MXU at full rate
        k = k_ref[:]
        v = v_ref[:]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:
            s = s * scale
        if bias_ref is not None:
            # per-key additive bias (padding masks, ALiBi-style): one
            # [8, bk] sublane-broadcast tile per k block (TPU blocks need
            # 8x128-aligned shapes); row 0 broadcasts over the q rows
            s = s + bias_ref[0:1, :].astype(jnp.float32)
        if apply_mask:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        @pl.when(run & diag)
        def _compute_diag():
            _compute(True)

        @pl.when(run & jnp.logical_not(diag))
        def _compute_full():
            _compute(False)
    else:
        @pl.when(run)
        def _compute_all():
            _compute(False)

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[:] = jnp.broadcast_to(m_ref[:, 0:1] + jnp.log(l),
                                      lse_ref.shape)


def _divisor_block(size, block):
    """Largest block <= `block` that divides `size` — 128-aligned when
    possible (TPU lane width); sub-128 blocks only appear in interpret-mode
    tests with tiny shapes."""
    b = min(block, size)
    if b >= 128 and size % 128 == 0:
        b -= b % 128
        while size % b:
            b -= 128
    else:
        while size % b:
            b -= 1
    return b


def _block_sizes(sq, sk, block_q, block_k):
    bq = _divisor_block(sq, block_q)
    bk = _divisor_block(sk, block_k)
    # keep the f32 score block under ~2MB of VMEM (only binds when a caller
    # passes blocks larger than the 512 defaults)
    while bq > 128 and bq * bk * 4 > 2 * 1024 * 1024:
        bq = _divisor_block(sq, bq // 2)
    return bq, bk


def _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k, interpret,
                   bias=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    q3 = q.reshape(b * h, sq, d)
    k3 = k.reshape(b * h, sk, d)
    v3 = v.reshape(b * h, sk, d)
    nk = sk // bk
    grid = (b * h, sq // bq, nk)
    has_bias = bias is not None
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               nk=nk, has_bias=has_bias)
    mem_kwargs = {}
    if not interpret:
        mem_kwargs = {"memory_space": pltpu.VMEM}
    in_specs = [
        pl.BlockSpec((None, bq, d), lambda i, j, kk: (i, j, 0),
                     **mem_kwargs),
        pl.BlockSpec((None, bk, d), lambda i, j, kk: (i, kk, 0),
                     **mem_kwargs),
        pl.BlockSpec((None, bk, d), lambda i, j, kk: (i, kk, 0),
                     **mem_kwargs),
    ]
    operands = [q3, k3, v3]
    if has_bias:
        # per-key additive bias, pre-tiled to [b*h, sk] f32
        in_specs.append(pl.BlockSpec((None, 8, bk),
                                     lambda i, j, kk: (i, 0, kk),
                                     **mem_kwargs))
        operands.append(bias)
    out, lse = named_pallas_call(
        "flash_fwd",
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, sq, LSE_LANES), jnp.float32)),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((None, bq, d), lambda i, j, kk: (i, j, 0),
                         **mem_kwargs),
            pl.BlockSpec((None, bq, LSE_LANES), lambda i, j, kk: (i, j, 0),
                         **mem_kwargs),
        ),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, LSE_LANES), jnp.float32),
                        pltpu.VMEM((bq, LSE_LANES), jnp.float32)],
        interpret=interpret,
        **_compiler_params(("parallel", "parallel", "arbitrary")),
    )(*operands)
    return out.reshape(b, h, sq, d), lse


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   scale, causal, nk, has_bias=False):
    if has_bias:
        bias_ref, dq_ref, dq_acc = refs
    else:
        dq_ref, dq_acc = refs
        bias_ref = None
    # Streaming: grid = (b*h, nq, nk); dq_i = scale * sum_j ds_ij @ k_j
    # accumulated in VMEM scratch across the k steps, flushed on the last.
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (ki * bk < (qi + 1) * bq) if causal else (ki >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:, 0:1]
        delta = delta_ref[:, 0:1]
        k = k_ref[:]
        v = v_ref[:]
        p, ds = _tile_p_ds(q, k, v, do, lse, delta, scale, causal,
                           qi * bq, ki * bk,
                           None if bias_ref is None else bias_ref[:])
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        acc = dq_acc[:] * scale if scale != 1.0 else dq_acc[:]
        dq_ref[:] = acc.astype(dq_ref.dtype)


def _tile_p_ds(q, k, v, do, lse, delta, scale, causal, q_pos0, k_pos0,
               bias=None):
    """Shared backward tile math: recompute probabilities from the stored LSE
    and form ds = p * (dO·v^T - delta). Used by all three backward kernels so
    masking/lse/dtype fixes land in exactly one place. Returns (p, ds) with
    p in the dO dtype and ds in the k dtype (MXU-ready)."""
    bq, bk = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if bias is not None:
        s = s + bias[0:1, :].astype(jnp.float32)
    if causal:
        q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = (p * (dp - delta)).astype(k.dtype)
    return p.astype(do.dtype), ds


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                    scale, causal, nq, has_bias=False):
    if has_bias:
        (bias_ref, dk_ref, dv_ref, dbias_ref,
         dk_acc, dv_acc, db_acc) = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
        bias_ref = dbias_ref = db_acc = None
    # Streaming: grid = (b*h, nk, nq); Q/dO blocks arrive on the innermost
    # dim; dk_j / dv_j accumulate in VMEM scratch, flushed on the last step.
    bk, d = k_ref.shape
    bq = q_ref.shape[0]
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if db_acc is not None:
            db_acc[:] = jnp.zeros_like(db_acc)

    # causal: q blocks strictly before the diagonal see nothing of this k blk
    run = ((qi + 1) * bq > ki * bk) if causal else (qi >= 0)

    @pl.when(run)
    def _compute():
        k = k_ref[:]
        v = v_ref[:]
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:, 0:1]
        delta = delta_ref[:, 0:1]
        p, ds = _tile_p_ds(q, k, v, do, lse, delta, scale, causal,
                           qi * bq, ki * bk,
                           None if bias_ref is None else bias_ref[:])
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if db_acc is not None:
            # dL/dbias_k = sum over q rows of ds (bias enters s additively,
            # after the scale) — accumulated across streamed q blocks
            col = jnp.sum(ds.astype(jnp.float32), axis=0)
            db_acc[:] += jnp.broadcast_to(col[None, :], db_acc.shape)

    @pl.when(qi == nq - 1)
    def _flush():
        acc = dk_acc[:] * scale if scale != 1.0 else dk_acc[:]
        dk_ref[:] = acc.astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)
        if db_acc is not None:
            dbias_ref[:] = db_acc[:]


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *refs, scale, causal, block_q, sq, nk,
                      has_bias=False):
    if has_bias:
        bias_ref, dq_ref, dk_ref, dv_ref, dbias_ref, dq_acc = refs
    else:
        dq_ref, dk_ref, dv_ref, dq_acc = refs
        bias_ref = dbias_ref = None
    """One-pass backward: grid over k-blocks (sequential per (b,h) row), q
    streamed inside. Computes p = exp(s - lse) ONCE per (i,j) tile and feeds
    all three grads: dv_j += p^T dO_i, dk_j += ds^T q_i, and dq_i accumulated
    across j in a VMEM scratch flushed on the last k-block. Versus separate
    dq/dkv kernels this halves the exp work and drops two of seven dots."""
    bk, d = k_ref.shape
    ki = pl.program_id(1)
    k = k_ref[:]
    v = v_ref[:]

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    nq = sq // block_q
    first_q = (ki * bk) // block_q if causal else 0

    def body(i, carry):
        dk_acc, dv_acc, db_acc = carry
        q = q_ref[pl.ds(i * block_q, block_q), :]
        do = do_ref[pl.ds(i * block_q, block_q), :]
        lse = lse_ref[pl.ds(i * block_q, block_q), 0:1]
        delta = delta_ref[pl.ds(i * block_q, block_q), 0:1]
        p, ds = _tile_p_ds(q, k, v, do, lse, delta, scale, causal,
                           i * block_q, ki * bk,
                           None if bias_ref is None else bias_ref[:])
        dv_acc = dv_acc + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dbias_ref is not None:
            col = jnp.sum(ds.astype(jnp.float32), axis=0, keepdims=True)
            db_acc = db_acc + jnp.broadcast_to(col, db_acc.shape)
        dq_tile = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_acc[pl.ds(i * block_q, block_q), :] += dq_tile
        return dk_acc, dv_acc, db_acc

    z = jnp.zeros((bk, d), jnp.float32)
    zb = jnp.zeros((8, bk), jnp.float32)
    dk_acc, dv_acc, db_acc = jax.lax.fori_loop(first_q, nq, body, (z, z, zb))
    dk_ref[:] = ((dk_acc * scale) if scale != 1.0 else dk_acc) \
        .astype(dk_ref.dtype)
    dv_ref[:] = dv_acc.astype(dv_ref.dtype)
    if dbias_ref is not None:
        # dL/dbias for this k block: sum of ds over all q rows
        dbias_ref[:] = db_acc

    @pl.when(ki == nk - 1)
    def _flush():
        acc = dq_acc[:] * scale if scale != 1.0 else dq_acc[:]
        dq_ref[:] = acc.astype(dq_ref.dtype)


def _delta_kernel(o_ref, do_ref, delta_ref):
    # delta = rowsum(dO * O), written pre-broadcast over LSE_LANES. Doing
    # this in Pallas instead of XLA matters: the minor-axis (d=64) reduce
    # plus the 8-lane broadcast measured 1.26ms/layer at GPT-2-small batch
    # 16 as an XLA fusion (~5x over the bandwidth bound, r4 per-op
    # profile); here it is one streaming pass at copy speed.
    d = jnp.sum(o_ref[...].astype(jnp.float32) *
                do_ref[...].astype(jnp.float32), axis=1, keepdims=True)
    delta_ref[...] = jnp.broadcast_to(d, (d.shape[0], LSE_LANES))


def _delta_rows(o3, do3, interpret):
    """[b*h, sq, d] x2 -> broadcast delta [b*h, sq, LSE_LANES] f32."""
    bh, sq, d = o3.shape
    bq = next((b for b in (512, 256, 128) if sq % b == 0), sq)
    mem_kwargs = {}
    if not interpret:
        mem_kwargs = {"memory_space": pltpu.VMEM}
    row = pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0), **mem_kwargs)
    out = pl.BlockSpec((None, bq, LSE_LANES), lambda i, j: (i, j, 0),
                       **mem_kwargs)
    return named_pallas_call(
        "flash_bwd_delta",
        _delta_kernel,
        out_shape=jax.ShapeDtypeStruct((bh, sq, LSE_LANES), jnp.float32),
        grid=(bh, sq // bq),
        in_specs=[row, row],
        out_specs=out,
        interpret=interpret,
        **_compiler_params(("parallel", "arbitrary")),
    )(o3, do3)


def _flash_bwd_fused(q, k, v, o, lse, g, scale, causal, block_q, block_k,
                     interpret, bias=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    q3, k3, v3 = (x.reshape(b * h, x.shape[2], d) for x in (q, k, v))
    do3 = g.reshape(b * h, sq, d)
    delta3 = _delta_rows(o.reshape(b * h, sq, d), do3, interpret)
    mem_kwargs = {}
    if not interpret:
        mem_kwargs = {"memory_space": pltpu.VMEM}
    scratch = [pltpu.VMEM((sq, d), jnp.float32)]

    qfull = pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0), **mem_kwargs)
    kcol = pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0), **mem_kwargs)
    vec_full = pl.BlockSpec((None, sq, LSE_LANES), lambda i, j: (i, 0, 0),
                            **mem_kwargs)
    in_specs = [qfull, kcol, kcol, qfull, vec_full, vec_full]
    operands = [q3, k3, v3, do3, lse, delta3]
    out_shape = [jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
                 jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                 jax.ShapeDtypeStruct((b * h, sk, d), v.dtype)]
    biascol = pl.BlockSpec((None, 8, bk), lambda i, j: (i, 0, j),
                           **mem_kwargs)
    out_specs = [qfull, kcol, kcol]
    if bias is not None:
        in_specs.append(biascol)
        operands.append(bias)
        out_shape.append(jax.ShapeDtypeStruct((b * h, 8, sk), jnp.float32))
        out_specs.append(biascol)
    outs = named_pallas_call(
        "flash_bwd_fused",
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=bq, sq=sq, nk=sk // bk,
                          has_bias=bias is not None),
        out_shape=tuple(out_shape),
        grid=(b * h, sk // bk),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        interpret=interpret,
        **_compiler_params(("parallel", "arbitrary")),
    )(*operands)
    dq, dk, dv = outs[:3]
    dbias3 = outs[3] if bias is not None else None
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d), dbias3)


def _flash_bwd(q, k, v, o, lse, g, scale, causal, block_q, block_k,
               interpret, bias=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = _block_sizes(sq, sk, block_q, block_k)
    q3, k3, v3 = (x.reshape(b * h, x.shape[2], d) for x in (q, k, v))
    do3 = g.reshape(b * h, sq, d)
    lse3 = lse  # already [b*h, sq, LSE_LANES]
    delta3 = _delta_rows(o.reshape(b * h, sq, d), do3, interpret)
    mem_kwargs = {}
    if not interpret:
        mem_kwargs = {"memory_space": pltpu.VMEM}

    nq, nk = sq // bq, sk // bk
    # dq pass: grid (bh, nq, nk) — q row pinned per j, k/v streamed on kk
    qrow = pl.BlockSpec((None, bq, d), lambda i, j, kk: (i, j, 0),
                        **mem_kwargs)
    kstream = pl.BlockSpec((None, bk, d), lambda i, j, kk: (i, kk, 0),
                           **mem_kwargs)
    vec_row = pl.BlockSpec((None, bq, LSE_LANES), lambda i, j, kk: (i, j, 0),
                           **mem_kwargs)
    dq_specs = [qrow, kstream, kstream, qrow, vec_row, vec_row]
    dq_ops = [q3, k3, v3, do3, lse3, delta3]
    if bias is not None:
        dq_specs.append(pl.BlockSpec((None, 8, bk),
                                      lambda i, j, kk: (i, 0, kk),
                                      **mem_kwargs))
        dq_ops.append(bias)
    dq = named_pallas_call(
        "flash_bwd_dq",
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, nk=nk,
                          has_bias=bias is not None),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, nq, nk),
        in_specs=dq_specs,
        out_specs=qrow,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        **_compiler_params(("parallel", "parallel", "arbitrary")),
    )(*dq_ops)

    # dkv pass: grid (bh, nk, nq) — k/v column pinned per j, q/dO streamed
    kcol = pl.BlockSpec((None, bk, d), lambda i, j, qq: (i, j, 0),
                        **mem_kwargs)
    qstream = pl.BlockSpec((None, bq, d), lambda i, j, qq: (i, qq, 0),
                           **mem_kwargs)
    vec_stream = pl.BlockSpec((None, bq, LSE_LANES),
                              lambda i, j, qq: (i, qq, 0), **mem_kwargs)
    dkv_specs = [qstream, kcol, kcol, qstream, vec_stream, vec_stream]
    dkv_ops = [q3, k3, v3, do3, lse3, delta3]
    dkv_out_shape = [jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                     jax.ShapeDtypeStruct((b * h, sk, d), v.dtype)]
    dkv_out_specs = [kcol, kcol]
    dkv_scratch = [pltpu.VMEM((bk, d), jnp.float32),
                   pltpu.VMEM((bk, d), jnp.float32)]
    if bias is not None:
        biascol = pl.BlockSpec((None, 8, bk), lambda i, j, qq: (i, 0, j),
                               **mem_kwargs)
        dkv_specs.append(biascol)
        dkv_ops.append(bias)
        dkv_out_shape.append(
            jax.ShapeDtypeStruct((b * h, 8, sk), jnp.float32))
        dkv_out_specs.append(biascol)
        dkv_scratch.append(pltpu.VMEM((8, bk), jnp.float32))
    outs = named_pallas_call(
        "flash_bwd_dkv",
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, nq=nq,
                          has_bias=bias is not None),
        out_shape=tuple(dkv_out_shape),
        grid=(b * h, nk, nq),
        in_specs=dkv_specs,
        out_specs=tuple(dkv_out_specs),
        scratch_shapes=dkv_scratch,
        interpret=interpret,
        **_compiler_params(("parallel", "parallel", "arbitrary")),
    )(*dkv_ops)
    dk, dv = outs[:2]
    dbias3 = outs[2] if bias is not None else None

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d), dbias3)


def _reference_attention(q, k, v, scale, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    """q,k,v: [B,H,S,D]. S must be a multiple of 128."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, _ = _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k,
                            interpret)
    return out


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k,
                              interpret)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # Fused single-pass backward VMEM residency per (b,h) grid row:
    # dq_acc scratch (sq*d f32) + q, dO inputs and dq output window
    # (sq*d bf16 each) + lse/delta (~sq*8 f32 each) + double-buffered
    # k/v/dk/dv column blocks. Budget the sq-proportional part (~10 bytes
    # per sq*d element) at 8MB of the ~16MB core; larger shapes take the
    # two-kernel path whose dkv pass pins only q/dO (no f32 accumulator).
    if q.shape[2] * q.shape[3] * 10 <= 8 * 1024 * 1024:
        return _flash_bwd_fused(q, k, v, out, lse, g, scale, causal, block_q,
                                block_k, interpret)[:3]
    return _flash_bwd(q, k, v, out, lse, g, scale, causal, block_q, block_k,
                      interpret)[:3]


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def _tile_bias(bias, b, h):
    """[B, Sk] f32 -> [b*h, 8, Sk]: head-tiled with an 8-sublane broadcast
    so the per-k-block tile is a TPU-aligned [8, bk] block."""
    sk = bias.shape[-1]
    return jnp.broadcast_to(bias.astype(jnp.float32)[:, None, None, :],
                            (b, h, 8, sk)).reshape(b * h, 8, sk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention_bias(q, k, v, bias, causal=False, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=False):
    """Flash attention with a PER-KEY additive bias [B, Sk] f32 — the
    [B,1,1,S] additive-mask form BERT-class encoders build (padding in
    any pattern, per-key score offsets). Per-QUERY-relative biases
    (ALiBi's -m*|q-k|) are NOT expressible per-key and take the XLA
    path. The bias is tiled over heads and streamed to the kernels one
    k-block at a time; its cotangent is the true per-key gradient
    (sum of dS over q rows and heads, accumulated in the backward
    kernels), so trainable biases match the XLA path's grad."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bias3 = _tile_bias(bias, q.shape[0], q.shape[1])
    out, _ = _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k,
                            interpret, bias3)
    return out


def _fab_fwd(q, k, v, bias, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bias3 = _tile_bias(bias, q.shape[0], q.shape[1])
    out, lse = _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k,
                              interpret, bias3)
    return out, (q, k, v, bias, bias3, out, lse)


def _fab_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, bias, bias3, out, lse = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if q.shape[2] * q.shape[3] * 10 <= 8 * 1024 * 1024:
        dq, dk, dv, db3 = _flash_bwd_fused(q, k, v, out, lse, g, scale,
                                           causal, block_q, block_k,
                                           interpret, bias3)
    else:
        dq, dk, dv, db3 = _flash_bwd(q, k, v, out, lse, g, scale, causal,
                                     block_q, block_k, interpret, bias3)
    # kernels emit per-(b,h) column sums [b*h, 8, sk] (8 identical sublane
    # rows); the [B, Sk] bias broadcast over heads, so its cotangent sums
    # over h. This is the TRUE gradient — a trainable per-key bias (e.g.
    # learned ALiBi-style offsets) now matches the XLA path's grad.
    b, h = q.shape[0], q.shape[1]
    sk = k.shape[2]
    dbias = db3.reshape(b, h, 8, sk)[:, :, 0, :].sum(axis=1)
    if bias.shape[0] == 1 and b > 1:  # broadcast batch: sum its cotangent
        dbias = dbias.sum(axis=0, keepdims=True)
    return dq, dk, dv, dbias.astype(bias.dtype)


flash_attention_bias.defvjp(_fab_fwd, _fab_bwd)
