"""Flash attention — Pallas TPU kernels, token-major.

Replaces the reference's fused_attention CUDA op (north-star: "fused_attention
→ Pallas flash-attn"). Blockwise online-softmax: each grid step owns one Q
block in VMEM, streams K/V blocks from VMEM, and accumulates on the MXU in
f32 (inputs stay bf16 — the MXU multiplies bf16 natively and accumulates f32
via preferred_element_type; casting inputs to f32 would quarter the MXU rate
and double VMEM traffic). O(S) memory instead of the O(S²) score matrix.

Forward emits the per-row LSE so the backward (also Pallas) can recompute
probabilities blockwise without a second softmax pass — the standard
flash-attention training recipe (delta = rowsum(dO·O), then one fused
dq/dk/dv kernel, or a dq kernel + a dkv kernel at long sequences).

Layout. Every kernel takes q, k, v, dO and gives o, dq, dk, dv TOKEN-MAJOR,
[B, S, H*Dh]: the layout in which the projection GEMMs write them and read
their gradients, so no relayout pass stands around a launch. A block is
(rows, W) with W = max(128, Dh) lanes: `heads_per_block(Dh)` = 128 // Dh
heads side by side (two at Dh = 64, four at 32, one at 128 and 256), and the
grid's head axis steps over blocks of heads. Inside a body the heads of a
block are told apart by lane masks, without leaving VMEM and at no more MXU
passes than a head-major body (a contraction of 64 fills half of the
128-deep array either way, an output 64 wide half of its width):

  * scores of head i are `dot(x_i, y)` over all W lanes, where x_i is the
    resident operand with the other heads' lanes zeroed (the q tile in the
    forward and the dq kernel, the k/v tiles in the fused and dkv kernels);
  * `p_i @ v` (and every other [rows, W] product) is right in head i's
    lanes only: the block's result is one `where` chain over its heads, so
    acc, dq, dk, dv stay one lane-dense tile each.

q, k and v may be ONE array (GPT-2's fused projection [B, S, 3E]): the three
BlockSpecs then read it at lane-block offsets 0, E/W, 2E/W, and the fused
backward writes dq, dk, dv into ONE d(qkv) [B, S, 3E] (by DMA from VMEM
staging tiles: a concatenate of three arrays cost 1.7 ms a GPT-2-medium
step, PERF.md section 6, PR 33). `scale` costs no HBM pass: the resident q
tile is scaled in VMEM and dq where it is flushed.

lse and delta are [B, H/G, S, 128] float32: head i of a block broadcast over
lanes [i*128/G, (i+1)*128/G), one lane-dense tile a block of heads.

The head-major entries (`flash_attention`, `flash_attention_bias`,
`_flash_fwd_lse`, `_flash_bwd`) convert at their border — a transpose that
XLA cancels against the caller's own — and run the same bodies.
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
LANES = 128      # lane width of a block of heads, and of an lse/delta tile
BIAS_ROWS = 8    # a per-key bias row is broadcast over 8 sublanes to
                 # satisfy the TPU (8, 128)-tile layout


def heads_per_block(head_dim):
    """Heads side by side in one lane block of a token-major operand."""
    if head_dim % LANES == 0:
        return 1
    if LANES % head_dim:
        raise ValueError(f"head_dim {head_dim} neither divides nor is a "
                         f"multiple of the {LANES}-lane block")
    return LANES // head_dim


def _compiler_params(semantics):
    return {"compiler_params":
            pltpu.CompilerParams(dimension_semantics=semantics)}


def _vmem(interpret):
    return {} if interpret else {"memory_space": pltpu.VMEM}


def _lane_masks(width, seg, g):
    """[1, width] masks of the g heads of a block, `seg` lanes each (None
    for a block of one head: nothing to tell apart)."""
    if g == 1:
        return [None]
    head = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // seg
    return [head == i for i in range(g)]


def _only(mask, x):
    """x with the other heads' lanes zeroed."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _by_head(masks, tiles):
    """One tile whose lanes of head i are tiles[i]'s."""
    out = tiles[-1]
    for mask, tile in zip(masks[-2::-1], tiles[-2::-1]):
        out = jnp.where(mask, tile, out)
    return out


def _stat_tile(masks, cols, rows):
    """Per-head [rows, 1] columns -> the block's [rows, 128] lse/delta
    tile."""
    return _by_head(masks, [jnp.broadcast_to(c, (rows, LANES))
                            for c in cols])


def _causal_mask(s, q_pos0, k_pos0):
    bq, bk = s.shape
    q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _scaled(x, scale):
    if scale == 1.0:
        return x
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, nk, head_dim,
                has_bias=False):
    if has_bias:
        bias_ref, o_ref, lse_ref, qs_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, lse_ref, qs_ref, acc_ref, m_ref, l_ref = refs
        bias_ref = None
    # Streaming layout: grid = (b, head blocks, nq, nk), K/V blocks arrive
    # one per grid step on the innermost ("arbitrary") dim — nothing larger
    # than a block is ever resident in VMEM, so sequence length is
    # unbounded. Online softmax state (acc for the block, m and l per head)
    # is carried in VMEM scratch across k steps.
    # q_ref/o_ref: [bq, W]; k_ref/v_ref: [bk, W]; lse_ref: [bq, 128];
    # qs_ref: [G, bq, W], the scaled q tile by head, the others' lanes zero.
    g, bq, w = qs_ref.shape
    bk = k_ref.shape[0]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    masks = _lane_masks(w, head_dim, g)

    @pl.when(ki == 0)
    def _init():
        qs = _scaled(q_ref[:], scale)  # resident over the k steps
        for i in range(g):
            qs_ref[i] = _only(masks[i], qs)
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
        k = k_ref[:]  # keep input dtype — bf16 feeds the MXU at full rate
        v = v_ref[:]
        pv, alphas = [], []
        for i in range(g):
            s = jax.lax.dot_general(qs_ref[i], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if bias_ref is not None:
                # per-key additive bias (padding masks, ALiBi-style): one
                # [8, bk] sublane-broadcast tile per k block; row 0
                # broadcasts over the q rows
                s = s + bias_ref[0:1, :].astype(jnp.float32)
            if apply_mask:
                s = _causal_mask(s, qi * bq, ki * bk)
            m_prev = m_ref[i, :, 0:1]
            l_prev = l_ref[i, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv.append(jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            alphas.append(jnp.broadcast_to(alpha, (bq, w)))
            m_ref[i] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[i] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        acc_ref[:] = acc_ref[:] * _by_head(masks, alphas) \
            + _by_head(masks, pv)

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
        ls = [jnp.maximum(l_ref[i, :, 0:1], 1e-30) for i in range(g)]
        inv = _by_head(masks, [jnp.broadcast_to(1.0 / l, (bq, w))
                               for l in ls])
        o_ref[:] = (acc_ref[:] * inv).astype(o_ref.dtype)
        lse_ref[:] = _stat_tile(
            _lane_masks(LANES, LANES // g, g),
            [m_ref[i, :, 0:1] + jnp.log(l) for i, l in enumerate(ls)], bq)


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


class _Operands:
    """q, k, v of one call as the kernels read them: three token-major
    arrays, or ONE fused projection [B, S, 3E] read three times at lane-block
    offsets. `w` lanes a block of `g` heads, `nb` blocks."""

    def __init__(self, q, k, v, num_heads):
        self.fused = fused = k is None
        e = q.shape[-1] // 3 if fused else q.shape[-1]
        if e % num_heads:
            raise ValueError(f"{e} lanes do not hold {num_heads} heads")
        self.head_dim = e // num_heads
        self.g = heads_per_block(self.head_dim)
        if num_heads % self.g:
            raise ValueError(
                f"{num_heads} heads of {self.head_dim} do not fill blocks "
                f"of {self.g} heads")
        self.w = self.g * self.head_dim
        self.nb = num_heads // self.g
        self.e = e
        self.arrays = (q, q, q) if fused else (q, k, v)
        self.offsets = (0, self.nb, 2 * self.nb) if fused else (0, 0, 0)
        self.b, self.sq = q.shape[0], q.shape[1]
        self.sk = self.arrays[1].shape[1]
        self.dtype = q.dtype

    def spec(self, which, rows, index, interpret):
        """BlockSpec of q (0), k (1) or v (2): `rows` rows by one block of
        heads; `index(*grid ids)` -> (batch, row block, head block)."""
        off = self.offsets[which]

        def index_map(*ids):
            b, r, hb = index(*ids)
            return b, r, hb + off

        return pl.BlockSpec((None, rows, self.w), index_map,
                            **_vmem(interpret))


def _fwd(ops, scale, causal, block_q, block_k, interpret, bias=None):
    """-> (o [B, Sq, E], lse [B, H/G, Sq, 128])."""
    bq, bk = _block_sizes(ops.sq, ops.sk, block_q, block_k)
    nk = ops.sk // bk
    mem = _vmem(interpret)
    q_at = lambda b, hb, j, kk: (b, j, hb)
    k_at = lambda b, hb, j, kk: (b, kk, hb)
    in_specs = [ops.spec(0, bq, q_at, interpret),
                ops.spec(1, bk, k_at, interpret),
                ops.spec(2, bk, k_at, interpret)]
    operands = list(ops.arrays)
    if bias is not None:
        # per-key additive bias [B, 8, Sk] f32, shared by every head
        in_specs.append(pl.BlockSpec((None, BIAS_ROWS, bk),
                                     lambda b, hb, j, kk: (b, 0, kk), **mem))
        operands.append(bias)
    return named_pallas_call(
        "flash_fwd",
        functools.partial(_fwd_kernel, scale=scale, causal=causal, nk=nk,
                          head_dim=ops.head_dim, has_bias=bias is not None),
        out_shape=(jax.ShapeDtypeStruct((ops.b, ops.sq, ops.e), ops.dtype),
                   jax.ShapeDtypeStruct((ops.b, ops.nb, ops.sq, LANES),
                                        jnp.float32)),
        grid=(ops.b, ops.nb, ops.sq // bq, nk),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((None, bq, ops.w), q_at, **mem),
            pl.BlockSpec((None, None, bq, LANES),
                         lambda b, hb, j, kk: (b, hb, j, 0), **mem),
        ),
        scratch_shapes=[pltpu.VMEM((ops.g, bq, ops.w), ops.dtype),
                        pltpu.VMEM((bq, ops.w), jnp.float32),
                        pltpu.VMEM((ops.g, bq, LANES), jnp.float32),
                        pltpu.VMEM((ops.g, bq, LANES), jnp.float32)],
        interpret=interpret,
        **_compiler_params(("parallel", "parallel", "parallel",
                            "arbitrary")),
    )(*operands)


def _tile_p_ds(qs, k, v, do, lse, delta, causal, q_pos0, k_pos0, bias=None):
    """Shared backward tile math of ONE head: recompute probabilities from
    the stored LSE and form ds = p * (dO·v^T - delta). Used by all three
    backward kernels so masking/lse/dtype fixes land in exactly one place.
    `qs` is the scaled q tile as the forward used it; of qs/do and k/v one
    side has the other heads' lanes zeroed, so both contractions are this
    head's alone. Returns (p, ds) with p in the dO dtype and ds in the k
    dtype (MXU-ready)."""
    s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if bias is not None:
        s = s + bias[0:1, :].astype(jnp.float32)
    if causal:
        s = _causal_mask(s, q_pos0, k_pos0)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = (p * (dp - delta)).astype(k.dtype)
    return p.astype(do.dtype), ds


def _stat_col(ref, rows, i, g):
    """Head i's [rows, 1] column of an lse/delta tile."""
    lane = i * (LANES // g)
    return ref[rows, lane:lane + 1]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   scale, causal, nk, head_dim, has_bias=False):
    if has_bias:
        bias_ref, dq_ref, qs_ref, dos_ref, dq_acc = refs
    else:
        dq_ref, qs_ref, dos_ref, dq_acc = refs
        bias_ref = None
    # Streaming: grid = (b, head blocks, nq, nk); dq_i = scale * sum_j
    # ds_ij @ k_j accumulated in VMEM scratch across the k steps, flushed on
    # the last. The resident side (q, dO) carries the head masks.
    g, bq, w = qs_ref.shape
    bk = k_ref.shape[0]
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    masks = _lane_masks(w, head_dim, g)

    @pl.when(ki == 0)
    def _init():
        qs = _scaled(q_ref[:], scale)
        do = do_ref[:]
        for i in range(g):
            qs_ref[i] = _only(masks[i], qs)
            dos_ref[i] = _only(masks[i], do)
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (ki * bk < (qi + 1) * bq) if causal else (ki >= 0)

    @pl.when(run)
    def _compute():
        k = k_ref[:]
        v = v_ref[:]
        tiles = []
        for i in range(g):
            _, ds = _tile_p_ds(
                qs_ref[i], k, v, dos_ref[i],
                _stat_col(lse_ref, slice(None), i, g),
                _stat_col(delta_ref, slice(None), i, g), causal,
                qi * bq, ki * bk,
                None if bias_ref is None else bias_ref[:])
            tiles.append(jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        dq_acc[:] += _by_head(masks, tiles)

    @pl.when(ki == nk - 1)
    def _flush():
        acc = dq_acc[:] * scale if scale != 1.0 else dq_acc[:]
        dq_ref[:] = acc.astype(dq_ref.dtype)


def _kv_heads_step(qs, do, ks, vs, lse_of, delta_of, masks, causal, q_pos0,
                   k_pos0, bias, want_dq):
    """One (q tile, k block) step of the kernels in which k/v are resident:
    `ks`/`vs` are the k/v tile by head (the others' lanes zeroed), `qs` the
    scaled q tile. -> the block's (dk, dv) contributions [bk, W] (dk with
    the scale in it, through qs), dq's [bq, W] without the scale (None
    unless wanted) and the bias gradient's column sums [1, bk] (None
    without a bias)."""
    dks, dvs, dq, db = [], [], None, None
    for i, (k_i, v_i) in enumerate(zip(ks, vs)):
        p, ds = _tile_p_ds(qs, k_i, v_i, do, lse_of(i), delta_of(i), causal,
                           q_pos0, k_pos0, bias)
        dvs.append(jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        dks.append(jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        if want_dq:
            # k_i is zero outside head i's lanes, so the heads' tiles add
            # up to the block's without a select
            t = jax.lax.dot_general(ds, k_i, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            dq = t if dq is None else dq + t
        if bias is not None:
            # dL/dbias_k = sum over q rows of ds (bias enters s additively,
            # after the scale), over the heads of the block too
            col = jnp.sum(ds.astype(jnp.float32), axis=0, keepdims=True)
            db = col if db is None else db + col
    return _by_head(masks, dks), _by_head(masks, dvs), dq, db


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                    scale, causal, nq, head_dim, has_bias=False):
    if has_bias:
        (bias_ref, dk_ref, dv_ref, dbias_ref,
         ks_ref, vs_ref, dk_acc, dv_acc, db_acc) = refs
    else:
        dk_ref, dv_ref, ks_ref, vs_ref, dk_acc, dv_acc = refs
        bias_ref = dbias_ref = db_acc = None
    # Streaming: grid = (b, head blocks, nk, nq); Q/dO blocks arrive on the
    # innermost dim; dk_j / dv_j accumulate in VMEM scratch, flushed on the
    # last step. The resident side (k, v) carries the head masks.
    g, bk, w = ks_ref.shape
    bq = q_ref.shape[0]
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    masks = _lane_masks(w, head_dim, g)

    @pl.when(qi == 0)
    def _init():
        k = k_ref[:]
        v = v_ref[:]
        for i in range(g):
            ks_ref[i] = _only(masks[i], k)
            vs_ref[i] = _only(masks[i], v)
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if db_acc is not None:
            db_acc[:] = jnp.zeros_like(db_acc)

    # causal: q blocks strictly before the diagonal see nothing of this k blk
    run = ((qi + 1) * bq > ki * bk) if causal else (qi >= 0)

    @pl.when(run)
    def _compute():
        dk, dv, _, db = _kv_heads_step(
            _scaled(q_ref[:], scale), do_ref[:],
            [ks_ref[i] for i in range(g)], [vs_ref[i] for i in range(g)],
            lambda i: _stat_col(lse_ref, slice(None), i, g),
            lambda i: _stat_col(delta_ref, slice(None), i, g), masks, causal,
            qi * bq, ki * bk, None if bias_ref is None else bias_ref[:],
            want_dq=False)
        dk_acc[:] += dk
        dv_acc[:] += dv
        if db_acc is not None:
            db_acc[:] += jnp.broadcast_to(db, db_acc.shape)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)
        if db_acc is not None:
            dbias_ref[:] = db_acc[:]


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *refs, scale, causal, block_q, nk, head_dim,
                      has_bias=False, one_gradient=False):
    """One-pass backward: grid over k-blocks (sequential per (b, head block)
    row), q streamed inside. Computes p = exp(s - lse) ONCE per (i,j) tile
    and feeds all three grads: dv_j += p^T dO_i, dk_j += ds^T q_i, and dq_i
    accumulated across j in a VMEM scratch flushed on the last k-block.
    Versus separate dq/dkv kernels this halves the exp work and drops two of
    seven dots."""
    refs = list(refs)
    bias_ref = refs.pop(0) if has_bias else None
    if one_gradient:
        # a fused projection's gradient d(qkv) [B, S, 3E] stays in HBM: dq,
        # dk and dv are staged in VMEM and sent to their lane blocks of it
        # by DMA, so that no pass assembles it from three arrays afterwards
        dqkv_ref, *refs = refs
        dbias_ref = refs.pop(0) if has_bias else None
        dq_acc, dq_ref, dkv_ref, sems = refs
        dk_ref, dv_ref = dkv_ref.at[0], dkv_ref.at[1]
    else:
        dq_ref, dk_ref, dv_ref, *refs = refs
        dbias_ref = refs.pop(0) if has_bias else None
        dq_acc, = refs
    sq = q_ref.shape[0]
    bk, w = k_ref.shape
    g = w // head_dim
    ki = pl.program_id(2)
    masks = _lane_masks(w, head_dim, g)
    k = k_ref[:]
    v = v_ref[:]
    ks = [_only(m, k) for m in masks]  # resident over the q loop
    vs = [_only(m, v) for m in masks]
    bias = None if bias_ref is None else bias_ref[:]

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    nq = sq // block_q
    first_q = (ki * bk) // block_q if causal else 0

    def body(i, carry):
        dk_acc, dv_acc, db_acc = carry
        rows = pl.ds(i * block_q, block_q)
        dk, dv, dq, db = _kv_heads_step(
            _scaled(q_ref[rows, :], scale), do_ref[rows, :], ks, vs,
            lambda h: _stat_col(lse_ref, rows, h, g),
            lambda h: _stat_col(delta_ref, rows, h, g), masks, causal,
            i * block_q, ki * bk, bias, want_dq=True)
        dq_acc[rows, :] += dq
        if bias is not None:
            db_acc = db_acc + jnp.broadcast_to(db, db_acc.shape)
        return dk_acc + dk, dv_acc + dv, db_acc

    z = jnp.zeros((bk, w), jnp.float32)
    zb = jnp.zeros((BIAS_ROWS, bk), jnp.float32)
    dk_acc, dv_acc, db_acc = jax.lax.fori_loop(first_q, nq, body, (z, z, zb))
    dk_ref[:] = dk_acc.astype(dk_ref.dtype)
    dv_ref[:] = dv_acc.astype(dv_ref.dtype)
    if dbias_ref is not None:
        # dL/dbias for this k block: sum of ds over all q rows
        dbias_ref[:] = db_acc

    # program ids are read out here: not inside a `pl.when` body
    b, hb, nb = pl.program_id(0), pl.program_id(1), pl.num_programs(1)

    def send(tile, which, row0, sem):
        """Start a staged tile on its way to d(qkv): its rows, the lane
        block of this block of heads in q's (0), k's (1) or v's (2) third."""
        lane = pl.multiple_of((which * nb + hb) * w, LANES)
        copy = pltpu.make_async_copy(
            tile, dqkv_ref.at[b, pl.ds(row0, tile.shape[0]), pl.ds(lane, w)],
            sem)
        copy.start()
        return copy

    if one_gradient:
        sent = [send(dk_ref, 1, ki * bk, sems.at[0]),
                send(dv_ref, 2, ki * bk, sems.at[1])]

    @pl.when(ki == nk - 1)
    def _flush():
        acc = dq_acc[:] * scale if scale != 1.0 else dq_acc[:]
        dq_ref[:] = acc.astype(dq_ref.dtype)
        if one_gradient:
            send(dq_ref, 0, 0, sems.at[2]).wait()

    if one_gradient:  # before the next step stages its tiles
        for copy in sent:
            copy.wait()


def _delta_kernel(o_ref, do_ref, delta_ref, *, head_dim):
    # delta = rowsum(dO * O) by head, written as the block's lane-dense
    # tile. Doing this in Pallas instead of XLA matters: the minor-axis
    # reduce plus the lane broadcast measured 1.26ms/layer at GPT-2-small
    # batch 16 as an XLA fusion (~5x over the bandwidth bound, r4 per-op
    # profile); here it is one streaming pass.
    rows, w = o_ref.shape
    g = w // head_dim
    prod = o_ref[...].astype(jnp.float32) * do_ref[...].astype(jnp.float32)
    delta_ref[...] = _stat_tile(
        _lane_masks(LANES, LANES // g, g),
        [jnp.sum(_only(m, prod), axis=1, keepdims=True)
         for m in _lane_masks(w, head_dim, g)], rows)


def _delta(o, do, ops, interpret):
    """[B, Sq, E] x2 -> delta [B, H/G, Sq, 128] f32."""
    bq = next((b for b in (512, 256, 128) if ops.sq % b == 0), ops.sq)
    mem = _vmem(interpret)
    row = pl.BlockSpec((None, bq, ops.w), lambda b, hb, j: (b, j, hb), **mem)
    return named_pallas_call(
        "flash_bwd_delta",
        functools.partial(_delta_kernel, head_dim=ops.head_dim),
        out_shape=jax.ShapeDtypeStruct((ops.b, ops.nb, ops.sq, LANES),
                                       jnp.float32),
        grid=(ops.b, ops.nb, ops.sq // bq),
        in_specs=[row, row],
        out_specs=pl.BlockSpec((None, None, bq, LANES),
                               lambda b, hb, j: (b, hb, j, 0), **mem),
        interpret=interpret,
        **_compiler_params(("parallel", "parallel", "arbitrary")),
    )(o, do)


def _grad_shapes(ops):
    return [jax.ShapeDtypeStruct((ops.b, s, ops.e), ops.dtype)
            for s in (ops.sq, ops.sk, ops.sk)]


def _bwd_fused(ops, o, lse, do, scale, causal, block_q, block_k, interpret,
               bias=None):
    """-> (dq, dk, dv [B, S, E], dbias column sums [B, H/G, 8, Sk] | None);
    of a fused projection (d(qkv) [B, S, 3E], None, None, dbias sums): the
    kernel writes the three into ONE array."""
    bq, bk = _block_sizes(ops.sq, ops.sk, block_q, block_k)
    delta = _delta(o, do, ops, interpret)
    mem = _vmem(interpret)
    q_at = lambda b, hb, j: (b, 0, hb)
    k_at = lambda b, hb, j: (b, j, hb)
    qfull = pl.BlockSpec((None, ops.sq, ops.w), q_at, **mem)
    kcol = pl.BlockSpec((None, bk, ops.w), k_at, **mem)
    vec_full = pl.BlockSpec((None, None, ops.sq, LANES),
                            lambda b, hb, j: (b, hb, 0, 0), **mem)
    in_specs = [ops.spec(0, ops.sq, q_at, interpret),
                ops.spec(1, bk, k_at, interpret),
                ops.spec(2, bk, k_at, interpret), qfull, vec_full, vec_full]
    operands = [*ops.arrays, do, lse, delta]
    scratch = [pltpu.VMEM((ops.sq, ops.w), jnp.float32)]
    if ops.fused:
        out_shape = [jax.ShapeDtypeStruct((ops.b, ops.sq, 3 * ops.e),
                                          ops.dtype)]
        out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
        scratch += [pltpu.VMEM((ops.sq, ops.w), ops.dtype),
                    pltpu.VMEM((2, bk, ops.w), ops.dtype),
                    pltpu.SemaphoreType.DMA((3,))]
    else:
        out_shape = _grad_shapes(ops)
        out_specs = [qfull, kcol, kcol]
    if bias is not None:
        in_specs.append(pl.BlockSpec((None, BIAS_ROWS, bk),
                                     lambda b, hb, j: (b, 0, j), **mem))
        operands.append(bias)
        out_shape.append(jax.ShapeDtypeStruct(
            (ops.b, ops.nb, BIAS_ROWS, ops.sk), jnp.float32))
        out_specs.append(pl.BlockSpec((None, None, BIAS_ROWS, bk),
                                      lambda b, hb, j: (b, hb, 0, j), **mem))
    outs = named_pallas_call(
        "flash_bwd_fused",
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=bq, nk=ops.sk // bk,
                          head_dim=ops.head_dim, has_bias=bias is not None,
                          one_gradient=ops.fused),
        out_shape=tuple(out_shape),
        grid=(ops.b, ops.nb, ops.sk // bk),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        interpret=interpret,
        **_compiler_params(("parallel", "parallel", "arbitrary")),
    )(*operands)
    grads = (outs[0], None, None) if ops.fused else outs[:3]
    return (*grads, outs[-1] if bias is not None else None)


def _bwd_split(ops, o, lse, do, scale, causal, block_q, block_k, interpret,
               bias=None):
    """The two-kernel backward (a dq pass, then a dkv pass): nothing but
    blocks resident, for sequences the fused kernel cannot pin in VMEM.
    Same returns as `_bwd_fused`."""
    bq, bk = _block_sizes(ops.sq, ops.sk, block_q, block_k)
    delta = _delta(o, do, ops, interpret)
    mem = _vmem(interpret)
    nq, nk = ops.sq // bq, ops.sk // bk
    has_bias = bias is not None
    tile = lambda rows: pltpu.VMEM((ops.g, rows, ops.w), ops.dtype)
    acc = lambda rows: pltpu.VMEM((rows, ops.w), jnp.float32)

    # dq pass: grid (b, hb, nq, nk) — q row pinned per j, k/v streamed on kk
    q_at = lambda b, hb, j, kk: (b, j, hb)
    k_at = lambda b, hb, j, kk: (b, kk, hb)
    qrow = pl.BlockSpec((None, bq, ops.w), q_at, **mem)
    vec_row = pl.BlockSpec((None, None, bq, LANES),
                           lambda b, hb, j, kk: (b, hb, j, 0), **mem)
    dq_specs = [ops.spec(0, bq, q_at, interpret),
                ops.spec(1, bk, k_at, interpret),
                ops.spec(2, bk, k_at, interpret), qrow, vec_row, vec_row]
    operands = [*ops.arrays, do, lse, delta]
    if has_bias:
        dq_specs.append(pl.BlockSpec((None, BIAS_ROWS, bk),
                                     lambda b, hb, j, kk: (b, 0, kk), **mem))
        operands.append(bias)
    dq = named_pallas_call(
        "flash_bwd_dq",
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, nk=nk,
                          head_dim=ops.head_dim, has_bias=has_bias),
        out_shape=_grad_shapes(ops)[0],
        grid=(ops.b, ops.nb, nq, nk),
        in_specs=dq_specs,
        out_specs=qrow,
        scratch_shapes=[tile(bq), tile(bq), acc(bq)],
        interpret=interpret,
        **_compiler_params(("parallel", "parallel", "parallel",
                            "arbitrary")),
    )(*operands)

    # dkv pass: grid (b, hb, nk, nq) — k/v column pinned per j, q/dO streamed
    q_at = lambda b, hb, j, qq: (b, qq, hb)
    k_at = lambda b, hb, j, qq: (b, j, hb)
    kcol = pl.BlockSpec((None, bk, ops.w), k_at, **mem)
    vec_stream = pl.BlockSpec((None, None, bq, LANES),
                              lambda b, hb, j, qq: (b, hb, qq, 0), **mem)
    dkv_specs = [ops.spec(0, bq, q_at, interpret),
                 ops.spec(1, bk, k_at, interpret),
                 ops.spec(2, bk, k_at, interpret),
                 pl.BlockSpec((None, bq, ops.w), q_at, **mem),
                 vec_stream, vec_stream]
    dkv_out_shape = _grad_shapes(ops)[1:]
    dkv_out_specs = [kcol, kcol]
    dkv_scratch = [tile(bk), tile(bk), acc(bk), acc(bk)]
    if has_bias:
        dkv_specs.append(pl.BlockSpec((None, BIAS_ROWS, bk),
                                      lambda b, hb, j, qq: (b, 0, j), **mem))
        dkv_out_shape.append(jax.ShapeDtypeStruct(
            (ops.b, ops.nb, BIAS_ROWS, ops.sk), jnp.float32))
        dkv_out_specs.append(pl.BlockSpec(
            (None, None, BIAS_ROWS, bk),
            lambda b, hb, j, qq: (b, hb, 0, j), **mem))
        dkv_scratch.append(pltpu.VMEM((BIAS_ROWS, bk), jnp.float32))
    outs = named_pallas_call(
        "flash_bwd_dkv",
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, nq=nq,
                          head_dim=ops.head_dim, has_bias=has_bias),
        out_shape=tuple(dkv_out_shape),
        grid=(ops.b, ops.nb, nk, nq),
        in_specs=dkv_specs,
        out_specs=tuple(dkv_out_specs),
        scratch_shapes=dkv_scratch,
        interpret=interpret,
        **_compiler_params(("parallel", "parallel", "parallel",
                            "arbitrary")),
    )(*operands)
    return (dq, *outs[:2], outs[2] if has_bias else None)


def _fused_fits(ops):
    """Whether the fused one-pass backward may pin a whole (b, head block)
    row in VMEM: dq_acc scratch (f32), q, dO and the dq window (input
    dtype, double-buffered) and the lse/delta tiles (f32, double-buffered),
    all sq-proportional, budgeted at 8MB of the ~16MB core. Larger shapes
    take the two-kernel path, which pins blocks only."""
    item = jnp.dtype(ops.dtype).itemsize
    row = ops.w * (4 + 6 * item) + 2 * 2 * LANES * 4
    return ops.sq * row <= 8 * 1024 * 1024


def _bias_rows(bias, b):
    """[B | 1, Sk] -> [B, 8, Sk] f32: an 8-sublane broadcast so the
    per-k-block tile is a TPU-aligned [8, bk] block, shared by every head."""
    sk = bias.shape[-1]
    return jnp.broadcast_to(bias.astype(jnp.float32)[:, None, :],
                            (b, BIAS_ROWS, sk))


def _bias_grad(db, bias):
    """The kernels' column sums [B, H/G, 8, Sk] (8 identical sublane rows a
    block of heads) -> the cotangent of the [B | 1, Sk] bias. The bias
    broadcast over heads, so its cotangent sums over them. This is the TRUE
    gradient — a trainable per-key bias (e.g. learned ALiBi-style offsets)
    matches the XLA path's grad."""
    dbias = db[:, :, 0, :].sum(axis=1)
    if bias.shape[0] == 1 and db.shape[0] > 1:  # broadcast batch
        dbias = dbias.sum(axis=0, keepdims=True)
    return dbias.astype(bias.dtype)


def _reference_attention(q, k, v, scale, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def flash_attention_token_major(q, k, v, bias, num_heads, causal=False,
                                scale=None, block_q=DEFAULT_BLOCK_Q,
                                block_k=DEFAULT_BLOCK_K, interpret=False):
    """Token-major flash attention: q [B, Sq, E], k/v [B, Sk, E] with the
    `num_heads` heads side by side on the lanes, as a projection GEMM writes
    them — or q the fused projection [B, S, 3E] (q, k, v side by side) and
    k = v = None. Returns [B, Sq, E]. `num_heads` must fill blocks of
    `heads_per_block(E // num_heads)` heads; S must be a multiple of 128 on
    the chip.

    `bias`: None, or a PER-KEY additive bias [B | 1, Sk] — the [B,1,1,S]
    additive-mask form BERT-class encoders build (padding in any pattern,
    per-key score offsets). Per-QUERY-relative biases (ALiBi's -m*|q-k|) are
    NOT expressible per-key and take the XLA path. It is streamed to the
    kernels one k-block at a time; its cotangent is the true per-key
    gradient (sum of dS over q rows and heads, accumulated in the backward
    kernels), so trainable biases match the XLA path's grad."""
    return _tm_fwd(q, k, v, bias, num_heads, causal, scale, block_q, block_k,
                   interpret)[0]


# Forward and backward are jitted by themselves: a model calls them once a
# layer with the same shapes, and a jitted function is traced once and lowered
# to ONE function of the step's module, where 24 layers' kernels traced and
# lowered apart took a third of a train cell's trace-and-lower time.
_STATIC = ("num_heads", "causal", "scale", "block_q", "block_k", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(q, k, v, bias, *, num_heads, causal, scale, block_q, block_k,
             interpret):
    ops = _Operands(q, k, v, num_heads)
    if scale is None:
        scale = ops.head_dim ** -0.5
    return _fwd(ops, scale, causal, block_q, block_k, interpret,
                None if bias is None else _bias_rows(bias, ops.b))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(q, k, v, bias, out, lse, g, *, num_heads, causal, scale,
              block_q, block_k, interpret):
    ops = _Operands(q, k, v, num_heads)
    if scale is None:
        scale = ops.head_dim ** -0.5
    bwd = _bwd_fused if _fused_fits(ops) else _bwd_split
    dq, dk, dv, db = bwd(ops, out, lse, g, scale, causal, block_q, block_k,
                         interpret,
                         None if bias is None else _bias_rows(bias, ops.b))
    dbias = None if bias is None else _bias_grad(db, bias)
    if k is None and dk is not None:  # the two-kernel backward's three
        dq = jnp.concatenate([dq, dk, dv], axis=-1)
        dk = dv = None
    return dq, dk, dv, dbias


def _tm_fwd(q, k, v, bias, *static):
    out, lse = _forward(q, k, v, bias, **dict(zip(_STATIC, static)))
    return out, (q, k, v, bias, out, lse)


def _tm_bwd(*args):
    *static, res, g = args
    return _backward(*res, g, **dict(zip(_STATIC, static)))


flash_attention_token_major.defvjp(_tm_fwd, _tm_bwd)


# ---- the head-major border ------------------------------------------------

def _token_major(x):
    """[B, H, S, D] -> [B, S, Hp*D]: heads side by side on the lanes, zero
    heads appended to fill the last block of heads."""
    b, h, s, d = x.shape
    pad = -h % heads_per_block(d)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3).reshape(b, s, (h + pad) * d)


def _head_major(x, h, d):
    """[B, S, Hp*D] -> [B, H, S, D]."""
    b, s, e = x.shape
    return x.reshape(b, s, e // d, d).transpose(0, 2, 1, 3)[:, :h]


def stat_rows(stat, h, head_dim):
    """lse or delta as the kernels hold it, [B, H/G, S, 128] -> [B, H, S]."""
    b, nb, s, _ = stat.shape
    g = heads_per_block(head_dim)
    cols = stat.reshape(b, nb, s, g, LANES // g)[..., 0]
    return cols.transpose(0, 1, 3, 2).reshape(b, nb * g, s)[:, :h]


def stat_tiles(rows, head_dim):
    """[B, H, S] -> lse or delta as the kernels take it."""
    b, h, s = rows.shape
    g = heads_per_block(head_dim)
    pad = -h % g
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
    cols = rows.reshape(b, (h + pad) // g, g, s).transpose(0, 1, 3, 2)
    return jnp.repeat(cols, LANES // g, axis=-1)


def _head_major_call(q, k, v, bias, *args):
    h, d = q.shape[1], q.shape[3]
    q, k, v = map(_token_major, (q, k, v))
    return _head_major(flash_attention_token_major(
        q, k, v, bias, q.shape[-1] // d, *args), h, d)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    """q,k,v: [B,H,S,D]. S must be a multiple of 128."""
    return _head_major_call(q, k, v, None, causal, scale, block_q, block_k,
                            interpret)


def flash_attention_bias(q, k, v, bias, causal=False, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=False):
    """`flash_attention` with a per-key additive bias [B | 1, Sk] (see
    `flash_attention_token_major`)."""
    return _head_major_call(q, k, v, bias, causal, scale, block_q, block_k,
                            interpret)


def _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k, interpret,
                   bias=None):
    """Head-major forward with its lse ([B, H/G, S, 128]: `stat_rows`), for
    callers that carry softmax state across calls (ring attention). `bias`
    as `_bias_rows` gives it."""
    h, d = q.shape[1], q.shape[3]
    q, k, v = map(_token_major, (q, k, v))
    out, lse = _fwd(_Operands(q, k, v, q.shape[-1] // d), scale, causal,
                    block_q, block_k, interpret, bias)
    return _head_major(out, h, d), lse


def _flash_bwd(q, k, v, o, lse, g, scale, causal, block_q, block_k,
               interpret, bias=None):
    """Head-major two-kernel backward from a given lse (`stat_tiles`).
    -> (dq, dk, dv, dbias column sums [B, H/G, 8, Sk] | None)."""
    h, d = q.shape[1], q.shape[3]
    q, k, v, o, g = map(_token_major, (q, k, v, o, g))
    dq, dk, dv, db = _bwd_split(_Operands(q, k, v, q.shape[-1] // d), o, lse,
                                g, scale, causal, block_q, block_k,
                                interpret, bias)
    return (*(_head_major(x, h, d) for x in (dq, dk, dv)), db)
