"""paddle.nn.decode module path (ref: nn/decode.py) + the paged decode
engine.

`PagedDecoder` is the jitted prefill/step pair that runs a decoder against
the block-pool cache (inference/kv_cache.py).  It takes one of two
layouts: GPT-2's six-field tuple, described below, which this file builds
every program for; or a `nn/decode_blocks.DecoderDescription` (latent and
recurrent mixers, routed experts), which gets `packed_prefill` and
`decode_step` from that module and raises, by name, for the rest.  One
trunk for both is ROADMAP D1.  For the GPT-2 layout:

  * prefill — one causal pass over a right-padded prompt batch, writing
    each row's K/V into its block-table blocks and sampling token 0 at
    the row's true last position (per-row `lens`, no pad-value
    matching);
  * step — one token per sequence against the paged cache via
    ops.paged_decode_attention (Pallas ragged kernel on TPU, XLA gather
    elsewhere), writing the incoming token's K/V at its cache position;
  * packed_prefill — ONE dispatch over a token-packed multi-sequence
    chunk stream (segment-causal attention against the paged cache via
    ops.ragged_prefill_attention), the engine of the serving
    scheduler's packed/chunked prefill. The chunk contract is
    position-based, not history-based: a chunk's tokens attend
    whatever K/V the block tables reach at positions <= pos,
    regardless of WHO wrote it — an earlier chunk of the same prompt
    (PR 3 chunking) or a cached prefix another sequence prefilled and
    `PagedKVCache.attach_prefix` re-attached (round 9 prefix caching).
    Prefix-cache resume therefore needs no engine change: the server
    just starts the packed stream at the first uncached token.
  * packed_verify — speculative-decoding verification (round 11): the
    SAME packed trunk as packed_prefill (the `_packed_trunk` refactor)
    scoring each speculating slot's [last_token, draft_1..draft_k]
    region in one dispatch, with a [P, K1] readout (one sample per
    draft position plus the bonus position) and ON-DEVICE acceptance:
    the counter-based PRNG makes the target's token at every step
    deterministic, so rejection sampling reduces to exact match and
    fixed-seed output is token-identical to non-speculative decode.
  * unified_round — the ONE-KERNEL serving round (r16): prefill chunk
    rows, plain decode rows and speculative verify regions of a whole
    scheduler round scored in a SINGLE dispatch over the generic
    packed trunk (the segment-causal mask generalizes all three), with
    a slot-indexed device CARRY (next token / write position / PRNG
    step per slot) that lets the async double-buffered engine loop
    chain round N's samples into round N+1's decode rows without a
    host sync.  Subsumes packed_prefill + step + packed_verify, which
    remain the split path (default OFF in the server, parity-tested).

Sampling (round 10) is PER-SLOT: every program takes a struct-of-arrays
parameter dict `sp` (paddle_tpu/sampling/buffers.py) — temperature /
top-k / top-p / min-p / penalty columns, per-request counter-based PRNG
seeds, and the per-slot stop-token matrix — and pushes the logits
through the vectorized processor pipeline
(paddle_tpu/sampling/processors.py), so one jitted dispatch serves a
batch mixing greedy and arbitrarily-configured sampled requests. The
`mode` pair (any-sampled, any-penalties) is STATIC: (False, False) is
the all-greedy fast path that compiles to a bare argmax plus the stop
check; parameter VALUES are traced and never recompile. Every program
returns device-checked `stopped` flags (per-slot stop-token matrix,
EOS folded in by the server) and, in penalty mode, the updated token-
count scatter buffer.

Both are pure functions of (params, inputs, cache arrays) so the cache
arrays round-trip functionally (donated on accelerators). Masking is by
LENGTH everywhere: a prompt legitimately containing the server's
pad_token_id decodes exactly like any other prompt. Padded prefill
lanes and idle decode slots write to the reserved trash block 0.

Params use the GPT-2 flat naming ("h.{i}.qkv_proj.weight", ...); the
weight-only-int8 "::w8c"/"::w8s" key convention of models/gpt2.py is
honored transparently — `GPT2.quantize_weights()` params make every
program a W8A16 dispatch with a fused rescale epilogue, no decoder
change needed.

int8 KV (quantized-serving round): `PagedDecoder(kv_dtype="int8")`
builds the same program family over a QUANTIZED pool
(`PagedKVCache(kv_dtype="int8")`) — cache appends quantize each
written K/V vector to int8 with a per-vector absmax scale
(inference/kv_quant.py), and the attention ops dequantize inside the
kernel, so the cache is streamed as raw int8 and a bf16 copy never
exists in HBM. The kv_quant flag is STATIC (part of every builder
cache key); the default-False path traces exactly the pre-quantization
program. Dispatches check the decoder/cache pairing eagerly and raise
naming the mismatched argument.
"""
from __future__ import annotations

import functools

from .layer.legacy import BeamSearchDecoder, Decoder, dynamic_decode  # noqa: F401,E501

__all__ = ["BeamSearchDecoder", "dynamic_decode", "PagedDecoder"]

GREEDY_MODE = (False, False)


@functools.lru_cache(maxsize=4)
def _kv_write(kv_quant):
    """The K/V append over the cache arrays, selected by the STATIC
    kv_quant flag (quantized-serving round). Dense pools are plain
    [L, N, BS, H*Dh] arrays (a token's heads side by side:
    `inference.kv_cache`); int8 pools are
    `inference.kv_quant.QuantizedKV` (codes like them, per-vector
    scales [L, N, BS, H]) pytrees. The write takes t [.., H, Dh] and
    quantizes ON APPEND — each written vector gets its own absmax
    scale, so no already-stored code ever needs rescaling and the
    functional scatter stays a scatter.

    It is a scatter of whole rows into the donated STACK, and the
    attention ops read the same stack through `layer=` (PR 25): with
    nothing between a layer's scatter and its launch but the stack
    itself, the chain scatter_i -> attention_i -> scatter_{i+1} is
    linear, the stack lies on the device the way both want it, and XLA
    keeps the chain in the one donated buffer: no program holds a copy,
    a re-laid copy or a layer's slice of the pool."""
    def rows(t):  # [.., H, Dh] -> [.., H*Dh]
        return t.reshape(t.shape[:-2] + (-1,))

    if not kv_quant:
        def write(cache, i, blk, off, t):
            return cache.at[i, blk, off].set(rows(t))
    else:
        from ..inference.kv_quant import QuantizedKV, kv_encode

        def write(cache, i, blk, off, t):
            codes, sc = kv_encode(t, cache.scales.dtype)
            return QuantizedKV(
                cache.codes.at[i, blk, off].set(rows(codes)),
                cache.scales.at[i, blk, off].set(sc))
    return write


@functools.lru_cache(maxsize=32)
def _layer_helpers(spec, cq=None):
    """Shared GPT-2-layout building blocks (layernorm, int8-aware matmul,
    qkv split, embed/head, residual+MLP) used by every paged program
    builder below. spec = (L, H, Dh, E, eps, tied) — the tuple
    models/gpt2.py builds.

    cq (quantized-collectives round): a STATIC
    `serving_dist.collectives.CollectiveQuant` makes the row-split
    projections (out_proj / fc2) and the vocab-parallel embedding
    reduce through explicit quantized shard_map seams instead of the
    XLA-inserted compute-dtype collectives; None (the default) traces
    the exact pre-round program — cq is part of this cache's key, so
    flipping it never mutates an existing program family."""
    import jax
    import jax.numpy as jnp

    L, H, Dh, E, eps, tied = spec

    def ln(x, w, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * w + b

    def matw(p, name, x, dt):
        codes = p.get(name + "::w8c")
        if codes is None:
            return x @ p[name]
        return (x @ codes.astype(dt)) * p[name + "::w8s"].astype(dt)

    def matw_row(p, name, x, dt):
        """matw for the ROW-SPLIT projections (out_proj / fc2): under a
        CollectiveQuant the contraction's psum goes through the
        quantized wire (the per-output-column W8A16 scales apply AFTER
        the reduction, outside the seam — they are replicated)."""
        if cq is None:
            return matw(p, name, x, dt)
        codes = p.get(name + "::w8c")
        if codes is None:
            return cq.matmul_psum(x, p[name])
        return cq.matmul_psum(x, codes, cast=dt) \
            * p[name + "::w8s"].astype(dt)

    def qkv_split(p, i, a):
        qkv = matw(p, f"h.{i}.qkv_proj.weight", a, a.dtype) \
            + p[f"h.{i}.qkv_proj.bias"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        new = q.shape[:-1] + (H, Dh)
        return q.reshape(new), k.reshape(new), v.reshape(new)

    def make_embed_head(params, dt):
        wte_codes = params.get("wte.weight::w8c")
        if wte_codes is None:
            wte_full = params["wte.weight"]

            if cq is not None and cq.vocab_sharded(wte_full.shape[0]):
                def embed(t):
                    return cq.embed_psum(t, wte_full, dt=dt)
            else:
                def embed(t):
                    return wte_full[t]
        else:
            wte_rs = params["wte.weight::w8s"]

            if cq is not None and cq.vocab_sharded(wte_codes.shape[0]):
                def embed(t):
                    return cq.embed_psum(t, wte_codes, scales=wte_rs,
                                         dt=dt)
            else:
                def embed(t):
                    return wte_codes[t].astype(dt) \
                        * wte_rs[t][..., None].astype(dt)

        def head(xf):
            if tied:
                if wte_codes is None:
                    return (xf @ wte_full.T).astype(jnp.float32)
                return ((xf @ wte_codes.T.astype(dt))
                        * wte_rs[None, :].astype(dt)).astype(jnp.float32)
            return matw(params, "lm_head.weight", xf,
                        dt).astype(jnp.float32)

        return embed, head

    def block_and_mlp(params, i, x, o, dt):
        x = x + matw_row(params, f"h.{i}.out_proj.weight", o, dt) \
            + params[f"h.{i}.out_proj.bias"]
        m = ln(x, params[f"h.{i}.ln_2.weight"],
               params[f"h.{i}.ln_2.bias"])
        hdn = jax.nn.gelu(
            matw(params, f"h.{i}.fc1.weight", m, dt)
            + params[f"h.{i}.fc1.bias"], approximate=True)
        return x + matw_row(params, f"h.{i}.fc2.weight", hdn, dt) \
            + params[f"h.{i}.fc2.bias"]

    ns = type("LayerHelpers", (), {})()
    ns.ln, ns.matw, ns.qkv_split = ln, matw, qkv_split
    ns.make_embed_head, ns.block_and_mlp = make_embed_head, block_and_mlp
    return ns


def _make_readout(cq, pin, mode, proc):
    """The head readout every program builder shares: logits -> token.

    Unquantized (cq None): pin the head logits replicated (`_rep_pin`)
    and run the sampling pipeline — the exact pre-round path.  Under a
    CollectiveQuant with the vocab actually sharded, the all-greedy
    no-logits fast path replaces the f32 logits all-gather with the
    LOSSLESS per-shard argmax exchange (8 bytes/row/peer), and every
    other mode ships the logits through the quantized codes+scales
    gather before the unchanged sampling pipeline (still pinned
    replicated — the r14 partitioner guard).  Returns (tok, logits);
    logits is None exactly when the fast path skipped materializing
    them (callers that return logits pass need_logits=True)."""
    sampled, penalties = mode

    def readout(head, xf, sp, need_logits):
        lg = head(xf)
        if cq is not None and cq.vocab_sharded(lg.shape[-1]):
            if not sampled and not penalties and not need_logits:
                return pin(cq.greedy_tokens(lg)), None
            logits = pin(cq.gather_logits(lg))
        else:
            logits = pin(lg)
        tok = proc.sample_tokens(logits, sp, sampled=sampled,
                                 penalties=penalties)
        return tok, logits

    return readout


def _mesh_of(rep_constraint):
    """The device mesh of a sharded program (its replicated pin names
    it), handed to the paged attention ops so a Pallas kernel runs per
    device under shard_map; None for the unsharded program."""
    return None if rep_constraint is None else rep_constraint.mesh


def _rep_pin(rep_constraint):
    """Logit pin for SHARDED programs (serving_dist round): gather the
    vocab-sharded head output to every device BEFORE the sampling
    pipeline.  This is the vocab-parallel all-gather placement — and it
    is load-bearing for parity: left to itself, the SPMD partitioner
    shards the sort/threefry/argmax pipeline over 2-D meshes and the
    pinned toolchain MISCOMPILES it (observed: an argmax result 6.0
    below the true max at dp x mp > 1).  With the logits pinned
    replicated, every downstream sampling op computes replicated —
    bitwise the single-device pipeline.  None (the unsharded path) is
    the identity."""
    if rep_constraint is None:
        return lambda x: x
    import jax

    return lambda x: jax.lax.with_sharding_constraint(x, rep_constraint)


@functools.lru_cache(maxsize=64)
def _build_paged_fns(spec, block_size, return_logits, mode,
                     kv_quant=False, rep_constraint=None, cq=None):
    """(spec, block_size, mode, kv_quant) -> (prefill_fn, step_fn), raw
    and jittable. mode = (any_sampled, any_penalties): the static
    variant pair of the sampling pipeline (see module docstring).
    kv_quant=True takes/returns `QuantizedKV` cache pytrees: appends
    quantize on write, attention dequantizes in-kernel.
    rep_constraint: replicated NamedSharding for the logits pin of
    sharded programs (see _rep_pin); None traces the exact unsharded
    program. cq: a CollectiveQuant routes the TP collectives through
    the quantized shard_map seams (quantized-collectives round); None
    traces the exact pre-round program."""
    import jax
    import jax.numpy as jnp

    from ..sampling import processors as _proc

    pin = _rep_pin(rep_constraint)
    mesh = _mesh_of(rep_constraint)

    L, H, Dh, E, eps, tied = spec
    scale = Dh ** -0.5
    BS = int(block_size)
    sampled, penalties = mode
    kv_write = _kv_write(bool(kv_quant))
    hp = _layer_helpers(spec, cq)
    ln, qkv_split, make_embed_head, block_and_mlp = (
        hp.ln, hp.qkv_split, hp.make_embed_head, hp.block_and_mlp)
    readout = _make_readout(cq, pin, mode, _proc)

    def prefill_fn(params, ids, lens, tables, kc, vc, sp):
        """ids [B, S0] right-padded; lens [B]; tables [B, M]. Returns
        (tok0 [B], stopped [B], kc, vc, counts|None[, logits0 f32])."""
        B, S0 = ids.shape
        dt = params["ln_f.weight"].dtype
        embed, head = make_embed_head(params, dt)
        t = jnp.arange(S0)
        valid = t[None, :] < lens[:, None]             # [B, S0]
        x = embed(ids) + params["wpe.weight"][t]
        # masked writes route to the trash block; the gather that feeds
        # `blk` may clamp at the table edge for padded t, but `valid`
        # gates it before use
        blk = jnp.where(valid, tables[:, t // BS], 0)  # [B, S0]
        off = t % BS
        causal = jnp.tril(jnp.ones((S0, S0), bool))
        kmask = causal[None, None] & valid[:, None, None, :]
        for i in range(L):
            a = ln(x, params[f"h.{i}.ln_1.weight"],
                   params[f"h.{i}.ln_1.bias"])
            q, k, v = qkv_split(params, i, a)          # [B, S0, H, Dh]
            kc = kv_write(kc, i, blk, off, k)
            vc = kv_write(vc, i, blk, off, v)
            qh, kh, vh = (u.transpose(0, 2, 1, 3) for u in (q, k, v))
            s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(
                jnp.float32) * scale
            s = jnp.where(kmask, s, -1e30)
            w = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("bhqk,bhkd->bhqd", w, vh)
            o = o.transpose(0, 2, 1, 3).reshape(B, S0, E)
            x = block_and_mlp(params, i, x, o, dt)
        xf = x[jnp.arange(B), lens - 1]                # true last token
        xf = ln(xf, params["ln_f.weight"], params["ln_f.bias"])
        tok, logits = readout(head, xf, sp, return_logits)
        stopped = _proc.check_stops(tok, sp["stop"],
                                    jnp.ones((B,), bool))
        counts = None
        if penalties:
            counts = _proc.update_counts(sp["counts"], jnp.arange(B),
                                         tok, jnp.ones((B,), bool))
        if return_logits:
            return tok, stopped, kc, vc, counts, logits
        return tok, stopped, kc, vc, counts

    def step_fn(params, tok, pos, active, tables, kc, vc, sp, prev=None):
        """One decode token per sequence. tok [B] is written at cache
        position pos [B]; attention sees positions [0, pos]. Idle slots
        (active False) write to trash and emit token 0. A row whose
        tok is negative goes on from `prev` [B], the tokens the step
        before this one returned, still on the device: the engine
        queues a step before it has read the last one back."""
        from ..ops.attention import paged_decode_attention

        B = tok.shape[0]
        if prev is not None:
            tok = jnp.where(tok < 0, prev, tok)
        dt = params["ln_f.weight"].dtype
        embed, head = make_embed_head(params, dt)
        x = embed(tok) + params["wpe.weight"][pos]     # [B, E]
        blk = jnp.where(active, tables[jnp.arange(B), pos // BS], 0)
        off = pos % BS
        ctx = jnp.where(active, pos + 1, 1)
        for i in range(L):
            a = ln(x, params[f"h.{i}.ln_1.weight"],
                   params[f"h.{i}.ln_1.bias"])
            q, k, v = qkv_split(params, i, a)          # [B, H, Dh]
            kc = kv_write(kc, i, blk, off, k)
            vc = kv_write(vc, i, blk, off, v)
            o = paged_decode_attention(q, kc, vc, tables, ctx,
                                       scale=scale, mesh=mesh, layer=i
                                       ).reshape(B, E)
            x = block_and_mlp(params, i, x, o, dt)
        xf = ln(x, params["ln_f.weight"], params["ln_f.bias"])
        tok, logits = readout(head, xf, sp, return_logits)
        nxt = jnp.where(active, tok, 0)
        stopped = _proc.check_stops(nxt, sp["stop"], active)
        counts = None
        if penalties:
            counts = _proc.update_counts(sp["counts"], jnp.arange(B),
                                         nxt, active)
        if return_logits:
            return nxt, stopped, kc, vc, counts, logits
        return nxt, stopped, kc, vc, counts

    return prefill_fn, step_fn


def _sp_stream_pin(sp_mesh):
    """Token-axis pin for the SEQUENCE-PARALLEL packed trunk (long-
    context round): constrain a [T, ...] stream tensor to shard its
    token axis over the mesh `sp` axis.  The per-token trunk work —
    embed, layer norms, QKV/out projections, the MLP — is data-parallel
    over tokens, so anchoring x at the embed and at every block output
    lets the partitioner run the whole trunk at T/sp tokens per shard
    without any re-association of contractions (the reduction axes stay
    whole, which is why sp is token-identical).  None is the identity
    (the unsharded / sp=1 trace is byte-for-byte the pre-round one)."""
    if sp_mesh is None:
        return lambda x: x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def pin(x):
        spec = P(*(("sp",) + (None,) * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(sp_mesh, spec))

    return pin


def _sp_kv_gather(sp_mesh):
    """The explicit shard_map seam of the sp packed trunk (r14/r20
    seam discipline): re-replicate the sp-sharded K/V token stream over
    `sp` BEFORE the paged-pool scatter.  Each sp shard computes the
    K/V projections for ITS T/sp slice of the packed stream; the pool
    is REPLICATED over sp (kv_pool_specs shards heads over mp and
    blocks over dp only), so a shard-local scatter would leave the sp
    replicas divergent.  One tiled all-gather over sp per (layer, k/v)
    moves exactly the freshly-projected chunk bytes — [T, H/mp, Dh]
    per shard — after which every shard performs the identical full
    scatter and the replicas stay bitwise in lockstep.  The head axis
    keeps its mp sharding through the seam (in/out specs name it), so
    tp x sp meshes compose."""
    if sp_mesh is None:
        return lambda t: t
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    return shard_map(
        lambda t: jax.lax.all_gather(t, "sp", axis=0, tiled=True),
        mesh=sp_mesh, in_specs=P("sp", "mp", None),
        out_specs=P(None, "mp", None), check_vma=False)


@functools.lru_cache(maxsize=32)
def _packed_trunk(spec, block_size, kv_quant=False, cq=None,
                  sp_mesh=None, sp_attention="allgather", mesh=None):
    """Shared packed ragged forward trunk: embed a token-packed
    multi-sequence stream, write each token's K/V into its paged block
    rows, and run segment-causal attention per layer. Returns the final
    hidden stream [T, E] plus the updated cache arrays. The trunk of
    BOTH `packed_prefill` (PR 3 chunked prefill) and `packed_verify`
    (speculative decoding) — the two programs differ only in their
    readout: one sample position per segment vs. one per draft
    position.

    sp_mesh (long-context round): a Mesh with an `sp` axis makes the
    trunk SEQUENCE-PARALLEL over the packed token axis — x is pinned
    to shard [T] over sp (`_sp_stream_pin`), each shard projects Q/K/V
    for its T/sp token slice, the `_sp_kv_gather` shard_map seam
    re-replicates K/V before the pool scatter, and segment-causal
    attention runs with sp-sharded queries against the sp-replicated
    pool (the softmax reduction is over KV positions — whole per
    query — so sharding queries reassociates nothing).  The Pallas
    stream kernel is bypassed inside the sp trunk (its sp-local
    tile_base wiring over shard_map is the ROADMAP follow-up); the
    XLA fallback partitions cleanly.  None traces the exact pre-round
    trunk.

    sp_attention (memory-flat round): "allgather" (default) keeps the
    r21 seam above; "ring"/"ulysses" replace BOTH the K/V all-gather
    and the attention with the serving_dist.sp_attention shard_map
    seam — fresh K/V sub-blocks rotate (ring) or all-to-all (ulysses)
    around sp, each shard scatters every visiting block into its pool
    replica and folds it into an online-softmax accumulator, so peak
    cross-shard fresh-K/V bytes per shard are O(block), flat in chunk
    length.  The pool pass inside the seam covers columns before this
    dispatch's first written position per segment (`segment_starts`);
    fresh rows cover the rest — the union is exactly the all-gather
    path's key set.

    mesh: the sharded engine's device mesh (`_mesh_of`), so the stream
    kernel runs per device with the pool's heads split over mp."""
    import jax.numpy as jnp

    L, H, Dh, E, eps, tied = spec
    scale = Dh ** -0.5
    BS = int(block_size)
    kv_write = _kv_write(bool(kv_quant))
    hp = _layer_helpers(spec, cq)
    spin = _sp_stream_pin(sp_mesh)
    spg = _sp_kv_gather(sp_mesh)
    sp_flat = sp_mesh is not None and sp_attention != "allgather"
    if sp_flat:
        from ..serving_dist import sp_attention as _spa

        sp_attn = _spa.build_sp_fresh_attention(
            sp_mesh, sp_attention, bool(kv_quant), BS, scale)

    def trunk(params, toks, seg, pos, tables, kc, vc):
        from ..ops.attention import ragged_prefill_attention

        T = toks.shape[0]
        dt = params["ln_f.weight"].dtype
        embed, _head = hp.make_embed_head(params, dt)
        valid = pos >= 0
        p0 = jnp.where(valid, pos, 0)
        x = spin(embed(toks) + params["wpe.weight"][p0])  # [T, E]
        # pad tokens write to the trash block; their attention output is
        # finite garbage (uniform weights over masked -inf scores) that
        # no sample index ever reads
        blk = jnp.where(valid, tables[seg, p0 // BS], 0)  # [T]
        off = p0 % BS
        if sp_flat:
            from ..serving_dist.sp_attention import (
                kv_get_layer, kv_set_layer, segment_starts)

            starts = segment_starts(seg, pos, tables.shape[0])
        for i in range(L):
            a = hp.ln(x, params[f"h.{i}.ln_1.weight"],
                      params[f"h.{i}.ln_1.bias"])
            q, k, v = hp.qkv_split(params, i, a)          # [T, H, Dh]
            if sp_flat:
                o, kc_i, vc_i = sp_attn(
                    q, k, v, kv_get_layer(kc, i, H), kv_get_layer(vc, i, H),
                    tables, seg, pos, starts)
                kc = kv_set_layer(kc, i, kc_i)
                vc = kv_set_layer(vc, i, vc_i)
                o = o.reshape(T, E)
            else:
                kc = kv_write(kc, i, blk, off, spg(k))
                vc = kv_write(vc, i, blk, off, spg(v))
                o = ragged_prefill_attention(
                    q, kc, vc, tables, seg, pos, scale=scale,
                    allow_pallas=sp_mesh is None, mesh=mesh,
                    layer=i).reshape(T, E)
            x = spin(hp.block_and_mlp(params, i, x, o, dt))
        return x, kc, vc

    return trunk


@functools.lru_cache(maxsize=64)
def _build_packed_prefill(spec, block_size, return_logits, mode,
                          kv_quant=False, rep_constraint=None, cq=None,
                          sp_mesh=None, sp_attention="allgather"):
    """Packed ragged prefill: ONE dispatch prefills a token-packed
    multi-sequence chunk stream (the tentpole of the chunked-prefill
    scheduler, inference/serving.py). Raw and jittable.

    sp_mesh (long-context round): sequence-parallel trunk over the
    packed token axis (see `_packed_trunk`); the readout rows are
    pinned replicated before the sampling pipeline, so sampling stays
    bitwise the single-stream pipeline.  None = the exact pre-round
    program."""
    import jax.numpy as jnp

    from ..sampling import processors as _proc

    sampled, penalties = mode
    hp = _layer_helpers(spec, cq)
    trunk = _packed_trunk(spec, block_size, bool(kv_quant), cq, sp_mesh,
                          sp_attention, _mesh_of(rep_constraint))
    pin = _rep_pin(rep_constraint)
    readout = _make_readout(cq, pin, mode, _proc)

    def packed_prefill_fn(params, toks, seg, pos, tables, sample_idx,
                          kc, vc, sp):
        """toks [T] packed token stream; seg [T] slot row per token;
        pos [T] absolute cache position (-1 = packing pad); tables
        [B, M]; sample_idx [B] packed index of each slot row's last
        prompt token (host only reads rows whose prompt completed this
        chunk). Returns (tok [B], stopped [B], kc, vc, counts|None
        [, logits [B, V] f32]).

        Every token attends its own sequence's cache positions [0, pos]
        via ops.ragged_prefill_attention — which sees both this chunk's
        freshly written K/V and earlier chunks' blocks, so a prompt
        split across chunks needs no state beyond the paged cache.
        Blocks a prefix-cache attach copied into the table read
        identically: a chunk starting at the first uncached token
        resumes on top of K/V another sequence prefilled.

        Sampling rows are COMPACT plan rows: sp's columns are gathered
        host-side to plan order, sp["crows"] maps plan row -> slot for
        the count buffer, and sp["row_done"] masks the rows whose
        token-0 sample is real (still-feeding and padding rows compute
        a discarded token)."""
        x, kc, vc = trunk(params, toks, seg, pos, tables, kc, vc)
        _embed, head = hp.make_embed_head(
            params, params["ln_f.weight"].dtype)
        xf = x[sample_idx]                                # [B, E]
        if sp_mesh is not None:
            # the sp trunk leaves x token-sharded; the B readout rows
            # are gathered to every shard so the sampling pipeline
            # computes replicated (the _rep_pin discipline)
            xf = pin(xf)
        xf = hp.ln(xf, params["ln_f.weight"], params["ln_f.bias"])
        tok, logits = readout(head, xf, sp, return_logits)
        B = sample_idx.shape[0]
        stopped = _proc.check_stops(tok, sp["stop"],
                                    jnp.ones((B,), bool))
        counts = None
        if penalties:
            counts = _proc.update_counts(sp["counts"], sp["crows"], tok,
                                         sp["row_done"])
        if return_logits:
            return tok, stopped, kc, vc, counts, logits
        return tok, stopped, kc, vc, counts

    return packed_prefill_fn


@functools.lru_cache(maxsize=64)
def _jitted_packed_prefill(spec, block_size, return_logits, donate, mode,
                           kv_quant=False):
    import jax

    fn = _build_packed_prefill(spec, block_size, return_logits, mode,
                               kv_quant)
    return jax.jit(fn, donate_argnums=(6, 7) if donate else ())


@functools.lru_cache(maxsize=32)
def _verify_trunk(spec, block_size, kv_quant=False, cq=None, mesh=None):
    """The packed trunk specialized to the verify plan's PINNED layout:
    T = P * W with one W-token region per plan row (verifier.py). Same
    embed/scatter/MLP as `_packed_trunk`, but attention goes through
    `ops.verify_window_attention` — on TPU that is literally the
    packed-prefill Pallas kernel on the flattened stream; off TPU the
    dense [P, W] layout avoids the generic packed fallback's cross-row
    score materialization (P-fold wasted compute on a dispatch that
    runs every scheduler round)."""
    import jax.numpy as jnp

    L, H, Dh, E, eps, tied = spec
    scale = Dh ** -0.5
    BS = int(block_size)
    kv_write = _kv_write(bool(kv_quant))
    hp = _layer_helpers(spec, cq)

    def trunk(params, toks, seg, pos, tables, kc, vc):
        from ..ops.attention import verify_window_attention

        T = toks.shape[0]
        P = tables.shape[0]
        W = T // P
        dt = params["ln_f.weight"].dtype
        embed, _head = hp.make_embed_head(params, dt)
        valid = pos >= 0
        p0 = jnp.where(valid, pos, 0)
        x = embed(toks) + params["wpe.weight"][p0]        # [T, E]
        blk = jnp.where(valid, tables[seg, p0 // BS], 0)  # [T]
        off = p0 % BS
        pos2 = pos.reshape(P, W)
        for i in range(L):
            a = hp.ln(x, params[f"h.{i}.ln_1.weight"],
                      params[f"h.{i}.ln_1.bias"])
            q, k, v = hp.qkv_split(params, i, a)          # [T, H, Dh]
            kc = kv_write(kc, i, blk, off, k)
            vc = kv_write(vc, i, blk, off, v)
            o = verify_window_attention(
                q.reshape(P, W, H, Dh), kc, vc, tables, pos2,
                scale=scale, mesh=mesh, layer=i).reshape(T, E)
            x = hp.block_and_mlp(params, i, x, o, dt)
        return x, kc, vc

    return trunk


@functools.lru_cache(maxsize=64)
def _build_packed_verify(spec, block_size, mode, kv_quant=False,
                         rep_constraint=None, cq=None):
    """Speculative verification (spec_decode round): score a packed
    stream of [last_token, draft_1 .. draft_k] regions — one region per
    speculating slot — in ONE ragged dispatch, and decide acceptance ON
    DEVICE with the same per-slot sampling pipeline a plain decode step
    would run.

    Because the PR 5 PRNG is counter-based (`fold_in(seed, step)` — a
    pure function of the request seed and the generation step), the
    target's token at every draft position is DETERMINISTIC given its
    logits: rejection sampling against it reduces to exact match.
    Draft j is accepted iff it equals the token the target pipeline
    samples at step base+j-1 AND every earlier draft was accepted;
    greedy requests degenerate to argmax match. The emitted tokens are
    therefore the exact tokens non-speculative decode would have
    produced, regardless of how many drafts were accepted."""
    import jax
    import jax.numpy as jnp

    from ..sampling import processors as _proc

    sampled, penalties = mode
    hp = _layer_helpers(spec, cq)
    trunk = _verify_trunk(spec, block_size, bool(kv_quant), cq,
                          _mesh_of(rep_constraint))
    pin = _rep_pin(rep_constraint)
    readout = _make_readout(cq, pin, mode, _proc)

    def verify_fn(params, toks, seg, pos, tables, sample_idx, dlen,
                  kc, vc, sp):
        """toks/seg/pos: packed stream as in packed_prefill, holding
        each speculating slot's last emitted token followed by its
        draft tokens (K/V written at positions pos..pos+k — rejected
        tail positions are rolled back host-side via
        PagedKVCache.truncate_seq). sample_idx [P, K1] packed index of
        each plan row's verify position j (clamped to the region end
        for j > dlen); dlen [P] draft count per row — 0 is a REAL row
        with no drafts this round (its single verify position is
        exactly a decode step, so draft-free slots ride the same
        dispatch), -1 marks a padding row. sp: verify_args buffers —
        per-row base PRNG steps in sp["steps"]; position j samples at
        step base+j.

        Returns (vtok [P, K1] target tokens, accepted [P] accepted
        draft counts, stopped [P, K1] per-position stop flags, kc, vc,
        counts|None). Row r's emitted tokens are vtok[r, :accepted+1]
        truncated after the first stopped position — exactly what
        accepted+1 sequential decode steps would have emitted."""
        P, K1 = sample_idx.shape
        x, kc, vc = trunk(params, toks, seg, pos, tables, kc, vc)
        _embed, head = hp.make_embed_head(
            params, params["ln_f.weight"].dtype)
        xf = x[sample_idx.reshape(-1)]                    # [P*K1, E]
        xf = hp.ln(xf, params["ln_f.weight"], params["ln_f.bias"])
        fed = toks[sample_idx]                            # [P, K1]
        j = jnp.arange(K1)[None, :]
        draft_valid = (j >= 1) & (j <= dlen[:, None])     # real drafts
        row_valid = dlen >= 0
        # flatten the per-row sp columns to per-position rows (row-major
        # [P, K1] order matches the logits reshape)
        spf = {"stop": jnp.repeat(sp["stop"], K1, axis=0)}
        if sampled:
            for col in ("temperature", "top_k", "top_p", "min_p",
                        "seeds", "sample"):
                spf[col] = jnp.repeat(sp[col], K1, axis=0)
            # position j is generation step base+j: the SAME counter a
            # plain decode step would fold in — fixed-seed invariance
            spf["steps"] = (sp["steps"][:, None]
                            + jnp.arange(K1)[None, :]).reshape(-1)
        if penalties:
            for col in ("rep", "pres", "freq"):
                spf[col] = jnp.repeat(sp[col], K1, axis=0)
            # position j's "text so far" includes drafts 1..j (they ARE
            # the emitted tokens whenever position j's verdict matters)
            base = sp["counts"][sp["crows"]]              # [P, V]
            V = base.shape[-1]
            oh = jax.nn.one_hot(fed, V, dtype=jnp.int32) \
                * draft_valid[..., None].astype(jnp.int32)
            spf["counts"] = (base[:, None]
                             + jnp.cumsum(oh, axis=1)).reshape(P * K1, V)
        tok, _logits = readout(head, xf, spf, False)      # [P*K1]
        vtok = tok.reshape(P, K1)
        stopped = _proc.check_stops(
            tok, spf["stop"], jnp.repeat(row_valid, K1)).reshape(P, K1)
        # draft j accepted iff it matches the target's token at the
        # previous position and every earlier draft was accepted
        matches = (fed[:, 1:] == vtok[:, :-1]) & draft_valid[:, 1:]
        accepted = jnp.cumprod(matches.astype(jnp.int32),
                               axis=1).sum(axis=1).astype(jnp.int32)
        counts = None
        if penalties:
            # count exactly the emitted tokens: vtok[:, :accepted+1]
            # truncated after the first stop (host truncation beyond
            # that — stop strings / budget — always ends the request,
            # so its counts row is reset on the next admit anyway)
            sint = stopped.astype(jnp.int32)
            stop_before = jnp.cumsum(sint, axis=1) - sint
            emit = (j <= accepted[:, None]) & (stop_before == 0) \
                & row_valid[:, None]
            counts = _proc.update_counts(
                sp["counts"], jnp.repeat(sp["crows"], K1), tok,
                emit.reshape(-1))
        return vtok, accepted, stopped, kc, vc, counts

    return verify_fn


@functools.lru_cache(maxsize=64)
def _jitted_packed_verify(spec, block_size, donate, mode,
                          kv_quant=False):
    import jax

    fn = _build_packed_verify(spec, block_size, mode, kv_quant)
    return jax.jit(fn, donate_argnums=(7, 8) if donate else ())


@functools.lru_cache(maxsize=64)
def _build_unified_round(spec, block_size, mode, kv_quant=False,
                         rep_constraint=None, window=False, cq=None):
    """The ONE-KERNEL serving round (r16): score a single packed token
    stream mixing prefill chunk rows, plain decode rows and
    speculative verify regions — the whole scheduler round — in ONE
    dispatch over the generic `_packed_trunk` (attention =
    `ops.unified_stream_attention`, the segment-causal kernel that
    already generalizes all three row kinds).

    The readout generalizes `_build_packed_verify`: every plan row has
    up to K1 = K+1 verify positions (`sample_idx` [P, K1]) and `dlen`
    drafts — a plain decode row is dlen=0 (its one position IS its
    decode step), a prefill row completing its prompt this round is
    dlen=0 at base PRNG step len(generated so far), a still-feeding
    prefill row (or a padding row) is dlen=-1 and emits nothing while
    its K/V writes land normally.  Acceptance, stop flags and penalty
    counting are exactly the verify program's — so the unified round
    is token-identical to the split packed_prefill + step +
    packed_verify sequence by construction.

    DEVICE CARRY (async double-buffered loop): the round's inputs may
    be the PREVIOUS round's device outputs, resolved on device so the
    host never syncs between rounds.  `carry_tok/carry_pos/
    carry_steps` [S] are slot-indexed arrays from the previous
    dispatch; `carry_map`/`pos_map` [T] name the slot whose carry
    value feeds a stream position (-1 = the host-provided
    toks/pos value; carried `pos` entries hold the offset WITHIN the
    region, added to the slot's carried write position), and
    `steps_map` [P] likewise overrides a row's base PRNG step.  The
    round emits the updated carry: for every emitting row, its slot's
    next decode input token (the last token emitted this round, stop-
    truncated), next write position and next PRNG step — chaining
    round N's samples into round N+1's decode rows entirely on
    device.  A synchronous unified round passes all maps as -1 and
    zero carries: the program is then a pure function of the host
    plan."""
    import jax
    import jax.numpy as jnp

    from ..sampling import processors as _proc

    sampled, penalties = mode
    hp = _layer_helpers(spec, cq)
    # window=True: the chunk-free round specialization — every plan
    # row is one pinned W-token region (T = P * W exactly), so the
    # trunk is `_verify_trunk` and off-TPU attention runs the dense
    # per-row [P, W] fallback instead of the generic packed fallback's
    # P-fold cross-row materialization.  The same CPU lesson the r11
    # verify dispatch learned — and steady-state decode rounds (no
    # admission churn) are the common case, so they must not pay the
    # mixed-round geometry.  window=False scores the general mixed
    # stream (chunk rows + step rows) over `_packed_trunk`.
    trunk = (_verify_trunk if window else _packed_trunk)(
        spec, block_size, bool(kv_quant), cq,
        mesh=_mesh_of(rep_constraint))
    pin = _rep_pin(rep_constraint)
    readout = _make_readout(cq, pin, mode, _proc)

    def unified_fn(params, toks, seg, pos, tables, sample_idx, dlen,
                   row_slot, carry_map, pos_map, steps_map, carry_tok,
                   carry_pos, carry_steps, kc, vc, sp):
        """Returns (vtok [P, K1], accepted [P], stopped [P, K1], kc,
        vc, counts|None, carry_tok [S], carry_pos [S],
        carry_steps [S])."""
        P, K1 = sample_idx.shape
        S = carry_tok.shape[0]
        # resolve device-carried inputs (sync rounds: every map is -1
        # and the where is the identity on the host plan)
        cm = jnp.clip(carry_map, 0, S - 1)
        toks_eff = jnp.where(carry_map >= 0, carry_tok[cm], toks)
        pm = jnp.clip(pos_map, 0, S - 1)
        pos_eff = jnp.where(pos_map >= 0, carry_pos[pm] + pos, pos)
        x, kc, vc = trunk(params, toks_eff, seg, pos_eff, tables, kc,
                          vc)
        _embed, head = hp.make_embed_head(
            params, params["ln_f.weight"].dtype)
        xf = x[sample_idx.reshape(-1)]                    # [P*K1, E]
        xf = hp.ln(xf, params["ln_f.weight"], params["ln_f.bias"])
        fed = toks_eff[sample_idx]                        # [P, K1]
        j = jnp.arange(K1)[None, :]
        draft_valid = (j >= 1) & (j <= dlen[:, None])     # real drafts
        row_valid = dlen >= 0
        sm = jnp.clip(steps_map, 0, S - 1)
        spf = {"stop": jnp.repeat(sp["stop"], K1, axis=0)}
        if sampled:
            for col in ("temperature", "top_k", "top_p", "min_p",
                        "seeds", "sample"):
                spf[col] = jnp.repeat(sp[col], K1, axis=0)
            # position j is generation step base+j — the SAME counter a
            # plain decode step (or the split verify) would fold in, so
            # fixed-seed output is invariant to the round fusion
            base = jnp.where(steps_map >= 0, carry_steps[sm],
                             sp["steps"])
            spf["steps"] = (base[:, None]
                            + jnp.arange(K1)[None, :]).reshape(-1)
        else:
            base = jnp.zeros((P,), jnp.int32)
        if penalties:
            for col in ("rep", "pres", "freq"):
                spf[col] = jnp.repeat(sp[col], K1, axis=0)
            # position j's "text so far" includes drafts 1..j (they ARE
            # the emitted tokens whenever position j's verdict matters)
            bc = sp["counts"][sp["crows"]]                # [P, V]
            V = bc.shape[-1]
            oh = jax.nn.one_hot(fed, V, dtype=jnp.int32) \
                * draft_valid[..., None].astype(jnp.int32)
            spf["counts"] = (bc[:, None]
                             + jnp.cumsum(oh, axis=1)).reshape(P * K1, V)
        tok, _logits = readout(head, xf, spf, False)      # [P*K1]
        vtok = tok.reshape(P, K1)
        stopped = _proc.check_stops(
            tok, spf["stop"], jnp.repeat(row_valid, K1)).reshape(P, K1)
        matches = (fed[:, 1:] == vtok[:, :-1]) & draft_valid[:, 1:]
        accepted = jnp.cumprod(matches.astype(jnp.int32),
                               axis=1).sum(axis=1).astype(jnp.int32)
        # emitted positions: the accepted prefix plus the bonus token,
        # truncated after the first stop — exactly the tokens the host
        # reads out (and the split path would have emitted)
        sint = stopped.astype(jnp.int32)
        stop_before = jnp.cumsum(sint, axis=1) - sint
        emit = (j <= accepted[:, None]) & (stop_before == 0) \
            & row_valid[:, None]
        counts = None
        if penalties:
            counts = _proc.update_counts(
                sp["counts"], jnp.repeat(sp["crows"], K1), tok,
                emit.reshape(-1))
        # device carry for the NEXT round: per emitting row, the
        # slot's next decode input (last emitted token), next write
        # position and next PRNG step. Rows that emit nothing (feeding
        # prefill, pads) and slots with no row pass through unchanged,
        # so carry values persist across rounds that skip a slot.
        emit_n = emit.sum(axis=1)                          # >= 1 valid
        last = vtok[jnp.arange(P), jnp.maximum(emit_n - 1, 0)]
        p0 = pos_eff[sample_idx[:, 0]]
        upd = row_valid & (row_slot >= 0)
        # out-of-range index = dropped scatter: masked rows touch nothing
        si = jnp.where(upd, jnp.clip(row_slot, 0, S - 1), S)
        carry_tok = carry_tok.at[si].set(last, mode="drop")
        carry_pos = carry_pos.at[si].set(p0 + emit_n, mode="drop")
        carry_steps = carry_steps.at[si].set(base + emit_n, mode="drop")
        return (vtok, accepted, stopped, kc, vc, counts, carry_tok,
                carry_pos, carry_steps)

    return unified_fn


@functools.lru_cache(maxsize=64)
def _jitted_unified_round(spec, block_size, donate, mode,
                          kv_quant=False, window=False):
    import jax

    fn = _build_unified_round(spec, block_size, mode, kv_quant,
                              window=window)
    return jax.jit(fn, donate_argnums=(14, 15) if donate else ())


@functools.lru_cache(maxsize=64)
def _jitted_paged_fns(spec, block_size, return_logits, donate, mode,
                      kv_quant=False):
    import jax

    prefill_fn, step_fn = _build_paged_fns(spec, block_size,
                                           return_logits, mode, kv_quant)
    dp = (4, 5) if donate else ()   # kc, vc in prefill_fn
    ds = (5, 6) if donate else ()   # kc, vc in step_fn
    return (jax.jit(prefill_fn, donate_argnums=dp),
            jax.jit(step_fn, donate_argnums=ds))


@functools.lru_cache(maxsize=64)
def _jitted_block_programs(desc, block_size, return_logits, donate, mode):
    """(packed_prefill, decode_step) jitted for a `DecoderDescription`
    (`decode_blocks.build_block_programs`): its caches ((kc, state), or
    (kc, vc, state) where the pool holds K and V rows) donated, as
    GPT-2's pools are."""
    import jax

    from .decode_blocks import build_block_programs

    packed_fn, step_fn = build_block_programs(desc, block_size,
                                              return_logits, mode)
    n = 3 if desc.values else 2
    return (jax.jit(packed_fn, donate_argnums=tuple(range(6, 6 + n))
                    if donate else ()),
            jax.jit(step_fn, donate_argnums=tuple(range(5, 5 + n))
                    if donate else ()))


def _not_built(program, option):
    """A program slot of a description that has no such program yet."""
    def refuse(*_a, **_k):
        raise ValueError(
            f"PagedDecoder.{program} is built for the GPT-2 layout only: "
            f"a description with latent or recurrent layers serves "
            f"through packed_prefill and decode_step ({option} has no "
            f"meaning beside it yet)")

    return refuse


@functools.lru_cache(maxsize=32)
def _sharded_jits(spec, block_size, return_logits, donate, mode,
                  kv_quant, sh, cq=None, sp_attention="allgather"):
    """The four decode programs jitted with EXPLICIT in/out shardings
    (sharded-serving round): params per the serving_dist plan, kc/vc
    pinned to the per-shard pool layout on BOTH sides (so the pool
    sharding is stable across the functional round-trip and never
    re-propagates), every host-side input/output replicated.  The
    traced functions are the exact `_build_*` programs the unsharded
    path jits — sharding is a placement property, so XLA partitions the
    same HLO and inserts the TP collectives itself.  Cached
    process-wide per (program, mode, shardings bundle) — the bundle is
    hashable, so servers on equal meshes share compiled programs.

    A mesh with sp > 1 (long-context round) swaps ONLY the packed-
    prefill program for its sequence-parallel variant (`_packed_trunk`
    sp_mesh path); decode/verify/unified stay the plain TP programs —
    decode stays TP by design, and sp=1 meshes trace the exact
    pre-round programs bitwise."""
    import jax

    pr, kv, rep = sh.params, sh.kv, sh.rep
    sp_mesh = (sh.mesh
               if dict(sh.mesh.shape).get("sp", 1) > 1 else None)
    prefill_fn, step_fn = _build_paged_fns(spec, block_size,
                                           return_logits, mode, kv_quant,
                                           rep, cq)
    packed_fn = _build_packed_prefill(spec, block_size, return_logits,
                                      mode, kv_quant, rep, cq, sp_mesh,
                                      sp_attention)
    verify_fn = _build_packed_verify(spec, block_size, mode, kv_quant,
                                     rep, cq)
    unified_fn = _build_unified_round(spec, block_size, mode, kv_quant,
                                      rep, cq=cq)
    uniwin_fn = _build_unified_round(spec, block_size, mode, kv_quant,
                                     rep, window=True, cq=cq)
    tail = (rep,) if return_logits else ()
    out5 = (rep, rep, kv, kv, rep) + tail
    prefill = jax.jit(
        prefill_fn, in_shardings=(pr, rep, rep, rep, kv, kv, rep),
        out_shardings=out5, donate_argnums=(4, 5) if donate else ())
    step = jax.jit(
        step_fn, in_shardings=(pr, rep, rep, rep, rep, kv, kv, rep, rep),
        out_shardings=out5, donate_argnums=(5, 6) if donate else ())
    packed = jax.jit(
        packed_fn,
        in_shardings=(pr, rep, rep, rep, rep, rep, kv, kv, rep),
        out_shardings=out5, donate_argnums=(6, 7) if donate else ())
    verify = jax.jit(
        verify_fn,
        in_shardings=(pr, rep, rep, rep, rep, rep, rep, kv, kv, rep),
        out_shardings=(rep, rep, rep, kv, kv, rep),
        donate_argnums=(7, 8) if donate else ())
    ush = dict(
        in_shardings=(pr,) + (rep,) * 13 + (kv, kv, rep),
        out_shardings=(rep, rep, rep, kv, kv, rep, rep, rep, rep),
        donate_argnums=(14, 15) if donate else ())
    unified = jax.jit(unified_fn, **ush)
    uniwin = jax.jit(uniwin_fn, **ush)
    return prefill, step, packed, verify, unified, uniwin


@functools.lru_cache(maxsize=64)
def _build_multistep(spec, block_size, n_steps, mode, kv_quant=False,
                     rep_constraint=None, cq=None):
    """`n_steps` decode tokens in ONE dispatch (a lax.scan over step_fn):
    multi-step scheduling for dispatch-latency-bound serving — where
    the per-dispatch cost rivals a decode step, the server amortizes it
    over n_steps tokens and discards (at most n_steps-1)
    post-stop/post-budget tokens host-side. Per-slot PRNG steps
    advance with the scan index, so the fused scan draws the same
    per-request streams as n_steps separate
    dispatches. Returns (toks [n_steps, B], stopped [n_steps, B], kc,
    vc, counts|None). Raw and jittable."""
    import jax

    _, step_fn = _build_paged_fns(spec, block_size, False, mode,
                                  kv_quant, rep_constraint, cq)
    sampled, penalties = mode

    def multi(params, tok, pos, active, tables, kc, vc, sp):
        def body(carry, j):
            tok, pos, kc, vc, counts = carry
            spj = dict(sp)
            if sampled:
                spj["steps"] = sp["steps"] + j
            if penalties:
                spj["counts"] = counts
            nxt, stopped, kc, vc, counts = step_fn(
                params, tok, pos, active, tables, kc, vc, spj)
            if not penalties:
                counts = carry[4]
            return (nxt, pos + 1, kc, vc, counts), (nxt, stopped)

        counts0 = sp.get("counts")
        (tok, pos, kc, vc, counts), (toks, stops) = jax.lax.scan(
            body, (tok, pos, kc, vc, counts0),
            jax.numpy.arange(n_steps))
        return toks, stops, kc, vc, counts

    return multi


@functools.lru_cache(maxsize=64)
def _jitted_multistep(spec, block_size, n_steps, donate, mode,
                      kv_quant=False):
    import jax

    multi = _build_multistep(spec, block_size, n_steps, mode, kv_quant)
    return jax.jit(multi, donate_argnums=(5, 6) if donate else ())


@functools.lru_cache(maxsize=32)
def _sharded_multistep(spec, block_size, n_steps, donate, mode,
                       kv_quant, sh, cq=None):
    """Explicit-in/out-sharded multistep jit, cached process-wide per
    shardings bundle (see _sharded_jits)."""
    import jax

    pr, kv, rep = sh.params, sh.kv, sh.rep
    return jax.jit(
        _build_multistep(spec, block_size, n_steps, mode, kv_quant,
                         rep, cq),
        in_shardings=(pr, rep, rep, rep, rep, kv, kv, rep),
        out_shardings=(rep, rep, kv, kv, rep),
        donate_argnums=(5, 6) if donate else ())


class PagedDecoder:
    """Jitted (prefill, step, packed_prefill) family over the paged KV
    cache for one GPT-2-layout spec. Instances are cheap — the compiled
    functions are cached process-wide by (spec, block_size,
    return_logits, mode, kv_quant); per-instance only the tracing
    wrappers are held. `mode` is the (any_sampled, any_penalties)
    static pair from `SlotParamStore.mode()` — the default is the
    all-greedy fast path.

    kv_dtype: None pairs with a dense `PagedKVCache`; "int8" pairs
    with `PagedKVCache(kv_dtype="int8")` — appends quantize on write,
    attention dequantizes inside the kernel. Every dispatch checks the
    pairing EAGERLY (`_check_kv`): an int8 decoder handed dense bf16
    cache arrays (or vice versa) raises a ValueError naming the
    mismatched argument instead of failing deep inside a jit trace.

    shardings: a `serving_dist.DecodeShardings` bundle (sharded
    serving round) makes every program an explicit-in/out-sharded jit
    over the bundle's mesh — params per the TP plan, kc/vc pinned to
    the per-shard pool layout on both sides of the functional
    round-trip, host-side inputs/outputs replicated, and the head
    logits pinned replicated before the sampling pipeline
    (`_rep_pin`). These jits are cached per decoder INSTANCE; None
    (the default) uses the exact pre-round process-wide caches.

    collective_quant (quantized-collectives round): a
    `serving_dist.collectives.CollectiveQuant` routes the sharded
    programs' mp-axis collectives (row-split psums, embed psum,
    vocab-parallel logits) through the quantized shard_map seams.
    Requires `shardings`; None keeps the exact r16 programs.  Sharded
    decoders additionally keep HOST-SIDE wire-byte accounting per
    dispatch (`wire_stats()` — analytic formulas mirroring the seams,
    counted for the actual path AND the bf16 baseline)."""

    def __init__(self, spec, block_size, return_logits=False, donate=None,
                 kv_dtype=None, shardings=None, collective_quant=None,
                 sp_attention="allgather"):
        import jax

        if donate is None:  # CPU donation is a no-op warning in jaxlib
            donate = jax.default_backend() not in ("cpu",)
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                             "(supported: None, 'int8')")
        if sp_attention != "allgather":
            # the default mode needs no validation and must not pull
            # serving_dist in (the unsharded path never imports it);
            # any non-default value — including a bogus one — takes
            # this branch and validates against the canonical tuple
            from ..serving_dist.config import SP_ATTENTION_MODES

            if sp_attention not in SP_ATTENTION_MODES:
                raise ValueError(
                    f"PagedDecoder(sp_attention={sp_attention!r}): "
                    f"must be one of {SP_ATTENTION_MODES}")
        if sp_attention != "allgather" and shardings is None:
            raise ValueError(
                f"PagedDecoder(sp_attention={sp_attention!r}) requires "
                f"shardings with an sp>1 mesh — memory-flat sequence-"
                f"parallel attention only exists on an sp mesh")
        if collective_quant is not None and shardings is None:
            raise ValueError(
                "collective_quant requires shardings: quantized "
                "collectives only exist on a sharded mesh")
        # GPT-2's six-field tuple, or a `decode_blocks.DecoderDescription`,
        # which has packed_prefill and decode_step, unsharded and over
        # dense caches, and says so here
        from .decode_blocks import DecoderDescription

        self.description = spec if isinstance(spec, DecoderDescription) \
            else None
        if self.description is not None:
            for name, value, ok in (
                    ("kv_dtype", kv_dtype, None),
                    ("shardings", shardings, None),
                    ("collective_quant", collective_quant, None),
                    ("sp_attention", sp_attention, "allgather")):
                if value != ok:
                    raise ValueError(
                        f"PagedDecoder({name}={value!r}) has no meaning "
                        f"yet beside latent or recurrent layers: their "
                        f"caches are dense and their programs unsharded")
        self.spec = self.description or tuple(spec)
        self.block_size = int(block_size)
        self.return_logits = bool(return_logits)
        self.kv_dtype = kv_dtype
        self._kv_quant = kv_dtype == "int8"
        self._donate = bool(donate)
        # sharded serving: a serving_dist.DecodeShardings bundle makes
        # every program an explicit-in/out-sharded jit over the bundle's
        # mesh (None = the exact pre-round process-cached jits)
        self._shardings = shardings
        self._cq = collective_quant
        # sp_attention (memory-flat round): how the sp>1 packed-prefill
        # trunk attends across shards; "allgather" is the exact r21
        # path, and sp=1 meshes normalize ring/ulysses back to it (the
        # degenerate mesh has nothing to rotate — config.py logs it)
        if shardings is not None \
                and int(dict(shardings.mesh.shape).get("sp", 1)) <= 1:
            sp_attention = "allgather"
        self._sp_attention = sp_attention
        # wire-byte accounting (sharded decoders only): {(collective,
        # dtype): bytes} incremented host-side per dispatch, the
        # "baseline" dtype carrying what bf16 would have shipped
        import threading

        self._wire_lock = threading.Lock()
        self._wire = {}
        self._tp = 1
        if shardings is not None:
            self._tp = int(dict(shardings.mesh.shape).get("mp", 1))
        self._variants = {}
        self._msteps = {}
        self._prev0 = {}   # rows -> the zeros `step` takes for no `prev`

    @property
    def tp_degree(self):
        """Mesh tensor-parallel degree the decoder dispatches over
        (1 = unsharded: no collective wire, `wire_stats` stays zero)."""
        return self._tp

    def _check_kv(self, kc, vc):
        """Eager dtype-consistency assert (CI/tooling satellite): the
        cache arrays must match the decoder's kv_dtype BEFORE any jit
        tracing, so a miswired server fails with the argument named."""
        if self.description is not None:
            values = self.description.values
            if vc is not None and not values:
                raise ValueError(
                    "a latent pool has no V: PagedDecoder takes vc=None "
                    "and the store as state= for this description")
            if vc is None and values:
                raise ValueError(
                    "this description's pool holds K and V rows: "
                    "PagedDecoder takes both, and the store as state=")
            return   # pools and a state store: dense by construction
        for name, arr in (("kc", kc), ("vc", vc)):
            got = hasattr(arr, "codes")
            if got != self._kv_quant:
                have = "a quantized int8 (QuantizedKV)" if got \
                    else "a dense"
                raise ValueError(
                    f"kv dtype mismatch: PagedDecoder(kv_dtype="
                    f"{self.kv_dtype!r}) was handed {have} cache array "
                    f"for argument '{name}' — build the PagedKVCache "
                    f"and the PagedDecoder with the SAME kv_dtype")

    @property
    def _shard_label(self):
        """The `shard` label compile metrics carry (serving_dist
        round): the bundle's mesh shape for sharded decoders, "none"
        for the single-device path."""
        if self._shardings is None:
            return "none"
        return getattr(self._shardings, "shard_label", "mesh")

    def _variant(self, mode):
        """(prefill, step, packed_prefill, packed_verify,
        unified_round, unified_round_window) tracing-wrapped jitted
        fns for one static sampling mode.
        Dispatch-boundary spans (ISSUE 2): every jitted call is its
        own span (`pt:step_dispatch` etc. in a profiler's trace, inside
        the engine's `pt:dispatch` phase; an event of the JSONL log
        when tracing is on) — the device-side cost inside a request's
        prefill/decode phases. Compile tracking (ISSUE 10) wraps INSIDE
        the span: any call that grew the jit's executable cache is
        recorded as an XLA compile of that program, labeled with
        whether requests were in flight — the event that lets a bench
        window prove itself compile-clean."""
        v = self._variants.get(mode)
        if v is None:
            from ..observability import compile_tracker as _ct
            from ..observability import tracing as _tracing

            if self.description is not None:
                packed, step = _jitted_block_programs(
                    self.description, self.block_size, self.return_logits,
                    self._donate, mode)
                prefill = _not_built("prefill", "the padded-batch prefill")
                verify = _not_built("packed_verify", "speculation")
                unified = uniwin = _not_built("unified_round",
                                              "unified_round")
            elif self._shardings is not None:
                (prefill, step, packed, verify, unified,
                 uniwin) = _sharded_jits(
                    self.spec, self.block_size, self.return_logits,
                    self._donate, mode, self._kv_quant,
                    self._shardings, self._cq, self._sp_attention)
            else:
                prefill, step = _jitted_paged_fns(
                    self.spec, self.block_size, self.return_logits,
                    self._donate, mode, self._kv_quant)
                packed = _jitted_packed_prefill(
                    self.spec, self.block_size, self.return_logits,
                    self._donate, mode, self._kv_quant)
                verify = _jitted_packed_verify(
                    self.spec, self.block_size, self._donate, mode,
                    self._kv_quant)
                unified = _jitted_unified_round(
                    self.spec, self.block_size, self._donate, mode,
                    self._kv_quant)
                uniwin = _jitted_unified_round(
                    self.spec, self.block_size, self._donate, mode,
                    self._kv_quant, window=True)
            sh = self._shard_label
            v = (_tracing.wrap("prefill_dispatch",
                               _ct.wrap("prefill", prefill, sh)),
                 _tracing.wrap("step_dispatch",
                               _ct.wrap("decode_step", step, sh)),
                 _tracing.wrap("packed_prefill_dispatch",
                               _ct.wrap("packed_prefill", packed, sh)),
                 _tracing.wrap("verify_dispatch",
                               _ct.wrap("packed_verify", verify, sh)),
                 _tracing.wrap("unified_round_dispatch",
                               _ct.wrap("unified_round", unified, sh)),
                 _tracing.wrap("unified_round_dispatch",
                               _ct.wrap("unified_round", uniwin, sh)))
            if self._tp > 1:
                # wire-byte accounting (quantized-collectives round):
                # analytic per-dispatch bytes from the host-visible
                # shapes — rows through the trunk and head readout rows
                # per program (prefill pads count: they cross the wire)
                v = (self._acct_wrap(v[0], mode, lambda a: (
                        a[1].shape[0] * a[1].shape[1], a[1].shape[0])),
                     self._acct_wrap(v[1], mode, lambda a: (
                        a[1].shape[0], a[1].shape[0])),
                     self._acct_wrap(v[2], mode, lambda a: (
                        a[1].shape[0], a[5].shape[0])),
                     self._acct_wrap(v[3], mode, lambda a: (
                        a[1].shape[0],
                        a[5].shape[0] * a[5].shape[1])),
                     self._acct_wrap(v[4], mode, lambda a: (
                        a[1].shape[0],
                        a[5].shape[0] * a[5].shape[1])),
                     self._acct_wrap(v[5], mode, lambda a: (
                        a[1].shape[0],
                        a[5].shape[0] * a[5].shape[1])))
            self._variants[mode] = v
        return v

    # ---- wire-byte accounting (quantized-collectives round) ----------

    def _acct_wrap(self, fn, mode, rows_fn):
        def wrapped(*args):
            trunk_rows, logit_rows = rows_fn(args)
            self._account(args[0], mode, trunk_rows, logit_rows)
            return fn(*args)

        return wrapped

    def _account(self, params, mode, trunk_rows, logit_rows):
        from ..serving_dist import collectives as _coll

        wte = params.get("wte.weight")
        if wte is None:
            wte = params["wte.weight::w8c"]
        dt = params["ln_f.weight"].dtype
        greedy_fast = (self._cq is not None and mode == GREEDY_MODE
                       and not self.return_logits)
        bytes_by_key = _coll.dispatch_wire_bytes(
            spec=self.spec, vocab=wte.shape[0], tp=self._tp,
            mode=(self._cq.mode if self._cq is not None else None),
            group=(self._cq.group if self._cq is not None else 32),
            trunk_rows=int(trunk_rows), logit_rows=int(logit_rows),
            greedy_fast=greedy_fast, base_itemsize=dt.itemsize)
        with self._wire_lock:
            for key, nbytes in bytes_by_key.items():
                self._wire[key] = self._wire.get(key, 0) + nbytes
        _coll.record_wire_bytes(bytes_by_key)

    def wire_stats(self):
        """Accumulated per-device collective wire bytes since the last
        `reset_wire_stats()`: {"bytes_total", "bytes_baseline",
        "by_collective"} — bytes_total is the path actually dispatched
        (= bytes_baseline when collective_quant is off), bytes_baseline
        what the bf16 collectives would have shipped for the same
        dispatches. Zeros for unsharded / tp=1 decoders."""
        with self._wire_lock:
            items = list(self._wire.items())
        total = baseline = 0
        by = {}
        for (name, dtype), nbytes in items:
            if dtype == "baseline":
                baseline += nbytes
            else:
                total += nbytes
                by[name] = by.get(name, 0) + nbytes
        return {"bytes_total": total, "bytes_baseline": baseline,
                "by_collective": by}

    def reset_wire_stats(self):
        with self._wire_lock:
            self._wire.clear()

    def prefill(self, params, ids, lens, tables, kc, vc, sp,
                mode=GREEDY_MODE):
        self._check_kv(kc, vc)
        return self._variant(mode)[0](params, ids, lens, tables, kc, vc,
                                      sp)

    def step(self, params, tok, pos, active, tables, kc, vc, sp,
             mode=GREEDY_MODE, state=None, prev=None):
        """One decode token a row.  GPT-2: (token, stopped, kc, vc,
        counts[, logits]).  A `DecoderDescription` takes its cache's store
        as `state` and, for a latent pool, vc=None, and returns (token,
        stopped, kc, state, counts, routed[, logits]), with vc between kc
        and state where its pool holds K and V rows (`decode_blocks`).
        `prev` is the
        token result of the step before, for the rows whose `tok` is
        negative (`step_fn`); None where no row is."""
        self._check_kv(kc, vc)
        if prev is None:
            prev = self._no_prev(tok.shape[0])
        return self._variant(mode)[1](
            params, tok, pos, active, tables, *self._caches(kc, vc, state),
            sp, prev)

    def _caches(self, kc, vc, state):
        """The cache arguments of a program: GPT-2's (kc, vc), a
        description's (kc, state) or (kc, vc, state)."""
        if self.description is None:
            return kc, vc
        return (kc, state) if vc is None else (kc, vc, state)

    def _no_prev(self, rows):
        """The `prev` of a step that follows none: zeros, placed as a
        step's own token result is, so that the first step of a chain
        and the later ones are one executable."""
        z = self._prev0.get(rows)
        if z is None:
            import jax
            import jax.numpy as jnp

            z = jnp.zeros((rows,), jnp.int32)
            if self._shardings is not None:
                z = jax.device_put(z, self._shardings.rep)
            self._prev0[rows] = z
        return z

    def packed_prefill(self, params, toks, seg, pos, tables, sample_idx,
                       kc, vc, sp, mode=GREEDY_MODE, state=None):
        """A packed stream of prompt chunks; `state` and the returns as
        `step`'s."""
        self._check_kv(kc, vc)
        return self._variant(mode)[2](
            params, toks, seg, pos, tables, sample_idx,
            *self._caches(kc, vc, state), sp)

    def packed_verify(self, params, toks, seg, pos, tables, sample_idx,
                      dlen, kc, vc, sp, mode=GREEDY_MODE):
        """Speculative draft verification over a packed stream (see
        _build_packed_verify). sample_idx is [P, K1] — one readout per
        draft position plus the bonus position — and dlen [P] carries
        each plan row's draft count (0 = real draft-free row, -1 =
        padding row)."""
        self._check_kv(kc, vc)
        return self._variant(mode)[3](params, toks, seg, pos, tables,
                                      sample_idx, dlen, kc, vc, sp)

    def unified_round(self, params, toks, seg, pos, tables, sample_idx,
                      dlen, row_slot, carry_map, pos_map, steps_map,
                      carry_tok, carry_pos, carry_steps, kc, vc, sp,
                      mode=GREEDY_MODE, window=False):
        """The one-kernel serving round (see _build_unified_round):
        prefill chunk rows, decode rows and speculative verify regions
        in ONE dispatch, with optional device-carried inputs for the
        async double-buffered loop. window=True selects the chunk-free
        specialization (pinned T = P * W regions over the dense
        verify-window trunk)."""
        self._check_kv(kc, vc)
        return self._variant(mode)[5 if window else 4](
            params, toks, seg, pos, tables, sample_idx, dlen, row_slot,
            carry_map, pos_map, steps_map, carry_tok, carry_pos,
            carry_steps, kc, vc, sp)

    def multistep(self, n_steps, mode=GREEDY_MODE):
        """Fused n-token decode (see _build_multistep)."""
        from ..observability import compile_tracker as _ct
        from ..observability import tracing as _tracing

        if self.description is not None:
            return _not_built("multistep", "steps_per_dispatch > 1")
        if self._shardings is not None:
            key = (int(n_steps), mode)
            fn = self._msteps.get(key)
            if fn is None:
                fn = _sharded_multistep(self.spec, self.block_size,
                                        int(n_steps), self._donate,
                                        mode, self._kv_quant,
                                        self._shardings, self._cq)
                self._msteps[key] = fn
        else:
            fn = _jitted_multistep(self.spec, self.block_size,
                                   int(n_steps), self._donate, mode,
                                   self._kv_quant)
        wrapped = _tracing.wrap(
            "multistep_dispatch",
            _ct.wrap("multistep", fn, self._shard_label),
            k=int(n_steps))
        if self._tp > 1:
            # n_steps scanned decode steps = n_steps [B, E] psum rounds
            # and n_steps head readouts
            wrapped = self._acct_wrap(wrapped, mode, lambda a: (
                int(n_steps) * a[1].shape[0],
                int(n_steps) * a[1].shape[0]))

        def checked(params, tok, pos, active, tables, kc, vc, sp):
            self._check_kv(kc, vc)
            return wrapped(params, tok, pos, active, tables, kc, vc, sp)

        return checked

    @classmethod
    def for_config(cls, cfg, block_size, **kw):
        """Build from a GPT2Config-like object."""
        spec = (cfg.num_layers, cfg.num_heads,
                cfg.hidden_size // cfg.num_heads, cfg.hidden_size,
                cfg.layer_norm_epsilon, cfg.tie_embeddings)
        return cls(spec, block_size, **kw)

    @classmethod
    def for_model(cls, model, block_size, **kw):
        """Build from a model: its own `decoder_description()` where it
        has one, else its GPT2Config-like `cfg`."""
        if hasattr(model, "decoder_description"):
            return cls(model.decoder_description(), block_size, **kw)
        return cls.for_config(model.cfg, block_size, **kw)
