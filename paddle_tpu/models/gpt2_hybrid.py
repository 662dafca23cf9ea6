"""GPT-2 with full 4-D hybrid parallelism: dp × pp × mp × sp on ONE mesh.

The north-star configuration (BASELINE.json: "ERNIE/BERT-large pretraining
under Fleet collective mode on v5e-256") needs data, pipeline, tensor and
sequence parallelism composed in a single train step. Reference lineage:
fleet meta_optimizers (sharding/pipeline/hybrid_parallel_optimizer) rewrite
the program graph with NCCL send/recv + allreduce; here the whole step is one
shard_map over the (dp, pp, mp, sp) mesh and XLA emits the ICI collectives:

  dp — batch split; gradient reduction comes out of shard_map's transpose
       (replicated params -> psum cotangent), no hand-written allreduce.
  pp — GPipe microbatch rotation via ppermute (parallel/pipeline.py).
  mp — Megatron tensor parallel: column-split QKV/fc1, row-split out/fc2
       with one psum per half-block. QKV is stored [E, H, 3, d] so the mp
       split on H keeps each rank's q/k/v for its own heads contiguous.
  sp — ring attention over the sequence shards (parallel/ring_attention.py,
       Pallas flash kernels inside each ring step when shapes allow);
       ring_impl="zigzag" selects the load-balanced causal ring (the
       caller feeds the batch in zigzag_order layout; position embeddings
       follow the permutation inside inner()), "ulysses" the all-to-all
       mode.

Params are a flat dict of jnp arrays; per-stage leaves are stacked
[pp, L/pp, ...] so the pp axis shards stages and a lax.scan walks the
layers inside a stage. `reference_loss` computes the identical math without
any mesh for the single-device parity assertion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def pad_vocab(vocab_size, mp):
    """Megatron vocab padding: round V up so the mp axis divides it; the
    padded logit columns are masked to -inf in the loss."""
    return -(-vocab_size // mp) * mp


def init_hybrid_gpt2_params(key, vocab_size, hidden, num_heads, num_layers,
                            pp, max_position, intermediate=None,
                            dtype=jnp.float32, mp=1):
    """Flat param dict; stage leaves stacked [pp, L/pp, ...]. The embedding
    is vocab-padded to a multiple of `mp` (vocab-parallel sharding)."""
    assert num_layers % pp == 0, (num_layers, pp)
    lps = num_layers // pp
    e = hidden
    h = num_heads
    d = e // h
    f = intermediate or 4 * e
    ks = jax.random.split(key, 8)

    def nrm(k, shape, std=0.02):
        return (jax.random.normal(k, shape) * std).astype(dtype)

    v_pad = pad_vocab(vocab_size, mp)
    wte = nrm(ks[0], (vocab_size, e))
    if v_pad > vocab_size:  # padded rows zero: they receive no gradient mass
        wte = jnp.concatenate(
            [wte, jnp.zeros((v_pad - vocab_size, e), dtype)], axis=0)

    return {
        "wte": wte,
        "wpe": nrm(ks[1], (max_position, e)),
        "ln_f.w": jnp.ones((e,), dtype),
        "ln_f.b": jnp.zeros((e,), dtype),
        "blk.ln1.w": jnp.ones((pp, lps, e), dtype),
        "blk.ln1.b": jnp.zeros((pp, lps, e), dtype),
        # [E, H, 3, d]: mp splits H, so each rank holds q/k/v of its heads
        "blk.wqkv": nrm(ks[2], (pp, lps, e, h, 3, d)),
        "blk.bqkv": jnp.zeros((pp, lps, h, 3, d), dtype),
        "blk.wo": nrm(ks[3], (pp, lps, h, d, e)),
        "blk.bo": jnp.zeros((pp, lps, e), dtype),
        "blk.ln2.w": jnp.ones((pp, lps, e), dtype),
        "blk.ln2.b": jnp.zeros((pp, lps, e), dtype),
        "blk.w1": nrm(ks[4], (pp, lps, e, f)),
        "blk.b1": jnp.zeros((pp, lps, f), dtype),
        "blk.w2": nrm(ks[5], (pp, lps, f, e)),
        "blk.b2": jnp.zeros((pp, lps, e), dtype),
    }


def hybrid_param_specs(params):
    """PartitionSpec per leaf: stage dim -> pp, TP dim -> mp, rest replicated.
    (Used both as shard_map in_specs and jit in_shardings.)"""
    specs = {
        # vocab-parallel (Megatron): each mp rank owns V/mp embedding rows;
        # the embed is a masked local gather + psum, the logits stay
        # [B,S,V/mp] per rank and the loss uses psum'd softmax statistics —
        # [B,S,V] never materializes on any rank (VERDICT r2 weak #7)
        "wte": P("mp", None),
        "wpe": P(),
        "ln_f.w": P(),
        "ln_f.b": P(),
        "blk.ln1.w": P("pp"),
        "blk.ln1.b": P("pp"),
        "blk.wqkv": P("pp", None, None, "mp"),
        "blk.bqkv": P("pp", None, "mp"),
        "blk.wo": P("pp", None, "mp"),
        "blk.bo": P("pp"),
        "blk.ln2.w": P("pp"),
        "blk.ln2.b": P("pp"),
        "blk.w1": P("pp", None, None, "mp"),
        "blk.b1": P("pp", None, "mp"),
        "blk.w2": P("pp", None, "mp"),
        "blk.b2": P("pp"),
    }
    assert set(specs) == set(params)
    return specs


def _ln(x, w, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _stage_fn(stage, x, *, sp_axis, mp_axis, ring_impl):
    """One pipeline stage: scan over its L/pp layers. `stage` leaves are this
    rank's slice: [L/pp, ...] (TP dims already local)."""
    from ..parallel.ring_attention import ring_attention

    def layer(h, wl):
        a = _ln(h, wl["blk.ln1.w"], wl["blk.ln1.b"])
        qkv = jnp.einsum("bse,ehtd->bshtd", a, wl["blk.wqkv"]) \
            + wl["blk.bqkv"]
        q = jnp.moveaxis(qkv[:, :, :, 0], 1, 2)  # [mb, H_loc, S_l, d]
        k = jnp.moveaxis(qkv[:, :, :, 1], 1, 2)
        v = jnp.moveaxis(qkv[:, :, :, 2], 1, 2)
        if sp_axis is not None:
            if ring_impl == "ulysses":  # all-to-all sequence parallelism
                from ..parallel.ulysses import ulysses_attention
                o = ulysses_attention(q, k, v, axis_name=sp_axis,
                                      causal=True)
            elif ring_impl == "zigzag":
                # load-balanced causal ring: the batch (and positions —
                # see inner()) are in zigzag layout, every rank does
                # equal work per ring step
                from ..parallel.ring_attention import zigzag_ring_attention
                o = zigzag_ring_attention(q, k, v, axis_name=sp_axis)
            else:
                o = ring_attention(q, k, v, axis_name=sp_axis, causal=True,
                                   impl=ring_impl)
        else:  # no sp axis: plain causal attention
            s = q.shape[2]
            logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
            mask = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(mask, logits, -1e30)
            o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)
        att = jnp.einsum("bhsd,hde->bse", o, wl["blk.wo"])
        if mp_axis is not None:
            att = jax.lax.psum(att, mp_axis)
        h = h + att + wl["blk.bo"]
        m = _ln(h, wl["blk.ln2.w"], wl["blk.ln2.b"])
        m = jax.nn.gelu(jnp.einsum("bse,ef->bsf", m, wl["blk.w1"])
                        + wl["blk.b1"], approximate=True)
        m = jnp.einsum("bsf,fe->bse", m, wl["blk.w2"])
        if mp_axis is not None:
            m = jax.lax.psum(m, mp_axis)
        return h + m + wl["blk.b2"], None

    blk = {k: v for k, v in stage.items() if k.startswith("blk.")}
    out, _ = jax.lax.scan(layer, x, blk)
    return out


def build_hybrid_gpt2_loss(mesh, num_microbatches=2, ring_impl=None,
                           vocab_size=None, pp_schedule="gpipe",
                           num_virtual=1):
    """Pure loss_fn(params, batch) running dp×pp×mp×sp on `mesh`.

    batch: {"input_ids": [B, S] int32, "labels": [B, S] int32} — B sharded
    over dp, S over sp. Differentiable end-to-end: grads of replicated
    leaves psum automatically via the shard_map transpose.

    `vocab_size`: the TRUE vocab size when the embedding is padded for the
    mp split (pad_vocab); padded logit columns are masked out of the
    softmax statistics.
    `pp_schedule`: "gpipe" or "interleaved" (circular; each pp rank holds
    `num_virtual` non-adjacent layer chunks — parallel/pipeline.py).
    """
    from jax import shard_map

    from ..parallel.pipeline import (pipeline_apply,
                                     pipeline_apply_interleaved)

    axes = dict(mesh.shape)
    use_pp = axes.get("pp", 1) > 1
    if pp_schedule not in ("gpipe", "interleaved"):
        raise ValueError(f"unknown pipeline schedule {pp_schedule!r}")
    interleaved = use_pp and pp_schedule == "interleaved" and num_virtual > 1
    sp_axis = "sp" if axes.get("sp", 1) > 1 else None
    mp_axis = "mp" if axes.get("mp", 1) > 1 else None

    def inner(params, ids, labels):
        sp_idx = jax.lax.axis_index("sp") if sp_axis else 0
        s_l = ids.shape[1]
        if ring_impl == "zigzag" and sp_axis is not None:
            # zigzag layout: this rank holds global chunks (i, 2n-1-i) of
            # 2n — position embeddings must follow the SAME permutation
            # the caller applied to the batch (zigzag_order)
            n_sp = jax.lax.axis_size(sp_axis)
            half = s_l // 2
            pos = jnp.concatenate(
                [sp_idx * half + jnp.arange(half),
                 (2 * n_sp - 1 - sp_idx) * half + jnp.arange(half)])
        else:
            pos = sp_idx * s_l + jnp.arange(s_l)
        wte = params["wte"]  # mp-local shard: [V_pad/mp, E]
        v_loc = wte.shape[0]
        if mp_axis:
            # vocab-parallel embed: masked local gather + psum over mp
            v_start = jax.lax.axis_index(mp_axis) * v_loc
            lids = ids - v_start
            ok = (lids >= 0) & (lids < v_loc)
            x = jnp.where(ok[..., None],
                          wte[jnp.clip(lids, 0, v_loc - 1)], 0.0)
            x = jax.lax.psum(x, mp_axis)
        else:
            v_start = 0
            x = wte[ids]
        x = x + params["wpe"][pos][None]
        stage_fn = functools.partial(_stage_fn, sp_axis=sp_axis,
                                     mp_axis=mp_axis, ring_impl=ring_impl)
        if interleaved:
            # pass ONLY the chunk-stacked blk leaves (the schedule indexes
            # every leaf's leading V dim); blk arrive [V, 1, nblk, ...]
            # with dim 1 pp-sharded
            chunks = {k: v[:, 0] for k, v in params.items()
                      if k.startswith("blk.")}
            m = num_microbatches
            mbs = x.reshape((m, x.shape[0] // m) + x.shape[1:])
            outs = pipeline_apply_interleaved(stage_fn, chunks, mbs, "pp")
            y = outs.reshape((x.shape[0],) + outs.shape[2:])
        elif use_pp:
            stage = {k: (v[0] if k.startswith("blk.") else v)
                     for k, v in params.items()}  # local: [1, L/pp, ...]
            m = num_microbatches
            mbs = x.reshape((m, x.shape[0] // m) + x.shape[1:])
            outs = pipeline_apply(stage_fn, stage, mbs, "pp")
            y = outs.reshape((x.shape[0],) + outs.shape[2:])
        else:
            stage = {k: (v[0] if k.startswith("blk.") else v)
                     for k, v in params.items()}
            y = stage_fn(stage, x)
        y = _ln(y, params["ln_f.w"], params["ln_f.b"])
        # logits stay vocab-sharded: [B, S_l, V_pad/mp] per rank
        logits = jnp.einsum("bse,ve->bsv", y, wte).astype(jnp.float32)
        if vocab_size is not None:  # mask padded vocab columns
            col = v_start + jnp.arange(v_loc)
            logits = jnp.where(col[None, None, :] < vocab_size, logits,
                               -jnp.inf)
        if mp_axis:
            # Megatron vocab-parallel CE from psum'd softmax statistics.
            # The max is detached (pmax has no VJP; the CE gradient
            # softmax(l) - onehot is exact for any constant shift).
            lmax = jax.lax.pmax(
                jax.lax.stop_gradient(jnp.max(logits, axis=-1)),
                mp_axis)  # [B,S]
            sumexp = jax.lax.psum(
                jnp.sum(jnp.exp(logits - lmax[..., None]), axis=-1),
                mp_axis)
            lt = labels - v_start
            ok = (lt >= 0) & (lt < v_loc)
            tgt = jnp.take_along_axis(
                logits, jnp.clip(lt, 0, v_loc - 1)[..., None], axis=-1
            )[..., 0]
            tgt = jax.lax.psum(jnp.where(ok, tgt, 0.0), mp_axis)
            nll = jnp.log(sumexp) + lmax - tgt
        else:
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None],
                                       axis=-1)[..., 0]
        loss = jnp.mean(nll)
        for ax in ("dp", "sp"):
            if axes.get(ax, 1) > 1:
                loss = jax.lax.pmean(loss, ax)
        if use_pp:
            loss = jax.lax.pmean(loss, "pp")
        return loss

    def loss_fn(params, batch):
        specs = hybrid_param_specs(params)
        data_spec = P("dp", "sp")
        params_in = params
        if interleaved:
            # blk [pp, lps, ...] is layer order p*lps + i; flatten to [L]
            # and regroup [V, S, nblk, ...] — sharding dim 1 on pp gives
            # rank r chunks {l*S + r}, the circular placement
            s_pp = axes["pp"]

            def regroup(k, v):
                if not k.startswith("blk."):
                    return v
                L = v.shape[0] * v.shape[1]
                if L % (num_virtual * s_pp):
                    raise ValueError(
                        f"interleaved schedule needs num_layers ({L}) "
                        f"divisible by num_virtual*pp "
                        f"({num_virtual}*{s_pp})")
                nblk = L // (num_virtual * s_pp)
                return v.reshape((L,) + v.shape[2:]).reshape(
                    (num_virtual, s_pp, nblk) + v.shape[2:])

            params_in = {k: regroup(k, v) for k, v in params.items()}

            def respec(k):
                if not k.startswith("blk."):
                    return specs[k]
                # (pp, lps_spec, rest...) -> (None_V, pp_S, None_nblk,
                # rest...): TP dims keep their mp sharding
                rest = tuple(specs[k])[2:]
                return P(*((None, "pp", None) + rest))

            specs = {k: respec(k) for k in specs}
        return shard_map(
            inner, mesh=mesh,
            in_specs=(specs, data_spec, data_spec),
            out_specs=P(),
            check_vma=False)(params_in, batch["input_ids"],
                             batch["labels"])

    return loss_fn


def reference_loss(params, batch, vocab_size=None):
    """Same math, no mesh — the parity oracle for dryrun_multichip."""
    ids, labels = batch["input_ids"], batch["labels"]
    s = ids.shape[1]
    x = params["wte"][ids] + params["wpe"][jnp.arange(s)][None]
    pp, lps = params["blk.w1"].shape[:2]
    for pi in range(pp):
        for li in range(lps):
            wl = {k: v[pi, li] for k, v in params.items()
                  if k.startswith("blk.")}
            a = _ln(x, wl["blk.ln1.w"], wl["blk.ln1.b"])
            qkv = jnp.einsum("bse,ehtd->bshtd", a, wl["blk.wqkv"]) \
                + wl["blk.bqkv"]
            q = jnp.moveaxis(qkv[:, :, :, 0], 1, 2)
            k = jnp.moveaxis(qkv[:, :, :, 1], 1, 2)
            v = jnp.moveaxis(qkv[:, :, :, 2], 1, 2)
            logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
            mask = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(mask, logits, -1e30)
            o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)
            att = jnp.einsum("bhsd,hde->bse", o, wl["blk.wo"])
            x = x + att + wl["blk.bo"]
            m = _ln(x, wl["blk.ln2.w"], wl["blk.ln2.b"])
            m = jax.nn.gelu(jnp.einsum("bse,ef->bsf", m, wl["blk.w1"])
                            + wl["blk.b1"], approximate=True)
            x = x + jnp.einsum("bsf,fe->bse", m, wl["blk.w2"]) + wl["blk.b2"]
    x = _ln(x, params["ln_f.w"], params["ln_f.b"])
    logits = jnp.einsum("bse,ve->bsv", x, params["wte"]).astype(jnp.float32)
    if vocab_size is not None and vocab_size < logits.shape[-1]:
        col = jnp.arange(logits.shape[-1])
        logits = jnp.where(col[None, None, :] < vocab_size, logits, -jnp.inf)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return jnp.mean(nll)


def hybrid_shardings(mesh, params, optimizer_state=None, zero_dp=True):
    """NamedShardings for jit: params per hybrid_param_specs; optimizer
    slots additionally ZeRO-sharded over dp on replicated leaves (stage-1
    style: the big replicated tensors' moments live dp-sharded)."""
    specs = hybrid_param_specs(params)
    p_sh = {k: NamedSharding(mesh, specs[k]) for k in params}

    def slot_spec(name, v):
        base = specs[name]
        if zero_dp and base == P():
            dp = mesh.shape["dp"]
            for i, s in enumerate(v.shape):
                if s % dp == 0 and s >= dp:
                    return NamedSharding(
                        mesh,
                        P(*([None] * i + ["dp"]
                            + [None] * (v.ndim - i - 1))))
        return NamedSharding(mesh, base)

    if optimizer_state is None:
        return p_sh, None
    slots = {name: {k: slot_spec(name, params[name])
                    for k in optimizer_state["slots"][name]}
             for name in optimizer_state["slots"]}
    os_sh = {"slots": slots, "t": NamedSharding(mesh, P())}
    return p_sh, os_sh
