"""The serving programs of a hybrid decoder: RMSNorm pre-norm blocks with
no position table and an untied head after a final RMSNorm, whose layers
mix by latent attention ("mla": a paged latent pool, absorbed decode) or by
a recurrent state ("kda": a slot of the state store) and feed forward
through a dense SwiGLU ("dense") or routed experts with a shared one
("experts").  `DecoderDescription` holds that layout's sizes and is what
`models.kimi_linear.KimiLinear.decoder_description()` hands the engine;
`build_block_programs` builds its `packed_prefill` and `decode_step` from
the parameter names of that model (`embed.weight`, `layers.<i>.{norm_1,
norm_2,kda.*,mla.*,mlp.*,moe.*}`, `norm_f.weight`, `lm_head.weight`).

This is a second layout beside GPT-2's, not a description GPT-2 is an
instance of: `nn.decode` keeps GPT-2's six-field tuple and its own
builders (LayerNorm, learned positions, paged K/V heads, GELU MLP, tied or
untied head, with their sharding, quantization and speculation seams), and
`PagedDecoder` takes either.  One trunk for both is ROADMAP D1.

The cache (`inference.kv_cache.PagedKVCache.for_description`) is two
things side by side: a paged pool of latent rows, which the programs take
and return as `kc`, and the slot-indexed store `state` ({"S": [L_kda,
slots, H, D, D] float32, "conv": [L_kda, slots, K-1, 3*H*D]}), its own
argument and its own result; both donated and written in place.  A row of
`tables` is [state slot | block table]: column 0 names the sequence's
slot of the store (0: the trash slot, as block 0 is the trash block).
Beside tokens, pool and store a program returns `routed`: what its expert
layers did in this dispatch ({"counts": [expert layers, 4] int32, the
counters of `parallel.moe.routed_expert_ffn`; "picks": [expert layers,
rows, k] int32, the experts every row's routers chose}; None for a
description without expert layers).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

MIXERS = ("mla", "kda")
FFNS = ("dense", "experts")


@dataclass(frozen=True)
class LayerDescription:
    mixer: str
    ffn: str

    def __post_init__(self):
        if self.mixer not in MIXERS or self.ffn not in FFNS:
            raise ValueError(f"unknown layer ({self.mixer!r}, {self.ffn!r})"
                             f": mixers {MIXERS}, FFNs {FFNS}")


@dataclass(frozen=True)
class DecoderDescription:
    hidden: int
    vocab: int
    eps: float                 # every RMSNorm's
    layers: tuple              # of LayerDescription
    heads: int = 0             # mla query heads
    nope_dim: int = 0          # mla: q/k width without position part
    pe_dim: int = 0            # mla: the part that would carry position
    v_dim: int = 0             # mla
    lora: int = 0              # mla: the latent's width
    kda_heads: int = 0
    kda_dim: int = 0
    conv: int = 0              # kda: short conv taps
    kda_chunk: int = 64        # kda: positions a prefill chunk
    experts: int = 0           # the router's width
    held_first: int = 0        # experts held here: first, count
    held: int = 0
    top_k: int = 0
    renormalize: bool = True
    scaling: float = 1.0

    #: the parameter whose dtype is the model's (the engine asks)
    final_norm = "norm_f.weight"

    def count(self, mixer):
        return sum(1 for l in self.layers if l.mixer == mixer)

    @property
    def pack_multiple(self):
        """A packed stream's regions must be aligned to this many tokens
        (a KDA chunk and an MLA tile are wholly one sequence's)."""
        return self.kda_chunk

    def cache_layout(self):
        """The device arrays a cache for this description holds."""
        # a pool row is a lane multiple wide (the latent, then zeros): a
        # row-major pool then lies unpadded where both the scatter that
        # writes rows and the kernel that reads blocks want it (PR 25).
        # At lora + pe = 576 the TPU otherwise puts the block's 128
        # tokens on the lanes and re-lays the whole pool around the kernel
        return {"pool_layers": self.count("mla"),
                "row_width": -(-(self.lora + self.pe_dim) // 128) * 128,
                "state_layers": self.count("kda"),
                "state_shape": (self.kda_heads, self.kda_dim, self.kda_dim),
                "conv_shape": (self.conv - 1,
                               3 * self.kda_heads * self.kda_dim)}


@functools.lru_cache(maxsize=16)
def _block_fns(desc):
    """The per-layer functions both programs share: norms, the three
    mixers in their packed and one-token forms, the FFNs."""
    import jax
    import jax.numpy as jnp

    from ..ops import kda as _kda
    from ..ops import mla as _mla
    from ..parallel.moe import routed_expert_ffn

    f32 = jnp.float32
    eps = desc.eps
    H, NOPE, PE, VD, LORA = (desc.heads, desc.nope_dim, desc.pe_dim,
                             desc.v_dim, desc.lora)
    KH, KD, K = desc.kda_heads, desc.kda_dim, desc.conv
    mla_scale = (NOPE + PE) ** -0.5
    # index of each layer among its own kind's cache layers
    kind_index, seen = [], {}
    for l in desc.layers:
        kind_index.append(seen.get(l.mixer, 0))
        seen[l.mixer] = kind_index[-1] + 1

    def rms(x, w):
        xf = x.astype(f32)
        return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                   + eps)).astype(x.dtype) * w

    def swiglu(p, pre, x):
        return (jax.nn.silu(x @ p[pre + "gate_proj.weight"])
                * (x @ p[pre + "up_proj.weight"])) \
            @ p[pre + "down_proj.weight"]

    def ffn(p, i, x, valid):
        """(y, counts|None, picks|None) of layer i's FFN on rows x
        [N, E]."""
        pre = f"layers.{i}."
        if desc.layers[i].ffn == "dense":
            return swiglu(p, pre + "mlp.", x), None, None
        m = pre + "moe."                       # "experts": routed + shared
        y, counts, picks = routed_expert_ffn(
            x, valid, p[m + "router.weight"], p[m + "router.bias"],
            p[m + "experts.gate"], p[m + "experts.up"],
            p[m + "experts.down"], held_first=desc.held_first,
            top_k=desc.top_k, scaling=desc.scaling,
            renormalize=desc.renormalize)
        return y + swiglu(p, m + "shared.", x), counts, picks

    # ---- KDA ------------------------------------------------------------
    def kda_inputs(p, pre, a, y, valid):
        """From the normed rows a [N, E] and their conv outputs y
        [N, 3*H*D]: (q, k, v, log_a [N, H, D], beta [N, H]) float32, a
        row that is not `valid` made inert (k = 0, beta = 0, log_a = 0)."""
        n = a.shape[0]
        q, k, v = (t.reshape(n, KH, KD) for t in
                   jnp.split(jax.nn.silu(y.astype(f32)), 3, axis=-1))

        def l2(t):
            return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                     + 1e-6)

        f = ((a @ p[pre + "f_down.weight"]) @ p[pre + "f_up.weight"]
             ).astype(f32) + p[pre + "dt_bias"]
        log_a = -jnp.exp(p[pre + "A_log"].astype(f32))[None, :, None] \
            * jax.nn.softplus(f.reshape(n, KH, KD))
        beta = jax.nn.sigmoid((a @ p[pre + "b_proj.weight"]).astype(f32))
        live = valid[:, None, None]
        return (l2(q) * KD ** -0.5, jnp.where(live, l2(k), 0.0), v,
                jnp.where(live, log_a, 0.0),
                jnp.where(valid[:, None], beta, 0.0))

    def kda_output(p, pre, a, o):
        """o [N, H, D] float32 -> the mixer's output [N, E]."""
        n = a.shape[0]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * p[pre + "o_norm.weight"].astype(f32)
        g = ((a @ p[pre + "g_down.weight"]) @ p[pre + "g_up.weight"]
             ).astype(f32).reshape(n, KH, KD)
        o = (jax.nn.sigmoid(g) * o).astype(a.dtype)
        return o.reshape(n, KH * KD) @ p[pre + "o_proj.weight"]

    def kda_packed(p, i, a, ctx, kc, state):
        pre, li = f"layers.{i}.kda.", kind_index[i]
        z = a @ p[pre + "qkv_proj.weight"]                     # [T, 3HD]
        w = p[pre + "qkv_conv.weight"]
        # a row's conv tail: its inputs at positions start-(K-1) ..
        # start-1; a row that starts at 0 (or is padding) has none
        tail = jnp.where((ctx["start_row"] > 0)[:, None, None],
                         state["conv"][li, ctx["slot_row"]], 0)   # [P, K-1, .]
        y = w[K - 1] * z
        for d in range(1, K):
            in_stream = ctx["pos"] - d >= ctx["start"]
            idx = jnp.clip(K - 1 - ctx["start"] + ctx["pos"] - d, 0, K - 2)
            y = y + w[K - 1 - d] * jnp.where(
                in_stream[:, None], jnp.roll(z, d, axis=0),
                tail[ctx["seg"], idx])
        # the tail each row leaves: its last K-1 inputs
        new_tail = []
        for m in range(K - 1):
            back = ctx["end_row"] - (K - 1) + m                # a position
            src = ctx["first_row"] + back - ctx["start_row"]   # stream idx
            old = tail[jnp.arange(tail.shape[0]),
                       jnp.clip(K - 1 - ctx["start_row"] + back, 0, K - 2)]
            new_tail.append(jnp.where(
                (back >= ctx["start_row"])[:, None],
                z[jnp.clip(src, 0, z.shape[0] - 1)],
                jnp.where((back >= 0)[:, None], old, 0)))
        conv = state["conv"].at[li, ctx["slot_row"]].set(
            jnp.stack(new_tail, axis=1).astype(state["conv"].dtype))
        q, k, v, log_a, beta = kda_inputs(p, pre, a, y, ctx["valid"])
        s_load = jnp.where((ctx["tile_p0"] > 0)[:, None, None, None],
                           state["S"][li, ctx["tile_slot"]], 0.0)
        o, s_out = _kda.kda_chunked_prefill(
            q, k, v, log_a, beta, s_load, ctx["tile_carry"],
            chunk=desc.kda_chunk)
        s_new = state["S"].at[li, jnp.where(ctx["tile_last"],
                                            ctx["tile_slot"], 0)].set(s_out)
        return kda_output(p, pre, a, o), kc, {"S": s_new, "conv": conv}

    def kda_step(p, i, a, ctx, kc, state):
        pre, li = f"layers.{i}.kda.", kind_index[i]
        z = a @ p[pre + "qkv_proj.weight"]                     # [B, 3HD]
        w = p[pre + "qkv_conv.weight"]
        tail = state["conv"][li, ctx["slot"]]                     # [B, K-1, .]
        y = w[K - 1] * z + jnp.einsum("kc,bkc->bc", w[:K - 1], tail)
        conv = state["conv"].at[li, ctx["slot"]].set(
            jnp.concatenate([tail[:, 1:], z[:, None]], axis=1))
        q, k, v, log_a, beta = kda_inputs(p, pre, a, y, ctx["valid"])
        o, s_new = _kda.kda_recurrent_step(state["S"], li, ctx["slot"], q,
                                           k, v, log_a, beta)
        return kda_output(p, pre, a, o), kc, {"S": s_new, "conv": conv}

    # ---- MLA ------------------------------------------------------------
    def mla_rows(p, pre, a, blk, off, kc, li):
        """Write each row's latent into the pool; return the absorbed
        queries [N, H, lora+pe] and the value half of W_kvb."""
        n = a.shape[0]
        q = (a @ p[pre + "q_proj.weight"]).reshape(n, H, NOPE + PE)
        kva = a @ p[pre + "kva_proj.weight"]
        pad = kc.shape[-1] - LORA - PE     # rows are a lane multiple wide
        lat = jnp.concatenate(
            [rms(kva[:, :LORA], p[pre + "kv_norm.weight"]), kva[:, LORA:],
             jnp.zeros((n, pad), a.dtype)], axis=-1)
        kc = kc.at[li, blk, off].set(lat.astype(kc.dtype))
        wkvb = p[pre + "kvb_proj.weight"].reshape(LORA, H, NOPE + VD)
        q_abs = jnp.einsum("nhd,lhd->nhl", q[..., :NOPE], wkvb[..., :NOPE])
        return (jnp.concatenate([q_abs, q[..., NOPE:],
                                 jnp.zeros((n, H, pad), a.dtype)], axis=-1),
                kc, wkvb[..., NOPE:])

    def mla_output(p, pre, o_lat, w_vb):
        o = jnp.einsum("nhl,lhv->nhv", o_lat, w_vb)
        return o.reshape(o.shape[0], H * VD) @ p[pre + "o_proj.weight"]

    def mla_packed(p, i, a, ctx, kc, state):
        pre, li = f"layers.{i}.mla.", kind_index[i]
        q_lat, kc, w_vb = mla_rows(p, pre, a, ctx["blk"], ctx["off"], kc,
                                   li)
        o_lat = _mla.mla_prefill_attention(
            q_lat, kc, li, ctx["btab"], ctx["tile_row"], ctx["pos"],
            scale=mla_scale, tile=desc.kda_chunk, lora=LORA)
        return mla_output(p, pre, o_lat, w_vb), kc, state

    def mla_step(p, i, a, ctx, kc, state):
        pre, li = f"layers.{i}.mla.", kind_index[i]
        q_lat, kc, w_vb = mla_rows(p, pre, a, ctx["blk"], ctx["off"], kc,
                                   li)
        o_lat = _mla.mla_decode_attention(
            q_lat, kc, li, ctx["btab"], ctx["ctx"], scale=mla_scale,
            lora=LORA)
        return mla_output(p, pre, o_lat, w_vb), kc, state

    packed = {"kda": kda_packed, "mla": mla_packed}
    step = {"kda": kda_step, "mla": mla_step}

    def trunk(p, x, ctx, kc, state, mixers):
        """Every layer: x + mixer(norm(x)), then x + ffn(norm(x)).
        Returns (x, kc, state, routed): see the module's docstring."""
        counts, picks = [], []
        for i, layer in enumerate(desc.layers):
            pre = f"layers.{i}."
            m, kc, state = mixers[layer.mixer](
                p, i, rms(x, p[pre + "norm_1.weight"]), ctx, kc, state)
            x = x + m
            y, c, pk = ffn(p, i, rms(x, p[pre + "norm_2.weight"]),
                           ctx["valid"])
            x = x + y
            if c is not None:
                counts.append(c)
                picks.append(pk)
        routed = {"counts": jnp.stack(counts),
                  "picks": jnp.stack(picks)} if counts else None
        return x, kc, state, routed

    def head(p):
        return lambda xf: (xf @ p["lm_head.weight"]).astype(f32)

    ns = type("BlockFns", (), {})()
    ns.rms, ns.trunk, ns.packed, ns.step, ns.head = (rms, trunk, packed,
                                                     step, head)
    return ns


@functools.lru_cache(maxsize=64)
def build_block_programs(desc, block_size, return_logits, mode):
    """(packed_prefill_fn, step_fn) for a description, raw and jittable:
    `nn.decode`'s own signatures (see `_build_packed_prefill` and
    `_build_paged_fns`, `prev` of its step included) with the store
    `state` where GPT-2's take the V pool; kc, state, a table row and
    `routed` are as this module's docstring says.  They return (token, stopped, kc, state, counts,
    routed), and the logits after that with `return_logits`."""
    import jax
    import jax.numpy as jnp

    from ..sampling import processors as _proc
    from .decode import _make_readout

    BS, C = int(block_size), desc.kda_chunk
    _sampled, penalties = mode
    fn = _block_fns(desc)
    readout = _make_readout(None, lambda x: x, mode, _proc)

    def finish(p, xf, sp):
        """(token, logits|None) of the rows xf [B, E]: final norm, untied
        head, the sampling pipeline every program shares."""
        return readout(fn.head(p), fn.rms(xf, p[desc.final_norm]), sp,
                       return_logits)

    def packed_prefill_fn(params, toks, seg, pos, tables, sample_idx,
                          kc, state, sp):
        T, P = toks.shape[0], tables.shape[0]
        if T % C:
            raise ValueError(f"a packed stream of {T} tokens is not whole "
                             f"chunks of {C}")
        valid = pos >= 0
        p0 = jnp.where(valid, pos, 0)
        btab = tables[:, 1:]
        big = jnp.iinfo(jnp.int32).max
        idx = jnp.arange(T, dtype=jnp.int32)
        start_row = jax.ops.segment_min(jnp.where(valid, pos, big), seg, P)
        tile_p0 = pos[::C]
        tile_row = seg[::C]
        tile_live = tile_p0 >= 0
        tile_carry = tile_live & (tile_p0 != start_row[tile_row])
        ctx = {
            "valid": valid, "pos": pos, "seg": seg, "btab": btab,
            "blk": jnp.where(valid, btab[seg, p0 // BS], 0),
            "off": p0 % BS,
            "slot_row": tables[:, 0], "start_row": start_row,
            "start": start_row[seg],
            "end_row": jax.ops.segment_max(jnp.where(valid, pos, -1), seg,
                                           P) + 1,
            "first_row": jax.ops.segment_min(jnp.where(valid, idx, big),
                                             seg, P),
            "tile_row": tile_row, "tile_p0": tile_p0,
            "tile_slot": jnp.where(tile_live, tables[tile_row, 0], 0),
            "tile_carry": tile_carry,
            # the last chunk of its sequence in this stream leaves the state
            "tile_last": tile_live & ~jnp.concatenate(
                [tile_carry[1:], jnp.zeros((1,), bool)]),
        }
        x, kc, state, routed = fn.trunk(
            params, params["embed.weight"][toks], ctx, kc, state, fn.packed)
        tok, logits = finish(params, x[sample_idx], sp)
        B = sample_idx.shape[0]
        stopped = _proc.check_stops(tok, sp["stop"], jnp.ones((B,), bool))
        counts = None
        if penalties:
            counts = _proc.update_counts(sp["counts"], sp["crows"], tok,
                                         sp["row_done"])
        if return_logits:
            return tok, stopped, kc, state, counts, routed, logits
        return tok, stopped, kc, state, counts, routed

    def step_fn(params, tok, pos, active, tables, kc, state, sp, prev=None):
        B = tok.shape[0]
        if prev is not None:   # as `nn.decode`'s step: a negative tok
            tok = jnp.where(tok < 0, prev, tok)   # goes on from `prev`
        btab = tables[:, 1:]
        ctx = {
            "valid": active, "btab": btab,
            "slot": jnp.where(active, tables[:, 0], 0),
            "blk": jnp.where(active, btab[jnp.arange(B), pos // BS], 0),
            "off": pos % BS,
            "ctx": jnp.where(active, pos + 1, 0),
        }
        x, kc, state, routed = fn.trunk(
            params, params["embed.weight"][tok], ctx, kc, state, fn.step)
        nxt, logits = finish(params, x, sp)
        nxt = jnp.where(active, nxt, 0)
        stopped = _proc.check_stops(nxt, sp["stop"], active)
        counts = None
        if penalties:
            counts = _proc.update_counts(sp["counts"], jnp.arange(B), nxt,
                                         active)
        if return_logits:
            return nxt, stopped, kc, state, counts, routed, logits
        return nxt, stopped, kc, state, counts, routed

    return packed_prefill_fn, step_fn
