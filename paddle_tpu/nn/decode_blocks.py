"""The serving programs of a decoder of RMSNorm pre-norm blocks with no
position table, whose layers mix by latent attention ("mla": a paged
latent pool, absorbed decode), by a recurrent state ("kda": a slot of the
state store), by compressed convolutional attention ("cca": paged K and
V rows of fewer K/V heads than query heads, rotary positions on part of a
head, and per-sequence conv tails in the store) or by power retention of
degree 2 ("power": grouped query heads with per-head q/k norms and rotary
positions on a whole head, a gate a K/V head, and a second-degree
feature-map state in the store; no paged pool) and feed forward through a
dense SwiGLU ("dense"), routed experts with a shared one under a sigmoid
router ("experts") or routed experts alone under an MLP router with a
softmax whose state goes from layer to layer ("mlp_routed").  The head
after the final RMSNorm is its own matrix or the embedding's (`tied_head`);
a sublayer's sum with the residual stream may carry learned scalings
(`residual_scaling`).  `DecoderDescription` holds that layout's sizes and
is what `models.kimi_linear.KimiLinear.decoder_description()`,
`models.zaya.Zaya.decoder_description()` and
`models.brumby.Brumby.decoder_description()` hand the engine;
`build_block_programs` builds its `packed_prefill` and `decode_step` from
the parameter names of those models (`embed.weight`, `layers.<i>.{norm_1,
norm_2,res_1.*,res_2.*,kda.*,mla.*,cca.*,power.*,mlp.*,moe.*}`,
`norm_f.weight`, `lm_head.weight`).

This is a second layout beside GPT-2's, not a description GPT-2 is an
instance of: `nn.decode` keeps GPT-2's six-field tuple and its own
builders (LayerNorm, learned positions, paged K/V heads, GELU MLP, tied or
untied head, with their sharding, quantization and speculation seams), and
`PagedDecoder` takes either.  One trunk for both is ROADMAP D1.

The cache (`inference.kv_cache.PagedKVCache.for_description`) is two
things side by side: a paged pool, which the programs take and return as
`kc` (latent rows) or as `kc` and `vc` (K and V rows [L, N, BS, Hkv*Dh], a
description with "cca" layers: `cache_layout()["values"]`), and the
slot-indexed store `state` (`cache_layout()["store"]`: {"S": [L_kda,
slots, H, D, D] float32, "conv": [L_kda, slots, K-1, 3*H*D]} for KDA
layers; {"conv0", "conv1", "v_prev"}: the last inputs of CCA's two
convolutions and of its shifted value, for CCA layers; {"P": [L_power,
slots, Hkv, D, Dh] float32, "Z": [L_power, slots, Hkv, D] float32}: the
retention state and its normaliser, for power layers), its own argument
and its own result; all donated and written in place.  A row of `tables`
is [state slot | block table]: column 0 names the sequence's slot of the
store (0: the trash slot, as block 0 is the trash block).  A description
none of whose layers pages anything (`cache_layout()["pool_layers"]` 0:
every layer "kda" or "power") has NO POOL: `kc` is an array of no rows
that the programs hand through, a table row is [state slot] alone, and no
program reads a block column.  Beside tokens,
pool and store a program returns `routed`: what its expert layers did in
this dispatch ({"counts": [expert layers, 4] int32, the counters of
`parallel.moe.dispatch_experts`; "picks": [expert layers, rows, k] int32,
the experts every row's routers chose}; None for a description without
expert layers).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

MIXERS = ("mla", "kda", "cca", "power")
FFNS = ("dense", "experts", "mlp_routed")


@dataclass(frozen=True)
class LayerDescription:
    mixer: str
    ffn: str

    def __post_init__(self):
        if self.mixer not in MIXERS or self.ffn not in FFNS:
            raise ValueError(f"unknown layer ({self.mixer!r}, {self.ffn!r})"
                             f": mixers {MIXERS}, FFNs {FFNS}")


@dataclass(frozen=True)
class CCADescription:
    """The sizes of a "cca" mixer (`ops/cca.py`)."""
    heads: int                 # query heads
    kv_heads: int              # K/V heads: a pool row is kv_heads * head_dim
    head_dim: int
    rotary_dim: int            # leading channels of a head that rotate
    theta: float
    time0: int                 # taps of the depthwise convolution
    time1: int                 # taps of the per-head convolution

    @property
    def channels(self):
        """Width of [q~ | k~], what the convolutions run over."""
        return (self.heads + self.kv_heads) * self.head_dim

    def tails(self):
        """{store name: (inputs kept, width)} a sequence and layer."""
        return {"conv0": (self.time0 - 1, self.channels),
                "conv1": (self.time1 - 1, self.channels),
                "v_prev": (1, self.kv_heads * self.head_dim // 2)}


@dataclass(frozen=True)
class PowerDescription:
    """The sizes of a "power" mixer (`ops/power_retention.py`)."""
    heads: int                 # query heads
    kv_heads: int              # K/V heads: one state and one gate each
    head_dim: int
    theta: float               # rotary base; the whole head rotates
    tile: int                  # channels a tile of the feature map
    chunk: int                 # positions a prefill chunk

    @property
    def state_dim(self):
        """D: rows of a K/V head's state."""
        from ..ops.power_retention import state_dim

        return state_dim(self.head_dim, self.tile)


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
    cca: CCADescription = None
    power: PowerDescription = None
    router_width: int = 0      # mlp_routed: the router MLP's width
    tied_head: bool = False    # the head is the embedding's matrix
    residual_scaling: bool = False   # layers.<i>.res_{1,2}.*

    #: the parameter whose dtype is the model's (the engine asks)
    final_norm = "norm_f.weight"

    def __post_init__(self):
        if self.count("cca") and (self.count("mla") or self.cca is None):
            raise ValueError("cca layers take the description's `cca` "
                             "sizes and the one paged pool: no mla layer "
                             "beside them")
        if self.count("power") and (self.power is None
                                    or self.count("kda")):
            raise ValueError("power layers take the description's `power` "
                             "sizes and its one chunk length: no kda "
                             "layer beside them")

    def count(self, mixer):
        return sum(1 for l in self.layers if l.mixer == mixer)

    @property
    def values(self):
        """Whether the pool holds K rows and V rows (else latent rows)."""
        return bool(self.count("cca"))

    @property
    def pooled(self):
        """Whether a layer pages anything: a description of "kda" and
        "power" layers alone keeps every sequence in the store."""
        return bool(self.count("mla") or self.count("cca"))

    @property
    def query_heads(self):
        if self.values:
            return self.cca.heads
        return self.power.heads if self.count("power") else self.heads

    @property
    def chunk(self):
        """Positions a chunk of the chunked layers (`chunked`)."""
        return self.power.chunk if self.count("power") else self.kda_chunk

    @property
    def chunked(self):
        """Whether a layer works in chunks of `chunk` positions."""
        return bool(self.count("kda") or self.count("mla")
                    or self.count("power"))

    @property
    def pack_multiple(self):
        """A packed stream's regions must be aligned to this many tokens
        (a KDA or power chunk and an MLA tile are wholly one
        sequence's)."""
        return self.chunk if self.chunked else 1

    def cache_layout(self):
        """The device arrays a cache for this description holds: a pool of
        `pool_layers` layers of rows `row_width` wide (K rows, and V rows
        beside them where `values`), and the slot-indexed `store`: {name:
        (layers, shape a slot, dtype or None for the model's)}.  Where no
        layer pages anything there is no pool: `pool_layers` 0 and
        `row_width` 0."""
        store = {}
        if self.count("kda"):
            store["S"] = (self.count("kda"), (self.kda_heads, self.kda_dim,
                                              self.kda_dim), "float32")
            store["conv"] = (self.count("kda"), (
                self.conv - 1, 3 * self.kda_heads * self.kda_dim), None)
        for name, shape in (self.cca.tails() if self.count("cca")
                            else {}).items():
            store[name] = (self.count("cca"), shape, None)
        if self.count("power"):
            pw = self.power
            store["P"] = (self.count("power"), (pw.kv_heads, pw.state_dim,
                                                pw.head_dim), "float32")
            store["Z"] = (self.count("power"), (pw.kv_heads, pw.state_dim),
                          "float32")
        if not self.pooled:
            pool = {"pool_layers": 0, "values": False, "row_width": 0}
        elif self.values:
            pool = {"pool_layers": self.count("cca"), "values": True,
                    "row_width": self.cca.kv_heads * self.cca.head_dim}
        else:
            # a pool row is a lane multiple wide (the latent, then zeros):
            # a row-major pool then lies unpadded where both the scatter
            # that writes rows and the kernel that reads blocks want it
            # (PR 25).  At lora + pe = 576 the TPU otherwise puts the
            # block's 128 tokens on the lanes and re-lays the whole pool
            # around the kernel
            pool = {"pool_layers": self.count("mla"), "values": False,
                    "row_width": -(-(self.lora + self.pe_dim) // 128) * 128}
        return {**pool, "store": store}


@functools.lru_cache(maxsize=16)
def _block_fns(desc):
    """The per-layer functions both programs share: norms, the mixers in
    their packed and one-token forms, the FFNs."""
    import jax
    import jax.numpy as jnp

    from ..ops import attention as _attn
    from ..ops import cca as _cca
    from ..ops import kda as _kda
    from ..ops import mla as _mla
    from ..ops import power_retention as _power
    from ..ops.rotary import apply_rotary
    from ..parallel.moe import dispatch_experts, routed_expert_ffn

    f32 = jnp.float32
    eps = desc.eps
    H, NOPE, PE, VD, LORA = (desc.heads, desc.nope_dim, desc.pe_dim,
                             desc.v_dim, desc.lora)
    KH, KD, K = desc.kda_heads, desc.kda_dim, desc.conv
    mla_scale = (NOPE + PE) ** -0.5 if desc.count("mla") else None
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

    def router_mlp(p, m, x, r_before):
        """(r, s) of an "mlp_routed" layer's router on rows x [N, E]: its
        state r [N, R] float32 (this layer's down-projection plus gamma
        times the layer before's state, None for the first) and the
        softmax scores s [N, n_experts].  The MLP is float32 at full
        precision: it is [R, R] and its argmax is the layer's choice."""
        hi = jax.lax.Precision.HIGHEST
        with jax.named_scope("zaya_router"):
            r = jnp.dot(x, p[m + "down.weight"], preferred_element_type=f32)
            if r_before is not None:
                r = r + p[m + "gamma"].astype(f32) * r_before
            h = r * jax.lax.rsqrt(jnp.mean(r * r, -1, keepdims=True) + eps) \
                * p[m + "norm.weight"].astype(f32)
            for w in ("w1.weight", "w2.weight"):
                h = jax.nn.gelu(jnp.dot(h, p[m + w].astype(f32),
                                        precision=hi), approximate=False)
            z = jnp.dot(h, p[m + "w3.weight"].astype(f32), precision=hi)
            return r, jax.nn.softmax(z, axis=-1)

    def ffn(p, i, x, valid, carry):
        """(y, counts|None, picks|None, carry) of layer i's FFN on rows x
        [N, E]; `carry` is what an "mlp_routed" layer hands the next (its
        router's state), None until the first."""
        pre = f"layers.{i}."
        if desc.layers[i].ffn == "dense":
            return swiglu(p, pre + "mlp.", x), None, None, carry
        m = pre + "moe."
        if desc.layers[i].ffn == "mlp_routed":   # no shared expert
            carry, s = router_mlp(p, m + "router.", x, carry)
            y, counts, picks = dispatch_experts(
                x, valid, s, s + p[m + "router.bias"].astype(f32),
                p[m + "experts.gate"], p[m + "experts.up"],
                p[m + "experts.down"], held_first=desc.held_first,
                top_k=desc.top_k, scaling=desc.scaling,
                renormalize=desc.renormalize)
            return y, counts, picks, carry
        # "experts": routed + shared, a sigmoid router
        y, counts, picks = routed_expert_ffn(
            x, valid, p[m + "router.weight"], p[m + "router.bias"],
            p[m + "experts.gate"], p[m + "experts.up"],
            p[m + "experts.down"], held_first=desc.held_first,
            top_k=desc.top_k, scaling=desc.scaling,
            renormalize=desc.renormalize)
        return y + swiglu(p, m + "shared.", x), counts, picks, carry

    # ---- a sequence's earlier rows: in the stream, or in its tail ---------
    def load_tail(ctx, store, li):
        """Layer `li` of the tails [L, slots, K-1, C] -> [P, K-1, C]: each
        plan row's inputs at positions start-(K-1) .. start-1; a row that
        starts at 0 (or is padding) has none."""
        return jnp.where((ctx["start_row"] > 0)[:, None, None],
                         store[li, ctx["slot_row"]], 0)

    def stream_back(ctx, z, tail, d):
        """z [T, C] of a packed stream, `d` positions back: the stream's
        own row where the sequence's chunk reaches that far, else the
        row's `tail` [P, K-1, C]."""
        k = tail.shape[1] + 1
        in_stream = ctx["pos"] - d >= ctx["start"]
        idx = jnp.clip(k - 1 - ctx["start"] + ctx["pos"] - d, 0, k - 2)
        return jnp.where(in_stream[:, None], jnp.roll(z, d, axis=0),
                         tail[ctx["seg"], idx])

    def stream_tail(ctx, z, tail):
        """The tail each plan row leaves [P, K-1, C]: its last K-1 inputs,
        from the stream z or, for a chunk shorter than that, from the
        `tail` it started with."""
        k = tail.shape[1] + 1
        new_tail = []
        for m in range(k - 1):
            back = ctx["end_row"] - (k - 1) + m                # a position
            src = ctx["first_row"] + back - ctx["start_row"]   # stream idx
            old = tail[jnp.arange(tail.shape[0]),
                       jnp.clip(k - 1 - ctx["start_row"] + back, 0, k - 2)]
            new_tail.append(jnp.where(
                (back >= ctx["start_row"])[:, None],
                z[jnp.clip(src, 0, z.shape[0] - 1)],
                jnp.where((back >= 0)[:, None], old, 0)))
        return jnp.stack(new_tail, axis=1)

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

    def kda_packed(p, i, a, ctx, kc, vc, state):
        pre, li = f"layers.{i}.kda.", kind_index[i]
        z = a @ p[pre + "qkv_proj.weight"]                     # [T, 3HD]
        w = p[pre + "qkv_conv.weight"]
        tail = load_tail(ctx, state["conv"], li)               # [P, K-1, .]
        y = w[K - 1] * z
        for d in range(1, K):
            y = y + w[K - 1 - d] * stream_back(ctx, z, tail, d)
        conv = state["conv"].at[li, ctx["slot_row"]].set(
            stream_tail(ctx, z, tail).astype(state["conv"].dtype))
        q, k, v, log_a, beta = kda_inputs(p, pre, a, y, ctx["valid"])
        s_load = jnp.where((ctx["tile_p0"] > 0)[:, None, None, None],
                           state["S"][li, ctx["tile_slot"]], 0.0)
        o, s_out = _kda.kda_chunked_prefill(
            q, k, v, log_a, beta, s_load, ctx["tile_carry"],
            chunk=desc.kda_chunk)
        s_new = state["S"].at[li, jnp.where(ctx["tile_last"],
                                            ctx["tile_slot"], 0)].set(s_out)
        return kda_output(p, pre, a, o), kc, vc, {"S": s_new, "conv": conv}

    def kda_step(p, i, a, ctx, kc, vc, state):
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
        return kda_output(p, pre, a, o), kc, vc, {"S": s_new, "conv": conv}

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

    def mla_packed(p, i, a, ctx, kc, vc, state):
        pre, li = f"layers.{i}.mla.", kind_index[i]
        q_lat, kc, w_vb = mla_rows(p, pre, a, ctx["blk"], ctx["off"], kc,
                                   li)
        o_lat = _mla.mla_prefill_attention(
            q_lat, kc, li, ctx["btab"], ctx["tile_row"], ctx["pos"],
            scale=mla_scale, tile=desc.kda_chunk, lora=LORA)
        return mla_output(p, pre, o_lat, w_vb), kc, vc, state

    def mla_step(p, i, a, ctx, kc, vc, state):
        pre, li = f"layers.{i}.mla.", kind_index[i]
        q_lat, kc, w_vb = mla_rows(p, pre, a, ctx["blk"], ctx["off"], kc,
                                   li)
        o_lat = _mla.mla_decode_attention(
            q_lat, kc, li, ctx["btab"], ctx["ctx"], scale=mla_scale,
            lora=LORA)
        return mla_output(p, pre, o_lat, w_vb), kc, vc, state

    # ---- CCA ------------------------------------------------------------
    def cca_rows(p, pre, a, pos, back):
        """From the normed rows a [N, E] at positions pos [N], with
        back(name, z, d) = the rows of z [N, C] `d` positions earlier in
        each row's sequence: (q [N, Hq, D], k [N, Hkv*D], v [N, Hkv*D],
        kept), `kept` the inputs whose last rows a sequence leaves as its
        tails, by the store's names."""
        cc, dt, n = desc.cca, a.dtype, a.shape[0]
        c = jnp.concatenate([a @ p[pre + "q_proj.weight"],
                             a @ p[pre + "k_proj.weight"]], axis=-1)
        # c' is rounded to the model's dtype before anything reads it: a
        # tail in the store is then the very row a longer chunk would use
        c1 = _cca.depthwise(
            p[pre + "conv0.weight"], p[pre + "conv0.bias"],
            [c] + [back("conv0", c, d) for d in range(1, cc.time0)]
        ).astype(dt)
        c2 = _cca.per_head(
            p[pre + "conv1.weight"], p[pre + "conv1.bias"],
            [c1] + [back("conv1", c1, d) for d in range(1, cc.time1)])
        q, k = _cca.qk_norm(*_cca.qk_mean(c, c2, cc.heads, cc.kv_heads,
                                          cc.head_dim), p[pre + "k_scale"])
        q = apply_rotary(q, pos, cc.rotary_dim, cc.theta).astype(dt)
        k = apply_rotary(k, pos, cc.rotary_dim, cc.theta).astype(dt)
        v2 = a @ p[pre + "v2_proj.weight"]     # the NEXT token's half
        v = jnp.concatenate([a @ p[pre + "v1_proj.weight"],
                             back("v_prev", v2, 1)], axis=-1)
        return (q, k.reshape(n, -1), v,
                {"conv0": c, "conv1": c1, "v_prev": v2})

    def cca_layer(p, i, a, ctx, kc, vc, state, tails, slots, back, leave,
                  attend):
        """What the packed and the one-token form share: the rows' q, k, v
        (`cca_rows`, earlier rows through `back`), the tails they leave
        (`leave(kept, tail)`) into `slots` of the store, k and v into the
        pools, `attend(q, kc, vc)`, the output projection."""
        pre, li = f"layers.{i}.cca.", kind_index[i]
        q, k, v, kept = cca_rows(p, pre, a, ctx["pos"], back)
        state = {name: state[name].at[li, slots].set(
            leave(kept[name], tails[name]).astype(state[name].dtype))
            for name in tails}
        kc = kc.at[li, ctx["blk"], ctx["off"]].set(k.astype(kc.dtype))
        vc = vc.at[li, ctx["blk"], ctx["off"]].set(v.astype(vc.dtype))
        o = attend(q, kc, vc)
        return o.reshape(o.shape[0], -1) @ p[pre + "o_proj.weight"], \
            kc, vc, state

    cca_scale = desc.cca.head_dim ** -0.5 if desc.cca else None

    def cca_packed(p, i, a, ctx, kc, vc, state):
        li = kind_index[i]
        tails = {name: load_tail(ctx, state[name], li)
                 for name in desc.cca.tails()}
        return cca_layer(
            p, i, a, ctx, kc, vc, state, tails, ctx["slot_row"],
            back=lambda name, z, d: stream_back(ctx, z, tails[name], d),
            leave=lambda z, tail: stream_tail(ctx, z, tail),
            attend=lambda q, kc, vc: _attn.ragged_prefill_attention(
                q, kc, vc, ctx["btab"], ctx["seg"], ctx["pos"],
                scale=cca_scale, layer=li))

    def cca_step(p, i, a, ctx, kc, vc, state):
        li = kind_index[i]
        # tails are oldest first: d positions back is row K-1-d
        tails = {name: state[name][li, ctx["slot"]]              # [B, K-1, C]
                 for name in desc.cca.tails()}
        return cca_layer(
            p, i, a, ctx, kc, vc, state, tails, ctx["slot"],
            back=lambda name, _z, d: tails[name][:, tails[name].shape[1] - d],
            leave=lambda z, tail: jnp.concatenate([tail[:, 1:], z[:, None]],
                                                  axis=1),
            attend=lambda q, kc, vc: _attn.paged_decode_attention(
                q, kc, vc, ctx["btab"], ctx["ctx"], scale=cca_scale,
                layer=li))

    # ---- power retention -------------------------------------------------
    def power_rows(p, pre, a, pos, valid):
        """From the normed rows a [N, E] at positions pos [N]: (q
        [N, Hq, D], k, v [N, Hkv, D], gamma [N, Hkv]) float32: the
        projections, an RMSNorm of every q and k head, rotary on the whole
        head, the log of a sigmoid gate a K/V head; a row that is not
        `valid` made inert (k = 0, gamma = 0)."""
        pw, n = desc.power, a.shape[0]

        def heads(w, h, norm=None):
            x = (a @ p[pre + w]).reshape(n, h, pw.head_dim)
            if norm is None:
                return x.astype(f32)
            return apply_rotary(rms(x, p[pre + norm]).astype(f32), pos,
                                pw.head_dim, pw.theta)

        q = heads("q_proj.weight", pw.heads, "q_norm.weight")
        k = heads("k_proj.weight", pw.kv_heads, "k_norm.weight")
        v = heads("v_proj.weight", pw.kv_heads)
        gamma = jax.nn.log_sigmoid((a @ p[pre + "gate_proj.weight"]
                                    ).astype(f32))
        return (q, jnp.where(valid[:, None, None], k, 0.0), v,
                jnp.where(valid[:, None], gamma, 0.0))

    def power_output(p, pre, a, o):
        return o.astype(a.dtype).reshape(o.shape[0], -1) \
            @ p[pre + "o_proj.weight"]

    def power_packed(p, i, a, ctx, kc, vc, state):
        pre, li = f"layers.{i}.power.", kind_index[i]
        q, k, v, gamma = power_rows(p, pre, a, ctx["pos"], ctx["valid"])
        with jax.named_scope("power_prefill"):
            o, s_new, z_new = _power.power_chunked_prefill(
                state["P"], state["Z"], li, ctx["tile_slot"],
                ctx["tile_p0"] == 0, q, k, v, gamma,
                chunk=desc.power.chunk, tile=desc.power.tile,
                readout_dtype=a.dtype)
        return power_output(p, pre, a, o), kc, vc, \
            {**state, "P": s_new, "Z": z_new}

    def power_step(p, i, a, ctx, kc, vc, state):
        pre, li = f"layers.{i}.power.", kind_index[i]
        q, k, v, gamma = power_rows(p, pre, a, ctx["pos"], ctx["valid"])
        o, s_new, z_new = _power.power_recurrent_step(
            state["P"], state["Z"], li, ctx["slot"], q, k, v, gamma,
            tile=desc.power.tile)
        return power_output(p, pre, a, o), kc, vc, \
            {**state, "P": s_new, "Z": z_new}

    packed = {"kda": kda_packed, "mla": mla_packed, "cca": cca_packed,
              "power": power_packed}
    step = {"kda": kda_step, "mla": mla_step, "cca": cca_step,
            "power": power_step}

    def joined(p, pre, x, y):
        """A sublayer's output y with the residual stream x."""
        if not desc.residual_scaling:
            return x + y
        return (p[pre + "a_res"] * x + p[pre + "b_res"]) \
            + (p[pre + "a_out"] * y + p[pre + "b_out"])

    def trunk(p, x, ctx, kc, vc, state, mixers):
        """Every layer: x + mixer(norm(x)), then x + ffn(norm(x)) (each sum
        scaled where the description says so).  Returns (x, kc, vc, state,
        routed): see the module's docstring."""
        counts, picks, carry = [], [], None
        for i, layer in enumerate(desc.layers):
            pre = f"layers.{i}."
            m, kc, vc, state = mixers[layer.mixer](
                p, i, rms(x, p[pre + "norm_1.weight"]), ctx, kc, vc, state)
            x = joined(p, pre + "res_1.", x, m)
            y, c, pk, carry = ffn(p, i, rms(x, p[pre + "norm_2.weight"]),
                                  ctx["valid"], carry)
            x = joined(p, pre + "res_2.", x, y)
            if c is not None:
                counts.append(c)
                picks.append(pk)
        routed = {"counts": jnp.stack(counts),
                  "picks": jnp.stack(picks)} if counts else None
        return x, kc, vc, state, routed

    def head(p):
        if desc.tied_head:
            return lambda xf: (xf @ p["embed.weight"].T).astype(f32)
        return lambda xf: (xf @ p["lm_head.weight"]).astype(f32)

    ns = type("BlockFns", (), {})()
    ns.rms, ns.trunk, ns.packed, ns.step, ns.head = (rms, trunk, packed,
                                                     step, head)
    ns.ffn = ffn
    return ns


@functools.lru_cache(maxsize=64)
def build_block_programs(desc, block_size, return_logits, mode):
    """(packed_prefill_fn, step_fn) for a description, raw and jittable:
    `nn.decode`'s own signatures (see `_build_packed_prefill` and
    `_build_paged_fns`, `prev` of its step included) with the
    description's caches where GPT-2's take (kc, vc): (kc, state) for a
    latent pool, (kc, vc, state) for a pool of K and V rows
    (`cache_layout()["values"]`); kc, vc, state, a table row and `routed`
    are as this module's docstring says.  They return (token, stopped,
    *caches, counts, routed), and the logits after that with
    `return_logits`."""
    import jax
    import jax.numpy as jnp

    from ..sampling import processors as _proc
    from .decode import _make_readout

    BS, C = int(block_size), desc.chunk
    _sampled, penalties = mode
    fn = _block_fns(desc)
    readout = _make_readout(None, lambda x: x, mode, _proc)
    values = desc.values

    def finish(p, xf, sp):
        """(token, logits|None) of the rows xf [B, E]: final norm, untied
        head, the sampling pipeline every program shares."""
        return readout(fn.head(p), fn.rms(xf, p[desc.final_norm]), sp,
                       return_logits)

    def packed(params, toks, seg, pos, tables, sample_idx, kc, vc, state,
               sp):
        T, P = toks.shape[0], tables.shape[0]
        valid = pos >= 0
        p0 = jnp.where(valid, pos, 0)
        btab = tables[:, 1:]
        big = jnp.iinfo(jnp.int32).max
        idx = jnp.arange(T, dtype=jnp.int32)
        start_row = jax.ops.segment_min(jnp.where(valid, pos, big), seg, P)
        tiles = {}
        if desc.chunked:   # a KDA chunk, an MLA tile: C positions of one row
            if T % C:
                raise ValueError(f"a packed stream of {T} tokens is not "
                                 f"whole chunks of {C}")
            tile_p0 = pos[::C]
            tile_row = seg[::C]
            tile_live = tile_p0 >= 0
            tile_carry = tile_live & (tile_p0 != start_row[tile_row])
            tiles = {"tile_row": tile_row, "tile_p0": tile_p0,
                     "tile_carry": tile_carry}
        paged = {}
        if desc.pooled:   # where each token's row lies in the pool
            paged = {"blk": jnp.where(valid, btab[seg, p0 // BS], 0),
                     "off": p0 % BS}
        ctx = {
            "valid": valid, "pos": pos, "seg": seg, "btab": btab, **paged,
            "slot_row": tables[:, 0], "start_row": start_row,
            "start": start_row[seg],
            "end_row": jax.ops.segment_max(jnp.where(valid, pos, -1), seg,
                                           P) + 1,
            "first_row": jax.ops.segment_min(jnp.where(valid, idx, big),
                                             seg, P),
            **tiles,
        }
        if desc.chunked:
            ctx["tile_slot"] = jnp.where(tile_live, tables[tile_row, 0], 0)
            # the last chunk of its sequence in this stream leaves the state
            ctx["tile_last"] = tile_live & ~jnp.concatenate(
                [tile_carry[1:], jnp.zeros((1,), bool)])
        x, kc, vc, state, routed = fn.trunk(
            params, params["embed.weight"][toks], ctx, kc, vc, state,
            fn.packed)
        tok, logits = finish(params, x[sample_idx], sp)
        B = sample_idx.shape[0]
        stopped = _proc.check_stops(tok, sp["stop"], jnp.ones((B,), bool))
        counts = None
        if penalties:
            counts = _proc.update_counts(sp["counts"], sp["crows"], tok,
                                         sp["row_done"])
        caches = (kc, vc, state) if values else (kc, state)
        if return_logits:
            return (tok, stopped, *caches, counts, routed, logits)
        return (tok, stopped, *caches, counts, routed)

    def step(params, tok, pos, active, tables, kc, vc, state, sp, prev=None):
        B = tok.shape[0]
        if prev is not None:   # as `nn.decode`'s step: a negative tok
            tok = jnp.where(tok < 0, prev, tok)   # goes on from `prev`
        btab = tables[:, 1:]
        paged = {}
        if desc.pooled:
            paged = {"blk": jnp.where(active,
                                      btab[jnp.arange(B), pos // BS], 0),
                     "off": pos % BS}
        ctx = {
            "valid": active, "btab": btab, **paged,
            "slot": jnp.where(active, tables[:, 0], 0),
            "ctx": jnp.where(active, pos + 1, 0),
            "pos": pos,
        }
        x, kc, vc, state, routed = fn.trunk(
            params, params["embed.weight"][tok], ctx, kc, vc, state, fn.step)
        nxt, logits = finish(params, x, sp)
        nxt = jnp.where(active, nxt, 0)
        stopped = _proc.check_stops(nxt, sp["stop"], active)
        counts = None
        if penalties:
            counts = _proc.update_counts(sp["counts"], jnp.arange(B), nxt,
                                         active)
        caches = (kc, vc, state) if values else (kc, state)
        if return_logits:
            return (nxt, stopped, *caches, counts, routed, logits)
        return (nxt, stopped, *caches, counts, routed)

    if values:
        return packed, step

    # a latent pool has no V: the programs' own signatures leave it out
    def packed_prefill_fn(params, toks, seg, pos, tables, sample_idx, kc,
                          state, sp):
        return packed(params, toks, seg, pos, tables, sample_idx, kc, None,
                      state, sp)

    def step_fn(params, tok, pos, active, tables, kc, state, sp, prev=None):
        return step(params, tok, pos, active, tables, kc, None, state, sp,
                    prev)

    return packed_prefill_fn, step_fn
