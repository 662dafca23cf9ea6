"""Kimi-Linear, the plain way: one sequence's forward in jax.numpy, float32,
"highest" matmul precision; no kernel, no cache, no chunking, no batching.

Follows the published `config.json` of Kimi-Linear-48B-A3B-Instruct and the
released implementation where the config is silent (`ASSUMED` below).
Pre-norm residual blocks with RMSNorm, no position embedding anywhere
(`mla_use_nope`), an untied head after a final RMSNorm.  Three mechanisms:

  KDA mixer (the `kda` layers): q, k, v = SiLU(causal depthwise conv4 of a
    linear projection); q, k L2-normalised per head, q scaled by D^-0.5;
    per head and key channel a decay a_t = exp(-exp(A_log) * softplus(
    W_f^up W_f^down x + dt_bias)); per head beta_t = sigmoid(W_b x); the
    state S (key x value) follows, TOKEN BY TOKEN,
        S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
    and the output is W_o (sigmoid(W_g^up W_g^down x) * RMSNorm_head(o_t)).
  MLA mixer (the `mla` layers), UNABSORBED: q = W_q x split per head into
    q_nope | q_pe; [c | k_pe] = W_kva x, RMSNorm on c; [k_nope | v] =
    W_kvb c per head; scores (q_nope.k_nope + q_pe.k_pe) * (nope+pe)^-0.5,
    causal softmax, o = W_o concat_h(P v).  No rotation on q_pe, k_pe.
  Expert FFN: s = sigmoid(W_r x) over ALL experts; the top 8 of s + bias
    (one group); weights s_i / sum_chosen(s) * routed_scaling_factor;
    y = sum_i w_i SwiGLU_i(x) + SwiGLU_shared(x), as a dense loop over the
    experts HELD with a mask.  Where two experts tie for the last place
    the caller may hand over a program's choice (`expert_ffn`).
    `held` = (first, count): the experts whose
    weights are here; what the others would add is left out, as the
    program leaves it out (one chip's share of an expert-parallel layer).
  The first `dense_layers` layers have a plain SwiGLU MLP instead.

It imports nothing of the program.  The one thing it takes from it is the
flat parameter dictionary, by these names ([in, out] weight layout):
  embed.weight [V, E]   norm_f.weight   lm_head.weight [E, V]
  layers.<i>.norm_1.weight   layers.<i>.norm_2.weight
  layers.<i>.kda.{qkv_proj.weight [E, 3HD] (q | k | v), qkv_conv.weight
    [4, 3HD], f_down.weight, f_up.weight, dt_bias [HD], A_log [H],
    b_proj.weight [E, H], g_down.weight, g_up.weight, o_norm.weight [D],
    o_proj.weight [HD, E]}
  layers.<i>.mla.{q_proj.weight [E, H(nope+pe)], kva_proj.weight
    [E, lora+pe], kv_norm.weight [lora], kvb_proj.weight
    [lora, H(nope+v)], o_proj.weight [Hv, E]}
  layers.<i>.mlp.{gate_proj,up_proj,down_proj}.weight      (dense layers)
  layers.<i>.moe.{router.weight [E, n_experts], router.bias [n_experts],
    experts.gate [held, E, F], experts.up [held, E, F], experts.down
    [held, F, E], shared.{gate_proj,up_proj,down_proj}.weight}

Weights are upcast to float32 where they are used, one matrix (one
expert) at a time, and logits are returned only at the positions asked
for, so that the published widths fit one chip beside nothing else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ASSUMED = {
    "l2_eps": 1e-6,       # q, k: x / sqrt(sum(x^2) + eps), as released
    "state": "float32",   # the recurrent state's precision
    "query_block": 512,   # MLA scores are formed 512 queries at a time
}


def arch(cfg):
    """The sizes this file needs, from a configuration file's keys (the
    published names).  `layers` counts the layers held, from the first."""
    lin = cfg["linear_attn_config"]
    n = cfg["layers"]
    return {
        "hidden": cfg["hidden_size"], "eps": cfg["rms_norm_eps"],
        "kinds": tuple("kda" if i + 1 in lin["kda_layers"] else "mla"
                       for i in range(n)),
        "dense_layers": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"], "kda_heads": lin["num_heads"],
        "kda_dim": lin["head_dim"], "conv": lin["short_conv_kernel_size"],
        "nope": cfg["qk_nope_head_dim"], "pe": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "lora": cfg["kv_lora_rank"],
        "top_k": cfg["num_experts_per_token"],
        "renormalize": cfg["moe_renormalize"],
        "scaling": cfg["routed_scaling_factor"],
        "held": tuple(cfg["deployment"]["held_experts"]),
    }


def _f32(w):
    return jnp.asarray(w).astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def kda_mixer(p, pre, x, a, state_dtype=None, state_len=None):
    """x [S, E] -> ([S, E], the state [H, Dk, Dv] after the first
    `state_len` tokens (None: all)); the recurrence runs one token at a
    time.  `state_dtype` rounds the state after every token (None:
    float32)."""
    s_len = x.shape[0]
    h, d = a["kda_heads"], a["kda_dim"]
    z = x @ _f32(p[pre + "qkv_proj.weight"])                  # [S, 3HD]
    w = _f32(p[pre + "qkv_conv.weight"])                      # [K, 3HD]
    k_w = w.shape[0]
    zp = jnp.pad(z, ((k_w - 1, 0), (0, 0)))
    z = jax.nn.silu(sum(w[j] * zp[j:j + s_len] for j in range(k_w)))
    q, k, v = (t.reshape(s_len, h, d) for t in jnp.split(z, 3, axis=-1))

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                 + ASSUMED["l2_eps"])

    q, k = l2(q) * d ** -0.5, l2(k)
    f = (x @ _f32(p[pre + "f_down.weight"])) @ _f32(p[pre + "f_up.weight"])
    log_a = -jnp.exp(_f32(p[pre + "A_log"]))[None, :, None] \
        * jax.nn.softplus((f + _f32(p[pre + "dt_bias"])).reshape(s_len, h, d))
    beta = jax.nn.sigmoid(x @ _f32(p[pre + "b_proj.weight"]))  # [S, H]

    def token(carry, inp):                        # state [H, Dk, Dv]
        state, kept = carry
        t, q_t, k_t, v_t, la_t, b_t = inp
        state = jnp.exp(la_t)[..., None] * state
        u = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + b_t[:, None, None] * k_t[..., None] \
            * (v_t - u)[:, None, :]
        if state_dtype is not None:    # a cast pair would be optimised away
            info = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
        kept = jnp.where(t < n_state, state, kept)
        return (state, kept), jnp.einsum("hk,hkv->hv", q_t, state)

    n_state = s_len if state_len is None else state_len
    zero = jnp.zeros((h, d, d), jnp.float32)
    (_, kept), o = jax.lax.scan(token, (zero, zero),
                                (jnp.arange(s_len), q, k, v, log_a, beta))
    o = rms_norm(o, p[pre + "o_norm.weight"], a["eps"])       # per head
    g = (x @ _f32(p[pre + "g_down.weight"])) @ _f32(p[pre + "g_up.weight"])
    o = jax.nn.sigmoid(g.reshape(s_len, h, d)) * o
    return o.reshape(s_len, h * d) @ _f32(p[pre + "o_proj.weight"]), kept


def mla_mixer(p, pre, x, a):
    """x [S, E] -> [S, E]; keys and values are formed per head from the
    latent (unabsorbed), scores a block of queries at a time."""
    s_len = x.shape[0]
    h, nope, pe, vd, lora = (a["heads"], a["nope"], a["pe"], a["v_dim"],
                             a["lora"])
    q = (x @ _f32(p[pre + "q_proj.weight"])).reshape(s_len, h, nope + pe)
    kva = x @ _f32(p[pre + "kva_proj.weight"])
    c = rms_norm(kva[:, :lora], p[pre + "kv_norm.weight"], a["eps"])
    k_pe = kva[:, lora:]                                      # [S, pe]
    kv = (c @ _f32(p[pre + "kvb_proj.weight"])).reshape(s_len, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + pe) ** -0.5
    t = jnp.arange(s_len)
    out = []
    for s0 in range(0, s_len, ASSUMED["query_block"]):
        qb = q[s0:s0 + ASSUMED["query_block"]]
        sc = (jnp.einsum("shd,thd->hst", qb[..., :nope], k_nope)
              + jnp.einsum("shd,td->hst", qb[..., nope:], k_pe)) * scale
        live = t[None, :] <= (s0 + jnp.arange(qb.shape[0]))[:, None]
        w = jax.nn.softmax(jnp.where(live[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hst,thd->shd", w, v))
    o = jnp.concatenate(out, axis=0).reshape(s_len, h * vd)
    return o @ _f32(p[pre + "o_proj.weight"])


def expert_ffn(p, pre, x, a, served=None, tie=0.0):
    """x [S, E] -> (y [S, E], found): the held experts' share of the
    routed sum plus the shared expert.

    `served` [S, k] is what a program under test chose at each position.
    How far its set lies from this function's own top k is `gap` [S]: the
    most that an expert it took lies under the 8th selection score, or
    one it left out over the 9th (0 where the sets are equal).  Where the
    gap is at most `tie` the position may take either set, and takes the
    program's (`swapped`); its weights are still this function's own
    scores.  A wider gap is the program's fault (`outside`): this
    function's own choice stands and the difference is left to show.
    `tied` says where the 8th and 9th scores lie within `tie` of each
    other, `spread` how far the 1st lies over the 8th."""
    s = jax.nn.sigmoid(x @ _f32(p[pre + "router.weight"]))    # [S, n]
    sel = s + _f32(p[pre + "router.bias"])
    top, idx = jax.lax.top_k(sel, a["top_k"] + 1)
    s8, s9 = top[:, a["top_k"] - 1], top[:, a["top_k"]]
    idx = idx[:, :a["top_k"]]
    found = {"tied": s8 - s9 < tie, "spread": top[:, 0] - s8}
    found["gap"] = jnp.zeros(x.shape[:1], jnp.float32)
    found["swapped"] = found["outside"] = jnp.zeros(x.shape[:1], bool)
    if served is not None:
        n = sel.shape[-1]
        theirs = jax.nn.one_hot(served, n, dtype=bool).any(1)      # [S, n]
        mine = jax.nn.one_hot(idx, n, dtype=bool).any(1)
        under = jnp.where(theirs & ~mine, s8[:, None] - sel, 0.0).max(-1)
        over = jnp.where(mine & ~theirs, sel - s9[:, None], 0.0).max(-1)
        differ = (theirs != mine).any(-1)
        found["gap"] = jnp.maximum(under, over)
        found["swapped"] = differ & (found["gap"] <= tie)
        found["outside"] = differ & (found["gap"] > tie)
        idx = jnp.where(found["swapped"][:, None], served, idx)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if a["renormalize"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * a["scaling"]
    first, count = a["held"]

    def one(y, e_and_w):            # a dense loop over the experts held
        e, gate, up, down = e_and_w
        mine = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)   # [S]
        return y + mine[:, None] * swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(count), p[pre + "experts.gate"], p[pre + "experts.up"],
         p[pre + "experts.down"]))
    sh = pre + "shared."
    return y + swiglu(x, p[sh + "gate_proj.weight"], p[sh + "up_proj.weight"],
                      p[sh + "down_proj.weight"]), found


def hidden(params, ids, a, state_dtype=None, served=None, tie=0.0,
           state_len=None):
    """ids [S] int tokens of ONE sequence -> (x [S, E] float32 after the
    last block, before the final norm; found).  `served` [expert layers,
    S, k] are a program's choices (`expert_ffn`); `found` holds, per
    position, in how many expert layers it was `tied`, `swapped` or
    `outside`, its widest `gap` and its narrowest `spread` over them, and
    `states` [KDA layers, H, Dk, Dv]: every KDA layer's state after the
    first `state_len` tokens (None: all)."""
    with jax.default_matmul_precision("highest"):
        p = params
        x = _f32(p["embed.weight"][ids])
        zero = jnp.zeros(ids.shape, jnp.int32)
        found = {"tied": zero, "swapped": zero, "outside": zero,
                 "gap": jnp.zeros(ids.shape, jnp.float32),
                 "spread": jnp.full(ids.shape, jnp.inf, jnp.float32)}
        states = []
        moe = 0
        for i, kind in enumerate(a["kinds"]):
            pre = f"layers.{i}."
            h = rms_norm(x, p[pre + "norm_1.weight"], a["eps"])
            if kind == "kda":
                y, state = kda_mixer(p, pre + "kda.", h, a, state_dtype,
                                     state_len)
                x = x + y
                states.append(state)
            else:
                x = x + mla_mixer(p, pre + "mla.", h, a)
            h = rms_norm(x, p[pre + "norm_2.weight"], a["eps"])
            if i < a["dense_layers"]:
                m = pre + "mlp."
                x = x + swiglu(h, p[m + "gate_proj.weight"],
                               p[m + "up_proj.weight"],
                               p[m + "down_proj.weight"])
            else:
                y, f = expert_ffn(
                    p, pre + "moe.", h, a,
                    None if served is None else served[moe], tie)
                x, moe = x + y, moe + 1
                for key in ("tied", "swapped", "outside"):
                    found[key] = found[key] + f[key]
                found["gap"] = jnp.maximum(found["gap"], f["gap"])
                found["spread"] = jnp.minimum(found["spread"], f["spread"])
        found["states"] = jnp.stack(states) if states else None
        return x, found


def head(params, x_rows, a):
    """Rows of `hidden`'s x -> their logits [rows, V] float32: the final
    RMSNorm, then the untied head."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x_rows, params["norm_f.weight"], a["eps"]) \
            @ _f32(params["lm_head.weight"])


def logits(params, ids, a, positions, **kw):
    """(logits [len(positions), V] float32 at `positions` of the one
    sequence ids [S], found); keywords and `found` as `hidden`'s."""
    x, found = hidden(params, ids, a, **kw)
    return head(params, x[positions], a), found
