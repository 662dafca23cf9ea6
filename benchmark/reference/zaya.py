"""ZAYA1, the plain way: one sequence's forward in jax.numpy, float32,
"highest" matmul precision; no kernel, no cache, no chunking, no batching.

Follows the published `config.json` of ZAYA1-8B and, where the config is
silent, the two public descriptions of the family (Compressed
Convolutional Attention, Zyphra, arXiv:2510.04476; the ZAYA1 technical
report, Zyphra, arXiv:2511.17127) as ISSUE 30 restates them (`ASSUMED`
below).  E hidden, Hq query heads on Hkv K/V heads of D (G = Hq / Hkv),
Cq = Hq D, Ck = Hkv D.  A layer is two pre-norm sublayers on the residual
stream, each joined by learned scaling,
    x <- (a_res * x + b_res) + (a_out * f(RMSNorm(x)) + b_out):

  CCA mixer, for the normed input a_t:
    1. q~_t = W_q a_t (Cq), k~_t = W_k a_t (Ck); c_t = [q~_t | k~_t].
    2. c'_t = sum_{j < time0} w0[j] * c_{t-j} + b0 (depthwise), then
       c''_t = sum_{j < time1} W1[j] c'_{t-j} + b1 with W1[j] block-
       diagonal over the Hq + Hkv heads; rows before the first are zero;
       [q^c_t | k^c_t] = c''_t.
    3. m^q_h = (q~_h + k~_{h // G}) / 2; m^k_g = mean of m^q_h over the G
       query heads of K/V head g; q = q^c + m^q, k = k^c + m^k.
    4. v_t = [W_v1 a_t | W_v2 a_{t-1}] (a_{-1} = 0), read as Hkv heads.
    5. q_h <- sqrt(D) q_h / |q_h|; k_g <- exp(tau_g) sqrt(D) k_g / |k_g|.
    6. rotary on the first partial_rotary_factor * D channels of every q
       and k head, by halves, theta = rope_parameters.hybrid.rope_theta.
    7. o_h = causal softmax(q_h . k_{h // G} / sqrt(D)) v_{h // G}; the
       output is W_o concat_h o_h.
  Expert sublayer, on the normed input a of layer l:
    r_l = W_down a + gamma_l r_{l-1} (r_{-1} = 0);
    z_l = W_3 gelu(W_2 gelu(W_1 RMSNorm(r_l))); s_l = softmax(z_l);
    the chosen expert is argmax(s_l + b_l); y = s_{l,e} SwiGLU_e(a), as a
    dense loop over the experts HELD with a mask.  Where the two largest
    selection scores tie the caller may hand over a program's choice
    (`expert_ffn`).  `held` = (first, count): the experts whose weights
    are here; what the others would add is left out, as the program
    leaves it out.
  A final RMSNorm, then the embedding's own matrix as the head.

It imports nothing of the program.  The one thing it takes from it is the
flat parameter dictionary, by these names ([in, out] weight layout):
  embed.weight [V, E]   norm_f.weight
  layers.<i>.norm_1.weight   layers.<i>.norm_2.weight
  layers.<i>.res_{1,2}.{a_res,b_res,a_out,b_out} [E]
  layers.<i>.cca.{q_proj.weight [E, Cq], k_proj.weight [E, Ck],
    v1_proj.weight [E, Ck/2], v2_proj.weight [E, Ck/2], conv0.weight
    [time0, Cq+Ck] (tap j on the input j back), conv0.bias [Cq+Ck],
    conv1.weight [time1, Hq+Hkv, D, D] (in, out), conv1.bias [Cq+Ck],
    k_scale [Hkv] (tau), o_proj.weight [Cq, E]}
  layers.<i>.moe.{router.down.weight [E, R], router.gamma [1],
    router.norm.weight [R], router.w1.weight [R, R], router.w2.weight
    [R, R], router.w3.weight [R, n_experts], router.bias [n_experts],
    experts.gate [held, E, F], experts.up [held, E, F], experts.down
    [held, F, E]}

Weights are upcast to float32 where they are used, one matrix (one
expert) at a time, and logits are returned only at the positions asked
for, so that the published widths fit one chip beside nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_UNCHECKED = "restated in ISSUE 30, not checked against the released " \
    "implementation"
ASSUMED = {
    "residual_scaling": "x <- (a_res * x + b_res) + (a_out * f(RMSNorm(x)) "
                        "+ b_out), four learned vectors of width E a "
                        "sublayer, initial values a = 1, b = 0; "
                        + _UNCHECKED,
    "conv_order": "the depthwise convolution, then the per-head one, each "
                  "with a bias; " + _UNCHECKED,
    "qk_mean": "m^q_h = (q~_h + k~_{h // G}) / 2, m^k_g = the mean of m^q "
               "over the G query heads of K/V head g, added to the "
               "convolutions' outputs; " + _UNCHECKED,
    "value_shift": "v_t = [W_v1 a_t | W_v2 a_{t-1}], each half Ck / 2 wide, "
                   "a_{-1} = 0, read as Hkv heads of D; " + _UNCHECKED,
    "qk_norm": "q to length sqrt(D), k to exp(tau_g) sqrt(D) with one "
               "learned scalar a K/V head, 0 at the start; |x| = sqrt(sum "
               "x^2 + 1e-6); " + _UNCHECKED,
    "rotary_pairing": "the two halves of the rotary channels are paired "
                      "(rotate-half); " + _UNCHECKED,
    "router": "r_l = W_down a + gamma_l r_{l-1} with one learned scalar a "
              "layer (initial 0.5); z = W_3 gelu(W_2 gelu(W_1 RMSNorm(r))), "
              "three layers, GELU (the exact, erf form), the norm's eps "
              "rms_norm_eps; " + _UNCHECKED,
    "query_block": 512,   # scores are formed 512 queries at a time
}
NORM_EPS = 1e-6


def arch(cfg):
    """The sizes this file needs, from a configuration file's keys (the
    published names).  `layers` counts the layers held, from the first."""
    rope = cfg["rope_parameters"]["hybrid"]
    return {
        "hidden": cfg["hidden_size"], "eps": cfg["rms_norm_eps"],
        "layers": cfg["layers"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "rotary_dim": int(rope["partial_rotary_factor"] * cfg["head_dim"]),
        "theta": float(rope["rope_theta"]),
        "top_k": cfg["num_experts_per_tok"],
        "held": tuple(cfg["deployment"]["held_experts"]),
    }


def _f32(w):
    return jnp.asarray(w).astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def shifted(x, j):
    """x [S, C] -> its rows j positions earlier, zeros before the first."""
    return x if j == 0 else jnp.pad(x, ((j, 0), (0, 0)))[:x.shape[0]]


def rotary(x, a):
    """x [S, H, D] at positions 0 .. S-1: the first `rotary_dim` channels
    of every head rotated by halves, the rest untouched."""
    r = a["rotary_dim"]
    half = r // 2
    freq = a["theta"] ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / r)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], axis=-1)


def cca_mixer(p, pre, x, a, tail_len=None):
    """x [S, E] (normed) -> ([S, E], tails): `tails` are what a sequence
    leaves behind for its next token after its first `tail_len` tokens
    (None: all): {"conv0": c_{t-1} [time0-1, C], "conv1": c'_{t-1}
    [time1-1, C], "v_prev": W_v2 a_{t-1} [1, Ck/2]}, oldest row first."""
    s_len = x.shape[0]
    hq, hkv, d = a["heads"], a["kv_heads"], a["head_dim"]
    g = hq // hkv
    c = jnp.concatenate([x @ _f32(p[pre + "q_proj.weight"]),
                         x @ _f32(p[pre + "k_proj.weight"])], axis=-1)
    w0 = _f32(p[pre + "conv0.weight"])
    c1 = sum(w0[j] * shifted(c, j) for j in range(w0.shape[0])) \
        + _f32(p[pre + "conv0.bias"])
    w1 = _f32(p[pre + "conv1.weight"])                  # [K, heads, D, D]
    c2 = sum(jnp.einsum("shd,hde->she",
                        shifted(c1, j).reshape(s_len, hq + hkv, d), w1[j])
             for j in range(w1.shape[0])).reshape(s_len, -1) \
        + _f32(p[pre + "conv1.bias"])
    q0 = c[:, :hq * d].reshape(s_len, hq, d)
    k0 = c[:, hq * d:].reshape(s_len, hkv, d)
    mq = (q0 + jnp.repeat(k0, g, axis=1)) / 2
    mk = mq.reshape(s_len, hkv, g, d).mean(axis=2)
    q = c2[:, :hq * d].reshape(s_len, hq, d) + mq
    k = c2[:, hq * d:].reshape(s_len, hkv, d) + mk
    v2 = x @ _f32(p[pre + "v2_proj.weight"])
    v = jnp.concatenate([x @ _f32(p[pre + "v1_proj.weight"]),
                         shifted(v2, 1)], axis=-1).reshape(s_len, hkv, d)

    def length(t):
        return jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + NORM_EPS)

    q = d ** 0.5 * q / length(q)
    k = jnp.exp(_f32(p[pre + "k_scale"]))[None, :, None] * d ** 0.5 * k \
        / length(k)
    q, k = rotary(q, a), rotary(k, a)
    t = jnp.arange(s_len)
    q = q.reshape(s_len, hkv, g, d)    # query head h = (h // G, h % G)
    out = []
    for s0 in range(0, s_len, ASSUMED["query_block"]):
        qb = q[s0:s0 + ASSUMED["query_block"]]
        live = t[None, :] <= (s0 + jnp.arange(qb.shape[0]))[:, None]
        # every query head against its own K/V head's keys and values
        sc = jnp.einsum("sgrd,tgd->grst", qb, k) * d ** -0.5
        w = jax.nn.softmax(jnp.where(live, sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("grst,tgd->sgrd", w, v)
                   .reshape(qb.shape[0], hq * d))
    o = jnp.concatenate(out, axis=0) @ _f32(p[pre + "o_proj.weight"])
    n = s_len if tail_len is None else tail_len

    def last(z, rows):     # rows n-rows .. n-1 of z, zeros before the first
        padded = jnp.pad(z, ((rows, 0), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(padded, n, rows, axis=0)

    return o, {"conv0": last(c, w0.shape[0] - 1),
               "conv1": last(c1, w1.shape[0] - 1), "v_prev": last(v2, 1)}


def router(p, pre, x, r_before, a):
    """(r_l [S, R], s_l [S, n_experts]) of the router on the normed x."""
    r = x @ _f32(p[pre + "down.weight"])
    if r_before is not None:
        r = r + _f32(p[pre + "gamma"]) * r_before
    h = rms_norm(r, p[pre + "norm.weight"], a["eps"])
    h = jax.nn.gelu(h @ _f32(p[pre + "w1.weight"]), approximate=False)
    h = jax.nn.gelu(h @ _f32(p[pre + "w2.weight"]), approximate=False)
    return r, jax.nn.softmax(h @ _f32(p[pre + "w3.weight"]), axis=-1)


def expert_ffn(p, pre, x, r_before, a, served=None, tie=0.0):
    """x [S, E] -> (y [S, E], r_l, found): the held experts' share of the
    routed sum (top 1: s_e * SwiGLU_e(x), no renormalisation).

    `served` [S, 1] is what a program under test chose at each position.
    How far its choice lies from this function's own is `gap` [S]: how far
    the expert it took lies under the largest selection score (0 where the
    two agree).  Where the gap is at most `tie` the position takes the
    program's choice (`swapped`), weighted by this function's own score of
    it; a wider gap is the program's fault (`outside`): this function's
    own choice stands and the difference is left to show.  `spread` says
    how far the largest selection score lies over the second."""
    r, s = router(p, pre + "router.", x, r_before, a)
    sel = s + _f32(p[pre + "router.bias"])
    top, idx = jax.lax.top_k(sel, 2)
    idx = idx[:, :1]
    found = {"spread": top[:, 0] - top[:, 1]}
    found["gap"] = jnp.zeros(x.shape[:1], jnp.float32)
    found["swapped"] = found["outside"] = jnp.zeros(x.shape[:1], bool)
    if served is not None:
        theirs = jnp.take_along_axis(sel, served, axis=-1)[:, 0]
        differ = served[:, 0] != idx[:, 0]
        found["gap"] = jnp.where(differ, top[:, 0] - theirs, 0.0)
        found["swapped"] = differ & (found["gap"] <= tie)
        found["outside"] = differ & (found["gap"] > tie)
        idx = jnp.where(found["swapped"][:, None], served, idx)
    w = jnp.take_along_axis(s, idx, axis=-1)
    first, count = a["held"]

    def one(y, e_and_w):            # a dense loop over the experts held
        e, gate, up, down = e_and_w
        mine = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)   # [S]
        return y + mine[:, None] * swiglu(x, gate, up, down), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(count), p[pre + "experts.gate"], p[pre + "experts.up"],
         p[pre + "experts.down"]))
    return y, r, found


def joined(p, pre, x, y):
    return (_f32(p[pre + "a_res"]) * x + _f32(p[pre + "b_res"])) \
        + (_f32(p[pre + "a_out"]) * y + _f32(p[pre + "b_out"]))


def layer(p, x, r_before, a, served=None, tie=0.0, tail_len=None):
    """One layer on the residual stream x [S, E]: `p` holds the layer's own
    parameters (their names after "layers.<i>."), `r_before` the router
    state of the layer before (None for the first).  -> (x, r, tails,
    found) as `cca_mixer` and `expert_ffn` give them."""
    h = rms_norm(x, p["norm_1.weight"], a["eps"])
    y, tails = cca_mixer(p, "cca.", h, a, tail_len)
    x = joined(p, "res_1.", x, y)
    h = rms_norm(x, p["norm_2.weight"], a["eps"])
    y, r, found = expert_ffn(p, "moe.", h, r_before, a, served, tie)
    return joined(p, "res_2.", x, y), r, tails, found


@functools.partial(jax.jit, static_argnames=("frozen",))
def _layer_once(p, x, r_before, served, tie, tail_len, frozen):
    """`layer`, compiled once for all the layers of a stack (they differ
    in their weights alone): the whole stack as one program takes the
    chip's compiler minutes at the published depth."""
    return layer(p, x, r_before, dict(frozen), served, tie, tail_len)


def hidden(params, ids, a, served=None, tie=0.0, tail_len=None):
    """ids [S] int tokens of ONE sequence -> (x [S, E] float32 after the
    last block, before the final norm; found).  `served` [layers, S, 1]
    are a program's choices (`expert_ffn`); `found` holds, per position,
    in how many layers it was `swapped` or `outside`, its widest `gap` and
    its narrowest `spread` over them, and `tails`: every layer's conv
    tails after the first `tail_len` tokens ({name: [layers, rows, C]})."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed.weight"][ids])
        zero = jnp.zeros(ids.shape, jnp.int32)
        found = {"swapped": zero, "outside": zero,
                 "gap": jnp.zeros(ids.shape, jnp.float32),
                 "spread": jnp.full(ids.shape, jnp.inf, jnp.float32)}
        tails, r = [], None
        n = ids.shape[0] if tail_len is None else tail_len
        for i in range(a["layers"]):
            pre = f"layers.{i}."
            mine = {k[len(pre):]: v for k, v in params.items()
                    if k.startswith(pre)}
            x, r, t, f = _layer_once(
                mine, x, r, None if served is None else served[i], tie, n,
                tuple(sorted(a.items())))
            tails.append(t)
            for key in ("swapped", "outside"):
                found[key] = found[key] + f[key]
            found["gap"] = jnp.maximum(found["gap"], f["gap"])
            found["spread"] = jnp.minimum(found["spread"], f["spread"])
        found["tails"] = {name: jnp.stack([t[name] for t in tails])
                          for name in tails[0]}
        return x, found


def head(params, x_rows, a):
    """Rows of `hidden`'s x -> their logits [rows, V] float32: the final
    RMSNorm, then the embedding's matrix (a tied head)."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(x_rows, params["norm_f.weight"], a["eps"]) \
            @ _f32(params["embed.weight"]).T


def logits(params, ids, a, positions, **kw):
    """(logits [len(positions), V] float32 at `positions` of the one
    sequence ids [S], found); keywords and `found` as `hidden`'s."""
    x, found = hidden(params, ids, a, **kw)
    return head(params, x[positions], a), found
