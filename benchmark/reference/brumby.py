"""Brumby, the plain way: one sequence's forward in jax.numpy, float32,
"highest" matmul precision; no kernel, no state, no cache, no chunking, no
batching, no feature map.

Follows the published `config.json` of Brumby-14B-Base, which is
Qwen3-14B's key for key and has no key for the retention, and, where the
config is silent, the public descriptions of the family (*Scaling Context
Requires Rethinking Attention*, Manifest AI, arXiv:2507.04239, and the
model's release note) as ISSUE 32 restates them (`ASSUMED` below).  E
hidden, Hq query heads on Hkv K/V heads of d (group = Hq / Hkv; query head
h reads K/V head g = h // group).  A layer is two pre-norm sublayers on the
residual stream, x <- x + f(RMSNorm(x)):

  Power-retention mixer, for the normed input a_t of position t:
    1. q_t = W_q a_t [Hq, d], k_t = W_k a_t, v_t = W_v a_t [Hkv, d].
    2. q_{t,h} <- w_qn * q_{t,h} / rms(q_{t,h}), k_{t,g} <- w_kn * k_{t,g} /
       rms(k_{t,g}): one learned vector of width d each a layer, the
       config's eps.
    3. rotary on all d channels of every q and k head, by halves, theta =
       rope_theta, position = the token's index in its sequence.
    4. gamma_{t,g} = log sigmoid((W_gamma a_t)_g) <= 0; G_{t,g} = its sum
       over r <= t.
    5. w_{t,s,h} = exp(G_{t,g} - G_{s,g}) (q_{t,h} . k_{s,g} / sqrt(d))^2
       for s <= t; o_{t,h} = sum_s w_{t,s,h} v_{s,g} / (sum_s w_{t,s,h} +
       eps).  No softmax, no maximum: an even power is non-negative.  The
       weights are formed from q . k as written, `query_block` query rows
       at a time so that a sequence of 18,000 tokens fits.
    6. the output is W_o concat_h o_{t,h}.
  SwiGLU: W_down(silu(W_gate a) * W_up a), `row_block` rows at a time.
  A final RMSNorm, then the untied head.

For the check of a program's state it also returns, of the FIRST layer and
after the first `state_len` positions n, sum_{s<n} e^{G_{n-1} - G_s} k~_s
(x) k~_s (x) v_s [Hkv, d, d, d] and sum_{s<n} e^{G_{n-1} - G_s} k~_s (x)
k~_s [Hkv, d, d] with k~ = k / d^(1/4): what a recurrence S_t = e^{gamma_t}
S_{t-1} + phi(k~_t) v_t^T holds, whatever its phi
(`paddle_tpu.ops.power_retention.unpack_state` brings a store row to this
form).

It imports nothing of the program.  The one thing it takes from it is the
flat parameter dictionary, by these names ([in, out] weight layout):
  embed.weight [V, E]   norm_f.weight   lm_head.weight [E, V]
  layers.<i>.norm_1.weight   layers.<i>.norm_2.weight
  layers.<i>.power.{q_proj.weight [E, Hq d], k_proj.weight [E, Hkv d],
    v_proj.weight [E, Hkv d], gate_proj.weight [E, Hkv] (W_gamma),
    q_norm.weight [d], k_norm.weight [d], o_proj.weight [Hq d, E]}
  layers.<i>.mlp.{gate_proj.weight [E, F], up_proj.weight [E, F],
    down_proj.weight [F, E]}

Weights are upcast to float32 where they are used, one matrix at a time,
and logits are returned only at the positions asked for, so that the
published widths fit one chip beside nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_UNCHECKED = "restated in ISSUE 32, not checked against the released " \
    "implementation"
ASSUMED = {
    "qk_norm": "Qwen3's per-head norms: q_{t,h} <- w_qn * q_{t,h} / "
               "rms(q_{t,h}), k_{t,g} <- w_kn * k_{t,g} / rms(k_{t,g}), one "
               "learned vector of width 128 each a layer, eps 1e-6: kept "
               "from the Qwen3 block the model was initialised from; "
               + _UNCHECKED,
    "rotary": "rotary embedding of all 128 channels of every q and k head, "
              "rope_theta 1,000,000, rope_scaling null, position = the "
              "token's index in its sequence, rotate-half pairing: kept, "
              "the pairing; " + _UNCHECKED,
    "gate": "gamma_{t,g} = log sigmoid((W_gamma a_t)_g) <= 0, W_gamma from "
            "E to Hkv = 8, the layer's only weight that Qwen3 lacks; "
            "G_{t,g} = sum_{r <= t} gamma_{r,g}: one gate a K/V head, the "
            "log-sigmoid, no bias; " + _UNCHECKED,
    "power": "power retention of degree p = 2: w_{t,s,h} = exp(G_{t,g} - "
             "G_{s,g}) * (q_{t,h} . k_{s,g} / sqrt(d))^2 for s <= t, "
             "o_{t,h} = sum_{s<=t} w_{t,s,h} v_{s,g} / (sum_{s<=t} "
             "w_{t,s,h} + eps): the degree; the scale 1/sqrt(d) inside the "
             "power; eps 1e-6; " + _UNCHECKED,
    "output": "the mixer's output is W_o concat_h o_{t,h}, W_o 5120 x "
              "5120: no output gate and no norm on o; " + _UNCHECKED,
    "query_block": 128,   # weights are formed 128 queries at a time
    "row_block": 2048,    # the SwiGLU runs 2,048 rows at a time
}
POWER_EPS = 1e-6


def arch(cfg):
    """The sizes this file needs, from a configuration file's keys (the
    published names).  `layers` counts the layers held, from the first."""
    return {
        "hidden": cfg["hidden_size"], "eps": cfg["rms_norm_eps"],
        "layers": cfg["layers"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "theta": float(cfg["rope_theta"]),
    }


def _f32(w):
    return jnp.asarray(w).astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def rotary(x, theta):
    """x [S, H, d] at positions 0 .. S-1: every head rotated by halves."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _blocks(s_len, block):
    if s_len % block:
        raise ValueError(f"{s_len} positions are not whole blocks of "
                         f"{block}: pad the sequence (causal: padding "
                         f"cannot reach back)")
    return s_len // block


def power_mixer(p, pre, x, a, state_len=None):
    """x [S, E] (normed) -> ([S, E], state): `state` is what the sequence's
    first `state_len` tokens (None: all) leave behind, {"S": [Hkv, d, d,
    d], "z": [Hkv, d, d]} as the module's docstring writes them."""
    s_len = x.shape[0]
    hq, hkv, d = a["heads"], a["kv_heads"], a["head_dim"]
    r = hq // hkv
    q = (x @ _f32(p[pre + "q_proj.weight"])).reshape(s_len, hq, d)
    k = (x @ _f32(p[pre + "k_proj.weight"])).reshape(s_len, hkv, d)
    v = (x @ _f32(p[pre + "v_proj.weight"])).reshape(s_len, hkv, d)
    q = rotary(rms_norm(q, p[pre + "q_norm.weight"], a["eps"]), a["theta"])
    k = rotary(rms_norm(k, p[pre + "k_norm.weight"], a["eps"]), a["theta"])
    gamma = jax.nn.log_sigmoid(x @ _f32(p[pre + "gate_proj.weight"]))
    g_run = jnp.cumsum(gamma, axis=0)                          # [S, Hkv]
    t = jnp.arange(s_len)
    block = ASSUMED["query_block"]
    qb = q.reshape(_blocks(s_len, block), block, hkv, r, d)

    def one(xs):
        qi, t0 = xs                     # a block's queries, its first row
        rows = t0 + jnp.arange(block)
        live = t[None, :] <= rows[:, None]                     # s <= t
        gq = jax.lax.dynamic_slice_in_dim(g_run, t0, block, axis=0)
        decay = jnp.exp(jnp.where(live[:, :, None],
                                  gq[:, None] - g_run[None, :], -jnp.inf))
        w = (jnp.einsum("tgrd,sgd->tsgr", qi, k) * d ** -0.5) ** 2 \
            * decay[..., None]
        return jnp.einsum("tsgr,sgv->tgrv", w, v) \
            / (w.sum(1)[..., None] + POWER_EPS)

    o = jax.lax.map(one, (qb, jnp.arange(0, s_len, block)))
    out = o.reshape(s_len, hq * d) @ _f32(p[pre + "o_proj.weight"])

    n = s_len if state_len is None else state_len
    left = jnp.where((t < n)[:, None], g_run[n - 1][None] - g_run,
                     -jnp.inf)
    wk = jnp.exp(left)[..., None] * k * d ** -0.25             # [S, Hkv, d]

    def head_state(xs):
        wk_g, k_g, v_g = xs                                    # [S, d] each
        kv = (k_g * d ** -0.25)[:, :, None] * v_g[:, None, :]
        return (wk_g.T @ kv.reshape(s_len, d * d)).reshape(d, d, d), \
            wk_g.T @ (k_g * d ** -0.25)

    s_n, z_n = jax.lax.map(head_state, (wk.transpose(1, 0, 2),
                                        k.transpose(1, 0, 2),
                                        v.transpose(1, 0, 2)))
    return out, {"S": s_n, "z": z_n}


def swiglu(p, pre, x):
    block = min(ASSUMED["row_block"], x.shape[0])
    gate, up = _f32(p[pre + "gate_proj.weight"]), \
        _f32(p[pre + "up_proj.weight"])
    down = _f32(p[pre + "down_proj.weight"])
    xb = x.reshape(_blocks(x.shape[0], block), block, x.shape[1])
    return jax.lax.map(
        lambda rows: (jax.nn.silu(rows @ gate) * (rows @ up)) @ down,
        xb).reshape(x.shape)


def layer(p, x, a, state_len=None):
    """One layer on the residual stream x [S, E]: `p` holds the layer's own
    parameters (their names after "layers.<i>.").  -> (x, state) as
    `power_mixer` gives it."""
    y, state = power_mixer(p, "power.", rms_norm(x, p["norm_1.weight"],
                                                 a["eps"]), a, state_len)
    x = x + y
    return x + swiglu(p, "mlp.", rms_norm(x, p["norm_2.weight"],
                                          a["eps"])), state


@functools.partial(jax.jit, static_argnames=("frozen",))
def _layer_once(p, x, state_len, frozen):
    """`layer`, compiled once for all the layers of a stack (they differ
    in their weights alone)."""
    return layer(p, x, dict(frozen), state_len)


def hidden(params, ids, a, state_len=None):
    """ids [S] int tokens of ONE sequence, S whole query blocks -> (x
    [S, E] float32 after the last block, before the final norm; found):
    `found` holds the FIRST layer's `state` after the first `state_len`
    tokens."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed.weight"][ids])
        n = jnp.asarray(ids.shape[0] if state_len is None else state_len,
                        jnp.int32)
        first = None
        for i in range(a["layers"]):
            pre = f"layers.{i}."
            mine = {k[len(pre):]: v for k, v in params.items()
                    if k.startswith(pre)}
            x, state = _layer_once(mine, x, n, tuple(sorted(a.items())))
            first = state if first is None else first
        return x, {"state": first}


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
