"""Kanana-2, the plain way: one sequence's forward in jax.numpy, float32,
"highest" matmul precision; no kernel, no cache, no absorption, no
batching.

Follows the published `config.json` of kanana-2-30b-a3b-instruct-2601 and
the DeepSeek-V3 layout its `model_type` names, written out as equations
(the released modelling code is not read: the configuration file's
`assumed` lists what the config is silent on).  Pre-norm residual blocks
with RMSNorm, no position table, an untied head after a final RMSNorm.

  MLA mixer (every layer), EXPANDED: q = W_q x split per head into
    q_nope [nope] | q_pe [pe]; [c | k_pe] = W_kva x, c RMS-normed with its
    own weight, k_pe ONE head shared by every query head; [k_nope | v] =
    W_kvb c per head.  q_pe and k_pe turn by their position t: pair i,
    channels 2i and 2i+1 (`rope_interleave`), by t * theta^(-2i / pe).
    Scores (q_nope . k_nope + q_pe . k_pe) * (nope + pe)^-0.5, causal
    softmax, o = W_o concat_h(P v).
  Expert FFN (layers from `first_k_dense_replace` on): Kimi-Linear's, the
    same equations (`reference/kimi_linear.expert_ffn`, imported): sigmoid
    scores over every expert, the top k of score + correction bias, weights
    the chosen scores over their sum times `routed_scaling_factor`, plus
    the shared experts, whose weights here are one SwiGLU of n_shared x
    the expert width.  Where two experts tie for the last place the caller
    may hand over a program's choice.
  The first `first_k_dense_replace` layers have a plain SwiGLU MLP.

It imports nothing of the program.  The one thing it takes from it is the
flat parameter dictionary, by these names ([in, out] weight layout):
  embed.weight [V, E]   norm_f.weight   lm_head.weight [E, V]
  layers.<i>.norm_1.weight   layers.<i>.norm_2.weight
  layers.<i>.mla.{q_proj.weight [E, H(nope+pe)], kva_proj.weight
    [E, lora+pe], kv_norm.weight [lora], kvb_proj.weight
    [lora, H(nope+v)], o_proj.weight [Hv, E]}
  layers.<i>.mlp.{gate_proj,up_proj,down_proj}.weight      (dense layers)
  layers.<i>.moe.{router.weight [E, n_experts], router.bias [n_experts],
    experts.gate [n, E, F], experts.up [n, E, F], experts.down [n, F, E],
    shared.{gate_proj,up_proj,down_proj}.weight}

Weights are upcast to float32 where they are used, scores are formed a
block of queries at a time and logits only at the rows asked for, so that
the published widths at 20k positions fit one chip beside the weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.kimi_linear import _f32, expert_ffn, rms_norm, swiglu

ASSUMED = {
    "query_block": 256,   # MLA scores are formed 256 queries at a time
}


def arch(cfg):
    """The sizes this file needs, from a configuration file's keys (the
    published names).  `layers` counts the layers held, from the first;
    every expert is held."""
    if not cfg["rope_interleave"] or cfg["rope_scaling"] is not None:
        raise ValueError("the interleaved pairing without scaling is what "
                         "this file computes")
    return {
        "hidden": cfg["hidden_size"], "eps": cfg["rms_norm_eps"],
        "layers": cfg["layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "pe": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "lora": cfg["kv_lora_rank"],
        "theta": float(cfg["rope_theta"]),
        "top_k": cfg["num_experts_per_tok"],
        "renormalize": cfg["norm_topk_prob"],
        "scaling": cfg["routed_scaling_factor"],
        "held": (0, cfg["n_routed_experts"]),
    }


def rope(x, a):
    """x [S, ..., pe] at positions 0 .. S-1, channels 2i and 2i+1 turned
    together."""
    s_len, pe = x.shape[0], x.shape[-1]
    angle = jnp.arange(s_len, dtype=jnp.float32)[:, None] \
        * a["theta"] ** (-jnp.arange(0, pe, 2, dtype=jnp.float32) / pe)
    angle = angle.reshape((s_len,) + (1,) * (x.ndim - 2) + (pe // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    u, w = x[..., 0::2], x[..., 1::2]
    return jnp.stack([u * cos - w * sin, w * cos + u * sin],
                     axis=-1).reshape(x.shape)


def mla_mixer(p, pre, x, a):
    """x [S, E] -> ([S, E], the latent rows [S, lora + pe]: RMSNorm(c) and
    the turned k_pe, what a cache of this layer would hold).  Keys and
    values are formed per head from the latent, scores a block of queries
    at a time."""
    s_len = x.shape[0]
    h, nope, pe, vd, lora = (a["heads"], a["nope"], a["pe"], a["v_dim"],
                             a["lora"])
    q = (x @ _f32(p[pre + "q_proj.weight"])).reshape(s_len, h, nope + pe)
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], a)
    kva = x @ _f32(p[pre + "kva_proj.weight"])
    c = rms_norm(kva[:, :lora], p[pre + "kv_norm.weight"], a["eps"])
    k_pe = rope(kva[:, lora:], a)                             # [S, pe]
    kv = (c @ _f32(p[pre + "kvb_proj.weight"])).reshape(s_len, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + pe) ** -0.5
    qb = ASSUMED["query_block"]
    blocks = -(-s_len // qb)
    pad = ((0, blocks * qb - s_len), (0, 0), (0, 0))

    def attend(inp):                 # one block of queries, every key
        s0, qn, qp = inp
        sc = (jnp.einsum("shd,thd->hst", qn, k_nope)
              + jnp.einsum("shd,td->hst", qp, k_pe)) * scale
        live = jnp.arange(s_len)[None, :] <= (s0 + jnp.arange(qb))[:, None]
        w = jax.nn.softmax(jnp.where(live[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hst,thd->shd", w, v)

    o = jax.lax.map(attend, (
        jnp.arange(blocks) * qb,
        jnp.pad(q_nope, pad).reshape(blocks, qb, h, nope),
        jnp.pad(q_pe, pad).reshape(blocks, qb, h, pe)))
    o = o.reshape(blocks * qb, h * vd)[:s_len]
    return o @ _f32(p[pre + "o_proj.weight"]), jnp.concatenate([c, k_pe], -1)


def hidden(params, ids, a, served=None, tie=0.0):
    """ids [S] int tokens of ONE sequence -> (x [S, E] float32 after the
    last block, before the final norm; found).  `served` [expert layers,
    S, k] are a program's choices (`reference/kimi_linear.expert_ffn`);
    `found` holds, per position, in how many expert layers it was `tied`,
    `swapped` or `outside`, its widest `gap` and its narrowest `spread`
    over them, and `latent` [S, lora + pe]: layer 0's latent rows."""
    with jax.default_matmul_precision("highest"):
        p = params
        x = _f32(p["embed.weight"][ids])
        zero = jnp.zeros(ids.shape, jnp.int32)
        found = {"tied": zero, "swapped": zero, "outside": zero,
                 "gap": jnp.zeros(ids.shape, jnp.float32),
                 "spread": jnp.full(ids.shape, jnp.inf, jnp.float32)}
        moe = 0
        for i in range(a["layers"]):
            pre = f"layers.{i}."
            y, lat = mla_mixer(p, pre + "mla.",
                               rms_norm(x, p[pre + "norm_1.weight"],
                                        a["eps"]), a)
            x = x + y
            if i == 0:
                found["latent"] = lat
            h = rms_norm(x, p[pre + "norm_2.weight"], a["eps"])
            if i < a["dense_layers"]:
                m = pre + "mlp."
                x = x + swiglu(h, p[m + "gate_proj.weight"],
                               p[m + "up_proj.weight"],
                               p[m + "down_proj.weight"])
                continue
            y, f = expert_ffn(p, pre + "moe.", h, a,
                              None if served is None else served[moe], tie)
            x, moe = x + y, moe + 1
            for key in ("tied", "swapped", "outside"):
                found[key] = found[key] + f[key]
            found["gap"] = jnp.maximum(found["gap"], f["gap"])
            found["spread"] = jnp.minimum(found["spread"], f["spread"])
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
