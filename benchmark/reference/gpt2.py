"""GPT-2, the plain way: forward and loss in jax.numpy, float32, "highest"
matmul precision, no kernel, no cache, no batching tricks.

Follows Radford et al. 2019 ("Language Models are Unsupervised Multitask
Learners") as released: learned token and position embeddings, pre-LN
blocks, one fused QKV projection split q|k|v, causal softmax attention with
1/sqrt(head) scale, tanh-GELU MLP, final LayerNorm, output head tied to the
token embedding, mean next-token cross-entropy.  No departure from the
release; dropout is 0 in every cell.

It imports nothing of the program.  The one thing it takes from it is the
flat parameter dictionary, by these names ([in, out] weight layout):
  wte.weight [V, E]   wpe.weight [P, E]   ln_f.{weight,bias}
  h.<i>.ln_1.*  h.<i>.qkv_proj.*  h.<i>.out_proj.*  h.<i>.ln_2.*
  h.<i>.fc1.*   h.<i>.fc2.*
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _f32(params):
    return {k: v.astype(jnp.float32) for k, v in params.items()}


def layer_norm(x, w, b, eps=LN_EPS):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def logits(params, input_ids, num_layers, num_heads):
    """[B, S] int tokens -> [B, S, V] float32 logits."""
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        b, s = input_ids.shape
        x = p["wte.weight"][input_ids] + p["wpe.weight"][jnp.arange(s)]
        e = x.shape[-1]
        d = e // num_heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(num_layers):
            g = lambda n: p[f"h.{i}.{n}"]  # noqa: E731
            a = layer_norm(x, g("ln_1.weight"), g("ln_1.bias"))
            qkv = a @ g("qkv_proj.weight") + g("qkv_proj.bias")
            q, k, v = (t.reshape(b, s, num_heads, d).transpose(0, 2, 1, 3)
                       for t in jnp.split(qkv, 3, axis=-1))
            scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(float(d))
            scores = jnp.where(causal, scores, -jnp.inf)
            o = jax.nn.softmax(scores, axis=-1) @ v
            o = o.transpose(0, 2, 1, 3).reshape(b, s, e)
            x = x + o @ g("out_proj.weight") + g("out_proj.bias")
            m = layer_norm(x, g("ln_2.weight"), g("ln_2.bias"))
            m = jax.nn.gelu(m @ g("fc1.weight") + g("fc1.bias"),
                            approximate=True)
            x = x + m @ g("fc2.weight") + g("fc2.bias")
        x = layer_norm(x, p["ln_f.weight"], p["ln_f.bias"])
        return x @ p["wte.weight"].T


def loss(params, batch, num_layers, num_heads):
    """Mean cross-entropy of `labels` under the logits of `input_ids`."""
    lg = logits(params, batch["input_ids"], num_layers, num_heads)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
    return -picked.mean()
