"""BERT's masked-language-model pretraining loss, the plain way: jax.numpy,
float32, "highest" matmul precision, no kernel, no batching tricks.

Follows Devlin et al. 2018 ("BERT: Pre-training of Deep Bidirectional
Transformers"): token + position + segment embeddings then LayerNorm,
post-LN encoder blocks (attention, add, LayerNorm; erf-GELU MLP, add,
LayerNorm), the MLM head (dense, GELU, LayerNorm, decoder tied to the token
embedding plus a bias), mean cross-entropy over the masked positions
(label -100 = not masked).  Departures, all the program's and mirrored here
so that the comparison means something:
  * the encoder blocks' LayerNorms use eps 1e-5 (the generic
    nn.TransformerEncoderLayer default); the release uses 1e-12 everywhere.
    The embedding and MLM-head LayerNorms use the release's 1e-12.
  * the next-sentence loss is left out (the cells train MLM only); the
    pooler and NSP parameters exist and get no gradient.
  * segment ids are all 0, no padding mask.

It imports nothing of the program; it takes its flat parameter dictionary
by name ([in, out] weight layout): embeddings.{word,position,token_type}_
embeddings.weight, embeddings.layer_norm.*, encoder.layers.<i>.self_attn.
{q,k,v,out}_proj.*, .linear1.*, .linear2.*, .norm1.*, .norm2.*,
mlm_transform.*, mlm_norm.*, mlm_bias.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ENCODER_LN_EPS = 1e-5
HEAD_LN_EPS = 1e-12
IGNORE = -100


def layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def mlm_logits(params, input_ids, num_layers, num_heads):
    """[B, S] int tokens -> [B, S, V] float32 logits of the MLM head."""
    with jax.default_matmul_precision("highest"):
        p = {k: v.astype(jnp.float32) for k, v in params.items()}
        b, s = input_ids.shape
        emb = "embeddings."
        x = (p[emb + "word_embeddings.weight"][input_ids]
             + p[emb + "position_embeddings.weight"][jnp.arange(s)]
             + p[emb + "token_type_embeddings.weight"][0])
        x = layer_norm(x, p[emb + "layer_norm.weight"],
                       p[emb + "layer_norm.bias"], HEAD_LN_EPS)
        e = x.shape[-1]
        d = e // num_heads

        def heads(t):
            return t.reshape(b, s, num_heads, d).transpose(0, 2, 1, 3)

        for i in range(num_layers):
            g = lambda n: p[f"encoder.layers.{i}.{n}"]  # noqa: E731
            q, k, v = (heads(x @ g(f"self_attn.{n}_proj.weight")
                             + g(f"self_attn.{n}_proj.bias"))
                       for n in "qkv")
            scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(float(d))
            o = jax.nn.softmax(scores, axis=-1) @ v
            o = o.transpose(0, 2, 1, 3).reshape(b, s, e)
            o = o @ g("self_attn.out_proj.weight") \
                + g("self_attn.out_proj.bias")
            x = layer_norm(x + o, g("norm1.weight"), g("norm1.bias"),
                           ENCODER_LN_EPS)
            m = jax.nn.gelu(x @ g("linear1.weight") + g("linear1.bias"),
                            approximate=False)
            m = m @ g("linear2.weight") + g("linear2.bias")
            x = layer_norm(x + m, g("norm2.weight"), g("norm2.bias"),
                           ENCODER_LN_EPS)
        h = jax.nn.gelu(x @ p["mlm_transform.weight"]
                        + p["mlm_transform.bias"], approximate=False)
        h = layer_norm(h, p["mlm_norm.weight"], p["mlm_norm.bias"],
                       HEAD_LN_EPS)
        return h @ p[emb + "word_embeddings.weight"].T + p["mlm_bias"]


def loss(params, batch, num_layers, num_heads):
    """Mean cross-entropy over the positions whose label is not -100."""
    lg = mlm_logits(params, batch["input_ids"], num_layers, num_heads)
    logp = jax.nn.log_softmax(lg, axis=-1)
    labels = batch["labels"]
    valid = labels != IGNORE
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return -(jnp.where(valid, picked, 0.0).sum() / valid.sum())
