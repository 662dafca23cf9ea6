"""GPT-2 pipeline-parallel training step.

BASELINE config 5: "GPT-2 medium with fused_attention_op → Pallas flash-attn,
pipeline-parallel Fleet". The L transformer blocks are stacked into per-leaf
[L, ...] arrays, the leading dim is sharded over the `pp` mesh axis, and each
rank scans its local L/S blocks inside the GPipe schedule
(parallel/pipeline.py). Embedding + final-LN/head run replicated outside the
pipelined region; their grads flow through the shard_map boundary.
"""
from __future__ import annotations

import numpy as np

from .gpt2 import GPT2, GPT2Config


def _split_block_params(params):
    """Split flat name->array params into (stacked_blocks, other).

    stacked_blocks: {subname: [L, ...]} for names 'h.{i}.{subname}'.
    """
    import jax.numpy as jnp
    blocks = {}
    other = {}
    for name, v in params.items():
        if name.startswith("h."):
            _, idx, sub = name.split(".", 2)
            blocks.setdefault(sub, {})[int(idx)] = v
        else:
            other[name] = v
    stacked = {sub: jnp.stack([d[i] for i in range(len(d))])
               for sub, d in blocks.items()}
    return stacked, other


def _merge_block_params(stacked, other):
    params = dict(other)
    for sub, arr in stacked.items():
        for i in range(arr.shape[0]):
            params[f"h.{i}.{sub}"] = arr[i]
    return params


def build_pp_train_step(cfg: GPT2Config, mesh, num_microbatches=4,
                        pp_axis="pp", schedule="gpipe", num_virtual=1):
    """Returns (loss_fn(stacked, other, batch), init()) where loss_fn runs
    the selected pipeline schedule over `pp_axis` of `mesh` ("gpipe", or
    "interleaved" with `num_virtual` chunks per rank — see
    parallel/pipeline.py for the schedules and their bubble fractions)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..core import rng as rng_mod
    from ..core.tensor import Tensor
    from ..parallel.pipeline import (pipeline_apply,
                                     pipeline_apply_interleaved)

    model = GPT2(cfg)
    model.train()
    assert cfg.dropout == 0.0, "pp step: disable dropout (rng is per-trace)"
    s_pp = mesh.shape[pp_axis]
    assert cfg.num_layers % s_pp == 0
    if schedule not in ("gpipe", "interleaved"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    interleaved = schedule == "interleaved" and num_virtual > 1
    if interleaved:
        assert cfg.num_layers % (s_pp * num_virtual) == 0
        assert num_microbatches % s_pp == 0

    block0 = model.h[0]

    def block_apply(block_tree, x):
        """Apply one transformer block with the given param tree (names are
        block-relative, e.g. 'ln_1.weight')."""
        lookup = dict(block0.named_parameters())
        saved = {n: p._value for n, p in lookup.items()}
        for n, v in block_tree.items():
            lookup[n]._value = v
        try:
            return block0(Tensor(x))._value
        finally:
            for n, p in lookup.items():
                p._value = saved[n]

    def stage_fn(stage_tree, x):
        # stage_tree leaves: [L/S, ...] — scan the local blocks
        def body(h, one_block):
            return block_apply(one_block, h), None

        out, _ = jax.lax.scan(body, x, stage_tree)
        return out

    def init():
        params, _ = model.functional_state()
        stacked, other = _split_block_params(params)
        return stacked, other

    def embed(other, input_ids):
        s = input_ids.shape[1]
        pos = jnp.arange(s)
        return (jnp.take(other["wte.weight"], input_ids, axis=0)
                + jnp.take(other["wpe.weight"], pos, axis=0))

    def head_loss(other, h, labels):
        ln_w = other["ln_f.weight"]
        ln_b = other["ln_f.bias"]
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.var(h, axis=-1, keepdims=True)
        h = (h - mu) * jax.lax.rsqrt(var + cfg.layer_norm_epsilon)
        h = h * ln_w + ln_b
        logits = jnp.einsum("bsd,vd->bsv", h, other["wte.weight"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, labels[..., None].astype(jnp.int32), axis=-1)
        return jnp.mean(nll)

    def loss_fn(stacked, other, batch):
        x0 = embed(other, batch["input_ids"])

        def inner(stacked_local, x0, labels):
            m = num_microbatches
            mbs = x0.reshape((m, x0.shape[0] // m) + x0.shape[1:])
            if interleaved:
                # leaves [V, 1, L/(V*S), ...]: this rank's V chunks
                chunk_tree = jax.tree_util.tree_map(
                    lambda p: p[:, 0], stacked_local)
                outs = pipeline_apply_interleaved(stage_fn, chunk_tree,
                                                  mbs, pp_axis)
            else:
                stage_tree = stacked_local  # leaves [L/S, ...] local
                outs = pipeline_apply(stage_fn, stage_tree, mbs, pp_axis)
            h = outs.reshape((x0.shape[0],) + outs.shape[2:])
            return h

        if interleaved:
            # [L, ...] layer order -> [V, S, L/(V*S), ...]; shard dim 1 on
            # pp: rank r holds chunks {l*S + r} of consecutive layers —
            # the circular placement (layers l*S*(L/VS) + r*(L/VS) ...)
            nblk = cfg.num_layers // (s_pp * num_virtual)
            stacked_in = jax.tree_util.tree_map(
                lambda p: p.reshape((num_virtual, s_pp, nblk)
                                    + p.shape[1:]),
                stacked)
            spec_stk = jax.tree_util.tree_map(
                lambda _: P(None, pp_axis), stacked_in)
        else:
            stacked_in = stacked
            spec_stk = jax.tree_util.tree_map(lambda _: P(pp_axis), stacked)
        h = shard_map(inner, mesh=mesh,
                      in_specs=(spec_stk, P(), P()),
                      out_specs=P(), check_vma=False)(
            stacked_in, x0, batch["labels"])
        return head_loss(other, h, batch["labels"])

    return loss_fn, init
