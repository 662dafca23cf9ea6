"""Ulysses-style all-to-all sequence parallelism.

The second long-context mode next to ring attention (lineage: DeepSpeed
Ulysses, public pattern; reference capability: sequence-parallel training
of long sequences). Where the ring rotates K/V blocks around `sp` and
keeps heads whole, Ulysses swaps the sharding axis itself with one ICI
all-to-all: seq-sharded activations [B, H, S/n, D] become head-sharded
[B, H/n, S, D], each rank runs an ordinary FULL-sequence attention over
its own heads (the Pallas flash kernel — no cross-rank softmax state at
all), and a second all-to-all restores seq sharding.

Trade-off vs ring (why both exist): Ulysses moves q,k,v,o once each
(4 tensors × 1 all-to-all) regardless of sequence length, while the ring
moves k,v n-1 times — Ulysses wins when S_local is large and H ≥ n;
the ring wins when heads are few (H < n) or memory for a full-S score
pass is tight. `sp_attention` picks by that rule.

Used inside shard_map over the `sp` mesh axis, composes with dp/pp/mp
exactly like ring_attention (drop-in: same [B, H, S_local, D] contract).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.lax import axis_size as _axis_size


def _local_attention(q, k, v, causal, scale, interpret):
    from .ring_attention import _flash_ok
    if _flash_ok(q):
        from ..ops.pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               interpret=interpret)
    s = q.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name="sp", causal=False, scale=None,
                      interpret=None):
    """q, k, v: [B, H, S_local, D] seq-sharded over `axis_name`.
    Returns [B, H, S_local, D]. Requires H % axis_size == 0."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n = _axis_size(axis_name)
    h = q.shape[1]
    if h % n:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by sp ({n}); "
            f"use ring attention for head counts below the sp degree")

    def seq_to_head(x):  # [B, H, S/n, D] -> [B, H/n, S, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def head_to_seq(x):  # [B, H/n, S, D] -> [B, H, S/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    oh = _local_attention(qh, kh, vh, causal, scale, interpret)
    return head_to_seq(oh)


def sp_attention(q, k, v, axis_name="sp", causal=False, scale=None,
                 impl=None, interpret=None):
    """Sequence-parallel attention front door: impl = "ring" | "ulysses" |
    "zigzag" | None (auto: ulysses when every rank can own ≥1 head — one
    all-to-all round beats n-1 ppermute rounds — else ring).

    "zigzag" is the load-balanced causal ring: the caller must hold the
    LOCAL shard in zigzag layout (rank i = global chunks i and 2n-1-i;
    see ring_attention.zigzag_order) — it halves causal ring step cost
    and is never auto-picked because of that layout contract."""
    from .ring_attention import ring_attention, zigzag_ring_attention
    if impl == "zigzag":
        if not causal:
            raise ValueError("zigzag layout only pays off under a causal "
                             "mask; use ring/ulysses for bidirectional")
        return zigzag_ring_attention(q, k, v, axis_name=axis_name,
                                     scale=scale)
    if impl is None:
        n = _axis_size(axis_name)
        impl = "ulysses" if q.shape[1] % n == 0 else "ring"
    if impl == "ulysses":
        return ulysses_attention(q, k, v, axis_name, causal, scale,
                                 interpret)
    return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                          scale=scale, interpret=interpret)
