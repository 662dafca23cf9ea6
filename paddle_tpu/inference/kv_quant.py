"""int8 KV-cache quantization primitives (quantized serving round).

The paged pool can store K/V blocks as int8 codes plus a parallel scale
buffer (`PagedKVCache(kv_dtype="int8")`) — roughly half the HBM per
resident token, so the same pool bytes hold ~2x the concurrent
sequences, and the saving compounds with prefix caching (more retained
prefixes per byte). EQuARX (PAPERS.md) is the direction: serving decode
is memory-bound, so low-bit compression of the streamed bytes is where
TPU wins come from.

Scale layout: one symmetric absmax scale PER STORED VECTOR — i.e. per
(layer, block, row, head) over the Dh lanes, `scales[l, b, r, h] =
max|K[l, b, r, h, :]| / 127`. This is the finest granularity the
write path can produce exactly: every cache append quantizes only the
vectors it writes (the running per-block absmax IS the per-row absmax
— no already-written code ever needs rescaling, so the functional
jitted writers stay single-scatter), and a block copy (CoW), share
(prefix attach), swap-out or truncate moves codes and scales by the
same block index, keeping the scale buffer in lockstep with the block
table machinery by construction. The cost is one scale element per
Dh codes (~3% at Dh=32, ~1.5% at Dh=64) — still ~1.9x fewer bytes per
token than bf16.

Round-trip bound (unit-tested): symmetric round-to-nearest gives
|x - dequant(quant(x))| <= scale/2 = absmax/254 per element.

`QuantizedKV` is a NamedTuple, hence automatically a JAX pytree: the
serving engine passes it through jitted dispatches exactly where a
plain bf16 array went, `jax.tree.map` copies handle CoW, and donation
donates both leaves. Attention ops detect it by the `codes` attribute
(duck-typed — no import cycle) and dequantize INSIDE the kernel, so a
bf16 copy of the cache never materializes in HBM.
"""
from __future__ import annotations

from typing import Any, NamedTuple


class QuantizedKV(NamedTuple):
    """One K or V pool quantized: int8 `codes` plus the per-vector
    `scales` buffer (compute dtype), one scale a (token, head).  In the
    pool a token's heads lie side by side (`kv_cache`); what leaves it
    (a tier payload, an exported block) names them."""
    codes: Any   # int8  [..., BS, H*Dh] in the pool, [..., H, Dh] outside
    scales: Any  # float [..., BS, H]


def kv_encode(t, scale_dtype=None):
    """Quantize `t` [..., Dh] to (int8 codes, per-vector scales [...]).

    Symmetric absmax over the last axis, computed in f32 regardless of
    the input dtype (a bf16 absmax would quantize against a value up to
    0.4% off). Zero vectors get the 1e-12 floor, so their codes are 0
    and the round trip is exact."""
    import jax.numpy as jnp

    sd = t.dtype if scale_dtype is None else scale_dtype
    tf = t.astype(jnp.float32)
    amax = jnp.max(jnp.abs(tf), axis=-1)
    sc = jnp.maximum(amax, 1e-12) / 127.0
    codes = jnp.clip(jnp.round(tf / sc[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, sc.astype(sd)


def kv_decode(codes, scales, dtype):
    """Dequantize int8 codes [..., Dh] with per-vector scales [...] to
    `dtype`. Library/test helper — the attention kernels fold the
    scales into their score/output contractions instead of calling
    this on the full cache."""
    return codes.astype(dtype) * scales[..., None].astype(dtype)


def is_quantized(kv):
    """Duck-typed QuantizedKV check (usable from modules that must not
    import this package at module scope)."""
    return hasattr(kv, "codes") and hasattr(kv, "scales")
