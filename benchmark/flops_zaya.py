"""Operations and bytes of the zaya family's paged attention, as pure
functions of shapes: nothing here imports the program or JAX.  The
conventions are flops.py's: one multiply-add is 2 FLOPs, bf16 is 2 bytes,
recomputed work is not counted, and a count is what the algorithm needs,
not what a form of it happens to do.

  Hq query heads on Hkv K/V heads of D (whole groups of Hq / Hkv query
  heads share a K/V head), L layers.  The pool holds Hkv heads: a cached
  token is read once for the whole group, so bytes go by Hkv and FLOPs by
  Hq.  The expert matmul is flops_kimi_linear.py's (`moe_gmm_*`).
"""
from __future__ import annotations


def kv_bytes_per_token(layers, kv_heads, head_dim, itemsize=2):
    """K and V rows of one cached token over all layers."""
    return 2 * layers * kv_heads * head_dim * itemsize


def cca_decode_bytes(context_len, layers, kv_heads, head_dim, itemsize=2):
    """A decode token reads K and V of its whole context in every layer,
    once for all the query heads of a group.  Queries, outputs and the
    block table are negligible."""
    return context_len * kv_bytes_per_token(layers, kv_heads, head_dim,
                                            itemsize)


def cca_decode_flops(context_len, layers, heads, head_dim):
    """QK^T and PV of one decode token over its context: every query head
    does its own, whatever K/V head it reads."""
    return 4 * context_len * layers * heads * head_dim


def cca_prefill_flops(prompt_len, layers, heads, head_dim):
    """Causal self-attention of one whole prompt, all layers and query
    heads (however the engine chunks it): (S + 1) / (2 S) of the square."""
    return 2 * layers * heads * prompt_len * (prompt_len + 1) * head_dim


def cca_prefill_bytes(prompt_len, layers, heads, kv_heads, head_dim,
                      itemsize=2):
    """Least bytes of one prompt's prefill attention: read Q and write O
    by the query heads, read K and V by the K/V heads, once a layer."""
    return 2 * layers * prompt_len * (heads + kv_heads) * head_dim * itemsize
