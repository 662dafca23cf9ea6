"""Operations and bytes of the kimi_linear family's three mechanisms, as
pure functions of shapes: nothing here imports the program or JAX.  The
conventions are flops.py's: one multiply-add is 2 FLOPs, bf16 is 2 bytes,
recomputed work is not counted, and a count is what the algorithm needs,
not what a form of it happens to do.

  E hidden width, F an expert's intermediate width, H heads, D the KDA
  head size (key = value), W a latent row's used width (lora + pe), R the
  latent's own width (lora), L layers of the kind.

Only the decode side is counted: the prefill forms of KDA and MLA are XLA
in this program and have no kernel whose time a count could be held to.
"""
from __future__ import annotations

F32 = 4


def expert_weight_bytes(hidden, width, itemsize=2):
    """One SwiGLU expert: gate and up [E, F], down [F, E]."""
    return 3 * hidden * width * itemsize


def moe_gmm_bytes(experts_touched, held_picks, hidden, width, itemsize=2):
    """Least HBM traffic of the grouped expert matmul: every expert that
    got a pick streams its weights once; every pick's row comes in and its
    result goes out."""
    return (experts_touched * expert_weight_bytes(hidden, width, itemsize)
            + 2 * held_picks * hidden * itemsize)


def moe_gmm_flops(held_picks, hidden, width):
    """A pick is one row through gate, up and down: 3 matmuls of E x F."""
    return 6 * held_picks * hidden * width


def kda_decode_bytes(tokens, layers, heads, dim):
    """A decode token reads and writes its float32 state [H, D, D] in
    every KDA layer; q, k, v, decay and the output are under 1%."""
    return 2 * tokens * layers * heads * dim * dim * F32


def kda_decode_flops(tokens, layers, heads, dim):
    """Per head: the decay (D^2), k^T S and S^T q (2 D^2 each), the rank-1
    update (2 D^2)."""
    return 7 * tokens * layers * heads * dim * dim


def mla_decode_bytes(context_len, layers, latent, itemsize=2):
    """A decode token reads one latent row per position of its context in
    every MLA layer, once for all heads."""
    return context_len * layers * latent * itemsize


def mla_decode_flops(context_len, layers, heads, latent, lora):
    """Absorbed form per head and position: the score over the latent's W
    values, the sum over its R values."""
    return 2 * context_len * layers * heads * (latent + lora)
