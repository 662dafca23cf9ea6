"""Operations and bytes of the measured work, as pure functions of shapes.

The yardstick's arithmetic: nothing here imports the program or JAX.  A
roofline share is `least_time / measured device time`, where least_time is
the larger of FLOPs over the peak FLOP rate and bytes over the peak byte
rate of `peaks.json`.  Recomputed work (a flash backward that rebuilds the
probabilities) is NOT counted: the numbers are what the algorithm needs.

Conventions (all counts are per call of the thing named):
  B batch, H heads, S query length, T key length, D head size, L layers.
  One multiply-add is 2 FLOPs.  bf16 is 2 bytes.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def device_peaks(device_kind, path=None):
    """The peaks of `device_kind` from peaks.json; unknown kind raises."""
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(f"no peaks recorded for device_kind {device_kind!r} "
                       f"(known: {known}); add it to peaks.json with its "
                       f"source")
    return table[device_kind]


def train_flops_per_token_6n(n_params):
    """The 6N rule: forward 2N, backward 4N multiply-add FLOPs a token, N
    the parameter count (embeddings included, as BENCH_r05 counted)."""
    return 6 * n_params


def attention_flops_fwd(batch, heads, q_len, k_len, head_dim, causal):
    """QK^T and PV of one attention layer, forward: 2 matmuls of
    [S, D] x [D, T], 2 FLOPs a multiply-add.  A causal square mask keeps
    (S+1)/(2S) of the score matrix; only square causal calls are used."""
    full = 4 * batch * heads * q_len * k_len * head_dim
    if not causal:
        return full
    if q_len != k_len:
        raise ValueError("causal count is for square attention")
    return full * (q_len + 1) // (2 * q_len)


def attention_flops_train(batch, heads, seq, head_dim, causal):
    """Forward plus backward of one layer: the backward needs dV, dP, dQ
    and dK (4 matmuls to the forward's 2), so 3x the forward.  The flash
    backward also recomputes QK^T; recomputation is not counted."""
    return 3 * attention_flops_fwd(batch, heads, seq, seq, head_dim, causal)


def attention_bytes_train(batch, heads, seq, head_dim, itemsize=2):
    """Least HBM traffic of one layer, forward plus backward, for a kernel
    that never writes the score matrix: forward reads Q, K, V and writes O
    (4 arrays); backward reads Q, K, V, O, dO and writes dQ, dK, dV (8).
    Row statistics (float32 [B, H, S] twice) are under 2% and left out."""
    return 12 * batch * heads * seq * head_dim * itemsize


def train_attention_extra_flops_per_token(layers, seq, hidden, causal):
    """The attention-inclusive correction to 6N (PaLM appendix B):
    12 * L * S * hidden per token forward plus backward, halved when
    causal.  Equals attention_flops_train summed over layers / tokens."""
    full = 12 * layers * seq * hidden
    return full // 2 if causal else full


def paged_decode_bytes(context_len, layers, heads, head_dim, itemsize=2):
    """KV bytes one decode token must read: K and V of its whole context
    in every layer.  Queries, outputs and the block table are negligible."""
    return 2 * context_len * layers * heads * head_dim * itemsize


def paged_decode_flops(context_len, layers, heads, head_dim):
    """QK^T and PV of one decode token over its context, all layers."""
    return 4 * context_len * layers * heads * head_dim


def prefill_attention_flops(prompt_len, layers, heads, head_dim):
    """Causal self-attention of one whole prompt, all layers (however the
    engine chunks it: chunks attend to earlier chunks through the pool, and
    the sum over chunks is the causal square)."""
    return layers * attention_flops_fwd(1, heads, prompt_len, prompt_len,
                                        head_dim, causal=True)


def prefill_attention_bytes(prompt_len, layers, heads, head_dim, itemsize=2):
    """Least bytes of one prompt's prefill attention: read Q, K, V, write
    O once a layer (K/V pool writes belong to the projection, not here)."""
    return 4 * layers * prompt_len * heads * head_dim * itemsize


def least_time_s(flops, nbytes, peaks):
    """(seconds, which) — the roofline bound and the resource that sets it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
