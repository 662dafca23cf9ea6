"""Operations and bytes of the brumby family's power retention, as pure
functions of shapes: nothing here imports the program or JAX.  The
conventions are flops.py's: one multiply-add is 2 FLOPs, float32 is 4
bytes, recomputed work is not counted, and a count is what the algorithm
needs, not what a form of it happens to do.

  Hq query heads on Hkv K/V heads of d, L layers.  A K/V head's state is S
  [D, d] and its normaliser z [D], float32, with D the width of the
  symmetric second power of a key.  D is counted at d (d + 1) / 2 (8,256
  for d = 128), the symmetric map's own size without a duplicate, whatever
  tile a program keeps it by, so that the same work reads the same
  whoever implements it: a program that keeps tiles of 32 channels (D
  10,240) can read 80.6% of this roofline at most.

Only the decode side is counted: the chunked prefill form is XLA in this
program and has no kernel whose time a count could be held to.
"""
from __future__ import annotations

F32 = 4


def power_state_dim(head_dim):
    """D of the symmetric second power of a key of `head_dim` channels."""
    return head_dim * (head_dim + 1) // 2


def power_state_bytes(layers, kv_heads, head_dim):
    """S and z of one sequence over all layers."""
    return layers * kv_heads * power_state_dim(head_dim) \
        * (head_dim + 1) * F32


def power_decode_bytes(tokens, layers, kv_heads, head_dim):
    """A decode token reads and writes its float32 S and z once in every
    layer; q, k, v, the gate and the output are under 0.1%."""
    return 2 * tokens * power_state_bytes(layers, kv_heads, head_dim)


def power_decode_flops(tokens, layers, heads, kv_heads, head_dim):
    """Per K/V head on S and z ([D, d + 1] together): the decay (1 a
    value) and the rank-1 update (2); per query head the readout phi(q)^T
    [S | z] (2)."""
    values = power_state_dim(head_dim) * (head_dim + 1)
    return tokens * layers * values * (3 * kv_heads + 2 * heads)
