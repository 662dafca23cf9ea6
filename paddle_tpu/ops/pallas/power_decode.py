"""`power_decode`: the power-retention recurrence (degree 2) for one token
of each batch row, against the state store where it lies.

The store is [L, slots, Hkv, D, d] float32: for every K/V head the state
S of `ops/power_retention.py`, D = tile^2 pairs rows of d value channels,
row (pair p = (a, b), i, j) holding sum_s e^{..} w_p k_a[i] k_b[j] v_s.  A
grid step owns one K/V head of one row: it reads that head's S once, forms

    S' = e^{gamma} S + phi(k) v^T

writes it back to the same place (the store is aliased to the output, the
layer and each row's slot are scalar-prefetched) and adds the `group` query
heads' phi(q)^T S' into the row's output, so a decode step moves every live
state exactly once each way and the program holds no copy of the store.
The division by the normaliser phi(q)^T z is the caller's.  Idle rows name
the trash slot 0.

phi is never formed.  For pair (a, b) the T rows (i, :) of S are a [T, d]
slab with j down the sublanes: the slab's update is the scalar k_a[i]
times one [T, d] tile k_b[j] v[c] shared by the pair, and the readout
sum_ij q_a[i] q_b[j] S'[(i, j), :] is accumulated as sum_i q_a[i] slab_i
([T, d], a scalar times a slab) and closed once a pair by q_b down the
sublanes and one sublane reduction.  The per-token vectors arrive
lane-major; each is broadcast to a [d, d] tile and transposed once a grid
step (value c on sublane c: `cols`), from which both a pair's [T, d]
column tile and, as a one-row load that broadcasts down the sublanes, the
scalar of a slab are read.  Everything else is elementwise float32 on the
VPU: no dot rounds the state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import named_pallas_call
from ..power_retention import state_dim, tile_pairs

# a head's S block, read and written, each double-buffered: 4 x 5.2 MB at
# tile 32, over the compiler's default scoped limit of 16 MB
VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(layer_ref, slots_ref, x_ref, s_ref, o_ref, so_ref, cols_ref, *,
            group, d, tile):
    del layer_ref, slots_ref          # steered the DMA; nothing to compute
    k_row, v_row, decay_row = group, group + 1, group + 2
    for r in range(group + 1):        # the query heads, then k
        cols_ref[r] = jnp.broadcast_to(x_ref[pl.ds(r, 1), :], (d, d)).T
    v = x_ref[pl.ds(v_row, 1), :]                             # [1, d]
    decay = x_ref[pl.ds(decay_row, 1), :]                     # [1, d]
    t2 = tile * tile
    out = [jnp.zeros((1, d), jnp.float32) for _ in range(group)]
    for p, (a, b) in enumerate(tile_pairs(d, tile)):
        w = 1.0 if a == b else math.sqrt(2.0)
        kv = (w * cols_ref[k_row, pl.ds(b * tile, tile), :]) * v   # [T, d]
        acc = [jnp.zeros((tile, d), jnp.float32) for _ in range(group)]
        for i in range(tile):
            rows = pl.ds(p * t2 + i * tile, tile)
            slab = s_ref[rows, :] * decay \
                + cols_ref[k_row, pl.ds(a * tile + i, 1), :] * kv
            so_ref[rows, :] = slab
            for h in range(group):
                acc[h] = acc[h] \
                    + cols_ref[h, pl.ds(a * tile + i, 1), :] * slab
        for h in range(group):
            out[h] = out[h] + jnp.sum(
                (w * cols_ref[h, pl.ds(b * tile, tile), :]) * acc[h],
                axis=0, keepdims=True)
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)
    for h in range(group):
        o_ref[pl.ds(h, 1), :] = out[h]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def power_decode_kernel(store, layer, slots, q, k, v, decay, *, tile,
                        interpret=False):
    """store [L, S, Hkv, D, d] float32; slots [B] int32; q [B, Hq, d] and k
    [B, Hkv, d] ALREADY scaled by d^(-1/4); v [B, Hkv, d]; decay [B, Hkv]
    = e^{gamma} in (0, 1].  Returns (num [B, Hq, d] float32 = phi(q)^T S'
    unnormalised, store) with the named rows of `layer` stepped in place."""
    b, hq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    big_d = state_dim(d, tile)
    if store.shape[2:] != (hkv, big_d, d) or hq % hkv:
        raise ValueError(f"power_decode: a store of {store.shape[2:]} a "
                         f"slot does not hold {hkv} K/V heads of "
                         f"[{big_d}, {d}] under {hq} query heads")
    f32 = jnp.float32
    rows = -(-(group + 3) // 8) * 8
    out_rows = -(-group // 8) * 8
    x = jnp.concatenate(
        [q.astype(f32).reshape(b, hkv, group, d), k.astype(f32)[:, :, None],
         v.astype(f32)[:, :, None],
         jnp.broadcast_to(decay.astype(f32)[:, :, None, None],
                          (b, hkv, 1, d)),
         jnp.zeros((b, hkv, rows - group - 3, d), f32)], axis=2)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    state_spec = pl.BlockSpec(
        (None, None, None, big_d, d),
        lambda bi, g, ly, sl: (ly[0], sl[bi], g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv),
        in_specs=[pl.BlockSpec((None, None, rows, d),
                               lambda bi, g, ly, sl: (bi, g, 0, 0)),
                  state_spec],
        out_specs=[pl.BlockSpec((None, None, out_rows, d),
                                lambda bi, g, ly, sl: (bi, g, 0, 0)),
                   state_spec],
        scratch_shapes=[pltpu.VMEM((group + 1, d, d), f32)],
    )
    num, store = named_pallas_call(
        "power_decode",
        functools.partial(_kernel, group=group, d=d, tile=tile),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, out_rows, d), f32),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        # operands: layer, slots, x, store -> outputs: num, store
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(layer, slots.astype(jnp.int32), x, store)
    return num[:, :, :group].reshape(b, hq, d), store
