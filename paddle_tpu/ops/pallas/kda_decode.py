"""`kda_decode`: the KDA recurrence for one token of each batch row,
against the recurrent-state store where it lies.

The store is [L, slots, H, D, D] float32 (key x value per head).  A grid
step owns `HEADS_PER_STEP` heads of one row's state: it reads them once,
applies

    S' = Diag(a) S;  u = k^T S';  S_t = S' + k (beta (v - u))^T;  o = S_t^T q

and writes them back to the same place (the store is aliased to the
output, the layer and each row's slot are scalar-prefetched), so a decode
step moves every live state exactly once each way and the program holds
no copy of the store.  Idle rows name the trash slot 0.

A per-key factor has to run down the sublanes of a [D, D] tile while the
per-token vectors arrive lane-major: each is broadcast to a tile and
transposed once (three XLU transposes a head), everything else is
elementwise or a sublane reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import named_pallas_call

HEADS_PER_STEP = 8
# rows of the stacked per-token operand
_Q, _K, _V, _A, _BETA = range(5)


def _kernel(layer_ref, slots_ref, x_ref, s_ref, o_ref, so_ref, *, hb, d):
    del layer_ref, slots_ref          # steered the DMA; nothing to compute

    def down(row):                    # [1, D] -> [D, D], value i on row i
        return jnp.broadcast_to(row, (d, d)).T

    def one_head(h, carry):
        x = x_ref[:, pl.ds(h, 1), :]                       # [5, 1, D]
        k_col = down(x[_K])
        state = s_ref[h] * down(x[_A])                     # [Dk, Dv]
        u = jnp.sum(k_col * state, axis=0, keepdims=True)  # [1, Dv]
        state = state + k_col * (x[_BETA] * (x[_V] - u))
        so_ref[h] = state
        o_ref[pl.ds(h, 1), :] = jnp.sum(down(x[_Q]) * state, axis=0,
                                        keepdims=True)
        return carry

    jax.lax.fori_loop(0, hb, one_head, 0, unroll=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_kernel(store, layer, slots, q, k, v, a, beta, *,
                      interpret=False):
    """store [L, S, H, D, D] float32; slots [B] int32; q, k, v, a
    [B, H, D] (a = the decay itself, in (0, 1]); beta [B, H].  Returns
    (o [B, H, D] float32, store) with the named rows of `layer` stepped
    in place."""
    b, h, d = q.shape
    hb = min(HEADS_PER_STEP, h)
    if h % hb:
        raise ValueError(f"kda_decode: {h} heads are not a multiple of "
                         f"{hb}")
    f32 = jnp.float32
    x = jnp.stack([q.astype(f32), k.astype(f32), v.astype(f32),
                   a.astype(f32),
                   jnp.broadcast_to(beta.astype(f32)[..., None], q.shape)],
                  axis=1)                                   # [B, 5, H, D]
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    state_spec = pl.BlockSpec(
        (None, None, hb, d, d),
        lambda bi, j, ly, sl: (ly[0], sl[bi], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb),
        in_specs=[pl.BlockSpec((None, 5, hb, d),
                               lambda bi, j, ly, sl: (bi, 0, j, 0)),
                  state_spec],
        out_specs=[pl.BlockSpec((None, hb, d),
                                lambda bi, j, ly, sl: (bi, j, 0)),
                   state_spec],
    )
    o, store = named_pallas_call(
        "kda_decode", functools.partial(_kernel, hb=hb, d=d),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, d), f32),
                   jax.ShapeDtypeStruct(store.shape, store.dtype)],
        # operands: layer, slots, x, store -> outputs: o, store
        input_output_aliases={3: 1},
        interpret=interpret,
    )(layer, slots.astype(jnp.int32), x, store)
    return o, store
