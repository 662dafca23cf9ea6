"""`moe_gmm`: the grouped SwiGLU expert matmul over tokens sorted by the
expert that holds them.

Rows arrive sorted by expert and padded so that every tile of `tm` rows
belongs to one expert (`tile_expert`, scalar-prefetched): the tile's
x [tm, D] meets that expert's gate and up [D, F] and down [F, D] where
they lie in the stacked weights [E, D, F] / [E, F, D], a slab of `tf`
intermediate channels a grid step (F_SLAB, or the widest lane multiple
under it that divides F: 384 of an expert 768 wide), and the tile's output
accumulates in VMEM across the slabs.  So an expert's 3 D F weights are streamed once
for each tile of rows it has (once, at decode), whatever the routing:
dropless, and nothing is computed for an expert no row chose.

Tiles past `n_valid` (the bound on padded rows is static, the rows that
came are not) name the slab before them, which is not fetched again, and
compute nothing; their output rows are never read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import named_pallas_call

F_SLAB = 512                       # intermediate channels a grid step
VMEM_LIMIT = 64 * 1024 * 1024      # the slabs of a 128-row tile: ~17 MB


def _kernel(te_ref, nv_ref, x_ref, g_ref, u_ref, d_ref, y_ref, acc_ref, *,
            slabs):
    del te_ref
    t, f = pl.program_id(0), pl.program_id(1)
    live = t < nv_ref[0]

    @pl.when(live & (f == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _slab():
        x = x_ref[...]
        gate = jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
        hid = (jax.nn.silu(gate) * up).astype(x.dtype)
        acc_ref[:] += jnp.dot(hid, d_ref[...],
                              preferred_element_type=jnp.float32)

    @pl.when(live & (f == slabs - 1))
    def _flush():
        y_ref[:] = acc_ref[:].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_gmm_kernel(x_rows, gate, up, down, tile_expert, n_valid, *, tm,
                   interpret=False):
    """x_rows [R, D] (R a multiple of tm); gate, up [E, D, F]; down
    [E, F, D]; tile_expert [R // tm] int32 (tiles past n_valid repeat
    the last valid tile's expert); n_valid [1] int32.  Returns
    y_rows [R, D] in x_rows' dtype: SwiGLU of each row under its tile's
    expert; rows of tiles past n_valid are not written."""
    r, d = x_rows.shape
    e, _, f_dim = gate.shape
    tf = min(F_SLAB, f_dim)
    while f_dim % tf and tf > 128:
        tf -= 128
    if r % tm or f_dim % tf:
        raise ValueError(f"moe_gmm: {r} rows / tile {tm}, {f_dim} "
                         f"channels / slab {tf}")
    slabs = f_dim // tf

    def slab(t, f, te, nv):          # past n_valid: the slab before, again
        return jnp.where(t < nv[0], f, slabs - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r // tm, slabs),
        in_specs=[
            pl.BlockSpec((tm, d), lambda t, f, te, nv: (t, 0)),
            pl.BlockSpec((None, d, tf),
                         lambda t, f, te, nv: (te[t], 0, slab(t, f, te, nv))),
            pl.BlockSpec((None, d, tf),
                         lambda t, f, te, nv: (te[t], 0, slab(t, f, te, nv))),
            pl.BlockSpec((None, tf, d),
                         lambda t, f, te, nv: (te[t], slab(t, f, te, nv), 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda t, f, te, nv: (t, 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    return named_pallas_call(
        "moe_gmm", functools.partial(_kernel, slabs=slabs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, d), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), n_valid.astype(jnp.int32), x_rows,
      gate, up, down)
