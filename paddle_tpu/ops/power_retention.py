"""Power retention of degree 2: a gated linear-attention mixer whose
weights are the SQUARED scaled dot products of queries and keys, as a
feature map, a chunked prefill and a one-token recurrent step.

For one K/V head (query head h reads K/V head h // group), with gamma_t =
the log of the gate <= 0 and G_t its running sum, the direct form is

    w[t, s] = exp(G_t - G_s) (q_t . k_s / sqrt(d))^2          (s <= t)
    o_t     = sum_s w[t, s] v_s / (sum_s w[t, s] + eps)

An even power is non-negative, so there is no softmax and no maximum.  With
phi: R^d -> R^D a map for which phi(x) . phi(y) = (x . y)^2 the same numbers
come from a state of constant size,

    S_t = e^{gamma_t} S_{t-1} + phi(k_t / d^(1/4)) v_t^T     [D, d]
    z_t = e^{gamma_t} z_{t-1} + phi(k_t / d^(1/4))           [D]
    o_t = phi(q_t / d^(1/4))^T S_t / (phi(q_t / d^(1/4))^T z_t + eps)

`phi` is the symmetric second power kept by tiles of `tile` channels: for
every pair of tiles a <= b all the products x_a[i] x_b[j], those of a < b
weighted by sqrt(2) (each stands for two entries of the symmetric x (x) x);
D = tile^2 n (n + 1) / 2 with n = d / tile: 8,256 at tile 1 (the map without
a duplicate), 10,240 at tile 32 for d = 128.  The outputs do not depend on
the tile.  Entry (pair p, i, j) lies at p tile^2 + i tile + j.

All forms take q and k as the mixer made them (normed, rotated) and scale
them by d^(-1/4) themselves; a state is float32.  None knows about
sequences beyond the indices it is handed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as _attention

EPS = 1e-6


@functools.lru_cache(maxsize=None)
def tile_pairs(d, tile):
    """The tile pairs (a, b), a <= b, in the state's order."""
    if d % tile:
        raise ValueError(f"power retention: a head of {d} channels is not "
                         f"whole tiles of {tile}")
    n = d // tile
    return tuple((a, b) for a in range(n) for b in range(a, n))


def state_dim(d, tile):
    """D: the width of phi(x) for x of d channels."""
    return tile * tile * len(tile_pairs(d, tile))


def phi(x, tile):
    """x [..., d] -> [..., D] with phi(x) . phi(y) = (x . y)^2."""
    d = x.shape[-1]
    pairs = np.asarray(tile_pairs(d, tile))
    xt = x.reshape(x.shape[:-1] + (d // tile, tile))
    w = jnp.asarray(np.where(pairs[:, 0] == pairs[:, 1], 1.0, np.sqrt(2.0)),
                    x.dtype)
    out = (w[:, None, None] * xt[..., pairs[:, 0], :, None]
           * xt[..., pairs[:, 1], None, :])
    return out.reshape(x.shape[:-1] + (len(pairs) * tile * tile,))


def unpack_state(s, z, tile):
    """A state as the store keeps it (s [..., D, d], z [..., D]) back to
    the symmetric tensors sum_s e^{..} k_s (x) k_s (x) v_s [..., d, d, d]
    and sum_s e^{..} k_s (x) k_s [..., d, d], whatever the tile (k scaled
    by d^(-1/4), as every form here scales it).  Host-side: numpy."""
    s, z = np.asarray(s, np.float32), np.asarray(z, np.float32)
    d = s.shape[-1]
    lead = z.shape[:-1]
    full_s = np.zeros(lead + (d, d, d), np.float32)
    full_z = np.zeros(lead + (d, d), np.float32)
    t2 = tile * tile
    for p, (a, b) in enumerate(tile_pairs(d, tile)):
        w = 1.0 if a == b else np.sqrt(2.0)
        ra, rb = slice(a * tile, (a + 1) * tile), slice(b * tile,
                                                        (b + 1) * tile)
        ps = s[..., p * t2:(p + 1) * t2, :].reshape(lead + (tile, tile, d)) / w
        pz = z[..., p * t2:(p + 1) * t2].reshape(lead + (tile, tile)) / w
        full_s[..., ra, rb, :] = ps
        full_s[..., rb, ra, :] = np.swapaxes(ps, -3, -2)
        full_z[..., ra, rb] = pz
        full_z[..., rb, ra] = np.swapaxes(pz, -2, -1)
    return full_s, full_z


def power_step_math(s, z, q, k, v, gamma, *, tile, eps=EPS):
    """The recurrence for one token of each row: s [B, Hkv, D, d], z
    [B, Hkv, D] float32; q [B, Hq, d], k, v [B, Hkv, d], gamma [B, Hkv]
    (the log gate) -> (o [B, Hq, d] float32, s, z)."""
    f32 = jnp.float32
    b, hq, d = q.shape
    hkv = k.shape[1]
    decay = jnp.exp(gamma.astype(f32))
    pk = phi(k.astype(f32) * d ** -0.25, tile)               # [B, Hkv, D]
    s = decay[..., None, None] * s \
        + pk[..., None] * v.astype(f32)[:, :, None, :]
    pq = phi(q.astype(f32) * d ** -0.25, tile).reshape(b, hkv, hq // hkv,
                                                       -1)
    num = jnp.einsum("bgrn,bgnv->bgrv", pq, s)
    den, z = _normaliser_step(z, decay, pk, pq)
    return (num / (den[..., None] + eps)).reshape(b, hq, d), s, z


def _normaliser_step(z, decay, pk, pq):
    """z [B, Hkv, D] stepped by one token and read by the group's queries
    pq [B, Hkv, r, D]: (phi(q)^T z' [B, Hkv, r], z')."""
    z = decay[..., None] * z + pk
    return jnp.einsum("bgrn,bgn->bgr", pq, z,
                      precision=jax.lax.Precision.HIGHEST), z


def power_chunk(q, k, v, gamma, s0, z0, *, tile, eps=EPS,
                readout_dtype=jnp.float32):
    """One chunk of one stream, ONE K/V head: q [C, r, d] (the head's group
    of r query heads), k, v [C, d], gamma [C], s0 [D, d], z0 [D] -> (o
    [C, r, d], s_C, z_C), all float32.  A padded position carries k = 0
    and gamma = 0 and leaves the state as it found it.  Inside the chunk
    the weights are the direct form's with the chunk's own G (the
    attention form: under D / 2 positions it is the cheaper one); every
    exponent is a difference G_t - G_s <= 0, never the factored form
    e^{G_t} e^{-G_s}.  What the positions before the chunk add is read
    from the state with phi(q) and the state in `readout_dtype` (the
    model's: for bfloat16 one pass with a float32 sum, which is also the
    TPU's default for a float32 dot; written out so that phi(q) is formed
    once, in that dtype); the sum that leaves the state is float32
    throughout."""
    c, _r, d = q.shape
    low = readout_dtype
    qs, ks = q * d ** -0.25, k * d ** -0.25
    g = jnp.cumsum(gamma)                                      # [C]
    t = jnp.arange(c)
    decay = jnp.exp(jnp.where(t[:, None] >= t[None, :],        # s <= t
                              g[:, None] - g[None, :], -jnp.inf))
    w = jnp.einsum("trd,sd->tsr", qs, ks) ** 2 * decay[..., None]
    pq = phi(qs, tile).astype(low)                            # [C, r, D]
    eg = jnp.exp(g)[:, None]                                   # [C, 1]
    num = jnp.einsum("tsr,sv->trv", w, v) + eg[..., None] * jnp.einsum(
        "trn,nv->trv", pq, s0.astype(low), preferred_element_type=q.dtype)
    den = w.sum(1) + eg * jnp.einsum(
        "trn,n->tr", pq, z0.astype(low), preferred_element_type=q.dtype)
    # what the chunk leaves: summed over its positions in float32 (the
    # state is read again thousands of positions later)
    pk = phi(ks, tile) * jnp.exp(g[-1] - g)[:, None]           # [C, D]
    s_c = jnp.exp(g[-1]) * s0 + jnp.einsum(
        "sn,sv->nv", pk, v, precision=jax.lax.Precision.HIGHEST)
    z_c = jnp.exp(g[-1]) * z0 + pk.sum(0)
    return num / (den[..., None] + eps), s_c, z_c


def power_chunked_prefill(store_s, store_z, layer, slots, fresh, q, k, v,
                          gamma, *, chunk, tile, eps=EPS,
                          readout_dtype=jnp.float32):
    """A packed stream in chunks of `chunk` positions, each chunk wholly
    one sequence's (or wholly padding), against the state store where it
    lies: store_s [L, slots, Hkv, D, d], store_z [L, slots, Hkv, D]
    float32, `layer` a static int; per chunk: `slots` [T // chunk] the
    sequence's slot (0, the trash slot, for padding) and `fresh`, True
    where the sequence starts at position 0 in this chunk: it starts from
    zero state whatever the slot holds.  q [T, Hq, d], k, v [T, Hkv, d],
    gamma [T, Hkv].  Returns (o [T, Hq, d] float32, store_s, store_z).
    One K/V head of one chunk at a time, in order (lax.scan) with the
    store carried through: each takes its head's state from its slot and
    leaves it there, so a chunk that continues the chunk before it finds
    that chunk's state, across chunk and dispatch boundaries alike, and
    one head's state at a time (5 MB) is out of the store."""
    t_len, hq, d = q.shape
    hkv = k.shape[1]
    n = t_len // chunk
    f32 = jnp.float32

    def by_head(x, *rest):
        """[T, Hkv, *rest] -> [n * Hkv, chunk, *rest]: chunk-major."""
        x = x.astype(f32).reshape((n, chunk, hkv) + rest)
        return jnp.moveaxis(x, 2, 1).reshape((n * hkv, chunk) + rest)

    def one(stores, xs):
        st_s, st_z = stores
        qc, kc, vc, gc, slot, head, new = xs
        o, s_c, z_c = power_chunk(
            qc, kc, vc, gc, jnp.where(new, 0.0, st_s[layer, slot, head]),
            jnp.where(new, 0.0, st_z[layer, slot, head]), tile=tile, eps=eps,
            readout_dtype=readout_dtype)
        return (st_s.at[layer, slot, head].set(s_c),
                st_z.at[layer, slot, head].set(z_c)), o

    (store_s, store_z), o = jax.lax.scan(
        one, (store_s, store_z),
        (by_head(q.reshape(t_len, hkv, hq // hkv, d), hq // hkv, d),
         by_head(k, d), by_head(v, d), by_head(gamma),
         jnp.repeat(slots, hkv), jnp.tile(jnp.arange(hkv), n),
         jnp.repeat(fresh, hkv)))
    # [n * Hkv, chunk, r, d] -> [T, Hq, d]
    o = jnp.moveaxis(o.reshape(n, hkv, chunk, hq // hkv, d), 1, 2)
    return o.reshape(t_len, hq, d), store_s, store_z


def power_recurrent_step(store_s, store_z, layer, slots, q, k, v, gamma, *,
                         tile, eps=EPS):
    """One decode token per row against the state store: store_s
    [L, slots, Hkv, D, d], store_z [L, slots, Hkv, D] float32, `layer` a
    static int, `slots` [B] the store row of each batch row (idle rows
    name the trash row 0 and carry k = 0, gamma = 0).  Returns (o
    [B, Hq, d] float32, store_s, store_z).  On the TPU the `power_decode`
    Pallas kernel reads and writes each row's S where it lies and returns
    the unnormalised outputs; elsewhere the rows are gathered, stepped and
    scattered back.  z (1/129 of the state) is stepped in XLA on both."""
    if not _attention._on_tpu():
        o, s, z = power_step_math(store_s[layer, slots],
                                  store_z[layer, slots], q, k, v, gamma,
                                  tile=tile, eps=eps)
        return o, store_s.at[layer, slots].set(s), \
            store_z.at[layer, slots].set(z)
    from .pallas.power_decode import power_decode_kernel

    f32 = jnp.float32
    b, hq, d = q.shape
    hkv = k.shape[1]
    qs = q.astype(f32) * d ** -0.25
    ks = k.astype(f32) * d ** -0.25
    decay = jnp.exp(gamma.astype(f32))
    num, store_s = power_decode_kernel(store_s, layer, slots, qs, ks,
                                       v.astype(f32), decay, tile=tile)
    with jax.named_scope("power_norm"):
        den, z = _normaliser_step(
            store_z[layer, slots], decay, phi(ks, tile),
            phi(qs, tile).reshape(b, hkv, hq // hkv, -1))
        store_z = store_z.at[layer, slots].set(z)
    return num / (den.reshape(b, hq, 1) + eps), store_s, store_z
