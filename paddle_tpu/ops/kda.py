"""Kimi Delta Attention (KDA): the gated delta rule with a per-channel
decay, as a chunked prefill and a one-token recurrent step.

Per head the state S (key x value, float32) follows

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with a_t in (0, 1] per key channel (handed over as log a_t <= 0) and
beta_t in (0, 1).  Both forms here take and leave the state; neither
knows about sequences or slots beyond the indices it is handed.

The chunked form (chunk C, XLA): with g_t the running sum of log a inside
the chunk and u_t = beta_t (v_t - (a_t k_t)^T S_{t-1}), the recurrence
unrolls to S_t = Diag(e^{g_t}) S_0 + sum_{i<=t} Diag(e^{g_t - g_i}) k_i
u_i^T, so the u of a chunk solve the unit-lower-triangular system

    (I + Diag(beta) A) U = Diag(beta) (V - (K e^{g}) S_0),
    A[t, i] = sum_c k_t[c] k_i[c] e^{g_t[c] - g_i[c]}   (i < t)

and O = (Q e^{g}) S_0 + tril(A_qk) U, S_C = Diag(e^{g_C}) S_0 +
(K e^{g_C - g})^T U.  Every exponent is a difference g_t - g_i with
i <= t, so none is positive: a strong decay cannot overflow, which the
factored form (K e^{g}) (K e^{-g})^T would.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import attention as _attention


def kda_chunk(q, k, v, log_a, beta, s0):
    """One chunk of one stream: q, k, v, log_a [C, H, D], beta [C, H],
    s0 [H, D, D] -> (o [C, H, D], s_C [H, D, D]), all float32.  A padded
    position carries k = 0, beta = 0, log_a = 0 and leaves the state as
    it found it."""
    c = q.shape[0]
    g = jnp.cumsum(log_a, axis=0)                              # [C, H, D]
    t = jnp.arange(c)
    lower = t[:, None] >= t[None, :]                           # i <= t
    # e^{g_t - g_i} where i <= t, 0 elsewhere: [C(t), C(i), H, D]
    decay = jnp.exp(jnp.where(lower[:, :, None, None],
                              g[:, None] - g[None, :], -jnp.inf))
    a_kk = jnp.einsum("thc,ihc,tihc->hti", k, k, decay)
    a_qk = jnp.einsum("thc,ihc,tihc->hti", q, k, decay)        # i <= t
    strict = (t[:, None] > t[None, :])[None]
    bt = beta.T[:, :, None]                                    # [H, C, 1]
    system = jnp.eye(c, dtype=jnp.float32)[None] \
        + jnp.where(strict, bt * a_kk, 0.0)
    eg = jnp.exp(g)
    rhs = bt * (v.transpose(1, 0, 2)
                - jnp.einsum("thc,hcv->htv", k * eg, s0))
    u = jax.scipy.linalg.solve_triangular(system, rhs, lower=True,
                                          unit_diagonal=True)  # [H, C, Dv]
    o = jnp.einsum("thc,hcv->thv", q * eg, s0) \
        + jnp.einsum("hti,hiv->thv", a_qk, u)
    k_end = k * jnp.exp(g[-1][None] - g)                       # [C, H, D]
    s_c = jnp.exp(g[-1])[..., None] * s0 \
        + jnp.einsum("ihc,hiv->hcv", k_end, u)
    return o, s_c


def kda_chunked_prefill(q, k, v, log_a, beta, s_load, carry_in, *, chunk):
    """A packed stream in chunks of `chunk` positions, each chunk wholly
    one sequence's (or wholly padding): q, k, v, log_a [T, H, D], beta
    [T, H]; s_load [T // chunk, H, D, D] the state each chunk would
    start from if it is the first of its sequence in this stream,
    carry_in [T // chunk] bool: True where the chunk continues the chunk
    before it.  Returns (o [T, H, D], s_out [T // chunk, H, D, D]: the
    state after each chunk).  The chunks run in order (lax.scan); inside
    a chunk everything is dense."""
    t_len, h, d = q.shape
    n = t_len // chunk

    def split(x):
        return x.reshape((n, chunk) + x.shape[1:])

    def one(carry, xs):
        qc, kc, vc, lc, bc, load, cont = xs
        s0 = jnp.where(cont, carry, load)
        o, s_c = kda_chunk(qc, kc, vc, lc, bc, s0)
        return s_c, (o, s_c)

    _, (o, s_out) = jax.lax.scan(
        one, jnp.zeros((h, d, d), jnp.float32),
        (split(q), split(k), split(v), split(log_a), split(beta),
         s_load, carry_in))
    return o.reshape(t_len, h, d), s_out


def kda_step_math(state, q, k, v, log_a, beta):
    """The recurrence itself for one token of each row: state
    [B, H, D, D], q, k, v, log_a [B, H, D], beta [B, H] ->
    (o [B, H, D], state)."""
    state = jnp.exp(log_a)[..., None] * state
    u = jnp.einsum("bhk,bhkv->bhv", k, state)
    state = state + (beta[..., None] * k)[..., None] \
        * (v - u)[:, :, None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, state), state


def kda_recurrent_step(store, layer, slots, q, k, v, log_a, beta):
    """One decode token per row against the state store: store
    [L, slots, H, D, D] float32, `layer` a static int, `slots` [B] the
    store row of each batch row (idle rows name the trash row 0).
    Returns (o [B, H, D] float32, store).  On the TPU the `kda_decode`
    Pallas kernel reads and writes each row's state where it lies;
    elsewhere the rows are gathered, stepped and scattered back."""
    if _attention._on_tpu():
        from .pallas.kda_decode import kda_decode_kernel

        return kda_decode_kernel(store, layer, slots, q, k, v,
                                 jnp.exp(log_a), beta)
    o, new = kda_step_math(store[layer, slots], q, k, v, log_a, beta)
    return o, store.at[layer, slots].set(new)
