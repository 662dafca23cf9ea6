"""Power retention of degree 2 (`ops/power_retention.py`,
`ops/pallas/power_decode.py`) on the CPU, float32 (tests/conftest.py pins
"highest" matmul precision): the feature map, and that the recurrence,
the chunked form and the direct form are the same numbers."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import power_retention as pr
from paddle_tpu.ops.pallas.power_decode import power_decode_kernel

D = 16


def direct(q, k, v, gamma):
    """Step 5 as written: weights exp(G_t - G_s) (q . k / sqrt(d))^2 for
    s <= t, normalised by their sum; query head h on K/V head h // r."""
    n, hq, d = q.shape
    hkv = k.shape[1]
    g = jnp.cumsum(gamma, 0)
    s = jnp.einsum("tgrd,sgd->tsgr", q.reshape(n, hkv, hq // hkv, d), k) \
        / np.sqrt(d)
    t = jnp.arange(n)
    low = (t[:, None] >= t[None, :])[:, :, None]
    w = jnp.where(low, jnp.exp(jnp.where(low, g[:, None] - g[None, :], 0)),
                  0)[..., None] * s ** 2
    return (jnp.einsum("tsgr,sgv->tgrv", w, v)
            / (w.sum(1)[..., None] + pr.EPS)).reshape(n, hq, d)


def inputs(n, hq, hkv, gates, seed=0):
    g = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(g.normal(size=s), jnp.float32)
    lo, hi = gates
    gamma = jnp.asarray(np.log(g.uniform(lo, hi, size=(n, hkv))),
                        jnp.float32)
    return f(n, hq, D), f(n, hkv, D), f(n, hkv, D), gamma


def recurrent(q, k, v, gamma, tile):
    big = pr.state_dim(D, tile)
    s = jnp.zeros((1, k.shape[1], big, D))
    z = jnp.zeros((1, k.shape[1], big))
    out = []
    for t in range(q.shape[0]):
        o, s, z = pr.power_step_math(s, z, q[t:t + 1], k[t:t + 1],
                                     v[t:t + 1], gamma[t:t + 1], tile=tile)
        out.append(o[0])
    return jnp.stack(out), s[0], z[0]


@pytest.mark.parametrize("tile", [1, 8, 16, 32])
def test_phi_is_the_square_of_the_dot_product(tile):
    g = np.random.default_rng(tile)
    d = 32 if tile == 32 else D
    x = jnp.asarray(g.normal(size=(7, d)), jnp.float32)
    y = jnp.asarray(g.normal(size=(7, d)), jnp.float32)
    n = d // tile
    assert pr.state_dim(d, tile) == tile * tile * n * (n + 1) // 2
    np.testing.assert_allclose((pr.phi(x, tile) * pr.phi(y, tile)).sum(-1),
                               (x * y).sum(-1) ** 2, rtol=2e-5)


def test_the_state_is_never_the_undeduplicated_square():
    assert pr.state_dim(128, 32) == 10240 and pr.state_dim(128, 16) == 9216
    assert pr.state_dim(128, 1) == 8256 < 128 * 128
    with pytest.raises(ValueError):
        pr.tile_pairs(128, 48)


@pytest.mark.parametrize("gates", [(0.9, 1.0), (0.01, 0.2), (0.01, 1.0)],
                         ids=["near1", "near0", "mixed"])
@pytest.mark.parametrize("tile", [1, 8])
def test_the_recurrence_is_the_direct_form(gates, tile):
    q, k, v, gamma = inputs(24, 4, 2, gates)
    o, _s, _z = recurrent(q, k, v, gamma, tile)
    np.testing.assert_allclose(o, direct(q, k, v, gamma), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("gates", [(0.9, 1.0), (0.01, 0.2)],
                         ids=["near1", "near0"])
def test_the_chunked_form_is_the_direct_form_across_a_chunk_boundary(gates):
    """Three chunks of one sequence (the second and third go on from what
    the chunk before left in the slot), a padding chunk, then a second
    sequence whose slot held another's state: it starts from zero."""
    tile, c = 8, 8
    q, k, v, gamma = inputs(24, 4, 2, gates)
    q2, k2, v2, gamma2 = inputs(8, 4, 2, gates, seed=5)
    big = pr.state_dim(D, tile)
    g = np.random.default_rng(1)
    store_s = jnp.asarray(g.normal(size=(2, 4, 2, big, D)), jnp.float32)
    store_z = jnp.asarray(g.normal(size=(2, 4, 2, big)), jnp.float32)
    pad = lambda x: jnp.zeros((c,) + x.shape[1:], x.dtype)
    cat = lambda a, b: jnp.concatenate([a, pad(a), b])
    o, new_s, new_z = pr.power_chunked_prefill(
        store_s, store_z, 1, jnp.array([2, 2, 2, 0, 3]),
        jnp.array([True, False, False, False, True]),
        cat(q, q2), cat(k, k2), cat(v, v2), cat(gamma, gamma2), chunk=c,
        tile=tile)
    np.testing.assert_allclose(o[:24], direct(q, k, v, gamma), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(o[32:], direct(q2, k2, v2, gamma2),
                               rtol=2e-4, atol=2e-4)
    _o, s_end, z_end = recurrent(q, k, v, gamma, tile)
    np.testing.assert_allclose(new_s[1, 2], s_end, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(new_z[1, 2], z_end, rtol=2e-5, atol=2e-5)
    # the other layer and the slot no sequence held are as they were
    np.testing.assert_array_equal(new_s[0], store_s[0])
    np.testing.assert_array_equal(new_s[1, 1], store_s[1, 1])


def test_a_chunk_that_goes_on_from_the_store_is_the_direct_form():
    """A dispatch boundary: the first 16 positions in one call, the last 8
    in another that loads the slot's state."""
    tile, c = 8, 8
    q, k, v, gamma = inputs(24, 4, 2, (0.5, 1.0))
    big = pr.state_dim(D, tile)
    store = (jnp.ones((1, 3, 2, big, D)), jnp.ones((1, 3, 2, big)))
    t, f = True, False
    o1, *store = pr.power_chunked_prefill(
        *store, 0, jnp.array([1, 1]), jnp.array([t, f]), q[:16], k[:16],
        v[:16], gamma[:16], chunk=c, tile=tile)
    o2, *store = pr.power_chunked_prefill(
        *store, 0, jnp.array([1]), jnp.array([f]), q[16:], k[16:], v[16:],
        gamma[16:], chunk=c, tile=tile)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]),
                               direct(q, k, v, gamma), rtol=2e-4,
                               atol=2e-4)


def test_a_padded_position_leaves_the_state_as_it_found_it():
    tile = 8
    q, k, v, gamma = inputs(8, 4, 2, (0.3, 1.0))
    k = k.at[5:].set(0.0)
    gamma = gamma.at[5:].set(0.0)
    big = pr.state_dim(D, tile)
    _o, s5, z5 = recurrent(q[:5], k[:5], v[:5], gamma[:5], tile)
    for h in range(2):     # one K/V head and its two query heads at a time
        _o, s8, z8 = pr.power_chunk(
            q[:, 2 * h:2 * h + 2], k[:, h], v[:, h], gamma[:, h],
            jnp.zeros((big, D)), jnp.zeros((big,)), tile=tile)
        np.testing.assert_allclose(s8, s5[h], atol=2e-6)
        np.testing.assert_allclose(z8, z5[h], atol=2e-6)


@pytest.mark.parametrize("hq,hkv", [(4, 2), (10, 2), (2, 2)],
                         ids=["4on2", "10on2", "2on2"])
def test_the_decode_kernel_is_the_step_math(hq, hkv):
    """`power_decode` in interpret mode against `power_step_math`: the
    named rows of one layer stepped in place, every other row and layer
    untouched; query head h reads K/V head h // group."""
    tile, b = 8, 3
    g = np.random.default_rng(hq)
    f = lambda *s: jnp.asarray(g.normal(size=s), jnp.float32)
    big = pr.state_dim(D, tile)
    store = f(2, 5, hkv, big, D)
    z = f(3, hkv, big)
    q, k, v = f(b, hq, D), f(b, hkv, D), f(b, hkv, D)
    gamma = jnp.asarray(np.log(g.uniform(0.1, 1, size=(b, hkv))),
                        jnp.float32)
    slots = jnp.array([4, 1, 2])
    num, new = power_decode_kernel(
        store, 1, slots, q * D ** -0.25, k * D ** -0.25, v, jnp.exp(gamma),
        tile=tile, interpret=True)
    _o, s_ref, z_ref = pr.power_step_math(store[1, slots], z, q, k, v,
                                          gamma, tile=tile)
    want = jnp.einsum(
        "bgrn,bgnv->bgrv",
        pr.phi(q * D ** -0.25, tile).reshape(b, hkv, hq // hkv, -1),
        s_ref).reshape(b, hq, D)
    np.testing.assert_allclose(num, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(new[1, slots], s_ref, atol=1e-5)
    np.testing.assert_array_equal(new[0], store[0])
    np.testing.assert_array_equal(new[1, jnp.array([0, 3])],
                                  store[1, jnp.array([0, 3])])


def test_the_recurrent_step_works_on_the_store_rows_it_is_handed():
    tile, b = 8, 2
    g = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(g.normal(size=s), jnp.float32)
    big = pr.state_dim(D, tile)
    store_s, store_z = f(2, 4, 2, big, D), jnp.abs(f(2, 4, 2, big))
    q, k, v = f(b, 4, D), f(b, 2, D), f(b, 2, D)
    gamma = -jnp.abs(f(b, 2))
    slots = jnp.array([3, 1])
    o, new_s, new_z = pr.power_recurrent_step(store_s, store_z, 0, slots, q,
                                              k, v, gamma, tile=tile)
    want, s, z = pr.power_step_math(store_s[0, slots], store_z[0, slots], q,
                                    k, v, gamma, tile=tile)
    np.testing.assert_allclose(o, want, atol=1e-6)
    np.testing.assert_allclose(new_s[0, slots], s, atol=1e-6)
    np.testing.assert_allclose(new_z[0, slots], z, atol=1e-6)
    np.testing.assert_array_equal(new_s[1], store_s[1])


@pytest.mark.parametrize("tile", [1, 8, 16])
def test_unpack_state_is_the_symmetric_tensor_whatever_the_tile(tile):
    q, k, v, gamma = inputs(12, 4, 2, (0.5, 1.0))
    _o, s, z = recurrent(q, k, v, gamma, tile)
    full_s, full_z = pr.unpack_state(s, z, tile)
    g_run = jnp.cumsum(gamma, 0)
    w = jnp.exp(g_run[-1][None] - g_run)
    ks = k * D ** -0.25
    np.testing.assert_allclose(
        full_s, jnp.einsum("sg,sgi,sgj,sgc->gijc", w, ks, ks, v), atol=2e-6)
    np.testing.assert_allclose(
        full_z, jnp.einsum("sg,sgi,sgj->gij", w, ks, ks), atol=2e-6)
