"""The three mechanisms a Kimi-Linear description adds, each against its
plain form on the CPU: KDA's chunked prefill against the token-by-token
recurrence (and wherever the chunks fall), MLA's absorbed decode against
the unabsorbed attention, the dropless routed experts against a dense loop
(and the two shares of an expert-parallel layer against the whole), and the
three Pallas kernels in interpret mode against the XLA forms.

tests/conftest.py pins "highest" matmul precision: both sides are float32
and differ by the order of their reductions, a few ulps a reduction.  The
tolerances are 1e-5 of the compared array's largest entry unless a case
says why it is wider."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import kda, mla
from paddle_tpu.parallel.moe import routed_expert_ffn

TOL = 1e-5


def close(a, b, tol=TOL):
    a, b = np.asarray(a), np.asarray(b)
    assert np.isfinite(a).all() and np.isfinite(b).all()
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), \
        (np.abs(a - b).max(), np.abs(b).max())


def kda_inputs(t_len, h, d, seed=0, strong=False):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa
    k = f(t_len, h, d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    # decays from nearly none to (strong) e^-4 a token: 64 tokens of the
    # latter are e^-256, which a factored form K e^g (K e^-g)^T cannot hold
    hi = 4.0 if strong else 1.5
    log_a = -jnp.asarray(r.uniform(1e-3, hi, (t_len, h, d)), jnp.float32)
    beta = jnp.asarray(r.uniform(0.05, 0.95, (t_len, h)), jnp.float32)
    return f(t_len, h, d) * d ** -0.5, k, f(t_len, h, d), log_a, beta


def recurrence(q, k, v, log_a, beta, state=None):
    h, d = q.shape[1:]
    state = jnp.zeros((1, h, d, d)) if state is None else state[None]
    out = []
    for t in range(q.shape[0]):
        o, state = kda.kda_step_math(state, q[t][None], k[t][None],
                                     v[t][None], log_a[t][None],
                                     beta[t][None])
        out.append(o[0])
    return jnp.stack(out), state[0]


@pytest.mark.parametrize("chunk", [4, 16, 48])
@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_kda_chunked_prefill_is_the_recurrence(chunk, strong):
    t_len, h, d = 48, 2, 16
    args = kda_inputs(t_len, h, d, strong=strong)
    want_o, want_s = recurrence(*args)
    n = t_len // chunk
    o, s = kda.kda_chunked_prefill(
        *args, jnp.zeros((n, h, d, d)), jnp.arange(n) > 0, chunk=chunk)
    close(o, want_o)
    close(s[-1], want_s)


def test_kda_chunks_start_from_a_given_state_and_pads_leave_it():
    """Two streams in one call: the second starts from a stored state; the
    first ends in padded positions (k = 0, beta = 0, log a = 0)."""
    h, d, c = 2, 16, 8
    q, k, v, la, b = kda_inputs(24, h, d, seed=3)
    s_given = jnp.asarray(
        np.random.default_rng(5).standard_normal((h, d, d)), jnp.float32)
    live = jnp.arange(24) < 13          # stream A: 13 tokens, then 3 pads
    live = live | (jnp.arange(24) >= 16)   # stream B: chunk 2, from s_given
    k = jnp.where(live[:, None, None], k, 0)
    la = jnp.where(live[:, None, None], la, 0)
    b = jnp.where(live[:, None], b, 0)
    o, s = kda.kda_chunked_prefill(
        q, k, v, la, b,
        jnp.stack([jnp.zeros((h, d, d)), jnp.zeros((h, d, d)), s_given]),
        jnp.asarray([False, True, False]), chunk=c)
    want_a, s_a = recurrence(q[:13], k[:13], v[:13], la[:13], b[:13])
    want_b, s_b = recurrence(q[16:], k[16:], v[16:], la[16:], b[16:],
                             s_given)
    close(o[:13], want_a)
    close(s[1], s_a)                    # the pads left the state alone
    close(o[16:], want_b)
    close(s[2], s_b)


def test_kda_decode_kernel_is_the_recurrence():
    from paddle_tpu.ops.pallas.kda_decode import kda_decode_kernel

    layers, slots, h, d, b = 2, 5, 8, 128, 3
    r = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa
    store, rows = f(layers, slots, h, d, d), jnp.asarray([2, 4, 0])
    q, k, v = f(b, h, d), f(b, h, d) * d ** -0.5, f(b, h, d)
    log_a, beta = -jnp.abs(f(b, h, d)), jax.nn.sigmoid(f(b, h))
    want_o, want_s = kda.kda_step_math(store[1, rows], q, k, v, log_a, beta)
    o, got = kda_decode_kernel(store, 1, rows, q, k, v, jnp.exp(log_a),
                               beta, interpret=True)
    close(o, want_o)
    close(got[1, rows], want_s)
    assert (got[0] == store[0]).all()           # the other layer: untouched
    assert (got[1, jnp.asarray([1, 3])] == store[1, jnp.asarray([1, 3])]).all()


def mla_case(seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa
    heads, nope, pe, vd, lora, bs, n_blocks = 4, 16, 8, 16, 32, 8, 12
    return dict(heads=heads, nope=nope, pe=pe, vd=vd, lora=lora, bs=bs,
                w_kvb=f(lora, heads, nope + vd) * lora ** -0.5,
                pool=f(2, n_blocks, bs, lora + pe), f=f, r=r)


def unabsorbed(c, q, lat, ctx_len):
    """softmax((q_nope.k_nope + q_pe.k_pe) * scale) v over the first
    ctx_len latents, keys and values formed per head from the latent."""
    nope, lora = c["nope"], c["lora"]
    kv = jnp.einsum("tl,lhd->thd", lat[:ctx_len, :lora], c["w_kvb"])
    s = (jnp.einsum("hd,thd->ht", q[:, :nope], kv[..., :nope])
         + jnp.einsum("hd,td->ht", q[:, nope:], lat[:ctx_len, lora:])) \
        * (nope + c["pe"]) ** -0.5
    return jnp.einsum("ht,thd->hd", jax.nn.softmax(s, -1), kv[..., nope:])


def absorb(c, q):
    return jnp.concatenate(
        [jnp.einsum("...hd,lhd->...hl", q[..., :c["nope"]],
                    c["w_kvb"][..., :c["nope"]]), q[..., c["nope"]:]], -1)


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_mla_absorbed_decode_is_the_unabsorbed_attention(form):
    c = mla_case()
    q = c["f"](3, c["heads"], c["nope"] + c["pe"])
    tables = jnp.asarray(c["r"].permutation(np.arange(1, 12))[:9]
                         .reshape(3, 3), jnp.int32)
    ctx = jnp.asarray([13, 24, 1], jnp.int32)
    scale = (c["nope"] + c["pe"]) ** -0.5
    if form == "xla":
        o_lat = mla.mla_decode_attention(absorb(c, q), c["pool"], 1, tables,
                                         ctx, scale=scale, lora=c["lora"])
    else:
        from paddle_tpu.ops.pallas.mla_decode import mla_decode_kernel

        o_lat = mla_decode_kernel(absorb(c, q), c["pool"], 1, tables, ctx,
                                  scale=scale, lora=c["lora"],
                                  interpret=True)
    got = jnp.einsum("bhl,lhv->bhv", o_lat, c["w_kvb"][..., c["nope"]:])
    for b in range(3):
        lat = c["pool"][1, tables[b]].reshape(-1, c["lora"] + c["pe"])
        close(got[b], unabsorbed(c, q[b], lat, int(ctx[b])))


# positions a step of the prefill's online softmax: one block of 8, two
# (the table's 3 columns end inside the second step), the default (all 3)
@pytest.mark.parametrize("step", [8, 16, mla.PREFILL_STEP_POSITIONS])
def test_mla_prefill_tiles_attend_their_own_rows_causally(monkeypatch, step):
    monkeypatch.setattr(mla, "PREFILL_STEP_POSITIONS", step)
    c = mla_case(1)
    tile, lens = 4, (7, 19)             # two sequences, tiles of 4 tokens
    tables = jnp.asarray([[3, 5, 9], [7, 2, 11]], jnp.int32)
    pos = np.full((28,), -1, np.int32)
    pos[0:7], pos[8:27] = np.arange(7), np.arange(19)
    tile_row = jnp.asarray([0, 0, 1, 1, 1, 1, 1])
    q = c["f"](28, c["heads"], c["nope"] + c["pe"])
    o_lat = mla.mla_prefill_attention(
        absorb(c, q), c["pool"], 0, tables, tile_row, jnp.asarray(pos),
        scale=(c["nope"] + c["pe"]) ** -0.5, tile=tile, lora=c["lora"])
    got = jnp.einsum("thl,lhv->thv", o_lat, c["w_kvb"][..., c["nope"]:])
    for row, (start, n) in enumerate(((0, lens[0]), (8, lens[1]))):
        lat = c["pool"][0, tables[row]].reshape(-1, c["lora"] + c["pe"])
        for t in range(n):
            close(got[start + t], unabsorbed(c, q[start + t], lat, t + 1))


def expert_case(n=24, d=16, f_dim=32, experts=8, seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa
    return dict(x=f(n, d), router_w=f(d, experts), experts=experts,
                router_b=f(experts) * 0.1, gate=f(experts, d, f_dim) * 0.2,
                up=f(experts, d, f_dim) * 0.2, down=f(experts, f_dim, d) * 0.2)


def dense_experts(c, first, count, top_k, scaling):
    """The reference's way: every held expert on every token, masked."""
    s = jax.nn.sigmoid(c["x"] @ c["router_w"])
    _, idx = jax.lax.top_k(s + c["router_b"], top_k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / w.sum(-1, keepdims=True) * scaling
    y = jnp.zeros_like(c["x"])
    for e in range(first, first + count):
        mine = jnp.where(idx == e, w, 0).sum(-1)
        hid = jax.nn.silu(c["x"] @ c["gate"][e]) * (c["x"] @ c["up"][e])
        y = y + mine[:, None] * (hid @ c["down"][e])
    return y


def routed(c, first, count, top_k=2, scaling=2.5, valid=None):
    sl = slice(first, first + count)
    valid = jnp.ones((c["x"].shape[0],), bool) if valid is None else valid
    return routed_expert_ffn(
        c["x"], valid, c["router_w"], c["router_b"], c["gate"][sl],
        c["up"][sl], c["down"][sl], held_first=first, top_k=top_k,
        scaling=scaling)[:2]


def test_routed_experts_are_dropless_and_equal_the_dense_loop():
    c = expert_case()
    y, counts = routed(c, 0, 8)
    close(y, dense_experts(c, 0, 8, 2, 2.5))
    tokens, picks, touched, most = (int(v) for v in counts)
    assert (tokens, picks) == (24, 48) and touched <= 8 and most >= 6


def test_the_shares_of_an_expert_parallel_layer_add_up_to_the_layer():
    """The guide's share test: experts 0-3 on one chip, 4-7 on the other,
    each routing over all 8; the two shares' sums, with what every chip
    computes alike (the shared expert, the residual) counted ONCE, are the
    uncut layer."""
    c = expert_case(seed=2)
    shared = jax.nn.silu(c["x"] @ c["gate"][0]) @ c["down"][0]  # any fixed fn
    y_a, counts_a = routed(c, 0, 4)
    y_b, counts_b = routed(c, 4, 4)
    whole = c["x"] + dense_experts(c, 0, 8, 2, 2.5) + shared
    close(c["x"] + y_a + y_b + shared, whole)
    close(y_a, dense_experts(c, 0, 4, 2, 2.5))
    assert int(counts_a[1]) + int(counts_b[1]) == 24 * 2   # every pick, once


def test_rows_that_are_not_valid_route_nowhere():
    c = expert_case(seed=4)
    valid = jnp.arange(24) % 3 != 0
    y, counts = routed(c, 0, 8, valid=valid)
    assert (np.asarray(y)[::3] == 0).all() and int(counts[0]) == 16
    close(np.asarray(y)[np.asarray(valid)],
          np.asarray(dense_experts(c, 0, 8, 2, 2.5))[np.asarray(valid)])


def test_moe_gmm_kernel_is_the_tiles_swiglu():
    from paddle_tpu.ops.pallas.moe_gmm import moe_gmm_kernel

    r = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa
    experts, d, f_dim, tm, rows = 3, 16, 1024, 8, 40    # two slabs of 512
    x, gate, up, down = (f(rows, d), f(experts, d, f_dim) * .1,
                         f(experts, d, f_dim) * .1, f(experts, f_dim, d) * .1)
    te, n_valid = jnp.asarray([0, 2, 2, 2, 2], jnp.int32), 3
    y = moe_gmm_kernel(x, gate, up, down, te, jnp.asarray([n_valid]), tm=tm,
                       interpret=True)
    xt = x.reshape(rows // tm, tm, d)
    want = jnp.einsum(
        "tmf,tfd->tmd",
        jax.nn.silu(jnp.einsum("tmd,tdf->tmf", xt, gate[te]))
        * jnp.einsum("tmd,tdf->tmf", xt, up[te]), down[te]).reshape(rows, d)
    close(y[:n_valid * tm], want[:n_valid * tm])
