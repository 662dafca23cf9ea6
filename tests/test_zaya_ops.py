"""The pieces ZAYA1's attention adds, on the CPU at tiny sizes, float32
(tests/conftest.py pins "highest" matmul precision): the paged kernels
with fewer K/V heads than query heads (interpret mode) and the XLA gather
path against plain attention; partial rotary positions; and CCA's
convolutions, q-k mean and shifted value split over two prefill chunks and
over prefill then decode against one pass of
benchmark/reference/zaya.py."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.models.zaya import Zaya, ZayaConfig
from paddle_tpu.nn.decode import PagedDecoder
from paddle_tpu.ops import attention
from paddle_tpu.ops.pallas import unified_attention as ua
from paddle_tpu.ops.rotary import apply_rotary
from paddle_tpu.sampling import SlotParamStore

from benchmark_harness import bench_paths  # noqa: F401 — sys.path
from reference import zaya as ref

BS = 8


def plain_attention(q, k, v, group):
    """q [S, Hq, D], k, v [S, Hkv, D] of one sequence -> [S, Hq, D]:
    causal softmax, query head h against K/V head h // group."""
    s_len, hq, d = q.shape
    out = np.zeros((s_len, hq, d), np.float32)
    for h in range(hq):
        sc = q[:, h] @ k[:, h // group].T * d ** -0.5
        sc = np.where(np.tril(np.ones((s_len, s_len), bool)), sc, -np.inf)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        out[:, h] = (w / w.sum(-1, keepdims=True)) @ v[:, h // group]
    return out


def paged(lengths, hq, hkv, d, seed=0):
    """Sequences of `lengths` in a pool stack of 2 layers (layer 1 used):
    (q, k, v per sequence, pools, tables)."""
    g = np.random.default_rng(seed)
    width = max(-(-n // BS) for n in lengths)
    kc = np.zeros((2, 1 + len(lengths) * width, BS, hkv * d), np.float32)
    vc = np.zeros_like(kc)
    tables = np.zeros((len(lengths), width), np.int32)
    seqs = []
    for r, n in enumerate(lengths):
        q, k, v = (g.standard_normal((n, h, d)).astype(np.float32)
                   for h in (hq, hkv, hkv))
        for b in range(-(-n // BS)):
            tables[r, b] = blk = 1 + r * width + b
            rows = slice(b * BS, min(n, (b + 1) * BS))
            kc[1, blk, :rows.stop - rows.start] = k[rows].reshape(-1, hkv * d)
            vc[1, blk, :rows.stop - rows.start] = v[rows].reshape(-1, hkv * d)
        seqs.append((q, k, v))
    return seqs, jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(tables)


@pytest.mark.parametrize("hq,hkv,d", [(8, 2, 16), (4, 2, 32), (4, 4, 16)],
                         ids=["8on2", "4on2", "4on4"])
@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_decode_attention_with_grouped_heads(path, hq, hkv, d):
    """One token a sequence against its paged context: the decode kernel
    (interpret mode: the [Hq, Hkv*D] query tile) and the gather path give
    plain attention's last row."""
    lengths = (5, 16, 23)
    seqs, kc, vc, tables = paged(lengths, hq, hkv, d)
    q = jnp.asarray(np.stack([s[0][-1] for s in seqs]))
    ctx = jnp.asarray(np.array(lengths, np.int32))
    if path == "kernel":
        got = ua.paged_decode_attention_kernel(q, kc, vc, tables, ctx, 1,
                                               interpret=True)
    else:
        got = attention.paged_decode_attention(q, kc, vc, tables, ctx,
                                               layer=1)
    for r, (qs, ks, vs) in enumerate(seqs):
        want = plain_attention(qs, ks, vs, hq // hkv)[-1]
        np.testing.assert_allclose(np.asarray(got[r]), want, atol=2e-5)


@pytest.mark.parametrize("lengths", [
    (0, 1, BS, BS + 1, 3 * BS), (0, 0, 2 * BS + 1, 0), (3 * BS,) * 3],
    ids=["block_edges", "one_live_row", "every_row_full"])
@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4)], ids=["8on2", "4on4"])
def test_decode_kernel_steps_over_live_blocks_with_grouped_heads(hq, hkv,
                                                                 lengths):
    """The decode kernel's grid is the launch's live (row, block) pairs
    (PR 31), at group 4 and group 1, the layer a traced scalar as in a
    program: a pad row, one token, a block and one past it, a full table,
    one live row among idle ones, every row full: plain attention's last
    row, zeros where a row sees nothing; and the gather path agrees."""
    d, width = 16, 3
    live = [r for r, n in enumerate(lengths) if n]      # rows that see any
    seqs, kc, vc, packed = paged([lengths[r] for r in live], hq, hkv, d,
                                 seed=2)
    tables = np.zeros((len(lengths), width), np.int32)
    q = np.ones((len(lengths), hq, d), np.float32)  # a pad row's: whatever
    for i, r in enumerate(live):
        tables[r, :packed.shape[1]] = np.asarray(packed[i])
        q[r] = seqs[i][0][-1]
    q, tables = jnp.asarray(q), jnp.asarray(tables)
    ctx = jnp.asarray(np.array(lengths, np.int32))
    got = np.asarray(jax.jit(lambda ly: ua.paged_decode_attention_kernel(
        q, kc, vc, tables, ctx, ly, interpret=True))(jnp.int32(1)))
    gathered = np.asarray(attention.paged_decode_attention(
        q, kc, vc, tables, ctx, layer=1))
    assert not got[[r for r, n in enumerate(lengths) if not n]].any()
    for i, r in enumerate(live):
        want = plain_attention(*seqs[i], hq // hkv)[-1]
        np.testing.assert_allclose(got[r], want, atol=2e-5)
        np.testing.assert_allclose(got[r], gathered[r], atol=2e-5)


@pytest.mark.parametrize("hq,hkv,d", [(8, 2, 16), (4, 2, 32), (4, 4, 16)],
                         ids=["8on2", "4on2", "4on4"])
@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_prefill_attention_with_grouped_heads(path, hq, hkv, d):
    """A packed stream of whole sequences (regions aligned to the query
    tile): the stream kernel and the gather path give plain attention."""
    lengths, tile = (5, 16, 23), 8
    seqs, kc, vc, tables = paged(lengths, hq, hkv, d, seed=1)
    regions = [-(-n // tile) * tile for n in lengths]
    total = sum(regions)
    q = np.zeros((total, hq, d), np.float32)
    seg = np.zeros((total,), np.int32)
    pos = np.full((total,), -1, np.int32)
    off = 0
    for r, (n, region) in enumerate(zip(lengths, regions)):
        q[off:off + n] = seqs[r][0]
        seg[off:off + region] = r
        pos[off:off + n] = np.arange(n)
        off += region
    if path == "kernel":
        # a tile's position is its first token's; rows past a region's
        # tokens are padding the caller discards
        tile_pos = np.concatenate([np.arange(0, region, tile)
                                   for region in regions]).astype(np.int32)
        got = ua.unified_ragged_attention_kernel(
            jnp.asarray(q), kc, vc, tables, jnp.asarray(seg[::tile]),
            jnp.asarray(tile_pos), 1, q_tile=tile, interpret=True)
    else:
        got = attention.ragged_prefill_attention(
            jnp.asarray(q), kc, vc, tables, jnp.asarray(seg),
            jnp.asarray(pos), layer=1)
    off = 0
    for r, (n, region) in enumerate(zip(lengths, regions)):
        want = plain_attention(*seqs[r], hq // hkv)
        np.testing.assert_allclose(np.asarray(got[off:off + n]), want,
                                   atol=2e-5)
        off += region


def test_grouped_heads_need_whole_groups_and_a_dense_pool_in_the_kernel():
    seqs, kc, vc, tables = paged((5,), 4, 2, 16)
    q = jnp.zeros((1, 3, 16), jnp.float32)
    with pytest.raises(ValueError, match="whole groups"):
        ua.paged_decode_attention_kernel(q, kc, vc, tables,
                                         jnp.ones((1,), jnp.int32), 1,
                                         interpret=True)
    assert ua.supported_shapes(128, 128, 8, kv_heads=2)
    assert ua.supported_shapes(64, 128, 16)            # GPT-2-medium's
    assert not ua.supported_shapes(128, 128, 8, kv_heads=3)
    assert not ua.supported_shapes(32, 128, 8, kv_heads=2)   # a 64-lane row


def test_partial_rotary_turns_the_leading_channels_by_position():
    """The first `rotary_dim` channels of every head turn, paired by
    halves, by pos * theta^(-2i/rotary_dim); the rest pass; a dot product
    of a rotated q and k depends on their distance alone."""
    g = np.random.default_rng(2)
    n, h, d, r, theta = 6, 3, 16, 8, 5e6
    x = g.standard_normal((n, h, d)).astype(np.float32)
    pos = np.array([0, 1, 2, 700, 9000, 131071], np.int32)
    got = np.asarray(apply_rotary(jnp.asarray(x), jnp.asarray(pos), r, theta))
    np.testing.assert_array_equal(got[..., r:], x[..., r:])
    np.testing.assert_allclose(got[0], x[0], atol=1e-7)      # position 0
    half = r // 2
    for i in range(half):
        angle = pos.astype(np.float64) * theta ** (-2.0 * i / r)
        z = (x[..., i] + 1j * x[..., i + half]) \
            * np.exp(1j * angle)[:, None]
        np.testing.assert_allclose(got[..., i], z.real, atol=2e-3)
        np.testing.assert_allclose(got[..., i + half], z.imag, atol=2e-3)
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    q, k = (jnp.asarray(g.standard_normal((1, 1, d)).astype(np.float32))
            for _ in range(2))

    def score(pq, pk):
        rot = lambda t, p: apply_rotary(t, jnp.array([p], jnp.int32), r,
                                        theta)
        return float(jnp.sum(rot(q, pq) * rot(k, pk)))

    assert abs(score(40, 33) - score(1007, 1000)) < 1e-4
    assert abs(score(40, 33) - score(40, 20)) > 1e-3
    with pytest.raises(ValueError, match="even part"):
        apply_rotary(jnp.asarray(x), jnp.asarray(pos), 7, theta)


# ---- CCA across chunk and dispatch boundaries ------------------------------

@pytest.fixture(scope="module")
def one_layer():
    """A one-layer model whose constants-at-init are moved (scalings, tau,
    biases), so that a program that left one out would show."""
    paddle.seed(11)
    cfg = ZayaConfig.tiny(num_hidden_layers=1)
    model = Zaya(cfg)
    model.eval()
    g = np.random.default_rng(5)
    params = {k: v + 0.1 * jnp.asarray(g.standard_normal(v.shape), v.dtype)
              if k.rsplit(".", 1)[-1] in ("a_res", "b_res", "a_out", "b_out",
                                          "k_scale", "bias", "gamma")
              else v for k, v in model.functional_state()[0].items()}
    model.load_functional_state(params)
    arch = {"hidden": cfg.hidden_size, "eps": cfg.rms_norm_eps, "layers": 1,
            "heads": 4, "kv_heads": 2, "head_dim": 16, "rotary_dim": 8,
            "theta": cfg.rope_theta, "top_k": 1, "held": (0, 4)}
    return cfg, model, params, arch


def run_split(model, params, ids, chunks, decode_from):
    """Feed ids[:decode_from] as packed prefill chunks of the given
    lengths, then the rest one decode step a token: (the cache, the logits
    after the last token)."""
    desc = model.decoder_description()
    cache = PagedKVCache.for_description(desc, block_size=BS, num_blocks=16,
                                         dtype=jnp.float32, max_slots=2)
    dec = PagedDecoder(desc, BS, return_logits=True)
    store = SlotParamStore(2, desc.vocab)
    fed, lg = 0, None
    assert sum(chunks) == decode_from
    for n in chunks:
        t_len = -(-n // 8) * 8
        toks = np.zeros((t_len,), np.int32)
        pos = np.full((t_len,), -1, np.int32)
        toks[:n], pos[:n] = ids[fed:fed + n], np.arange(fed, fed + n)
        cache.ensure_many([(0, fed + n)])
        _t, _s, kc, vc, state, _c, _r, lg = dec.packed_prefill(
            params, jnp.asarray(toks), jnp.zeros((t_len,), jnp.int32),
            jnp.asarray(pos), jnp.asarray(cache.table_array([0, None], 6)),
            jnp.asarray(np.array([n - 1, 0], np.int32)), cache.k_blocks,
            cache.v_blocks, store.warm_args(2), state=cache.state)
        cache.swap_arrays(kc, vc, state)
        fed += n
    for t in range(decode_from, len(ids)):
        cache.ensure_many([(0, t + 1)])
        sp, _m = store.step_args(np.zeros((2,), np.int32))
        _t, _s, kc, vc, state, _c, _r, lg = dec.step(
            params, jnp.asarray(np.array([ids[t], 0], np.int32)),
            jnp.asarray(np.array([t, 0], np.int32)),
            jnp.asarray(np.array([True, False])),
            jnp.asarray(cache.table_array([0, None], 6)), cache.k_blocks,
            cache.v_blocks, sp, state=cache.state)
        cache.swap_arrays(kc, vc, state)
    return cache, np.asarray(lg[0])


@pytest.mark.parametrize("chunks,decode_from", [
    ((21,), 21), ((8, 13), 21), ((13, 1, 7), 21), ((16,), 16), ((1,), 1)],
    ids=["one-pass", "two-chunks", "a-one-token-chunk", "prefill-then-decode",
         "decode-from-the-second-token"])
def test_cca_split_over_chunks_and_decode_is_one_pass(one_layer, chunks,
                                                      decode_from):
    """However 21 tokens reach the layer (one chunk, chunks that start
    from the tails the one before left, decode steps that shift them): the
    K and V rows in the pool, the tails in the store and the last logits
    are the reference's one pass over the whole sequence."""
    cfg, model, params, arch = one_layer
    ids = np.random.default_rng(3).integers(1, cfg.vocab_size, 21,
                                            dtype=np.int32)
    cache, lg = run_split(model, params, ids, chunks, decode_from)
    want, found = ref.logits(params, jnp.asarray(ids), arch, np.array([20]))
    np.testing.assert_allclose(lg, np.asarray(want[0]), atol=2e-5)
    for name, tail in found["tails"].items():
        np.testing.assert_allclose(np.asarray(cache.state[name][:, 1]),
                                   np.asarray(tail), atol=2e-6)
    one, _lg = run_split(model, params, ids, (21,), 21)
    table = cache.block_table(0)
    for mine, whole in ((cache.k_blocks, one.k_blocks),
                        (cache.v_blocks, one.v_blocks)):
        np.testing.assert_allclose(
            np.asarray(mine[0, np.array(table)]).reshape(-1, 32)[:21],
            np.asarray(whole[0, np.array(one.block_table(0))])
            .reshape(-1, 32)[:21], atol=2e-6)


def test_the_shifted_value_is_the_token_befores_half(one_layer):
    """V rows in the pool: head 0 is W_v1 of this token's normed input,
    head 1 W_v2 of the token before's (zero at position 0)."""
    cfg, model, params, arch = one_layer
    ids = np.random.default_rng(4).integers(1, cfg.vocab_size, 9,
                                            dtype=np.int32)
    cache, _lg = run_split(model, params, ids, (9,), 9)
    v = np.asarray(cache.v_blocks[0, np.array(cache.block_table(0))]) \
        .reshape(-1, 2, 16)[:9]
    x = np.asarray(params["embed.weight"])[ids]
    a = np.asarray(ref.rms_norm(jnp.asarray(x),
                                params["layers.0.norm_1.weight"], arch["eps"]))
    np.testing.assert_allclose(
        v[:, 0], a @ np.asarray(params["layers.0.cca.v1_proj.weight"]),
        atol=2e-6)
    np.testing.assert_allclose(
        v[1:, 1], (a @ np.asarray(params["layers.0.cca.v2_proj.weight"]))[:-1],
        atol=2e-6)
    assert not v[0, 1].any()
