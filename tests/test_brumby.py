"""Brumby through the serving stack on the CPU, tiny preset, seeded random
weights, against benchmark/reference/brumby.py (float32 both sides;
tests/conftest.py pins "highest" matmul precision).

The program keeps a sequence as a feature-map state, in chunks while it
prefills and a token at a time while it decodes; the reference forms every
weight directly from q . k over the whole sequence.  Their reductions are
ordered differently, so a logit moves by a few float32 ulps a reduction
through 3 layers.  LOGIT_TOL is 2e-4 of the logits' spread and STATE_TOL
1e-4 of the state's norm (measured: under 3e-6 and 1e-6); a gate left out,
rotary left out, a missing q/k norm, a query head on the wrong K/V head, an
off-diagonal weight of 1 or a slot's state not zeroed for its next holder
each move them by more than 1e-2."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import PagedGenerationServer
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.models.brumby import Brumby, BrumbyConfig
from paddle_tpu.nn.decode import PagedDecoder
from paddle_tpu.ops.power_retention import unpack_state
from paddle_tpu.sampling import SlotParamStore

from benchmark_harness import bench_paths  # noqa: F401 — sys.path
from reference import brumby as ref

LOGIT_TOL, STATE_TOL = 2e-4, 1e-4
BS, CHUNK = 8, 16


def arch_of(cfg):
    return {"hidden": cfg.hidden_size, "eps": cfg.rms_norm_eps,
            "layers": cfg.held_layers, "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
            "theta": float(cfg.rope_theta)}


@pytest.fixture(scope="module")
def tiny():
    """The tiny preset with what is constant at the start (every norm's
    weight 1) moved, so that a program that left a norm's weight out
    would show, and with the reference's blocks cut to the test's
    lengths."""
    paddle.seed(3)
    cfg = BrumbyConfig.tiny()
    model = Brumby(cfg)
    model.eval()
    g = np.random.default_rng(9)
    params = {k: v + 0.05 * jnp.asarray(g.standard_normal(v.shape), v.dtype)
              if "norm" in k else v
              for k, v in model.functional_state()[0].items()}
    model.load_functional_state(params)
    was = dict(ref.ASSUMED)
    ref.ASSUMED.update(query_block=8, row_block=8)
    yield cfg, model, params
    ref.ASSUMED.update(was)


def padded(seq, block=8):
    """A sequence as the reference takes it: whole blocks (causal: the
    padding cannot reach back)."""
    ids = np.zeros((-(-len(seq) // block) * block,), np.int32)
    ids[:len(seq)] = seq
    return jnp.asarray(ids)


def prompts_of(cfg, lengths, seed=0):
    g = np.random.default_rng(seed)
    return [g.integers(1, cfg.vocab_size, n, dtype=np.int32)
            for n in lengths]


def teacher_forced(model, params, seqs, n_prompts, slots=None):
    """The program's logits at every position from each prompt's last to
    the sequence's last but one, and the cache it leaves: the prompts
    prefilled TOGETHER, a chunk of 16 tokens of each a dispatch (two or
    more sequences packed in one stream), then decoded together with
    their own tokens fed back.  The cache has no pool: no block is ever
    taken, a table row is [state slot]."""
    desc = model.decoder_description()
    rows = len(seqs)
    cache = PagedKVCache.for_description(
        desc, block_size=BS, num_blocks=2, dtype=jnp.float32,
        max_slots=slots or rows)
    dec = PagedDecoder(desc, BS, return_logits=True)
    store = SlotParamStore(rows, desc.vocab)
    out = [[] for _ in seqs]
    fed = [0] * rows
    align = desc.pack_multiple
    while any(f < n for f, n in zip(fed, n_prompts)):
        plan, off = [], 0
        for i in range(rows):
            n = min(CHUNK, n_prompts[i] - fed[i])
            if n > 0:
                plan.append((i, fed[i], n, off))
                off += -(-n // align) * align
        t_len = align
        while t_len < off:
            t_len *= 2
        toks = np.zeros((t_len,), np.int32)
        seg = np.zeros((t_len,), np.int32)
        pos = np.full((t_len,), -1, np.int32)
        sample = np.zeros((rows,), np.int32)
        for r, (i, start, n, o) in enumerate(plan):
            toks[o:o + n] = seqs[i][start:start + n]
            seg[o:o + n] = r
            pos[o:o + n] = np.arange(start, start + n)
            sample[r] = o + n - 1
        cache.ensure_many([(i, start + n) for i, start, n, _ in plan])
        ids = [p[0] for p in plan] + [None] * (rows - len(plan))
        tables = cache.table_array(ids, 0)
        assert tables.shape == (rows, 1)
        _t, _s, kc, state, _c, routed, lg = dec.packed_prefill(
            params, jnp.asarray(toks), jnp.asarray(seg), jnp.asarray(pos),
            jnp.asarray(tables), jnp.asarray(sample), cache.k_blocks, None,
            store.warm_args(rows), state=cache.state)
        assert routed is None and kc.shape == (0, 2, BS, 0)
        cache.swap_arrays(kc, None, state)
        for r, (i, start, n, _o) in enumerate(plan):
            fed[i] = start + n
            if fed[i] == n_prompts[i]:
                out[i].append(np.asarray(lg[r]))
    at = list(n_prompts)
    while any(a < len(s) - 1 for a, s in zip(at, seqs)):
        live = np.array([a < len(s) - 1 for a, s in zip(at, seqs)])
        tok = np.array([s[a] if ok else 0
                        for s, a, ok in zip(seqs, at, live)], np.int32)
        cache.ensure_many([(i, at[i] + 1) for i in range(rows) if live[i]])
        sp, _m = store.step_args(np.zeros((rows,), np.int32))
        _t, _s, kc, state, _c, _routed, lg = dec.step(
            params, jnp.asarray(tok),
            jnp.asarray(np.where(live, at, 0).astype(np.int32)),
            jnp.asarray(live),
            jnp.asarray(cache.table_array(list(range(rows)), 0)),
            cache.k_blocks, None, sp, state=cache.state)
        cache.swap_arrays(kc, None, state)
        for i in range(rows):
            if live[i]:
                out[i].append(np.asarray(lg[i]))
                at[i] += 1
    return [np.stack(o) for o in out], cache


def state_error(cache, seq_id, cfg, want):
    """How far layer 0's state of `seq_id` in the cache lies from the
    reference's `want` ({"S", "z"}), as a share of its norm: the worse."""
    slot = cache.state_slot(seq_id)
    s, z = unpack_state(cache.state["P"][0, slot], cache.state["Z"][0, slot],
                        cfg.power_tile)
    return max(np.linalg.norm(got - np.asarray(want[k]))
               / np.linalg.norm(np.asarray(want[k]))
               for k, got in (("S", s), ("z", z)))


def test_prefill_then_decode_through_the_store_is_the_reference(tiny):
    """Prompts of 37, 21 and 5 tokens (3, 2 and 1 dispatches; the first
    crosses two dispatch boundaries and four chunk boundaries), packed
    together, then 6 decode steps: every logit of every position and the
    first layer's state against the reference's full forward of the whole
    sequence."""
    cfg, model, params = tiny
    n_prompts = [37, 21, 5]
    seqs = prompts_of(cfg, [n + 6 for n in n_prompts], seed=1)
    got, cache = teacher_forced(model, params, seqs, n_prompts)
    assert cache.stats()["used_blocks"] == 0      # no sequence took a block
    for i, (s, n, mine) in enumerate(zip(seqs, n_prompts, got)):
        want, found = ref.logits(params, padded(s), arch_of(cfg),
                                 jnp.arange(n - 1, len(s) - 1),
                                 state_len=len(s) - 1)
        want = np.asarray(want)
        assert mine.shape == want.shape
        assert np.abs(mine - want).max() <= LOGIT_TOL * want.std(), \
            np.abs(mine - want).max() / want.std()
        assert state_error(cache, i, cfg, found["state"]) <= STATE_TOL


def test_query_heads_read_the_kv_head_of_their_group(tiny):
    """GQA: query head h reads K/V head h // group.  With K/V head 1's
    value projection zeroed, the program's and the reference's logits
    still agree, and they differ from the whole model's."""
    cfg, model, params = tiny
    d, hkv = cfg.head_dim, cfg.num_key_value_heads
    cut = dict(params)
    for i in range(cfg.held_layers):
        name = f"layers.{i}.power.v_proj.weight"
        cut[name] = params[name].at[:, d:].set(0.0)       # K/V head 1
    seqs = prompts_of(cfg, [19], seed=4)
    got, _cache = teacher_forced(model, cut, seqs, [13])
    want, _f = ref.logits(cut, padded(seqs[0]), arch_of(cfg),
                          jnp.arange(12, 18))
    whole, _f = ref.logits(params, padded(seqs[0]), arch_of(cfg),
                           jnp.arange(12, 18))
    want, whole = np.asarray(want), np.asarray(whole)
    assert hkv == 2 and np.abs(got[0] - want).max() <= LOGIT_TOL * want.std()
    assert np.abs(whole - want).max() > 1e-2 * want.std()


def serve(model, prompts, new=6, **kw):
    """(tokens, stats, every request's last state slot, the stopped
    server)."""
    opts = dict(max_slots=2, block_size=BS, max_prompt_len=48,
                max_new_tokens=8, prefill_chunk_tokens=CHUNK)
    opts.update(kw)
    server = PagedGenerationServer(model, **opts)
    slots = [0] * len(prompts)

    def note(i):
        def on_routing(position, picks, slot):
            assert picks is None          # no expert layers
            slots[i] = slot
        return on_routing

    server.start()
    try:
        futs = [server.submit(p, max_new_tokens=new, on_routing=note(i))
                for i, p in enumerate(prompts)]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
        return outs, server.stats(), slots, server
    finally:
        server.stop()


def test_a_description_without_a_pool_builds_its_cache_and_serves(tiny):
    """Five requests through two slots: admission goes by state slots
    alone (the cache has one usable block and never gives it out), the
    served tokens are the reference's argmax, and the two requests that
    held their slots last left the reference's state there although
    another sequence held the slot before them."""
    cfg, model, params = tiny
    prompts = prompts_of(cfg, (5, 37, 20, 9, 44))
    outs, stats, slots, server = serve(model, prompts)
    assert server.cache.k_blocks.shape == (0, 2, BS, 0) \
        and server.cache.v_blocks is None
    for p, o in zip(prompts, outs):
        assert (o[:len(p)] == p).all() and len(o) == len(p) + 6
        lg, _f = ref.logits(params, padded(o), arch_of(cfg),
                            jnp.arange(len(p) - 1, len(o) - 1))
        lg = np.asarray(lg)
        deficit = lg.max(-1) - lg[np.arange(6), o[len(p):]]
        assert deficit.max() <= LOGIT_TOL * lg.std()
    kv, state = stats["kv_cache"], stats["state"]
    assert kv["bytes_per_token"] == 0 and kv["pool_bytes_total"] == 0
    assert kv["peak_used_blocks"] == 0 and kv["num_blocks"] == 1
    big = cfg.power_tile ** 2 * 3                    # D = 192: 3 tile pairs
    per_layer = {"P": 2 * big * 16 * 4, "Z": 2 * big * 4}
    assert state["slots"] == 2 and state["peak_used_slots"] == 2
    assert state["entries"] == {k: 3 * v for k, v in per_layer.items()}
    assert state["bytes_per_slot"] == sum(state["entries"].values())
    assert sorted(slots[3:]) == [1, 2] and set(slots[:3]) <= {1, 2}
    for i in (3, 4):                   # a re-used slot started from zero
        o = outs[i]
        _x, found = ref.hidden(params, padded(o), arch_of(cfg),
                               state_len=len(o) - 1)
        s, z = unpack_state(server.cache.state["P"][0, slots[i]],
                            server.cache.state["Z"][0, slots[i]],
                            cfg.power_tile)
        for got, want in ((s, found["state"]["S"]), (z, found["state"]["z"])):
            want = np.asarray(want)
            assert np.linalg.norm(got - want) \
                <= STATE_TOL * np.linalg.norm(want)


def test_the_default_pool_of_a_description_without_one_is_two_blocks(tiny):
    _cfg, model, _params = tiny
    server = PagedGenerationServer(model, max_slots=3, block_size=BS,
                                   max_prompt_len=40, max_new_tokens=8,
                                   prefill_chunk_tokens=CHUNK)
    assert server.cache.num_blocks == 2 and server.cache.state_slots == 3
    assert server.cache.blocks_for(10 ** 6) == 0


REFUSED = [
    ("enable_prefix_cache", True), ("speculation", True),
    ("kv_dtype", "int8"), ("quantization", "w8a16"),
    ("weight_quant", "int8"), ("unified_round", True),
    ("async_rounds", True), ("steps_per_dispatch", 2),
    ("sharding", True), ("kv_tier", True), ("tier_prefetch", True)]


@pytest.mark.parametrize("name,value", REFUSED,
                         ids=[n for n, _v in REFUSED])
def test_options_without_meaning_beside_a_state_alone_raise(tiny, name,
                                                            value):
    _cfg, model, _params = tiny
    with pytest.raises(ValueError, match=name):
        PagedGenerationServer(model, max_slots=2, block_size=BS,
                              max_prompt_len=16, max_new_tokens=4,
                              prefill_chunk_tokens=CHUNK, **{name: value})


def test_the_description_and_its_cache(tiny):
    """A fourth layout of `decode_blocks`: "power" mixers with "dense"
    FFNs, the state and its normaliser in the store, NO pool, chunks of
    `power_chunk`; the decoder takes vc=None."""
    from paddle_tpu.nn.decode_blocks import (DecoderDescription,
                                             LayerDescription)

    cfg, model, _params = tiny
    desc = model.decoder_description()
    assert [(l.mixer, l.ffn) for l in desc.layers] == [("power", "dense")] * 3
    assert desc.chunked and desc.pack_multiple == desc.chunk == 8
    assert not desc.pooled and not desc.values and desc.query_heads == 4
    assert desc.power.state_dim == 192
    lay = desc.cache_layout()
    assert (lay["pool_layers"], lay["row_width"], lay["values"]) \
        == (0, 0, False)
    assert lay["store"] == {"P": (3, (2, 192, 16), "float32"),
                            "Z": (3, (2, 192), "float32")}
    cache = PagedKVCache.for_description(desc, block_size=BS, num_blocks=2,
                                         dtype=jnp.float32, max_slots=3)
    assert cache.k_blocks.shape == (0, 2, BS, 0) and cache.v_blocks is None
    assert cache.state["P"].shape == (3, 4, 2, 192, 16)
    assert cache.state["P"].dtype == cache.state["Z"].dtype == jnp.float32
    cache.ensure_many([("a", 10_000), ("b", 3)])   # no block, a slot each
    assert cache.table_array(["a", None, "b"], 0).tolist() == [[1], [0], [2]]
    assert cache.stats()["used_blocks"] == 0 and cache.free_state_slots == 1
    cache.free("a")
    assert cache.free_state_slots == 2
    with pytest.raises(ValueError, match="no kda layer"):
        DecoderDescription(
            hidden=64, vocab=512, eps=1e-6, power=desc.power, kda_heads=2,
            kda_dim=16, conv=4,
            layers=(LayerDescription("power", "dense"),
                    LayerDescription("kda", "dense")))


@pytest.mark.parametrize("layers,want", [
    (("kda",), (0, 0, False)),            # a store alone: no pool
    (("kda", "mla"), (1, 128, False)),    # the latent (24) to a lane multiple
    (("mla",), (1, 128, False)),
], ids=["kda", "kda+mla", "mla"])
def test_a_pool_is_what_the_layers_page(layers, want):
    """`cache_layout()` says what it means for a description with no paged
    layer: no pool layers and no row, not a ceiling that happens to be 0;
    and `for_description` builds it with no division."""
    from paddle_tpu.nn.decode_blocks import (DecoderDescription,
                                             LayerDescription)

    desc = DecoderDescription(
        hidden=64, vocab=512, eps=1e-6, heads=2, nope_dim=8, pe_dim=8,
        v_dim=8, lora=16, kda_heads=2, kda_dim=16, conv=4,
        layers=tuple(LayerDescription(m, "dense") for m in layers))
    lay = desc.cache_layout()
    assert (lay["pool_layers"], lay["row_width"], lay["values"]) == want
    assert desc.pooled == bool(want[0])
    cache = PagedKVCache.for_description(desc, block_size=BS, num_blocks=4,
                                         dtype=jnp.float32, max_slots=2)
    assert cache.k_blocks.shape == (want[0], 4, BS, want[1])
    assert cache.blocks_for(BS + 1) == (2 if want[0] else 0)
    assert cache.stats()["bytes_per_token"] == (128 * 4 if want[0] else 0)
