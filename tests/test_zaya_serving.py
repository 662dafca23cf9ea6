"""ZAYA1 through the serving stack on the CPU, tiny preset, seeded random
weights, against benchmark/reference/zaya.py (float32 both sides;
tests/conftest.py pins "highest" matmul precision).

The program and the reference order their reductions differently (paged
against full attention, grouped against dense experts, taps gathered from
tails against shifted sums): a logit moves by a few float32 ulps a
reduction through 4 layers.  LOGIT_TOL is 2e-4 of the logits' spread
(measured: under 2e-6); rotary left out, a query head on the wrong K/V
head, a dropped value shift, a lost conv tail or a dropped router state
each move a logit by more than 1e-2 of it."""
import threading

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import PagedGenerationServer
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.models.zaya import Zaya, ZayaConfig
from paddle_tpu.nn.decode import PagedDecoder
from paddle_tpu.sampling import SlotParamStore

from benchmark_harness import bench_paths  # noqa: F401 — sys.path
from reference import zaya as ref

LOGIT_TOL = 2e-4
BS, CHUNK = 8, 16


def arch_of(cfg):
    return {"hidden": cfg.hidden_size, "eps": cfg.rms_norm_eps,
            "layers": cfg.held_layers, "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
            "rotary_dim": cfg.rotary_dim, "theta": float(cfg.rope_theta),
            "top_k": cfg.num_experts_per_tok, "held": cfg.held_experts}


def moved(params, seed=9):
    """What is constant at the start (scalings 1 and 0, tau 0, gamma 0.5,
    the balancing bias 0) moved, so that a program that left one out, or
    weighed by the biased score, would show."""
    g = np.random.default_rng(seed)
    return {k: v + 0.05 * jnp.asarray(g.standard_normal(v.shape), v.dtype)
            if k.rsplit(".", 1)[-1] in ("a_res", "b_res", "a_out", "b_out",
                                        "k_scale", "bias", "gamma")
            else v for k, v in params.items()}


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    cfg = ZayaConfig.tiny()
    model = Zaya(cfg)
    model.eval()
    params = moved(model.functional_state()[0])
    model.load_functional_state(params)
    return cfg, model, params


def prompts_of(cfg, lengths, seed=0):
    g = np.random.default_rng(seed)
    return [g.integers(1, cfg.vocab_size, n, dtype=np.int32)
            for n in lengths]


def teacher_forced_logits(model, params, seqs, n_prompts):
    """The program's logits at every position from each prompt's last to
    the sequence's last but one: the prompts prefilled TOGETHER, a chunk of
    16 tokens of each a dispatch (two or more sequences packed in one
    stream), then decoded together with their own tokens fed back."""
    desc = model.decoder_description()
    rows = len(seqs)
    width = max(-(-len(s) // BS) for s in seqs)
    cache = PagedKVCache.for_description(
        desc, block_size=BS, num_blocks=rows * width + 1,
        dtype=jnp.float32, max_slots=rows)
    dec = PagedDecoder(desc, BS, return_logits=True)
    store = SlotParamStore(rows, desc.vocab)
    out = [[] for _ in seqs]
    fed = [0] * rows
    while any(f < n for f, n in zip(fed, n_prompts)):
        plan, off = [], 0
        for i in range(rows):
            n = min(CHUNK, n_prompts[i] - fed[i])
            if n > 0:
                plan.append((i, fed[i], n, off))
                off += -(-n // 8) * 8
        t_len = 8
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
        _t, _s, kc, vc, state, _c, _routed, lg = dec.packed_prefill(
            params, jnp.asarray(toks), jnp.asarray(seg), jnp.asarray(pos),
            jnp.asarray(cache.table_array(ids, width)), jnp.asarray(sample),
            cache.k_blocks, cache.v_blocks, store.warm_args(rows),
            state=cache.state)
        cache.swap_arrays(kc, vc, state)
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
        _t, _s, kc, vc, state, _c, _routed, lg = dec.step(
            params, jnp.asarray(tok),
            jnp.asarray(np.where(live, at, 0).astype(np.int32)),
            jnp.asarray(live),
            jnp.asarray(cache.table_array(list(range(rows)), width)),
            cache.k_blocks, cache.v_blocks, sp, state=cache.state)
        cache.swap_arrays(kc, vc, state)
        for i in range(rows):
            if live[i]:
                out[i].append(np.asarray(lg[i]))
                at[i] += 1
    return [np.stack(o) for o in out]


def test_prefill_then_decode_through_pool_and_tails_is_the_reference(tiny):
    """Three sequences of unequal length, packed and chunked, then decoded
    side by side: every logit from the prompt's last position on is the
    reference's full forward's."""
    cfg, model, params = tiny
    n_prompts = (5, 23, 37)
    seqs = prompts_of(cfg, (13, 31, 44), seed=1)
    got = teacher_forced_logits(model, params, seqs, n_prompts)
    for s, n, mine in zip(seqs, n_prompts, got):
        want, _found = ref.logits(params, jnp.asarray(s), arch_of(cfg),
                                  jnp.arange(n - 1, len(s) - 1))
        want = np.asarray(want)
        assert np.abs(mine - want).max() <= LOGIT_TOL * want.std()


def serve(model, prompts, new=6, **kw):
    opts = dict(max_slots=4, block_size=BS, num_blocks=64,
                max_prompt_len=48, max_new_tokens=8,
                prefill_chunk_tokens=CHUNK)
    opts.update(kw)
    server = PagedGenerationServer(model, **opts)
    server.start()
    try:
        futs = [server.submit(p, max_new_tokens=new) for p in prompts]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
        return outs, server.stats()
    finally:
        server.stop()


def test_served_tokens_are_the_references_argmax_and_the_stats_read(tiny):
    """Through `PagedGenerationServer`: greedy tokens are the reference's
    argmax; `stats()["experts"]`, `["state"]` and `["kv_cache"]` read for
    this model as they read for Kimi, with the pool's K/V heads and the
    bytes a cached token takes."""
    cfg, model, params = tiny
    prompts = prompts_of(cfg, (5, 23, 37, 9, 16, 30))
    outs, stats = serve(model, prompts)
    for p, o in zip(prompts, outs):
        assert (o[:len(p)] == p).all() and len(o) == len(p) + 6
        lg, _found = ref.logits(params, jnp.asarray(o), arch_of(cfg),
                                jnp.arange(len(p) - 1, len(o) - 1))
        lg = np.asarray(lg)
        deficit = lg.max(-1) - lg[np.arange(6), o[len(p):]]
        assert deficit.max() <= LOGIT_TOL * lg.std()
    ex, state, kv = stats["experts"], stats["state"], stats["kv_cache"]
    # 4 layers, top 1 of 4 experts, all held: one held pick a token-layer
    assert ex["tokens"] == 4 * (sum(map(len, prompts)) + 6 * 5)
    assert ex["held_picks"] == ex["tokens"]
    assert 0 < ex["experts_touched"] <= 4 * 4 * len(ex["dispatches"])
    assert ex["max_load"] >= ex["mean_load"] > 0
    assert state["slots"] == 4 and 1 <= state["peak_used_slots"] <= 4
    assert kv["state"]["used_slots"] == 0               # all given back
    assert kv["kv_heads"] == cfg.num_key_value_heads == 2
    # K and V rows of 2 heads of 16 in float32, over 4 layers
    assert kv["bytes_per_token"] == 2 * 4 * 2 * 16 * 4


def test_the_engine_tells_its_routing_and_leaves_its_tails(tiny):
    """`submit(on_routing=)`: the routers' choice at every position fed,
    equal to the reference's; and the store of the stopped server holds,
    in the slot the request held, the reference's conv tails after those
    tokens."""
    cfg, model, params = tiny
    prompts = prompts_of(cfg, (37, 9, 21), seed=4)
    server = PagedGenerationServer(
        model, max_slots=4, block_size=BS, num_blocks=64, max_prompt_len=48,
        max_new_tokens=8, prefill_chunk_tokens=CHUNK)
    told = [{} for _ in prompts]
    slots = [0] * len(prompts)

    def note(i):
        def on_routing(position, picks, slot):
            for j in range(picks.shape[1]):
                told[i][position + j] = picks[:, j]
            slots[i] = slot
        return on_routing

    server.start()
    try:
        futs = [server.submit(p, max_new_tokens=6, on_routing=note(i))
                for i, p in enumerate(prompts)]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
    finally:
        server.stop()
    assert sorted(slots) == [1, 2, 3]
    for o, mine, slot in zip(outs, told, slots):
        n = len(o)
        assert sorted(mine) == list(range(n - 1))
        picks = np.stack([mine[p] for p in range(n - 1)], axis=1)
        assert picks.shape == (4, n - 1, 1)
        _x, found = ref.hidden(
            params, jnp.asarray(o), arch_of(cfg),
            served=jnp.asarray(np.pad(picks, ((0, 0), (0, 1), (0, 0)))),
            tie=1e-6, tail_len=n - 1)
        assert float(np.asarray(found["gap"])[:n - 1].max()) <= 1e-6
        assert not np.asarray(found["outside"])[:n - 1].any()
        for name, want in found["tails"].items():
            got = np.asarray(server.cache.state[name][:, slot])
            np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)


def test_a_sequence_whose_blocks_are_taken_is_prefilled_again(tiny):
    """Preempt a decoding request between rounds: its blocks and its slot
    of the store go, the request goes back to the queue with its tokens so
    far and is prefilled again from its first token (its tails start from
    zero); what it returns is what an undisturbed run returns."""
    cfg, model, _params = tiny
    prompt = prompts_of(cfg, (29,), seed=7)[0]
    (want,), _ = serve(model, [prompt], new=8)
    server = PagedGenerationServer(
        model, max_slots=2, block_size=BS, num_blocks=32, max_prompt_len=48,
        max_new_tokens=8, prefill_chunk_tokens=CHUNK)
    seen = threading.Semaphore(0)
    server.start()
    try:
        fut = server.submit(prompt, max_new_tokens=8,
                            on_token=lambda *_a: seen.release())
        for _ in range(3):
            assert seen.acquire(timeout=120)

        def evict():
            for i, slot in enumerate(server._slots):
                if slot is not None:
                    used = server.cache.stats()["state"]["used_slots"]
                    req = server._preempt_slot_locked(i)
                    server._queue.insert(0, req)
                    return used, server.cache.stats()["state"]["used_slots"]
            return None

        gone = server.run_host_op(evict, timeout=120)
        got = np.asarray(fut.result(timeout=300))
        stats = server.stats()
    finally:
        server.stop()
    assert gone == (1, 0)              # the slot went with the blocks
    assert stats["frontdoor"]["preemptions"] == 1
    assert stats["frontdoor"]["resumes"] == 1
    assert (got == want).all()


REFUSED = [
    ("enable_prefix_cache", True), ("speculation", True),
    ("kv_dtype", "int8"), ("quantization", "w8a16"),
    ("weight_quant", "int8"), ("unified_round", True),
    ("async_rounds", True), ("steps_per_dispatch", 2),
    ("sharding", True), ("kv_tier", True), ("tier_prefetch", True),
]


@pytest.mark.parametrize("name,value", REFUSED,
                         ids=[n for n, _v in REFUSED])
def test_options_without_meaning_beside_conv_tails_raise(tiny, name, value):
    _cfg, model, _params = tiny
    with pytest.raises(ValueError, match=name):
        PagedGenerationServer(model, max_slots=2, block_size=BS,
                              num_blocks=16, max_prompt_len=16,
                              max_new_tokens=4, prefill_chunk_tokens=CHUNK,
                              **{name: value})


def test_the_description_and_its_cache(tiny):
    """A third layout of `decode_blocks`: "cca" mixers with "mlp_routed"
    FFNs, a K pool and a V pool by the K/V heads, tails alone in the
    store, no chunk alignment; the decoder wants both pools."""
    from paddle_tpu.nn.decode_blocks import (DecoderDescription,
                                             LayerDescription)

    cfg, model, _params = tiny
    desc = model.decoder_description()
    assert [(l.mixer, l.ffn) for l in desc.layers] \
        == [("cca", "mlp_routed")] * 4
    assert desc.pack_multiple == 1 and desc.tied_head \
        and desc.residual_scaling
    lay = desc.cache_layout()
    assert (lay["pool_layers"], lay["row_width"], lay["values"]) \
        == (4, 32, True)
    assert {k: v[:2] for k, v in lay["store"].items()} == {
        "conv0": (4, (1, 96)), "conv1": (4, (1, 96)), "v_prev": (4, (1, 16))}
    cache = PagedKVCache.for_description(desc, block_size=BS, num_blocks=8,
                                         dtype=jnp.float32, max_slots=3)
    assert cache.k_blocks.shape == cache.v_blocks.shape == (4, 8, BS, 32)
    assert cache.num_heads == 2 and "S" not in cache.state
    assert cache.state["conv0"].shape == (4, 4, 1, 96)
    assert cache.table_array([None], 2).shape == (1, 3)   # [slot | blocks]
    dec = PagedDecoder(desc, BS)
    with pytest.raises(ValueError, match="K and V rows"):
        dec.step(None, None, None, None, None, object(), None, None)
    with pytest.raises(ValueError, match="no mla layer"):
        DecoderDescription(
            hidden=64, vocab=512, eps=1e-5, cca=desc.cca,
            layers=(LayerDescription("cca", "dense"),
                    LayerDescription("mla", "dense")))


def test_shares_of_the_expert_layer_add_up_to_the_whole(tiny):
    """One chip's share of an expert-parallel layer under the softmax
    top-1 router: the layer told it holds experts [0, 2) and the layer
    told [2, 4) add up to the layer told [0, 4), and each is the
    reference's under the same `held`.  The router sees all 4 experts in
    every case."""
    from paddle_tpu.nn.decode_blocks import _block_fns
    import dataclasses

    cfg, model, params = tiny
    desc = model.decoder_description()
    g = np.random.default_rng(6)
    x = jnp.asarray(g.standard_normal((24, cfg.hidden_size)), jnp.float32)
    valid = jnp.ones((24,), bool)
    pre = "layers.1.moe."
    layer = {k[len("layers.1."):]: v for k, v in params.items()
             if k.startswith("layers.1.")}
    r_before = jnp.asarray(g.standard_normal((24, cfg.router_hidden_size)),
                           jnp.float32)
    got, want = {}, {}
    for first, count in ((0, 2), (2, 2), (0, 4)):
        share = dataclasses.replace(desc, held_first=first, held=count)
        mine = dict(params)
        for w in ("gate", "up", "down"):
            mine[pre + "experts." + w] = \
                params[pre + "experts." + w][first:first + count]
        y, counts, picks, r = _block_fns(share).ffn(mine, 1, x, valid,
                                                    r_before)
        got[first, count] = np.asarray(y)
        assert int(counts[0]) == 24
        assert int(counts[1]) == int(((np.asarray(picks) >= first) & (
            np.asarray(picks) < first + count)).sum())
        theirs = dict(layer)
        for w in ("gate", "up", "down"):
            theirs["moe.experts." + w] = mine[pre + "experts." + w]
        y_ref, r_ref, _found = ref.expert_ffn(
            theirs, "moe.", x, r_before,
            dict(arch_of(cfg), held=(first, count)))
        want[first, count] = np.asarray(y_ref)
        np.testing.assert_allclose(np.asarray(r), np.asarray(r_ref),
                                   atol=1e-5)
    for key in got:
        np.testing.assert_allclose(got[key], want[key], atol=1e-5)
    np.testing.assert_allclose(got[0, 2] + got[2, 2], got[0, 4], atol=1e-5)
    assert np.abs(got[0, 2]).max() > 0 and np.abs(got[2, 2]).max() > 0


def test_the_reference_takes_a_programs_choice_only_at_a_tie():
    """`expert_ffn(served=, tie=)` under top 1: where the two largest
    selection scores lie within `tie`, the program's pick of the second is
    taken; a pick clearly under the largest is not, and the reference's
    own choice stands."""
    n, d = 3, 4
    p = {"router.down.weight": jnp.zeros((d, 2)), "router.gamma":
         jnp.zeros((1,)), "router.norm.weight": jnp.ones((2,)),
         "router.w1.weight": jnp.zeros((2, 2)),
         "router.w2.weight": jnp.zeros((2, 2)),
         "router.w3.weight": jnp.zeros((2, 3)),
         # uniform scores 1/3; the bias alone orders the experts
         "router.bias": jnp.asarray([0.30, 0.2995, 0.1]),
         "experts.gate": jnp.ones((3, d, d)), "experts.up": jnp.ones((3, d, d)),
         "experts.down": jnp.ones((3, d, d))}
    a = {"eps": 1e-5, "held": (0, 3)}
    x = jnp.ones((n, d))
    served = jnp.asarray([[0], [1], [2]])
    _y, _r, found = ref.expert_ffn(p, "", x, None, a, served, tie=1e-3)
    assert np.asarray(found["swapped"]).tolist() == [False, True, False]
    assert np.asarray(found["outside"]).tolist() == [False, False, True]
    np.testing.assert_allclose(np.asarray(found["gap"]), [0, 5e-4, 0.2],
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(found["spread"]), 5e-4, atol=1e-6)


def test_balancing_the_routers_spreads_random_tokens_over_the_experts():
    """The family's bias recipe (`families/zaya.balance_routers`) moves
    only the balancing biases (float32, used for the choice alone) and
    leaves every expert near its share."""
    from families import zaya as family

    paddle.seed(5)
    model = Zaya(ZayaConfig.tiny(num_experts=8))
    model.eval()
    before = dict(model.functional_state()[0])
    found = family.balance_routers(model, tokens=1024)
    after = model.functional_state()[0]
    assert len(found["before"]) == len(found["after"]) == 4
    assert max(found["after"]) < min(found["before"]) \
        and max(found["after"]) <= 1.15
    changed = {k for k in before
               if not np.array_equal(np.asarray(before[k]),
                                     np.asarray(after[k]))}
    assert changed == {f"layers.{i}.moe.router.bias" for i in range(4)}
    assert all(after[k].dtype == jnp.float32 for k in changed)
