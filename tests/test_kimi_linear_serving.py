"""Kimi-Linear through the serving stack on the CPU, tiny preset, seeded
random weights, against benchmark/reference/kimi_linear.py (float32 both
sides; tests/conftest.py pins "highest" matmul precision).

The program and the reference order their reductions differently (chunked
against token-by-token KDA, absorbed against unabsorbed MLA, grouped
against dense experts): a logit moves by a few float32 ulps a reduction
through 4 layers.  LOGIT_TOL is 2e-4 of the logits' spread (measured:
under 2e-5); a bf16 state, a dropped expert, a skipped decay or a lost conv
tail each move a logit by more than 1e-2 of it."""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import PagedGenerationServer
from paddle_tpu.inference.kv_cache import BlockPoolExhausted, PagedKVCache
from paddle_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig
from paddle_tpu.nn.decode import PagedDecoder
from paddle_tpu.sampling import SlotParamStore

from benchmark_harness import bench_paths  # noqa: F401 — sys.path
from reference import kimi_linear as ref

LOGIT_TOL = 2e-4
BS, CHUNK = 8, 16       # blocks of 8 tokens, prefill chunks of 16 (2 x KDA's)


def arch_of(cfg):
    return {"hidden": cfg.hidden_size, "eps": cfg.rms_norm_eps,
            "kinds": tuple(m for m, _f in cfg.layer_kinds()),
            "dense_layers": cfg.first_k_dense_replace,
            "heads": cfg.num_attention_heads, "kda_heads": cfg.kda_num_heads,
            "kda_dim": cfg.kda_head_dim, "conv": cfg.short_conv_kernel_size,
            "nope": cfg.qk_nope_head_dim, "pe": cfg.qk_rope_head_dim,
            "v_dim": cfg.v_head_dim, "lora": cfg.kv_lora_rank,
            "top_k": cfg.num_experts_per_token,
            "renormalize": cfg.moe_renormalize,
            "scaling": cfg.routed_scaling_factor, "held": cfg.held_experts}


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    cfg = KimiLinearConfig.tiny(held_experts=(0, 4))
    model = KimiLinear(cfg)
    model.eval()
    params, _ = model.functional_state()
    # the router's correction bias starts at 0: move it, so that a program
    # that weighed by the biased score would show
    g = np.random.default_rng(9)
    params = {k: v + 0.05 * jnp.asarray(g.standard_normal(v.shape),
                                        jnp.float32)
              if k.endswith("router.bias") else v for k, v in params.items()}
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
        _t, _s, kc, state, _c, _routed, lg = dec.packed_prefill(
            params, jnp.asarray(toks), jnp.asarray(seg), jnp.asarray(pos),
            jnp.asarray(cache.table_array(ids, width)), jnp.asarray(sample),
            cache.k_blocks, None, store.warm_args(rows), state=cache.state)
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
            jnp.asarray(cache.table_array(list(range(rows)), width)),
            cache.k_blocks, None, sp, state=cache.state)
        cache.swap_arrays(kc, None, state)
        for i in range(rows):
            if live[i]:
                out[i].append(np.asarray(lg[i]))
                at[i] += 1
    return [np.stack(o) for o in out]


def test_prefill_then_decode_through_both_caches_is_the_reference(tiny):
    """Prompts of 37 and 21 tokens (3 and 2 chunks, 5 and 3 blocks), packed
    two to a stream, then 6 decode steps: every logit of every position
    against the reference's full forward of the whole sequence."""
    cfg, model, params = tiny
    n_prompts = [37, 21, 5]
    seqs = prompts_of(cfg, [n + 6 for n in n_prompts], seed=1)
    got = teacher_forced_logits(model, params, seqs, n_prompts)
    for s, n, mine in zip(seqs, n_prompts, got):
        want, _swapped = ref.logits(params, jnp.asarray(s), arch_of(cfg),
                                jnp.arange(n - 1, len(s) - 1))
        want = np.asarray(want)
        assert mine.shape == want.shape
        assert np.abs(mine - want).max() <= LOGIT_TOL * want.std(), \
            np.abs(mine - want).max() / want.std()


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


def test_served_tokens_are_the_references_argmax_and_the_counters_count(tiny):
    cfg, model, params = tiny
    prompts = prompts_of(cfg, (5, 23, 37, 9, 16, 30))
    outs, stats = serve(model, prompts)
    for p, o in zip(prompts, outs):
        assert (o[:len(p)] == p).all() and len(o) == len(p) + 6
        lg, _swapped = ref.logits(params, jnp.asarray(o), arch_of(cfg),
                              jnp.arange(len(p) - 1, len(o) - 1))
        lg = np.asarray(lg)
        deficit = lg.max(-1) - lg[np.arange(6), o[len(p):]]
        assert deficit.max() <= LOGIT_TOL * lg.std()
    ex, state = stats["experts"], stats["state"]
    # 3 expert layers; top 2 of 8 with 4 held: about one held pick a token
    assert ex["tokens"] == 3 * (sum(map(len, prompts)) + 6 * 5)
    assert 0.6 * ex["tokens"] < ex["held_picks"] < 1.4 * ex["tokens"]
    assert 0 < ex["experts_touched"] <= 4 * 3 * len(ex["dispatches"])
    assert ex["max_load"] >= ex["mean_load"] > 0
    assert state["slots"] == 4 and 1 <= state["peak_used_slots"] <= 4
    assert stats["kv_cache"]["state"]["used_slots"] == 0   # all given back


def served_and_recorded(model, prompts, new, **kw):
    """`serve`, with every request's `on_routing` calls kept: ([tokens],
    [{position: picks [expert layers, k]}], [state slot], the stopped
    server)."""
    opts = dict(max_slots=4, block_size=BS, num_blocks=64,
                max_prompt_len=48, max_new_tokens=8,
                prefill_chunk_tokens=CHUNK)
    opts.update(kw)
    server = PagedGenerationServer(model, **opts)
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
        futs = [server.submit(p, max_new_tokens=new, on_routing=note(i))
                for i, p in enumerate(prompts)]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
    finally:
        server.stop()
    return outs, told, slots, server


def test_the_engine_tells_its_routing_and_leaves_each_last_state(tiny):
    """`submit(on_routing=)`: the routers' choice at every position the
    engine fed (all but the last token), equal to the reference's own;
    and the store of the stopped server holds, in the slot the request
    held, the reference's state after those tokens."""
    cfg, model, params = tiny
    prompts = prompts_of(cfg, (37, 9, 21), seed=4)
    outs, told, slots, server = served_and_recorded(model, prompts, new=6)
    assert sorted(slots) == [1, 2, 3]
    store = np.asarray(server.cache.state["S"])
    for o, mine, slot in zip(outs, told, slots):
        n = len(o)
        assert sorted(mine) == list(range(n - 1))
        picks = np.stack([mine[p] for p in range(n - 1)], axis=1)
        assert picks.shape == (3, n - 1, cfg.num_experts_per_token)
        _x, found = ref.hidden(
            params, jnp.asarray(o), arch_of(cfg),
            served=jnp.asarray(np.pad(picks, ((0, 0), (0, 1), (0, 0)))),
            tie=1e-6, state_len=n - 1)
        assert float(np.asarray(found["gap"])[:n - 1].max()) <= 1e-6
        assert not np.asarray(found["outside"])[:n - 1].any()
        want = np.asarray(found["states"])              # [3, H, D, D]
        err = np.linalg.norm(store[:, slot] - want) / np.linalg.norm(want)
        assert err <= 1e-5, err


def test_expert_counters_are_sums_and_a_bounded_ring(tiny, monkeypatch):
    """`stats()["experts"]`: the totals count every dispatch since
    `reset_stats()`; `dispatches` keeps only the newest EXPERT_RING."""
    from paddle_tpu.inference import serving

    monkeypatch.setattr(serving, "EXPERT_RING", 3)
    cfg, model, _params = tiny
    prompts = prompts_of(cfg, (5, 23, 37), seed=2)
    _outs, stats = serve(model, prompts)
    ex = stats["experts"]
    assert len(ex["dispatches"]) == 3
    assert ex["tokens"] == 3 * (sum(map(len, prompts)) + 3 * 5)
    assert ex["tokens"] > sum(e[1] for e in ex["dispatches"])
    assert ex["max_load"] >= max(e[4] for e in ex["dispatches"])


def test_a_sequence_whose_blocks_are_taken_is_prefilled_again(tiny):
    """Preempt a decoding request between rounds: its blocks and its state
    slot go, the request goes back to the queue with its tokens so far and
    is prefilled again from its first token; what it returns is what an
    undisturbed run returns."""
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

        moved = server.run_host_op(evict, timeout=120)
        got = np.asarray(fut.result(timeout=300))
        stats = server.stats()
    finally:
        server.stop()
    assert moved == (1, 0)              # the state slot went with the blocks
    assert stats["frontdoor"]["preemptions"] == 1
    assert stats["frontdoor"]["resumes"] == 1
    assert (got == want).all()


def test_state_slots_live_and_die_with_their_sequences(tiny):
    cfg, model, _params = tiny
    desc = model.decoder_description()
    cache = PagedKVCache.for_description(
        desc, block_size=BS, num_blocks=16, dtype=jnp.float32, max_slots=2)
    assert cache.state["S"].shape[:2] == (3, 3)         # 3 KDA layers, 2 + trash
    assert cache.v_blocks is None                       # latents have no V
    assert cache.k_blocks.shape == (1, 16, BS, 128)     # 32 + 8, lane-padded
    cache.allocate("a", 5)
    cache.allocate("b", 9)
    assert {cache.state_slot("a"), cache.state_slot("b")} == {1, 2}
    assert cache.free_state_slots == 0
    table = cache.table_array(["b", None, "a"], 3)
    assert table.shape == (3, 4)                        # [slot | blocks]
    assert list(table[:, 0]) == [cache.state_slot("b"), 0,
                                 cache.state_slot("a")]
    assert (table[1] == 0).all()                        # trash slot, trash block
    with pytest.raises(BlockPoolExhausted, match="recurrent-state slot"):
        cache.allocate("c", 1)
    assert not cache.has_seq("c") and cache.free_block_count == 15 - 3
    cache.ensure("a", 20)                               # growing keeps the slot
    slot_a = cache.state_slot("a")
    cache.free("a")
    assert cache.state_slot("a") == 0 and cache.free_state_slots == 1
    cache.allocate("c", 1)
    assert cache.state_slot("c") == slot_a              # the slot, reused
    st = cache.stats()["state"]
    # what a slot holds over the 3 KDA layers: float32 S and conv tails
    entries = {name: a.nbytes // a.shape[1]
               for name, a in cache.state.items()}
    assert entries == {"S": 12288, "conv": 6912}
    assert st == {"slots": 2, "used_slots": 2, "peak_used_slots": 2,
                  "bytes_per_slot": sum(entries.values()),
                  "entries": entries}


def test_a_reused_slot_starts_from_zero_state(tiny):
    """Two requests one after the other through ONE slot: the second finds
    the first one's state and conv tail in its slot and must not see them
    (a sequence that starts at position 0 starts from zero)."""
    cfg, model, _params = tiny
    a, b = prompts_of(cfg, (33, 19), seed=11)
    (alone,), _ = serve(model, [b], max_slots=1)
    (_first, after), stats = serve(model, [a, b], max_slots=1)
    assert (after == alone).all()
    assert (stats["state"]["slots"], stats["state"]["peak_used_slots"]) \
        == (1, 1)
    assert stats["state"]["bytes_per_slot"] \
        == sum(stats["state"]["entries"].values()) == 19200


REFUSED = [
    ("enable_prefix_cache", True), ("speculation", True),
    ("kv_dtype", "int8"), ("quantization", "w8a16"),
    ("weight_quant", "int8"), ("unified_round", True),
    ("async_rounds", True), ("steps_per_dispatch", 2),
    ("sharding", True), ("kv_tier", True), ("tier_prefetch", True),
]


@pytest.mark.parametrize("name,value", REFUSED,
                         ids=[n for n, _v in REFUSED])
def test_options_without_meaning_beside_recurrent_state_raise(tiny, name,
                                                              value):
    _cfg, model, _params = tiny
    with pytest.raises(ValueError, match=name):
        PagedGenerationServer(model, max_slots=2, block_size=BS,
                              num_blocks=16, max_prompt_len=16,
                              max_new_tokens=4, prefill_chunk_tokens=CHUNK,
                              **{name: value})


@pytest.mark.parametrize("name,value", [
    ("kv_dtype", "int8"), ("shardings", object()),
    ("collective_quant", object()), ("sp_attention", "ring")])
def test_decoder_options_without_meaning_raise(tiny, name, value):
    _cfg, model, _params = tiny
    with pytest.raises(ValueError, match=name):
        PagedDecoder(model.decoder_description(), BS, **{name: value})


@pytest.mark.parametrize("program", ["prefill", "packed_verify",
                                     "unified_round", "multistep"])
def test_gpt2_only_programs_name_themselves(tiny, program):
    _cfg, model, _params = tiny
    dec = PagedDecoder(model.decoder_description(), BS)
    with pytest.raises(ValueError, match=program):
        if program == "multistep":
            dec.multistep(2)()
        else:
            getattr(dec, program)(*[None] * {"prefill": 7,
                                             "packed_verify": 10,
                                             "unified_round": 17}[program])


def test_a_decoder_takes_gpt2s_tuple_or_a_description(tiny):
    """Two layouts, told apart by what is handed over: GPT-2's six-field
    tuple keeps `nn.decode`'s builders, a `DecoderDescription` gets
    `decode_blocks`'s; the description knows latent and recurrent mixers,
    dense and expert FFNs, and nothing else."""
    from paddle_tpu.nn.decode_blocks import LayerDescription

    _cfg, model, _params = tiny
    spec = (2, 4, 32, 128, 1e-5, True)
    gpt2 = PagedDecoder(spec, 16)
    assert gpt2.spec == spec and gpt2.description is None
    desc = model.decoder_description()
    dec = PagedDecoder(desc, BS)
    assert dec.description is desc and dec.spec is desc
    assert [(l.mixer, l.ffn) for l in desc.layers] == [
        ("kda", "dense"), ("kda", "experts"), ("mla", "experts"),
        ("kda", "experts")]
    assert desc.count("kda") == 3 and desc.pack_multiple == 8
    for bad in (("mha", "dense"), ("kda", "gelu_mlp")):
        with pytest.raises(ValueError, match="unknown layer"):
            LayerDescription(*bad)
    with pytest.raises(ValueError, match="vc=None"):
        dec.step(None, None, None, None, None, object(), object(), None)


def test_the_reference_takes_a_programs_choice_only_between_tied_experts():
    """`expert_ffn(served=, tie=)`: where the 2nd and 3rd of 6 selection
    scores (top 2) lie within `tie`, the program's pick of either is
    taken; a pick of an expert clearly under the 2nd, or the loss of one
    clearly over the 3rd, is not, and the reference's own choice stands."""
    n, d, k = 6, 4, 2
    a = {"top_k": k, "renormalize": True, "scaling": 1.0, "held": (0, n)}
    # scores through an identity-like router: logits chosen directly
    logit = jnp.asarray([[3.0, 1.0, 0.999, -1.0, -2.0, -3.0]] * 4)
    x = jnp.eye(d)[:1].repeat(4, 0)                      # rows e_0
    router = jnp.zeros((d, n)).at[0].set(logit[0])
    g = np.random.default_rng(0)
    w = lambda *s: jnp.asarray(g.standard_normal(s), jnp.float32)  # noqa
    p = {"m.router.weight": router, "m.router.bias": jnp.zeros((n,)),
         "m.experts.gate": w(n, d, 8), "m.experts.up": w(n, d, 8),
         "m.experts.down": w(n, 8, d), "m.shared.gate_proj.weight": w(d, 8),
         "m.shared.up_proj.weight": w(d, 8),
         "m.shared.down_proj.weight": w(8, d)}
    served = jnp.asarray([[0, 1],      # the reference's own choice
                          [0, 2],      # the tied third in place of the second
                          [0, 3],      # an expert far under the second: no
                          [1, 2]])     # the clear first is missing: no
    y, found = ref.expert_ffn(p, "m.", x, a, served, tie=1e-3)
    assert np.asarray(found["tied"]).all()
    assert list(np.asarray(found["swapped"])) == [False, True, False, False]
    assert list(np.asarray(found["outside"])) == [False, False, True, True]
    # how far each choice lies from the reference's own: nothing; the 2nd
    # score over the 3rd; the 2nd over the 4th; the 1st over the 3rd
    s = np.asarray(jax.nn.sigmoid(logit[0]))
    assert np.allclose(np.asarray(found["gap"]),
                       [0.0, s[1] - s[2], s[1] - s[3], s[0] - s[2]],
                       atol=1e-6)
    assert np.allclose(np.asarray(found["spread"]), s[0] - s[1], atol=1e-6)
    own, found0 = ref.expert_ffn(p, "m.", x, a)
    assert not np.asarray(found0["tied"]).any()
    assert np.allclose(y[0], own[0]) and np.allclose(y[2], own[2]) \
        and np.allclose(y[3], own[3])
    assert not np.allclose(y[1], own[1])
    y0, found0 = ref.expert_ffn(p, "m.", x, a, served, tie=0.0)
    assert not np.asarray(found0["swapped"]).any() and np.allclose(y0, own)


def test_balancing_the_routers_spreads_random_tokens_over_the_experts():
    """The family's weight recipe (`families/kimi_linear.balance_routers`)
    moves only the correction biases (float32, used for the choice alone)
    and leaves every expert near its share."""
    from families import kimi_linear as family

    paddle.seed(5)
    model = KimiLinear(KimiLinearConfig.tiny(
        held_experts=(0, 16), num_experts=32, num_experts_per_token=4))
    model.eval()
    before = dict(model.functional_state()[0])
    found = family.balance_routers(model, tokens=1024)
    after = model.functional_state()[0]
    assert len(found["before"]) == len(found["after"]) == 3
    assert max(found["after"]) < min(found["before"]) \
        and max(found["after"]) <= 1.15
    moved = {k for k in before
             if not np.array_equal(np.asarray(before[k]),
                                   np.asarray(after[k]))}
    assert moved == {f"layers.{i}.moe.router.bias" for i in (1, 2, 3)}
    assert all(after[k].dtype == jnp.float32 for k in moved)
