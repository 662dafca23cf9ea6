"""Kanana-2 through the serving stack on the CPU, tiny preset, seeded random
weights, against benchmark/reference/kanana2.py (float32 both sides;
tests/conftest.py pins "highest" matmul precision).

The program keeps a sequence as its rows of a latent pool, attends in the
absorbed form (the key half of W_kvb folded into the query) and routes
through grouped experts; the reference expands every head's keys and values
and loops densely over the experts.  Their reductions are ordered
differently, so a logit moves by a few float32 ulps a reduction through 4
layers.  LOGIT_TOL is 2e-4 of the logits' spread and LATENT_TOL 1e-5 of
the latent rows' norm (measured: 1.3e-6 and 1.3e-7); rotary left out, or
paired by halves where the layout pairs neighbours, moves them by 2.4e-2
and 7.8e-2 or more."""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import PagedGenerationServer
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.models.kanana2 import Kanana2, Kanana2Config
from paddle_tpu.nn.decode import PagedDecoder
from paddle_tpu.ops.rotary import apply_rotary
from paddle_tpu.sampling import SlotParamStore

from benchmark_harness import bench_paths  # noqa: F401 — sys.path
from reference import kanana2 as ref

LOGIT_TOL, LATENT_TOL = 2e-4, 1e-5
BS, CHUNK = 8, 16       # blocks of 8 tokens, prefill chunks of 16 (2 tiles)


def arch_of(cfg):
    return ref.arch({
        "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_norm_eps,
        "layers": cfg.held_layers,
        "first_k_dense_replace": cfg.first_k_dense_replace,
        "num_attention_heads": cfg.num_attention_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "rope_theta": cfg.rope_theta, "rope_interleave": cfg.rope_interleave,
        "rope_scaling": cfg.rope_scaling,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "n_routed_experts": cfg.n_routed_experts})


@pytest.fixture(scope="module")
def tiny():
    """The tiny preset with what is constant at the start (every norm's
    weight 1, the routers' correction bias 0) moved, so that a program
    that left a norm's weight out, or weighed by the biased score, would
    show; the reference's query blocks cut to the test's lengths."""
    paddle.seed(3)
    cfg = Kanana2Config.tiny()
    model = Kanana2(cfg)
    model.eval()
    g = np.random.default_rng(9)
    params = {k: v + 0.05 * jnp.asarray(g.standard_normal(v.shape), v.dtype)
              if "norm" in k or k.endswith("router.bias") else v
              for k, v in model.functional_state()[0].items()}
    model.load_functional_state(params)
    was = dict(ref.ASSUMED)
    ref.ASSUMED.update(query_block=8)
    yield cfg, model, params
    ref.ASSUMED.update(was)


def prompts_of(cfg, lengths, seed=0):
    g = np.random.default_rng(seed)
    return [g.integers(1, cfg.vocab_size, n, dtype=np.int32)
            for n in lengths]


def teacher_forced(desc, params, seqs, n_prompts):
    """The programs' logits at every position from each prompt's last to
    the sequence's last but one, and the cache they leave: the prompts
    prefilled TOGETHER, a chunk of 16 tokens of each a dispatch (two or
    more sequences packed in one stream), then decoded together with their
    own tokens fed back."""
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
        assert state == {}
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
    return [np.stack(o) for o in out], cache


def latent_rows(cache, seq_id, n, lora_pe):
    """Layer 0's pool rows of a sequence's first n positions."""
    blocks = np.asarray(cache.block_table(seq_id))
    t = np.arange(n)
    return np.asarray(cache.k_blocks[0])[blocks[t // BS], t % BS, :lora_pe]


def worst(cfg, desc, params, n_prompts=(37, 21, 5), new=6):
    """Prompts packed together, then decoded: the largest logit error over
    the logits' spread, and the largest latent-row error of layer 0 over
    the rows' norm, against the reference's full forward."""
    seqs = prompts_of(cfg, [n + new for n in n_prompts], seed=1)
    got, cache = teacher_forced(desc, params, seqs, list(n_prompts))
    logit_err = latent_err = 0.0
    for i, (s, n, mine) in enumerate(zip(seqs, n_prompts, got)):
        want, found = ref.logits(params, jnp.asarray(s), arch_of(cfg),
                                 jnp.arange(n - 1, len(s) - 1))
        want = np.asarray(want)
        assert mine.shape == want.shape
        logit_err = max(logit_err, np.abs(mine - want).max() / want.std())
        lat = np.asarray(found["latent"])[:len(s) - 1]
        rows = latent_rows(cache, i, len(s) - 1, lat.shape[1])
        latent_err = max(latent_err, np.linalg.norm(rows - lat)
                         / np.linalg.norm(lat))
    return logit_err, latent_err


def test_prefill_then_decode_through_the_latent_pool_is_the_reference(tiny):
    """Prompts of 37, 21 and 5 tokens (3, 2 and 1 dispatches; 5, 3 and 1
    blocks), packed together, then 6 decode steps: every logit of every
    position, and every latent row layer 0 left in the pool, against the
    reference's full forward of the whole sequence."""
    cfg, model, params = tiny
    logit_err, latent_err = worst(cfg, model.decoder_description(), params)
    assert logit_err <= LOGIT_TOL, logit_err
    assert latent_err <= LATENT_TOL, latent_err


@pytest.mark.parametrize("fault", [{"mla_theta": None},
                                   {"mla_interleaved": False}],
                         ids=["no_rotary", "halves_pairing"])
def test_the_tolerance_bites_on_a_rotary_fault(tiny, fault):
    """The same comparison with the program's rotary left out, or paired
    by halves on the interleaved layout: both the logits and the latent
    rows miss by far more than their tolerances."""
    cfg, model, params = tiny
    desc = dataclasses.replace(model.decoder_description(), **fault)
    logit_err, latent_err = worst(cfg, desc, params)
    assert logit_err > 50 * LOGIT_TOL, logit_err
    assert latent_err > 1e3 * LATENT_TOL, latent_err


def turned(x, pos, theta, pairs):
    """x [N, H, D] turned as complex numbers: pair i, as the channel
    indices `pairs[i]` (real, imaginary), times e^(i pos theta^(-2i/D))."""
    d = x.shape[-1]
    re, im = pairs
    z = x[..., re] + 1j * x[..., im]
    freq = theta ** (-np.arange(d // 2) * 2.0 / d)
    z = z * np.exp(1j * pos[:, None, None] * freq)
    out = np.empty_like(x)
    out[..., re], out[..., im] = z.real, z.imag
    return out


@pytest.mark.parametrize("interleaved", [True, False],
                         ids=["interleaved", "halves"])
def test_apply_rotary_is_a_complex_rotation_of_its_pairs(interleaved):
    """`apply_rotary(..., interleaved=True)` turns channels (2i, 2i+1) as
    one complex number; the default form, unchanged, turns (i, i + D/2)."""
    g = np.random.default_rng(2)
    x = g.standard_normal((5, 3, 8)).astype(np.float32)
    pos = np.array([0, 1, 7, 300, 31999])
    pairs = ((np.arange(0, 8, 2), np.arange(1, 8, 2)) if interleaved
             else (np.arange(4), np.arange(4, 8)))
    got = apply_rotary(jnp.asarray(x), jnp.asarray(pos), 8, 1e6,
                       interleaved=interleaved)
    want = turned(x.astype(np.float64), pos, 1e6, pairs)
    # the angle is a float32 product: at position 31,999 it is off by up
    # to 31,999 x 2^-24 rad (measured: 2e-4 on a channel); a wrong pairing
    # is off by the channels themselves
    assert np.abs(np.asarray(got) - want).max() < 2e-3
    if not interleaved:    # the default keyword is the form ZAYA and
        assert (np.asarray(apply_rotary(jnp.asarray(x), jnp.asarray(pos),
                                        8, 1e6)) == np.asarray(got)).all()


def test_kimi_linear_description_still_takes_no_position():
    """Kimi-Linear's MLA layers take NO position (`mla_use_nope`): its
    description leaves `mla_theta` unset, so its programs rotate nothing."""
    from paddle_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig

    desc = KimiLinear(KimiLinearConfig.tiny()).decoder_description()
    assert desc.count("mla") and desc.mla_theta is None
    assert not desc.mla_interleaved


@pytest.mark.parametrize("shared", [1, 2])
def test_the_shared_experts_are_one_swiglu_as_wide_as_their_sum(shared):
    """`_MoE` with one shared expert (Kimi-Linear's) and with two
    (Kanana-2's): the shared SwiGLU is `shared` experts wide, and the
    expert layer's output is the reference's routed sum plus it."""
    from paddle_tpu.nn.decode_blocks import _block_fns
    from reference import kimi_linear as kref

    paddle.seed(11)
    cfg = Kanana2Config.tiny(n_shared_experts=shared)
    model = Kanana2(cfg)
    params = model.functional_state()[0]
    f = cfg.moe_intermediate_size
    assert params["layers.1.moe.shared.gate_proj.weight"].shape \
        == (cfg.hidden_size, shared * f)
    assert params["layers.1.moe.shared.down_proj.weight"].shape \
        == (shared * f, cfg.hidden_size)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (12, cfg.hidden_size)), jnp.float32)
    fns = _block_fns(model.decoder_description())
    y, _counts, _picks, _carry = fns.ffn(params, 1, x, jnp.ones(12, bool),
                                         None)
    want, _found = kref.expert_ffn(params, "layers.1.moe.", x, arch_of(cfg))
    assert np.abs(np.asarray(y) - np.asarray(want)).max() \
        <= 1e-5 * np.abs(np.asarray(want)).max()


def test_a_latent_pool_alone_has_an_empty_store():
    """A description of "mla" layers alone: its cache is a latent pool and
    an empty store; a state slot holds nothing and costs nothing."""
    cfg = Kanana2Config.tiny()
    desc = Kanana2(cfg).decoder_description()
    lay = desc.cache_layout()
    assert lay["store"] == {} and lay["pool_layers"] == 4
    assert lay["row_width"] == 128 and not lay["values"]
    cache = PagedKVCache.for_description(desc, block_size=BS, num_blocks=9,
                                         dtype=jnp.float32, max_slots=3)
    assert cache.state == {} and cache.k_blocks.shape == (4, 9, BS, 128)
    cache.ensure_many([("a", 20), ("b", 3)])           # 3 blocks and 1
    assert cache.stats()["used_blocks"] == 4
    assert cache.state_slot_holder(cache.state_slot("b")) == "b"
    assert cache.stats()["state"]["bytes_per_slot"] == 0
    cache.free("a")
    cache.free("b")
    assert cache.stats()["used_blocks"] == 0 and cache.free_state_slots == 3
    assert cache.state_slot_holder(1) is None


def test_served_through_a_pool_that_holds_one_request_at_a_time(tiny):
    """Five requests through three slots and a pool of 8 usable blocks (a
    request here takes 3 to 7): admission waits for blocks, not for state
    slots; every served token is the reference's argmax and every block
    comes back."""
    cfg, model, params = tiny
    prompts = prompts_of(cfg, (5, 37, 20, 9, 44), seed=5)
    server = PagedGenerationServer(
        model, max_slots=3, block_size=BS, num_blocks=9, max_prompt_len=48,
        max_new_tokens=8, prefill_chunk_tokens=CHUNK)
    server.start()
    try:
        futs = [server.submit(p, max_new_tokens=6) for p in prompts]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
        stats = server.stats()
    finally:
        server.stop()
    for p, o in zip(prompts, outs):
        assert (o[:len(p)] == p).all() and len(o) == len(p) + 6
        lg, _f = ref.logits(params, jnp.asarray(o), arch_of(cfg),
                            jnp.arange(len(p) - 1, len(o) - 1))
        lg = np.asarray(lg)
        deficit = lg.max(-1) - lg[np.arange(6), o[len(p):]]
        assert deficit.max() <= LOGIT_TOL * lg.std()
    kv = stats["kv_cache"]
    assert kv["num_blocks"] == 8 and kv["peak_used_blocks"] <= 8
    assert kv["used_blocks"] == 0 and kv["state"]["used_slots"] == 0
    assert stats["state"]["bytes_per_slot"] == 0
    # 3 expert layers; top 2 of 8, all held: two picks a token and layer
    ex = stats["experts"]
    assert ex["held_picks"] == 2 * ex["tokens"] > 0


def test_the_decode_kernel_counter_counts_mla_decode(tiny, monkeypatch):
    """On an engine whose decode steps run `mla_decode` (the chip stood in
    for while the engine is built, the XLA path serving here): a launch an
    "mla" layer and step; every row of the table, idle or not, takes
    ceil(width / FAN) grid steps (the kernel's grid is rows x width / FAN),
    and a row's live blocks are those under its context."""
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas.mla_decode import FAN

    cfg, model, _params = tiny
    opts = dict(max_slots=2, block_size=BS, max_prompt_len=40,
                max_new_tokens=8, prefill_chunk_tokens=CHUNK)
    with monkeypatch.context() as m:
        m.setattr(attention, "_on_tpu", lambda: True)
        server = PagedGenerationServer(model, **opts)
    width = -(-48 // BS)                               # the table: 6 blocks
    server.start()
    try:
        (p,) = prompts_of(cfg, (21,), seed=8)
        server.submit(p, max_new_tokens=4).result(timeout=300)
        st = server.stats()
    finally:
        server.stop()
    d = st["dispatch_ahead"]["decode_dispatches"]
    # positions 21-23 fed one a step: 3 blocks each, the idle row none
    assert d == 3 and st["decode_kernel"] == {
        "kernel": "mla_decode", "fan": FAN, "launches": 4 * d,
        "live_blocks": 4 * d * 3, "steps": 4 * d * 2 * -(-width // FAN)}
