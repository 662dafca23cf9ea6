"""Quantized serving hot path: W8A16 weights in the engine + int8 paged
KV cache.

The parity policy the feature is gated behind (docs/SERVING.md,
"Quantized serving"): int8 moves a logit by a bounded amount — per-vector
absmax round-trip error propagated once through attention, empirically
under 2% of the logit scale on these configs, asserted at LOGIT_TOL = 5%
— so it CAN flip an argmax wherever the unquantized model's two best
logits lie closer than that. The served tests below therefore hold W8A16
and W8A16+int8-KV greedy tokens to the unquantized run's **up to the
reference's first near-tie** (top-2 margin under LOGIT_TOL of that step's
logit scale, read from one full float32 forward on this host): there
either candidate passes and the tail, which follows from that choice, is
not compared (tests/near_tie.py). Prompts are drawn from each test's
seeded stream until the reference meets no near-tie, and every case
asserts that at least 80% of the generated tokens were compared — no
token, seed or stop position is pinned to another host's rounding.
Covered: plain decode, chunked packed prefill, speculative-decode
verification, prefix-cache ON/OFF, preempt/resume; final-step logits
within the tolerance.

Plus the satellites: quantize->dequantize round-trip error bound for
the absmax scheme, scale-buffer lockstep under CoW, the eager
dtype-consistency assert, and the stats()["quantization"] schema."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import PagedGenerationServer, QuantizedKV
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.inference.kv_quant import kv_decode, kv_encode
from paddle_tpu.models.gpt2 import GPT2, GPT2Config
from paddle_tpu.spec_decode import SpecConfig

from near_tie import (LOGIT_TOL, clear_prompt, compare_to_first_near_tie,
                      compare_workload, ngram_drafts_during,
                      uniform_prompts)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(13)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


class TestRoundTrip:
    def test_absmax_roundtrip_error_bound(self):
        """|x - dequant(quant(x))| <= scale/2 = absmax/254 per element
        (symmetric round-to-nearest), across magnitudes and shapes."""
        import jax.numpy as jnp

        rs = np.random.RandomState(0)
        for shape, scale in (((16, 4, 32), 1.0), ((3, 8), 100.0),
                             ((5, 5, 5, 64), 1e-3)):
            x = jnp.asarray(rs.randn(*shape).astype(np.float32) * scale)
            codes, sc = kv_encode(x)
            assert str(codes.dtype) == "int8"
            assert sc.shape == shape[:-1]
            deq = np.asarray(kv_decode(codes, sc, jnp.float32))
            amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
            bound = amax / 254.0 + 1e-7
            assert (np.abs(deq - np.asarray(x)) <= bound).all()

    def test_zero_vector_roundtrips_exactly(self):
        import jax.numpy as jnp

        x = jnp.zeros((4, 8), jnp.float32)
        codes, sc = kv_encode(x)
        assert (np.asarray(codes) == 0).all()
        assert (np.asarray(kv_decode(codes, sc, jnp.float32)) == 0).all()

    def test_scale_dtype_follows_request(self):
        import jax.numpy as jnp

        x = jnp.ones((2, 4), jnp.float32)
        _, sc = kv_encode(x, jnp.bfloat16)
        assert sc.dtype == jnp.bfloat16


class TestQuantizedPoolUnit:
    def test_ctor_validates_kv_dtype(self):
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedKVCache(1, 1, 2, block_size=4, num_blocks=4,
                         kv_dtype="int4")

    def test_byte_accounting_halves_under_int8(self):
        import jax.numpy as jnp

        mk = lambda kvd: PagedKVCache(2, 2, 32, block_size=4,
                                      num_blocks=8, dtype=jnp.bfloat16,
                                      kv_dtype=kvd)
        dense, quant = mk(None), mk("int8")
        st_d, st_q = dense.stats(), quant.stats()
        assert st_d["kv_dtype"] == "bfloat16"
        assert st_q["kv_dtype"] == "int8"
        assert st_d["scale_bytes"] == 0
        assert st_q["scale_bytes"] > 0
        # bf16 -> int8+bf16-scales: (2*Dh) -> (Dh + 2) bytes/vector
        assert st_q["pool_bytes_total"] < 0.6 * st_d["pool_bytes_total"]
        assert st_q["pool_bytes_per_token"] \
            < 0.6 * st_d["pool_bytes_per_token"]

    def test_cow_copies_scales_with_codes(self):
        """The scale buffer must ride the block through copy-on-write:
        after prepare_write CoWs a shared block, the NEW block holds
        the same codes AND scales the original did."""
        import jax.numpy as jnp

        c = PagedKVCache(1, 1, 4, block_size=4, num_blocks=6,
                         kv_dtype="int8")
        toks = np.arange(1, 9, dtype=np.int32)
        c.allocate("pub", 8)
        b0 = c.block_table("pub")[0]
        # paint block b0 with recognizable codes + scales host-side
        kc = c.k_blocks.codes.at[0, b0].set(7)
        ks = c.k_blocks.scales.at[0, b0].set(3.5)
        c.k_blocks = QuantizedKV(kc, ks)
        c.publish_prefix("pub", toks)
        assert c.attach_prefix("att", toks) > 0
        shared = c.block_table("att")[0]
        assert shared == b0
        assert c.prepare_write("att", 0) is True  # CoW happened
        new = c.block_table("att")[0]
        assert new != b0
        np.testing.assert_array_equal(
            np.asarray(c.k_blocks.codes[0, new]),
            np.asarray(c.k_blocks.codes[0, b0]))
        np.testing.assert_array_equal(
            np.asarray(c.k_blocks.scales[0, new]),
            np.asarray(c.k_blocks.scales[0, b0]))
        for s in ("pub", "att"):
            c.free(s)

    def test_quantized_attach_truncate_swap_keep_scales_indexed(self):
        """swap_out / attach / truncate on an int8 pool run the exact
        dense bookkeeping (scales are block-indexed parallels)."""
        c = PagedKVCache(1, 1, 2, block_size=4, num_blocks=8,
                         kv_dtype="int8")
        toks = np.arange(1, 11, dtype=np.int32)
        c.allocate("a", 10)
        assert c.swap_out_seq("a", toks) == 10
        assert not c.has_seq("a")
        assert c.retained_block_count > 0
        assert c.attach_prefix("b", toks) == 9  # len-1 cap
        c.ensure("b", 10)
        c.truncate_seq("b", 3)
        assert c.seq_len("b") == 3
        c.free("b")


class TestDtypeConsistency:
    def test_decoder_rejects_mismatched_cache_eagerly(self, tiny_model):
        """CI/tooling satellite: an int8 decoder handed a bf16 pool (or
        vice versa) must raise BEFORE tracing, naming the argument."""
        import jax.numpy as jnp

        from paddle_tpu.nn.decode import PagedDecoder
        from paddle_tpu.sampling.buffers import greedy_args

        model, cfg = tiny_model
        mkcache = lambda kvd: PagedKVCache(
            cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, block_size=4,
            num_blocks=4, kv_dtype=kvd)
        args = (jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
                jnp.ones((2,), bool), jnp.zeros((2, 2), jnp.int32))
        for dec_kvd, cache_kvd in ((None, "int8"), ("int8", None)):
            dec = PagedDecoder.for_config(cfg, 4, kv_dtype=dec_kvd)
            cache = mkcache(cache_kvd)
            with pytest.raises(ValueError, match="'kc'"):
                dec.step({}, *args, cache.k_blocks, cache.v_blocks,
                         greedy_args(2))
            with pytest.raises(ValueError, match="kv dtype mismatch"):
                dec.multistep(2)({}, *args, cache.k_blocks,
                                 cache.v_blocks, greedy_args(2))

    def test_decoder_and_server_validate_kv_dtype_values(self,
                                                         tiny_model):
        from paddle_tpu.nn.decode import PagedDecoder

        model, cfg = tiny_model
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedDecoder.for_config(cfg, 4, kv_dtype="fp8")
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedGenerationServer(model, kv_dtype="fp8")
        with pytest.raises(ValueError, match="quantization"):
            PagedGenerationServer(model, quantization="w4a16")


def _serve(model, prompts, *, sampling=None, max_new=8, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_prompt_len", 48)
    kw.setdefault("max_new_tokens", max_new)
    kw.setdefault("prefill_chunk_tokens", 16)
    srv = PagedGenerationServer(model, **kw).start()
    try:
        outs = [f.result(timeout=600) for f in
                [srv.submit(p, sampling=sampling) for p in prompts]]
        st = srv.stats()
    finally:
        srv.stop()
    return outs, st


QUANT_MODES = [
    ("w8a16", dict(quantization="w8a16")),
    ("w8a16_kv8", dict(quantization="w8a16", kv_dtype="int8")),
    ("kv8_only", dict(kv_dtype="int8")),
]


@pytest.fixture(scope="module")
def chunked_workload(tiny_model):
    """Prompts longer than the chunk budget (2-3 chunk dispatches each)
    and their unquantized served reference."""
    model, cfg = tiny_model
    rs = np.random.RandomState(0)
    prompts = [clear_prompt(model, uniform_prompts(rs, cfg.vocab_size, n), 8)
               for n in (36, 30, 25, 21)]
    return prompts, _serve(model, prompts, prefill_chunk_tokens=16)[0]


@pytest.fixture(scope="module")
def prefix_workload(tiny_model):
    """Five prompts over one 14-token prefix, and their reference."""
    model, cfg = tiny_model
    rs = np.random.RandomState(11)
    prefix = rs.randint(1, cfg.vocab_size, (14,))

    def draw(tail):
        return lambda n: np.concatenate(
            [np.tile(prefix, (n, 1)),
             rs.randint(1, cfg.vocab_size, (n, tail))], axis=1)

    prompts = [clear_prompt(model, draw(int(rs.randint(2, 8))), 8)
               for _ in range(5)]
    return prompts, _serve(model, prompts)[0]


class TestNearTieRule:
    """The comparator itself, on logits written by hand: a prompt of 2,
    4 new tokens, the third of them decided by 0.01 between 5 and 6."""

    def _logits(self):
        lg = np.zeros((6, 8), np.float32)
        for row, tok in ((1, 3), (2, 4), (4, 7)):
            lg[row, tok] = 1.0
        lg[3, 5], lg[3, 6] = 1.0, 0.99
        return lg

    @pytest.mark.parametrize("out,verdict", [
        ([1, 2, 3, 4, 5, 7], 2),          # the reference itself
        ([1, 2, 3, 4, 6, 0], 2),          # the other side of the tie
        ([1, 2, 3, 4, 6], 2),             # ... and a tail that ended
        ([1, 2, 3, 4, 2, 7], "fail"),     # neither candidate
        ([1, 2, 3, 0, 5, 7], "fail"),     # differs where the choice is clear
        ([1, 2, 3, 4], "fail"),           # ended before the tie
    ])
    def test_compares_up_to_the_first_near_tie(self, out, verdict):
        ref = [1, 2, 3, 4, 5, 7]
        if verdict == "fail":
            with pytest.raises(AssertionError):
                compare_to_first_near_tie(ref, out, 2, self._logits())
        else:
            assert compare_to_first_near_tie(
                ref, out, 2, self._logits()) == verdict

    def test_clear_reference_is_compared_whole(self):
        lg = self._logits()
        lg[3, 6] = 0.5
        ref = [1, 2, 3, 4, 5, 7]
        assert compare_to_first_near_tie(ref, ref, 2, lg) == 4
        with pytest.raises(AssertionError):
            compare_to_first_near_tie(ref, ref[:5] + [0], 2, lg)

    def test_hollow_comparison_is_refused(self, tiny_model):
        """A workload whose reference ties at once compares nothing: the
        share assertion must say so rather than pass."""
        model, cfg = tiny_model
        p = np.arange(1, 9, dtype=np.int32)
        ref = model.generate(p[None], 4).numpy()[0]
        with pytest.raises(AssertionError, match="near-tie"):
            compare_workload(model, [ref], [ref], [p], tol=1e9)


class TestServedParity:
    """Greedy tokens against the unquantized engine's, up to the
    reference's first near-tie (tests/near_tie.py; module docstring)."""

    @pytest.mark.parametrize("name,qkw", QUANT_MODES)
    def test_decode_and_chunked_prefill_parity(self, tiny_model,
                                               chunked_workload, name,
                                               qkw):
        """Plain decode + multi-chunk packed prefill."""
        model, cfg = tiny_model
        prompts, ref = chunked_workload
        out, st = _serve(model, prompts, prefill_chunk_tokens=16, **qkw)
        compare_workload(model, ref, out, prompts)
        assert st["quantization"]["enabled"] is True

    @pytest.mark.parametrize("name,qkw", QUANT_MODES[:2])
    def test_prefix_cache_on_off_parity(self, tiny_model, prefix_workload,
                                        name, qkw):
        """Prefix-cache ON (shared prefix pool, publish + attach + CoW),
        cache OFF and a warm index all agree with the unquantized
        engine — the scale buffers ride the shared blocks."""
        model, cfg = tiny_model
        prompts, ref = prefix_workload
        off, _ = _serve(model, prompts, **qkw)
        on, st_on = _serve(model, prompts, enable_prefix_cache=True,
                           **qkw)
        # resubmit on a warm index: pure-attach path (near-full hits)
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=48, max_new_tokens=8,
                                    prefill_chunk_tokens=16,
                                    enable_prefix_cache=True,
                                    **qkw).start()
        try:
            [f.result(timeout=600) for f in
             [srv.submit(p) for p in prompts]]
            warm = [f.result(timeout=600) for f in
                    [srv.submit(p) for p in prompts]]
            assert srv.cache.stats()["prefix_cache"]["hit_tokens"] > 0
        finally:
            srv.stop()
        for out in (off, on, warm):
            compare_workload(model, ref, out, prompts)

    @pytest.mark.parametrize("name,qkw", QUANT_MODES[:2])
    def test_spec_decode_verify_parity(self, tiny_model, name, qkw):
        """Speculative decoding (packed verify + truncate_seq rollback)
        over a quantized engine. TWO guarantees, asserted separately:
        the ENGINE invariant — quantized speculative output is
        token-identical to quantized non-speculative output no matter
        the acceptance pattern (one engine, one arithmetic) — and
        parity with the unquantized server up to a near-tie."""
        model, cfg = tiny_model
        rs = np.random.RandomState(3)

        def draw(n):  # a 3-token motif repeated: the n-gram drafter's food
            return np.tile(rs.randint(1, cfg.vocab_size, (n, 3)),
                           (1, 5))[:, :15]

        # the counters asserted below need a proposal: take the first
        # workload of the stream on whose tokens, as this host computes
        # them, the default drafter finds one
        for _ in range(16):
            prompts = [clear_prompt(model, draw, 10) for _ in range(4)]
            ref, _ = _serve(model, prompts, max_new=10)
            if any(ngram_drafts_during(r, 15, SpecConfig()) for r in ref):
                break
        else:
            raise AssertionError("no workload the n-gram drafter drafts on")
        qplain, _ = _serve(model, prompts, max_new=10, **qkw)
        qspec, st = _serve(model, prompts, max_new=10,
                           speculation=True, **qkw)
        for b, c in zip(qplain, qspec):
            np.testing.assert_array_equal(b, c)  # engine invariant
        compare_workload(model, ref, qplain, prompts)
        assert st["speculation"]["verify_dispatches"] >= 1
        assert st["speculation"]["proposed_tokens"] > 0

    @pytest.mark.parametrize("name,qkw", QUANT_MODES[:2])
    def test_preempt_resume_parity(self, tiny_model, name, qkw):
        """Preempt-then-resume through the quantized pool: swap-out
        publishes int8 blocks + scales, resume attaches them — output
        token-identical to the uninterrupted quantized run, which
        agrees with the unquantized model up to a near-tie."""
        from paddle_tpu.frontend import FrontDoor

        model, cfg = tiny_model
        rs = np.random.RandomState(2)
        pv = clear_prompt(model, uniform_prompts(rs, cfg.vocab_size, 7), 24)
        pi = clear_prompt(model, uniform_prompts(rs, cfg.vocab_size, 4), 3)

        def run(**skw):
            fd = FrontDoor(model, max_slots=1, block_size=4,
                           max_prompt_len=16, max_new_tokens=24,
                           **skw).start()
            try:
                hv = fd.submit(pv, lane="batch", max_new_tokens=24)
                it = iter(hv)
                next(it)
                next(it)  # victim has emitted >= 2 tokens
                hi_ = fd.submit(pi, lane="interactive",
                                max_new_tokens=3)
                out_i = hi_.result(timeout=600)
                out_v = hv.result(timeout=600)
                st = fd.stats()
                assert st["frontdoor"]["preemptions"] >= 1
                assert st["frontdoor"]["resumes"] >= 1
            finally:
                fd.stop()
            return out_v, out_i

        # engine invariant: preempted == uninterrupted on the SAME
        # quantized engine (one arithmetic); then the uninterrupted
        # quantized run against the unquantized model
        (qref_v,), (qref_i,) = (
            _serve(model, [pv], max_new=24, max_slots=1,
                   max_prompt_len=16, **qkw)[0],
            _serve(model, [pi], max_new=3, max_slots=1,
                   max_prompt_len=16, **qkw)[0])
        out_v, out_i = run(**qkw)
        np.testing.assert_array_equal(out_v, qref_v)
        np.testing.assert_array_equal(out_i, qref_i)
        compare_workload(
            model, [model.generate(pv[None], 24).numpy()[0],
                    model.generate(pi[None], 3).numpy()[0]],
            [qref_v, qref_i], [pv, pi])

    def test_sampled_requests_deterministic_quantized(self, tiny_model):
        """Fixed-seed sampled traffic on the quantized engine is
        deterministic (counter-based PRNG is dtype-agnostic): two
        identical quantized servers agree token-for-token. Both runs
        are one engine on one host, so nothing here is compared across
        arithmetics and equality is exact."""
        from paddle_tpu.sampling import SamplingParams

        model, cfg = tiny_model
        rs = np.random.RandomState(17)
        prompts = [rs.randint(1, cfg.vocab_size,
                              (int(rs.randint(4, 20)),)).astype(np.int32)
                   for _ in range(3)]
        sp = SamplingParams(temperature=0.8, top_p=0.9, seed=123)
        a, _ = _serve(model, prompts, sampling=sp, kv_dtype="int8",
                      quantization="w8a16")
        b, _ = _serve(model, prompts, sampling=sp, kv_dtype="int8",
                      quantization="w8a16")
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


class TestLogitTolerance:
    def test_decoder_logits_within_documented_tolerance(self,
                                                        tiny_model):
        """Final-step logits of the int8-KV + W8A16 engine stay within
        LOGIT_TOL (absolute, f32 logits O(1) on this config) of bf16 —
        the documented parity-tolerance policy."""
        import jax.numpy as jnp

        from paddle_tpu.inference.kv_cache import blocks_for
        from paddle_tpu.nn.decode import PagedDecoder
        from paddle_tpu.sampling import SlotParamStore

        model, cfg = tiny_model
        params, _ = model.functional_state()
        wq = model.quantize_weights(params)
        rs = np.random.RandomState(2)
        B, S, new, bs = 3, 12, 5, 4
        # rows whose reference meets no near-tie: each run feeds itself
        # its own tokens, so a flipped tie would compare two different
        # sequences' logits
        draw = uniform_prompts(rs, cfg.vocab_size, S)
        ids = np.stack([clear_prompt(model, draw, new) for _ in range(B)])
        lens = np.full((B,), S, np.int32)

        def run(p, kvd):
            cache = PagedKVCache(
                cfg.num_layers, cfg.num_heads,
                cfg.hidden_size // cfg.num_heads, block_size=bs,
                num_blocks=B * blocks_for(S + new, bs) + 1,
                kv_dtype=kvd, name=f"tol-{kvd}")
            for b in range(B):
                cache.allocate(b, S + new)
            tables = jnp.asarray(cache.table_array(range(B)))
            dec = PagedDecoder.for_config(cfg, bs, return_logits=True,
                                          kv_dtype=kvd)
            store = SlotParamStore(B, cfg.vocab_size)
            sp, mode = store.step_args(np.zeros((B,), np.int32))
            tok, _, kc, vc, _, logits = dec.prefill(
                p, jnp.asarray(ids), jnp.asarray(lens), tables,
                cache.k_blocks, cache.v_blocks, sp, mode)
            logs = [np.asarray(logits)]
            toks = [np.asarray(tok)]
            pos = lens.copy()
            for step in range(1, new):
                sp, mode = store.step_args(
                    np.full((B,), step, np.int32))
                tok, _, kc, vc, _, logits = dec.step(
                    p, jnp.asarray(toks[-1]), jnp.asarray(pos),
                    jnp.ones((B,), bool), tables, kc, vc, sp, mode)
                toks.append(np.asarray(tok))
                logs.append(np.asarray(logits))
                pos += 1
            return np.stack(toks), np.stack(logs)

        t_ref, l_ref = run(params, None)
        t_q, l_q = run(wq, "int8")
        np.testing.assert_array_equal(t_ref, t_q)  # no tie: one sequence
        delta = np.abs(l_q - l_ref)
        scale = np.abs(l_ref).max()
        assert delta.max() <= LOGIT_TOL * max(scale, 1.0), \
            (delta.max(), scale)


class TestQuantStatsSchema:
    KEYS = {"enabled", "mode", "kv_dtype", "kv_scale_bytes",
            "kv_pool_bytes_total"}

    def test_paged_stats_block_zeroed_when_disabled(self, tiny_model):
        model, cfg = tiny_model
        srv = PagedGenerationServer(model, max_slots=1, block_size=4,
                                    max_prompt_len=8, max_new_tokens=2)
        st = srv.stats()["quantization"]
        assert set(st) == self.KEYS
        assert st["enabled"] is False
        assert st["mode"] == "none"
        assert st["kv_scale_bytes"] == 0
        srv.reset_stats()
        assert srv.stats()["quantization"] == st  # coherent reset
        srv.stop()

    def test_paged_stats_block_populated_when_enabled(self, tiny_model):
        model, cfg = tiny_model
        srv = PagedGenerationServer(model, max_slots=1, block_size=4,
                                    max_prompt_len=8, max_new_tokens=2,
                                    quantization="w8a16",
                                    kv_dtype="int8")
        st = srv.stats()["quantization"]
        assert st["enabled"] is True
        assert st["mode"] == "w8a16"
        assert st["kv_dtype"] == "int8"
        assert st["kv_scale_bytes"] > 0
        assert st["kv_pool_bytes_total"] > 0
        # pool stats expose the same dtype-aware accounting
        kv = srv.stats()["kv_cache"]
        assert kv["kv_dtype"] == "int8"
        assert kv["scale_bytes"] == st["kv_scale_bytes"]
        srv.stop()

    def test_dense_server_block_is_congruent(self):
        from paddle_tpu.inference import GenerationServer

        def prog(ids, *a):
            return np.zeros((ids.shape[0], ids.shape[1] + 1), np.int32)

        srv = GenerationServer(prog, batch_size=2, prompt_len=4)
        st = srv.stats()["quantization"]
        assert set(st) == self.KEYS
        assert st["enabled"] is False and st["mode"] == "none"

        prog2 = lambda ids, *a: prog(ids)
        prog2._meta = {"prompt_len": 4, "batch_size": 2,
                       "weight_quant": "int8", "kv_quant": "int8"}
        srv2 = GenerationServer(prog2)
        st2 = srv2.stats()["quantization"]
        assert st2["enabled"] is True
        assert st2["mode"] == "w8a16"
        assert st2["kv_dtype"] == "int8"

    def test_weight_quant_alias_maps_to_w8a16(self, tiny_model):
        model, cfg = tiny_model
        srv = PagedGenerationServer(model, max_slots=1, block_size=4,
                                    max_prompt_len=8, max_new_tokens=2,
                                    weight_quant="int8")
        assert srv.quantization == "w8a16"
        assert srv.stats()["quantization"]["mode"] == "w8a16"
        srv.stop()


class TestQuantizedPallasKernels:
    """int8 Pallas kernel variants (interpret mode on CPU) vs the
    scale-folded XLA fallbacks — same dequant-in-kernel semantics."""

    def _quant_pool(self, kb, vb):
        import jax.numpy as jnp

        ck, sk = kv_encode(jnp.asarray(kb))
        cv, sv = kv_encode(jnp.asarray(vb))
        return QuantizedKV(ck, sk), QuantizedKV(cv, sv)

    @pytest.mark.parametrize("lens", [[11, 5, 16], [0, 0, 13], [16, 16, 16],
                                      [1, 4, 5]],
                             ids=["ragged", "one_live_row", "every_row_full",
                                  "block_edges"])
    def test_quant_decode_kernel_matches_fallback(self, lens):
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import paged_decode_attention
        from paddle_tpu.ops.pallas.unified_attention import (
            paged_decode_attention_kernel)

        rs = np.random.RandomState(0)
        b, h, dh, n, bs, m = 3, 4, 8, 9, 4, 4
        q = jnp.asarray(rs.randn(b, h, dh).astype(np.float32))
        kq, vq = self._quant_pool(rs.randn(n, bs, h, dh),
                                  rs.randn(n, bs, h, dh))
        tables = jnp.asarray(np.array([[1, 2, 3, 8], [4, 5, 1, 3],
                                       [6, 7, 8, 2]], np.int32))
        # every table entry names a block of the pool, so any context up
        # to the table's width is a case; the kernel's grid is the live
        # (row, block) pairs, one step for a row that sees nothing
        seen = np.array(lens) > 0
        lens = jnp.asarray(np.array(lens, np.int32))
        ref = paged_decode_attention(q, kq, vq, tables, lens)
        out = paged_decode_attention_kernel(q, kq, vq, tables, lens,
                                            interpret=True)
        np.testing.assert_allclose(np.asarray(out)[seen],
                                   np.asarray(ref)[seen], atol=2e-5)
        assert not np.asarray(out)[~seen].any()

    def test_quant_ragged_prefill_kernel_matches_fallback(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import ragged_prefill_attention
        from paddle_tpu.ops.pallas.unified_attention import (
            unified_ragged_attention_kernel as
            ragged_prefill_attention_kernel)

        rs = np.random.RandomState(2)
        n, bs, h, dh, qt = 9, 8, 4, 8, 8
        kq, vq = self._quant_pool(rs.randn(n, bs, h, dh),
                                  rs.randn(n, bs, h, dh))
        tables = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 0]], np.int32)
        seg = np.array([0] * 8 + [1] * 8 + [2] * 8, np.int32)
        pos = np.array(list(range(8, 16)) + list(range(8))
                       + list(range(5)) + [-1] * 3, np.int32)
        q = rs.randn(len(seg), h, dh).astype(np.float32)
        ref = np.asarray(ragged_prefill_attention(
            jnp.asarray(q), kq, vq, jnp.asarray(tables),
            jnp.asarray(seg), jnp.asarray(pos)))
        out = np.asarray(ragged_prefill_attention_kernel(
            jnp.asarray(q), kq, vq, jnp.asarray(tables),
            jnp.asarray(seg[::qt]), jnp.asarray(pos[::qt]),
            q_tile=qt, interpret=True))
        valid = pos >= 0
        np.testing.assert_allclose(out[valid], ref[valid], atol=2e-5)

    def test_quant_verify_window_matches_dense_math(self):
        """The dense off-TPU verify fallback on a quantized pool vs an
        explicit dequantize-then-attend reference."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import verify_window_attention

        rs = np.random.RandomState(4)
        p, w, h, dh, n, bs, m = 2, 3, 2, 4, 7, 4, 3
        q = jnp.asarray(rs.randn(p, w, h, dh).astype(np.float32))
        kb = rs.randn(n, bs, h, dh).astype(np.float32)
        vb = rs.randn(n, bs, h, dh).astype(np.float32)
        kq, vq = self._quant_pool(kb, vb)
        tables = jnp.asarray(np.array([[1, 2, 3], [4, 5, 6]], np.int32))
        pos = jnp.asarray(np.array([[8, 9, 10], [4, 5, -1]], np.int32))
        out = verify_window_attention(q, kq, vq, tables, pos)
        # reference: dequantize the pool, run the dense path
        kd = np.asarray(kv_decode(kq.codes, kq.scales, jnp.float32))
        vd = np.asarray(kv_decode(vq.codes, vq.scales, jnp.float32))
        ref = verify_window_attention(q, jnp.asarray(kd),
                                      jnp.asarray(vd), tables, pos)
        valid = np.asarray(pos) >= 0
        np.testing.assert_allclose(np.asarray(out)[valid],
                                   np.asarray(ref)[valid], atol=2e-5)


class TestOfflinePagedKV8:
    def test_generate_paged_kv8_matches_bf16(self, tiny_model):
        """models/gpt2.py seam: the offline paged path serves the same
        quantized configuration (kv_quant='int8', optionally stacked
        on weight_quant), greedy tokens as the unquantized path's up to
        a near-tie."""
        model, cfg = tiny_model
        rs = np.random.RandomState(0)
        lens = [9, 6, 4]
        prompts = [clear_prompt(
            model, uniform_prompts(rs, cfg.vocab_size, n), 6) for n in lens]
        ids = np.zeros((3, 9), np.int32)
        for i, p in enumerate(prompts):
            ids[i, :len(p)] = p
        ref = model.generate(ids, 6, kv_cache="paged", block_size=4,
                             prompt_lens=lens).numpy()
        kv8 = model.generate(ids, 6, kv_cache="paged", block_size=4,
                             prompt_lens=lens, kv_quant="int8").numpy()
        both = model.generate(ids, 6, kv_cache="paged", block_size=4,
                              prompt_lens=lens, kv_quant="int8",
                              weight_quant="int8").numpy()
        rows = lambda out: [out[i, :n + 6] for i, n in enumerate(lens)]
        for out in (kv8, both):
            compare_workload(model, rows(ref), rows(out), prompts)
