"""One-kernel serving round (r16 tentpole): the unified ragged paged
attention kernel (interpret mode vs the XLA fallback, bf16-free f32 +
int8 KV), the fused `unified_round` engine path's token parity against
the split packed_prefill + step + packed_verify scheduler across the
whole composed stack (prefix cache, speculation, W8A16/int8-KV,
sharding, FrontDoor preempt/resume; greedy + fixed-seed sampled), the
tier-1 dispatch-count guarantee (a mixed prefill+decode+verify round =
exactly ONE attention dispatch), and the async loop's bucket
pre-compilation / stats-schema satellites."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt2 import GPT2, GPT2Config


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(21)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


def _mixed_stream_case(seed=0):
    """One packed stream mixing the three row kinds: a prefill chunk
    (8 tokens of row 0 at positions 5..12 — a chunk whose prefix is
    already cached), a plain decode row (1 token of row 1 at its write
    position), and a speculative verify region (1 + 3 tokens of row
    2). Regions aligned to the 8-token test query tile."""
    rs = np.random.RandomState(seed)
    n, bs, h, dh = 10, 8, 8, 8
    kb = rs.randn(n, bs, h, dh).astype(np.float32)
    vb = rs.randn(n, bs, h, dh).astype(np.float32)
    tables = np.array([[1, 2, 0], [3, 4, 5], [6, 7, 0]], np.int32)
    seg = np.array([0] * 8 + [1] + [0] * 7 + [2] * 4 + [0] * 4,
                   np.int32)
    pos = np.array(list(range(5, 13))            # chunk row
                   + [17] + [-1] * 7            # decode row + pads
                   + list(range(9, 13)) + [-1] * 4,  # verify + pads
                   np.int32)
    q = rs.randn(len(seg), h, dh).astype(np.float32)
    return q, kb, vb, tables, seg, pos


def _decode_cases():
    """(id, int8 pool, pool stack with a traced layer / one layer's pool,
    heads, head size, block, tables, contexts).  The first is the case of
    the bit-equality test this one replaces; the next walk a block's
    edges: a pad row (0), one token, exactly a block, one past it, the
    whole table; the last are the launches whose grid (the live (row,
    block) pairs since PR 31) is shortest and longest for its rows: one
    live row among idle ones, and every row full."""
    first = ("dense-one_layer-4heads", False, False, 4, 8, 4,
             [[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 2]], [11, 0, 16])
    tables = [[0, 0, 0], [1, 0, 0], [2, 0, 0], [3, 4, 0], [5, 6, 7]]
    lens = [0, 1, 8, 9, 24]
    edges = [
        (f"{'int8' if quant else 'dense'}-"
         f"{'stack' if stacked else 'one_layer'}-{h}heads",
         quant, stacked, h, 16, 8, tables, lens)
        for quant in (False, True) for stacked in (True, False)
        for h in (16, 8)]
    one_live = ([[0, 0, 0], [0, 0, 0], [1, 2, 3], [0, 0, 0]], [0, 0, 17, 0])
    all_full = ([[1, 2, 3], [4, 5, 6], [7, 8, 9]], [24, 24, 24])
    shapes = [
        (f"{'int8' if quant else 'dense'}-stack-8heads-{name}",
         quant, True, 8, 16, 8, *case)
        for quant in (False, True)
        for name, case in (("one_live_row", one_live),
                           ("every_row_full", all_full))]
    return [first] + edges + shapes


DECODE_CASES = _decode_cases()


class TestUnifiedKernel:
    def test_interpret_kernel_matches_fallback_mixed_stream(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import unified_stream_attention
        from paddle_tpu.ops.pallas.unified_attention import (
            unified_ragged_attention_kernel)

        q, kb, vb, tables, seg, pos = _mixed_stream_case()
        ref = np.asarray(unified_stream_attention(
            jnp.asarray(q), jnp.asarray(kb), jnp.asarray(vb),
            jnp.asarray(tables), jnp.asarray(seg), jnp.asarray(pos)))
        out = np.asarray(unified_ragged_attention_kernel(
            jnp.asarray(q), jnp.asarray(kb), jnp.asarray(vb),
            jnp.asarray(tables), jnp.asarray(seg[::8]),
            jnp.asarray(pos[::8]), q_tile=8, interpret=True))
        valid = pos >= 0
        np.testing.assert_allclose(out[valid], ref[valid], atol=2e-5)

    def test_interpret_kernel_matches_fallback_int8_kv(self):
        import jax.numpy as jnp

        from paddle_tpu.inference.kv_quant import QuantizedKV, kv_encode
        from paddle_tpu.ops.attention import unified_stream_attention
        from paddle_tpu.ops.pallas.unified_attention import (
            unified_ragged_attention_kernel)

        q, kb, vb, tables, seg, pos = _mixed_stream_case(3)
        ck, sk = kv_encode(jnp.asarray(kb))
        cv, sv = kv_encode(jnp.asarray(vb))
        kq, vq = QuantizedKV(ck, sk), QuantizedKV(cv, sv)
        ref = np.asarray(unified_stream_attention(
            jnp.asarray(q), kq, vq, jnp.asarray(tables),
            jnp.asarray(seg), jnp.asarray(pos)))
        out = np.asarray(unified_ragged_attention_kernel(
            jnp.asarray(q), kq, vq, jnp.asarray(tables),
            jnp.asarray(seg[::8]), jnp.asarray(pos[::8]), q_tile=8,
            interpret=True))
        valid = pos >= 0
        np.testing.assert_allclose(out[valid], ref[valid], atol=2e-4)

    @pytest.mark.parametrize("case", DECODE_CASES,
                             ids=[c[0] for c in DECODE_CASES])
    def test_decode_entry_matches_stream_kernel_and_gather(self, case):
        """The decode entry has a body of its own since PR 27 (all heads
        of a KV block in one pass: one score dot, one softmax, one value
        dot): held, within float32 rounding, to the stream kernel fed the
        same rows as one-token segments of 8-row tiles (the decode entry
        until then) and to the XLA gather path, and to zeros where a row
        sees nothing."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.inference.kv_quant import QuantizedKV, kv_encode
        from paddle_tpu.ops import attention
        from paddle_tpu.ops.pallas import unified_attention as ua

        _name, quant, stacked, h, dh, bs, tables, lens = case
        tables, lens = np.asarray(tables, np.int32), np.asarray(lens)
        rs = np.random.RandomState(3)
        b, n, layers = len(lens), int(tables.max()) + 1, 3
        q = jnp.asarray(rs.randn(b, h, dh).astype(np.float32))

        def pool():  # (what the entry takes, the same values as one layer)
            x = jnp.asarray(rs.randn(layers, n, bs, h, dh)
                            .astype(np.float32))
            codes, scales = kv_encode(x) if quant else (x, None)
            rows = codes.reshape(layers, n, bs, h * dh)
            if quant:
                return (QuantizedKV(rows, scales) if stacked
                        else QuantizedKV(codes[1], scales[1]),
                        QuantizedKV(codes[1], scales[1]))
            return (rows if stacked else x[1]), x[1]

        (k, k1), (v, v1) = pool(), pool()
        tables, lens = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)
        if stacked:  # the layer is a traced scalar in a program
            out = jax.jit(lambda ly: ua.paged_decode_attention_kernel(
                q, k, v, tables, lens, ly, interpret=True))(jnp.int32(1))
        else:
            out = ua.paged_decode_attention_kernel(q, k, v, tables, lens,
                                                   interpret=True)
        qt = 8
        stream = jnp.zeros((b, qt, h, dh), q.dtype).at[:, 0].set(q)
        tiled = ua.unified_ragged_attention_kernel(
            stream.reshape(b * qt, h, dh), k1, v1, tables, jnp.arange(b),
            lens - 1, q_tile=qt, interpret=True)[::qt]
        gathered = attention.paged_decode_attention(q, k1, v1, tables, lens)
        out, seen = np.asarray(out), np.asarray(lens) > 0
        assert seen.any()
        np.testing.assert_allclose(out, np.asarray(tiled), atol=2e-4)
        np.testing.assert_allclose(out[seen], np.asarray(gathered)[seen],
                                   atol=2e-4)
        assert np.abs(out[seen]).max(axis=(1, 2)).all()
        assert not out[~seen].any()  # ctx_len 0: a pad row, zeros

    @pytest.mark.parametrize("lens,width", [
        ([0, 1, 8, 9, 24], 3), ([0, 0, 17, 0], 3), ([24, 24, 24], 3),
        ([0, 0, 0, 0], 2), ([5], 1), ([30, 3], 3), ([7, 300, 128], 40)],
        ids=["edges", "one_live_row", "every_row_full", "all_idle",
             "one_row", "past_the_table", "wide_table"])
    def test_decode_work_list_is_the_live_row_block_pairs(self, lens, width):
        """The decode launch's grid (PR 31): a step a live block of a row,
        one for a row that has none (it still flushes its zeros), never
        more than the table is wide; rows ascend and a row's columns run
        0 .. n - 1, so its first step initialises and its last flushes;
        what lies past the used length stays inside the table."""
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import unified_attention as ua

        bs = 8
        rows, cols, used = ua.decode_work_list(jnp.asarray(lens, jnp.int32),
                                               bs, width)
        rows, cols, used = np.asarray(rows), np.asarray(cols), int(used)
        n = [min(width, max(1, -(-c // bs))) for c in lens]
        assert used == sum(n)
        assert rows.shape == cols.shape == (len(lens) * width,)
        assert rows.dtype == cols.dtype == np.int32
        assert rows[:used].tolist() == [b for b, k in enumerate(n)
                                        for _ in range(k)]
        assert cols[:used].tolist() == [c for k in n for c in range(k)]
        assert (rows >= 0).all() and (rows < len(lens)).all()
        assert (cols >= 0).all() and (cols < width).all()


def _serve(model, prompts, sampling_fn=None, timeout=300, **kw):
    from paddle_tpu.inference import PagedGenerationServer

    srv = PagedGenerationServer(model, **kw).start()
    try:
        futs = [srv.submit(p, sampling=(sampling_fn(i) if sampling_fn
                                        else None))
                for i, p in enumerate(prompts)]
        outs = [f.result(timeout=timeout) for f in futs]
        st = srv.stats()
    finally:
        srv.stop()
    return outs, st


BASE_KW = dict(max_slots=2, block_size=4, max_new_tokens=10,
               prefill_chunk_tokens=8)


class TestUnifiedRoundParity:
    """unified+async ON vs split OFF: token-for-token identical across
    the composed stack."""

    def _prompts(self, cfg, n=4, repetitive=True):
        rng = np.random.RandomState(7)
        if repetitive:  # motifs the n-gram drafter can actually predict
            base = rng.randint(1, cfg.vocab_size, (6,)).astype(np.int32)
            return [np.tile(base, 3)[:14 + i].astype(np.int32)
                    for i in range(n)]
        return [rng.randint(1, cfg.vocab_size,
                            (int(rng.randint(4, 20)),)).astype(np.int32)
                for _ in range(n)]

    def _mixed_sampling(self, i):
        from paddle_tpu.sampling import SamplingParams

        if i % 2 == 0:
            return None
        return SamplingParams(temperature=0.8, top_p=0.9, seed=100 + i,
                              repetition_penalty=1.2)

    def _assert_parity(self, model, prompts, sampling_fn=None, **extra):
        kw = dict(BASE_KW, **extra)
        ref, _ = _serve(model, prompts, sampling_fn, **kw)
        uni, st_u = _serve(model, prompts, sampling_fn,
                           unified_round=True, **kw)
        asy, st_a = _serve(model, prompts, sampling_fn,
                           async_rounds=True, **kw)
        for a, b, c in zip(ref, uni, asy):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
        for st in (st_u, st_a):
            assert st["rounds"]["unified"] is True
            assert st["rounds"]["dispatches_per_round"] == 1.0
            g = st["goodput"]
            assert g["decoded_tokens"] == (g["goodput_tokens"]
                                           + g["rolled_back_tokens"]
                                           + g["replayed_tokens"]), g
        assert st_a["rounds"]["async"] is True
        return st_u, st_a

    def test_parity_greedy_plain(self, tiny_model):
        model, cfg = tiny_model
        self._assert_parity(model, self._prompts(cfg, repetitive=False))

    def test_parity_speculation_mixed_sampling(self, tiny_model):
        """Speculation ON, 50% sampled (top-p + repetition penalty):
        the unified verify regions must accept/rollback exactly like
        the split packed_verify, and async's one-round-stale drafts
        must not change a single emitted token."""
        from paddle_tpu.spec_decode import SpecConfig

        model, cfg = tiny_model
        st_u, st_a = self._assert_parity(
            model, self._prompts(cfg), self._mixed_sampling,
            speculation=SpecConfig(max_draft_tokens=3))
        for st in (st_u, st_a):
            sp = st["speculation"]
            assert sp["proposed_tokens"] > 0
            assert sp["proposed_tokens"] == (sp["accepted_tokens"]
                                             + sp["rolled_back_tokens"])
            assert sp["accepted_tokens"] > 0  # repetitive mix accepts

    def test_parity_full_composed_stack(self, tiny_model):
        """Prefix cache + speculation + W8A16 + int8 KV + mixed
        sampling, all at once — the full stack through one dispatch
        per round."""
        model, cfg = tiny_model
        self._assert_parity(
            model, self._prompts(cfg), self._mixed_sampling,
            speculation=True, enable_prefix_cache=True,
            quantization="w8a16", kv_dtype="int8")

    def test_parity_sharded_one_device_mesh(self, tiny_model):
        """sharding=True (1-device mesh) is bitwise the unsharded
        engine (r14) — the unified program must hold that through its
        explicit-shardings jit too."""
        model, cfg = tiny_model
        self._assert_parity(model, self._prompts(cfg, repetitive=False),
                            self._mixed_sampling, sharding=True)

    @pytest.mark.parametrize("mode", ["greedy", "sampled"])
    def test_async_frontdoor_preempt_resume_parity(self, tiny_model,
                                                   mode):
        """FrontDoor preemption + warm resume on the ASYNC engine: the
        in-flight round drains before swap-out, and the resumed
        request is token-identical to an uninterrupted run on the
        split engine."""
        from paddle_tpu.frontend import FrontDoor
        from paddle_tpu.sampling import SamplingParams

        model, cfg = tiny_model
        sp = (None if mode == "greedy" else
              SamplingParams(temperature=0.8, top_p=0.9,
                             repetition_penalty=1.3, seed=77))
        rs = np.random.RandomState(33)
        pv = rs.randint(1, cfg.vocab_size, (7,)).astype(np.int32)
        pi = rs.randint(1, cfg.vocab_size, (4,)).astype(np.int32)

        def build(**kw):
            return FrontDoor(model, max_slots=1, block_size=4,
                             max_prompt_len=16, max_new_tokens=24,
                             enable_prefix_cache=True, **kw).start()

        fd = build(async_rounds=True)
        try:
            hv = fd.submit(pv, lane="batch", sampling=sp,
                           max_new_tokens=24)
            it = iter(hv)
            next(it)
            next(it)  # victim has emitted >= 2 tokens
            hi = fd.submit(pi, lane="interactive", max_new_tokens=3)
            out_i = hi.result(timeout=300)
            out_v = hv.result(timeout=300)
            st = fd.stats()
            assert st["frontdoor"]["preemptions"] >= 1
            assert st["frontdoor"]["resumes"] >= 1
            assert st["rounds"]["dispatches_per_round"] == 1.0
        finally:
            fd.stop()
        fd2 = build()  # uninterrupted references on the SPLIT engine
        try:
            ref_v = fd2.submit(pv, lane="batch", sampling=sp,
                               max_new_tokens=24).result(timeout=300)
            ref_i = fd2.submit(pi, lane="interactive",
                               max_new_tokens=3).result(timeout=300)
        finally:
            fd2.stop()
        np.testing.assert_array_equal(out_v, ref_v)
        np.testing.assert_array_equal(out_i, ref_i)


class TestDispatchCount:
    def test_mixed_round_is_one_attention_dispatch(self, tiny_model):
        """THE acceptance criterion: a scheduler round containing
        prefill chunk rows, a plain decode row AND speculative verify
        work costs exactly ONE attention dispatch — and the split
        programs (packed_prefill / step / packed_verify / multistep)
        are never dispatched at all."""
        import threading

        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rng = np.random.RandomState(5)
        base = rng.randint(1, cfg.vocab_size, (5,)).astype(np.int32)
        pa = np.tile(base, 4)[:18].astype(np.int32)  # draftable
        pb = rng.randint(1, cfg.vocab_size, (15,)).astype(np.int32)
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_new_tokens=30,
                                    prefill_chunk_tokens=5,
                                    speculation=True,
                                    unified_round=True)
        calls = {"unified": 0, "split": 0}
        dec = srv._decoder
        real_unified = dec.unified_round

        def count_unified(*a, **k):
            calls["unified"] += 1
            return real_unified(*a, **k)

        def count_split(*a, **k):  # pragma: no cover — must not fire
            calls["split"] += 1
            raise AssertionError("split program dispatched on the "
                                 "unified engine")

        dec.unified_round = count_unified
        dec.packed_prefill = count_split
        dec.step = count_split
        dec.packed_verify = count_split
        first_tok = threading.Event()
        srv.start()
        try:
            fa = srv.submit(pa, on_token=lambda t, r: first_tok.set())
            assert first_tok.wait(timeout=120)
            # A is now decoding (with drafts — repetitive prompt);
            # B's 15-token prompt at a 5-token chunk budget spans 3+
            # rounds, every one interleaved with A's decode/verify row
            fb = srv.submit(pb)
            fa.result(timeout=300)
            fb.result(timeout=300)
            st = srv.stats()
        finally:
            srv.stop()
        rd = st["rounds"]
        assert rd["dispatches_per_round"] == 1.0, rd
        assert rd["attention_dispatches"] == rd["rounds"] == \
            calls["unified"]
        assert calls["split"] == 0
        # the mixed rounds actually happened (chunk + decode in one
        # dispatch), and speculation ran through the same dispatches
        assert rd["mixed_rounds"] >= 1, rd
        assert st["speculation"]["proposed_tokens"] > 0
        assert st["speculation"]["verify_dispatches"] >= 1

    def test_split_path_reports_multi_dispatch_rounds(self, tiny_model):
        """The split engine reports the SAME rounds schema, with > 1
        dispatch on mixed rounds — the number the unified axis
        collapses."""
        model, cfg = tiny_model
        rng = np.random.RandomState(5)
        prompts = [rng.randint(1, cfg.vocab_size, (15,)).astype(np.int32)
                   for _ in range(3)]
        outs, st = _serve(model, prompts, max_slots=2, block_size=4,
                          max_new_tokens=8, prefill_chunk_tokens=5)
        rd = st["rounds"]
        assert rd["unified"] is False and rd["async"] is False
        assert rd["rounds"] >= 1
        assert rd["attention_dispatches"] >= rd["rounds"]
        assert rd["overlap_seconds"] == 0.0
        if rd["mixed_rounds"]:
            assert rd["dispatches_per_round"] > 1.0


class TestAsyncSatellites:
    def test_warm_buckets_then_compile_clean_window(self, tiny_model):
        """Satellite: `warm_buckets()` pre-compiles the unified-round
        bucket space; a greedy serving window on the warmed server
        must then be compile-clean (the r15 tracker proves it)."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rng = np.random.RandomState(11)
        prompts = [rng.randint(1, cfg.vocab_size,
                               (int(rng.randint(3, 12)),)).astype(np.int32)
                   for _ in range(4)]
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_new_tokens=6,
                                    prefill_chunk_tokens=8,
                                    async_rounds=True)
        n = srv.warm_buckets()
        assert n >= 1
        srv.start()
        srv.reset_stats()
        try:
            for f in [srv.submit(p) for p in prompts]:
                f.result(timeout=300)
            st = srv.stats()
        finally:
            srv.stop()
        assert st["compiles"]["window_total"] == 0, st["compiles"]
        assert st["rounds"]["overlap_seconds"] > 0.0

    def test_rounds_stats_schema_and_reset(self, tiny_model):
        """The stats()["rounds"] block is schema-stable (zeroed when
        the engine runs split/idle) and reset-coherent."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_new_tokens=4)
        keys = {"unified", "async", "rounds", "attention_dispatches",
                "dispatches_per_round", "mixed_rounds",
                "overlap_seconds", "overlap_fraction"}
        rd = srv.stats()["rounds"]
        assert set(rd) == keys
        assert rd["rounds"] == 0 and rd["overlap_seconds"] == 0.0
        srv.start()
        try:
            srv.submit([1, 2, 3]).result(timeout=300)
            assert srv.stats()["rounds"]["rounds"] >= 1
            srv.reset_stats()
            rd = srv.stats()["rounds"]
            assert rd["rounds"] == 0
            assert rd["attention_dispatches"] == 0
            assert rd["mixed_rounds"] == 0
        finally:
            srv.stop()

    def test_unified_requires_single_step_dispatch(self, tiny_model):
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            PagedGenerationServer(model, unified_round=True,
                                  steps_per_dispatch=4)
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            PagedGenerationServer(model, async_rounds=True,
                                  steps_per_dispatch=2)
