"""PagedGenerationServer: continuous batching over the block-pool KV
cache. CPU-sized tier-1 smoke of the full loop (submit -> prefill ->
ragged decode -> EOS/budget -> slot refill -> block free), correctness
vs solo generate, EOS slot refill and reservation-based admission."""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt2 import GPT2, GPT2Config


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(11)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


class TestContinuousBatching:
    def test_smoke_mixed_lengths_match_solo_generate(self, tiny_model):
        """Tier-1 smoke of the whole continuous-batching loop: more
        requests than slots, mixed lengths, every output must equal the
        dense-path solo generate for that prompt (NO padding anywhere in
        the paged path)."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(1)
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=16,
                                    max_new_tokens=5).start()
        try:
            prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                       for n in (3, 7, 5, 9, 16)]
            futs = [srv.submit(p) for p in prompts]
            outs = [f.result(timeout=300) for f in futs]
            for p, o in zip(prompts, outs):
                ref = model.generate(p[None], 5).numpy()[0]
                np.testing.assert_array_equal(o, ref)
            st = srv.stats()
            assert st["requests"] == 5
            assert st["new_tokens"] == 25
            assert st["prefills"] == 5
            # 5 requests through 2 slots: slots MUST have been refilled
            assert st["slot_fill"] > 0.5
            # every block returned to the pool at drain
            assert st["kv_cache"]["used_blocks"] == 0
            assert st["kv_cache"]["peak_used_blocks"] >= 2
        finally:
            srv.stop()

    def test_eos_frees_slot_early_and_refills(self, tiny_model):
        """Force EOS on the first generated token of every request: each
        slot must resolve after ~1 token (not hold for max_new) and be
        refilled from the queue; token budgets say the padded server
        would have spent 5x the decode steps."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(2)
        prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (4, 6, 8, 5)]
        # find each prompt's first greedy token; use it as "eos" for that
        # submission via a server whose eos matches the FIRST prompt
        first = int(model.generate(prompts[0][None], 1).numpy()[0, -1])
        srv = PagedGenerationServer(model, max_slots=1, block_size=4,
                                    max_prompt_len=8, max_new_tokens=5,
                                    eos_token_id=first).start()
        try:
            out = srv.submit(prompts[0]).result(timeout=300)
            # terminated AT the eos token, long before the 5-token budget
            assert out.shape[0] == prompts[0].size + 1
            assert out[-1] == first
            st = srv.stats()
            assert st["new_tokens"] == 1
            # the single slot is free again: a second request runs
            out2 = srv.submit(prompts[1]).result(timeout=300)
            assert out2.shape[0] >= prompts[1].size + 1
        finally:
            srv.stop()

    def test_admission_respects_block_reservation(self, tiny_model):
        """A pool too small for two worst-case requests must serve them
        SEQUENTIALLY (second waits for the first's blocks), not crash
        mid-flight."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(3)
        # worst case per request: ceil((8 + 4)/4) = 3 blocks; pool of 4
        # usable blocks fits one request at a time (plus trash)
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=8, max_new_tokens=4,
                                    num_blocks=5).start()
        try:
            prompts = [rs.randint(1, cfg.vocab_size, (8,)).astype(np.int32)
                       for _ in range(3)]
            futs = [srv.submit(p) for p in prompts]
            outs = [f.result(timeout=300) for f in futs]
            for p, o in zip(prompts, outs):
                ref = model.generate(p[None], 4).numpy()[0]
                np.testing.assert_array_equal(o, ref)
            st = srv.stats()
            assert st["kv_cache"]["used_blocks"] == 0
            assert st["kv_cache"]["peak_used_blocks"] <= 4
        finally:
            srv.stop()

    def test_multistep_dispatch_matches_single_step(self, tiny_model):
        """steps_per_dispatch > 1 (multi-step scheduling) must produce
        identical sequences — the post-EOS/budget overrun tokens are
        discarded host-side."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(4)
        prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (3, 9, 6)]
        outs = {}
        for k in (1, 4):
            srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                        max_prompt_len=12,
                                        max_new_tokens=6,
                                        steps_per_dispatch=k).start()
            try:
                outs[k] = [f.result(timeout=300)
                           for f in [srv.submit(p) for p in prompts]]
            finally:
                srv.stop()
        for a, b in zip(outs[1], outs[4]):
            np.testing.assert_array_equal(a, b)

    def test_admission_burst_is_one_packed_prefill_dispatch(self,
                                                            tiny_model):
        """ISSUE 3 acceptance: an admission burst of N requests must
        cost O(1) packed prefill dispatches, not N sequential B=1
        dispatches — all N prompts here fit one chunk budget, so the
        whole burst is exactly ONE dispatch."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(7)
        prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (3, 5, 4, 6)]
        srv = PagedGenerationServer(model, max_slots=4, block_size=4,
                                    max_prompt_len=8, max_new_tokens=3,
                                    prefill_chunk_tokens=64)
        futs = [srv.submit(p) for p in prompts]  # burst BEFORE start
        srv.start()
        try:
            for p, f in zip(prompts, futs):
                ref = model.generate(p[None], 3).numpy()[0]
                np.testing.assert_array_equal(f.result(timeout=300), ref)
            st = srv.stats()
            assert st["prefills"] == 4
            assert st["prefill_dispatches"] == 1
        finally:
            srv.stop()

    def test_chunked_prefill_spans_multiple_dispatches(self, tiny_model):
        """A prompt longer than the chunk budget must be prefilled
        across 3+ chunk dispatches (partial K/V carried in the paged
        cache) and still match solo generate token-for-token; a prompt
        shorter than one chunk rides along unharmed."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(8)
        long_p = rs.randint(1, cfg.vocab_size, (15,)).astype(np.int32)
        short_p = rs.randint(1, cfg.vocab_size, (3,)).astype(np.int32)
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=16, max_new_tokens=4,
                                    prefill_chunk_tokens=5).start()
        try:
            futs = [srv.submit(long_p), srv.submit(short_p)]
            for p, f in zip((long_p, short_p), futs):
                ref = model.generate(p[None], 4).numpy()[0]
                np.testing.assert_array_equal(f.result(timeout=300), ref)
            st = srv.stats()
            # 15-token prompt at a 5-token budget: >= 3 chunk dispatches
            assert st["prefill_dispatches"] >= 3
            assert st["prefills"] == 2
        finally:
            srv.stop()

    def test_itl_stats_populated(self, tiny_model):
        """stats() must carry the inter-token-latency percentiles the
        chunk-budget knob is tuned against."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(9)
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=8,
                                    max_new_tokens=6).start()
        try:
            srv.submit(rs.randint(1, cfg.vocab_size, (4,))
                       .astype(np.int32)).result(timeout=300)
            st = srv.stats()
            assert 0 < st["itl_p50_ms"] <= st["itl_p99_ms"]
            srv.reset_stats()
            assert srv.stats()["itl_p99_ms"] == 0.0
        finally:
            srv.stop()

    def test_failed_prefill_cleans_up_and_serves_on(self, tiny_model,
                                                    monkeypatch):
        """The failed-request cleanup path (satellite: has_seq, not
        _tables reach-in): with the recovery ladder DISABLED (r17:
        recovery=False pins the legacy blast radius — the default now
        retries instead), a packed prefill dispatch that raises must
        fail exactly the chunk's requests, return their blocks to the
        pool, and leave the server serving later requests."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(10)
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=8, max_new_tokens=3,
                                    recovery=False)
        boom = {"armed": True}
        real = srv._decoder.packed_prefill

        def flaky(*a, **kw):
            if boom.pop("armed", False):
                raise RuntimeError("injected prefill failure")
            return real(*a, **kw)

        monkeypatch.setattr(srv._decoder, "packed_prefill", flaky)
        srv.start()
        try:
            bad = srv.submit(rs.randint(1, cfg.vocab_size, (5,))
                             .astype(np.int32))
            with pytest.raises(RuntimeError, match="injected"):
                bad.result(timeout=300)
            assert srv.cache.stats()["used_blocks"] == 0
            assert not srv.cache.has_seq(0)
            p = rs.randint(1, cfg.vocab_size, (4,)).astype(np.int32)
            ref = model.generate(p[None], 3).numpy()[0]
            np.testing.assert_array_equal(
                srv.submit(p).result(timeout=300), ref)
        finally:
            srv.stop()

    def test_concurrent_clients(self, tiny_model):
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        rs = np.random.RandomState(5)
        prompts = [rs.randint(1, cfg.vocab_size,
                              (int(rs.randint(2, 12)),)).astype(np.int32)
                   for _ in range(6)]
        srv = PagedGenerationServer(model, max_slots=3, block_size=4,
                                    max_prompt_len=12,
                                    max_new_tokens=4).start()
        results = [None] * len(prompts)
        try:
            def client(i):
                results[i] = srv.submit(prompts[i]).result(timeout=300)

            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(len(prompts))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            for i, p in enumerate(prompts):
                ref = model.generate(p[None], 4).numpy()[0]
                np.testing.assert_array_equal(results[i], ref)
        finally:
            srv.stop()

    def test_stop_and_validation(self, tiny_model):
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        srv = PagedGenerationServer(model, max_slots=1, block_size=4,
                                    max_prompt_len=8, max_new_tokens=4)
        with pytest.raises(ValueError):
            srv.submit([])
        with pytest.raises(ValueError):
            srv.submit(list(range(9)))  # > max_prompt_len
        with pytest.raises(ValueError):
            srv.submit([1, 2], max_new_tokens=99)  # > max_new budget
        srv.start()
        srv.stop()
        with pytest.raises(RuntimeError):
            srv.submit([1, 2, 3])

    def test_open_loop_poisson_drive_accounts_every_arrival(self,
                                                            tiny_model):
        """`measure_poisson_load`: fixed-seed Poisson arrivals while
        earlier requests decode. Every arrival is served, the window's
        stats carry the offered and achieved rates beside the engine's
        latency and dispatch counts, and the same seed draws the same
        gaps (the offer is paced by them, so the achieved rate cannot
        exceed a burst's)."""
        from paddle_tpu.inference import (PagedGenerationServer,
                                          measure_poisson_load)

        model, cfg = tiny_model
        rs = np.random.RandomState(3)
        prompts = [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
                   for n in (3, 9, 5)]
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=16,
                                    max_new_tokens=4).start()
        try:
            out = measure_poisson_load(srv, prompts, offered_rps=100.0,
                                       n_requests=8, seed=5,
                                       max_new_tokens=3)
            assert out["requests"] == 8
            assert out["new_tokens"] == 8 * 3
            assert out["prefills"] == 8
            assert out["prefill_dispatches"] >= 1
            assert out["offered_rps"] == 100.0
            gaps = np.random.RandomState(5).exponential(1 / 100.0, 8)
            assert 0 < out["achieved_rps"] <= 8 / gaps.sum() * 1.001
            assert out["ttft_p99_ms"] >= out["ttft_p50_ms"] > 0
            assert out["kv_cache"]["used_blocks"] == 0
        finally:
            srv.stop()
