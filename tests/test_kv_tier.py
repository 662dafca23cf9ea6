"""Host-RAM KV tier (long-context round tentpole, part b): demotion/
promotion round trips at the pool level, tiering ON == OFF token
parity at the engine level (forced demotion mid-run included),
prefetch-on-attach warm resume through the FrontDoor preempt path,
and fleet migration of a partially-tiered session.

Parity policy: an int8 pool round-trips through the tier BIT-EXACTLY
(the tier stores the native codes+scales); a dense pool rides the
`kv_quant` int8 codec — the same error envelope the quantized-KV
serving path is parity-tested under — so both are asserted
token-identical on pinned workloads.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import PagedGenerationServer
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.inference.kv_tier import (HostKVTier,
                                          disabled_tier_stats,
                                          normalize_kv_tier)
from paddle_tpu.models.gpt2 import GPT2, GPT2Config
from paddle_tpu.sampling import SamplingParams


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(11)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


def _fill_blocks(cache, seq, n_tokens, rng):
    """Write deterministic content through the functional pool arrays
    (the same .at[].set path the jitted writers take)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.kv_quant import kv_encode

    tbl = cache.block_table(seq)
    k, v = cache.k_blocks, cache.v_blocks
    for i, b in enumerate(tbl):
        rows = min(cache.block_size, n_tokens - i * cache.block_size)
        kk = rng.randn(cache.num_layers, rows, cache.num_heads,
                       cache.head_dim).astype(np.float32)
        vv = rng.randn(cache.num_layers, rows, cache.num_heads,
                       cache.head_dim).astype(np.float32)
        if cache.kv_dtype == "int8":
            kc, ks = kv_encode(jnp.asarray(kk))
            vc, vs = kv_encode(jnp.asarray(vv))
            # pool rows hold a token's heads side by side: [.., H*Dh]
            merged = (cache.num_layers, rows, -1)
            k = type(k)(k.codes.at[:, b, :rows].set(kc.reshape(merged)),
                        k.scales.at[:, b, :rows].set(ks))
            v = type(v)(v.codes.at[:, b, :rows].set(vc.reshape(merged)),
                        v.scales.at[:, b, :rows].set(vs))
        else:
            merged = (cache.num_layers, rows, -1)
            k = k.at[:, b, :rows].set(kk.reshape(merged))
            v = v.at[:, b, :rows].set(vv.reshape(merged))
    cache.swap_arrays(k, v)
    return {b: jax.tree.map(lambda a: np.asarray(a[:, b]),
                            cache.k_blocks) for b in tbl}


class TestTierPoolUnit:
    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_demote_promote_round_trip(self, kv_dtype):
        cache = PagedKVCache(2, 2, 4, block_size=4, num_blocks=8,
                             kv_dtype=kv_dtype,
                             tier=HostKVTier(capacity_blocks=16,
                                             watermark=0.0))
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 100, size=11)
        cache.allocate("s", 11)
        tbl = cache.block_table("s")
        snap = _fill_blocks(cache, "s", 11, rng)
        cache.publish_prefix("s", ids)
        cache.free("s")
        avail0 = cache.available_block_count
        assert cache.demote_cold(10) == 3
        # admission arithmetic is INVARIANT under tiering: each
        # demotion moved a block retained -> free
        assert cache.available_block_count == avail0
        assert cache.retained_block_count == 0
        assert len(cache.tier) == 3 and not cache._index
        st = cache.stats()["tier"]
        assert st["enabled"] and st["demotions"] == 3
        assert st["tiered_tokens"] == 11
        # prefetch-on-match promotes the whole chain back
        assert cache.match_prefix_len(ids) == 10  # len-1 cap
        st = cache.stats()["tier"]
        assert st["promotions"] == 3 and st["hit_tokens"] == 10
        assert len(cache.tier) == 0
        assert cache.attach_prefix("t", ids) == 10
        tbl2 = cache.block_table("t")
        import jax

        for bi, (b_old, b_new) in enumerate(zip(tbl, tbl2)):
            rows = min(4, 11 - bi * 4)
            old, new = snap[b_old], jax.tree.map(
                lambda a: np.asarray(a[:, b_new]), cache.k_blocks)
            if kv_dtype == "int8":
                # native codes+scales round trip is bit-exact
                assert np.array_equal(old.codes[:, :rows],
                                      new.codes[:, :rows])
                assert np.array_equal(old.scales[:, :rows],
                                      new.scales[:, :rows])
            else:
                # dense pool: kv_quant bound |x - deq| <= absmax/254
                err = np.abs(old[:, :rows] - new[:, :rows])
                assert err.max() <= np.abs(old[:, :rows]).max() / 254 \
                    + 1e-6

    def test_watermark_sweep_on_release(self):
        cache = PagedKVCache(2, 2, 4, block_size=4, num_blocks=6,
                             tier=HostKVTier(capacity_blocks=8,
                                             watermark=0.9))
        cache.allocate("a", 9)
        cache.publish_prefix("a", np.arange(9))
        cache.free("a")
        # low = 0.9 * 5 = 4: free() left free=2, the sweep demotes
        # until free recovers to 4, leaving one retained
        assert cache.free_block_count == 4
        assert cache.retained_block_count == 1
        assert len(cache.tier) == 2

    def test_tier_capacity_lru_evicts(self):
        cache = PagedKVCache(1, 1, 2, block_size=4, num_blocks=8,
                             tier=HostKVTier(capacity_blocks=2,
                                             watermark=0.0))
        for i, tok0 in enumerate((0, 100, 200)):
            cache.allocate(i, 8)
            cache.publish_prefix(i, np.arange(tok0, tok0 + 8))
            cache.free(i)
            cache.demote_cold(4)
        assert len(cache.tier) == 2
        assert cache.tier.evictions > 0
        # the first chain is truly gone — no match, no promotion
        assert cache.match_prefix_len(np.arange(0, 9)) == 0

    def test_republish_drops_stale_tier_copy(self):
        """Move semantics: a hash re-published on device evicts the
        tier's stale copy (never resident in both indexes)."""
        cache = PagedKVCache(1, 1, 2, block_size=4, num_blocks=8,
                             tier=HostKVTier(capacity_blocks=8,
                                             watermark=0.0))
        ids = np.arange(8)
        cache.allocate("a", 8)
        cache.publish_prefix("a", ids)
        cache.free("a")
        cache.demote_cold(2)
        assert len(cache.tier) == 2
        cache.allocate("b", 8)
        cache.publish_prefix("b", ids)   # same content, new blocks
        assert len(cache.tier) == 0      # stale copies dropped
        assert not set(cache._index) & set(cache.tier._entries)

    def test_stats_zeroed_when_disabled(self):
        plain = PagedKVCache(1, 1, 2, block_size=4, num_blocks=4)
        tiered = PagedKVCache(1, 1, 2, block_size=4, num_blocks=4,
                              tier=True)
        off, on = plain.stats()["tier"], tiered.stats()["tier"]
        assert set(off) == set(on)       # congruent schema
        assert off == disabled_tier_stats()
        assert off["enabled"] is False and on["enabled"] is True
        assert all(off[k] == 0 for k in off if k != "enabled")

    def test_normalize_and_validation(self):
        assert normalize_kv_tier(None) is None
        assert isinstance(normalize_kv_tier(True), HostKVTier)
        t = HostKVTier(capacity_blocks=3)
        assert normalize_kv_tier(t) is t
        with pytest.raises(TypeError, match="HostKVTier"):
            normalize_kv_tier("big")
        with pytest.raises(ValueError, match="capacity_blocks"):
            HostKVTier(capacity_blocks=0)
        with pytest.raises(ValueError, match="watermark"):
            HostKVTier(watermark=1.5)

    def test_tier_gauges_and_counters(self):
        from paddle_tpu.observability import metrics

        was = metrics.enabled()
        metrics.enable()
        try:
            cache = PagedKVCache(1, 1, 2, block_size=4, num_blocks=8,
                                 tier=HostKVTier(capacity_blocks=8,
                                                 watermark=0.0))
            cache.allocate("a", 8)
            cache.publish_prefix("a", np.arange(8))
            cache.free("a")
            cache.demote_cold(2)
            cache.match_prefix_len(np.arange(9))
            text = metrics.to_prometheus()
            p = cache._name
            assert f'kv_pool_retained_blocks{{pool="{p}",' \
                f'tier="device"}}' in text
            assert f'kv_pool_retained_blocks{{pool="{p}",' \
                f'tier="host"}}' in text
            assert f'kv_tier_demotions_total{{pool="{p}"}} 2' in text
            assert f'kv_tier_promotions_total{{pool="{p}"}} 2' in text
            assert f'kv_tier_bytes_total{{pool="{p}",' \
                f'direction="out"}}' in text
            assert f'kv_tier_bytes_total{{pool="{p}",' \
                f'direction="in"}}' in text
            assert f'kv_tier_hit_tokens_total{{pool="{p}"}} 8' in text
        finally:
            if not was:
                metrics.disable()


def _serve(model, prompts, sps=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_prompt_len", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("prefill_chunk_tokens", 16)
    srv = PagedGenerationServer(model, **kw).start()
    try:
        sps = sps or [None] * len(prompts)
        outs = [f.result(timeout=600).tolist() for f in
                [srv.submit(p, sampling=s)
                 for p, s in zip(prompts, sps)]]
        st = srv.stats()
    finally:
        srv.stop()
    return outs, st


class TestTierServingParity:
    def test_ctor_requires_prefix_cache(self, tiny_model):
        model, _ = tiny_model
        with pytest.raises(ValueError, match="enable_prefix_cache"):
            PagedGenerationServer(model, kv_tier=True)

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_tier_on_off_token_parity_forced_demotion(self, tiny_model,
                                                      kv_dtype):
        """Tiering ON == OFF token-identical on a pool sized so
        demotion fires MID-RUN (shared-prefix churn under a high
        watermark), greedy + fixed-seed sampled."""
        model, cfg = tiny_model
        rng = np.random.RandomState(7)
        shared = rng.randint(1, cfg.vocab_size, (24,)).astype(np.int32)
        prompts = [np.concatenate([shared, rng.randint(
            1, cfg.vocab_size, (k,)).astype(np.int32)])
            for k in (3, 5, 7, 4)]
        sps = [None,
               SamplingParams(temperature=0.9, top_p=0.9, seed=5),
               None, None]
        kw = dict(enable_prefix_cache=True, num_blocks=14,
                  max_prompt_len=40, kv_dtype=kv_dtype)

        def run(tier):
            srv = PagedGenerationServer(model, max_slots=2,
                                        block_size=8, max_new_tokens=6,
                                        prefill_chunk_tokens=16,
                                        kv_tier=tier, **kw)
            outs = []
            srv.start()
            try:
                for p, s in zip(prompts, sps):  # sequential churn
                    outs.append(srv.submit(p, sampling=s)
                                .result(timeout=600).tolist())
                batch = [srv.submit(p, sampling=s)
                         for p, s in zip(prompts, sps)]
                outs += [f.result(timeout=600).tolist() for f in batch]
            finally:
                srv.stop()
            return outs, srv.stats()

        off, _ = run(None)
        on, st = run(HostKVTier(capacity_blocks=32, watermark=0.5))
        assert on == off
        t = st["kv_cache"]["tier"]
        assert t["demotions"] > 0, "pool never demoted — dead test"
        assert t["promotions"] > 0 and t["hit_tokens"] > 0

    def test_warm_resume_promotes_after_demotion(self, tiny_model):
        """swap-out -> full demotion -> resubmit: the attach promotes
        the tiered chain (prefetch-on-attach) and the resumed request
        is token-identical to solo generate."""
        model, cfg = tiny_model
        rng = np.random.RandomState(9)
        prompt = rng.randint(1, cfg.vocab_size, (21,)).astype(np.int32)
        srv = PagedGenerationServer(
            model, max_slots=1, block_size=8, max_prompt_len=32,
            max_new_tokens=6, enable_prefix_cache=True,
            kv_tier=HostKVTier(capacity_blocks=16, watermark=0.0),
            prefill_chunk_tokens=16).start()
        try:
            first = srv.submit(prompt).result(timeout=600)
            # completion published the prompt; force it out to host
            assert srv.cache.demote_cold(16) > 0
            assert srv.cache.retained_block_count == 0
            again = srv.submit(prompt).result(timeout=600)
            st = srv.stats()
        finally:
            srv.stop()
        np.testing.assert_array_equal(first, again)
        np.testing.assert_array_equal(
            first, model.generate(prompt[None], 6).numpy()[0])
        t = st["kv_cache"]["tier"]
        assert t["promotions"] > 0 and t["hit_tokens"] > 0
        assert st["kv_cache"]["prefix_cache"]["hit_tokens"] > 0

    def test_frontdoor_preempt_resume_with_tier(self, tiny_model):
        """The r12 preempt path composed with tiering: the victim's
        swap-out content survives pool pressure in the tier and the
        resume stays token-identical to solo generate."""
        from paddle_tpu.frontend import FrontDoor

        model, cfg = tiny_model
        rs = np.random.RandomState(2)
        pv = rs.randint(1, cfg.vocab_size, (1, 7)).astype(np.int32)[0]
        pi = rs.randint(1, cfg.vocab_size, (1, 4)).astype(np.int32)[0]
        fd = FrontDoor(model, max_slots=1, block_size=4,
                       max_prompt_len=16, max_new_tokens=24,
                       enable_prefix_cache=True,
                       kv_tier=HostKVTier(capacity_blocks=16,
                                          watermark=0.6)).start()
        try:
            hv = fd.submit(pv, lane="batch", max_new_tokens=24)
            it = iter(hv)
            next(it)
            next(it)
            hi_ = fd.submit(pi, lane="interactive", max_new_tokens=3)
            out_i = hi_.result(timeout=600)
            out_v = hv.result(timeout=600)
            st = fd.stats()
            assert st["frontdoor"]["preemptions"] >= 1
            assert st["frontdoor"]["resumes"] >= 1
        finally:
            fd.stop()
        np.testing.assert_array_equal(
            out_v, model.generate(pv[None], 24).numpy()[0])
        np.testing.assert_array_equal(
            out_i, model.generate(pi[None], 3).numpy()[0])

    def test_migration_of_partially_tiered_session(self, tiny_model):
        """Fleet export/import with half the chain in the tier: the
        source promotes its tiered continuation before serializing, so
        the target resumes with the full prefix warm."""
        model, cfg = tiny_model
        rng = np.random.RandomState(4)
        prompt = rng.randint(1, cfg.vocab_size, (21,)).astype(np.int32)
        mk = dict(max_slots=1, block_size=8, max_prompt_len=32,
                  max_new_tokens=6, enable_prefix_cache=True,
                  prefill_chunk_tokens=16)
        src = PagedGenerationServer(
            model, kv_tier=HostKVTier(capacity_blocks=16,
                                      watermark=0.0), **mk).start()
        try:
            first = src.submit(prompt).result(timeout=600)
            assert src.cache.demote_cold(1) == 1  # PARTIALLY tiered
            assert len(src.cache.tier) >= 1
            payload = src.cache.export_prefix(prompt)
        finally:
            src.stop()
        assert payload is not None
        assert sum(payload["fills"]) >= prompt.size - 1
        dst = PagedGenerationServer(model, **mk).start()
        try:
            assert dst.cache.import_prefix(payload) \
                == sum(payload["fills"])
            out = dst.submit(prompt).result(timeout=600)
            st = dst.stats()
        finally:
            dst.stop()
        np.testing.assert_array_equal(first, out)
        assert st["kv_cache"]["prefix_cache"]["hit_tokens"] \
            >= prompt.size - srv_tail(payload)


def srv_tail(payload):
    """Matchable slack: the attach cap (last prompt token is always
    recomputed) plus a possible partial-tail stop."""
    return payload["block_size"] + 1


class TestTierPrefetchAhead:
    """Overlapped tier prefetch-ahead (memory-flat long-context round,
    part b): a QUEUED request's cold tier blocks promote into the
    device pool while the current round computes, so admission's
    attach finds them resident — token-identical either way (the
    synchronous promote-on-attach path remains the fallback)."""

    def test_ctor_validation(self, tiny_model):
        model, _ = tiny_model
        with pytest.raises(ValueError, match="tier_prefetch"):
            PagedGenerationServer(model, tier_prefetch=True,
                                  enable_prefix_cache=True)
        with pytest.raises(ValueError, match="tier_prefetch"):
            PagedGenerationServer(model, tier_prefetch=0, kv_tier=True,
                                  enable_prefix_cache=True)

    def test_prefetch_ahead_hits_and_token_parity(self, tiny_model):
        """Demote a finished prompt's chain, occupy the only slot, and
        queue the same prompt again: the prefetch tick promotes the
        chain DURING the occupier's rounds, the admission settles every
        block as a hit, and the tokens match the first run exactly."""
        model, cfg = tiny_model
        rng = np.random.RandomState(9)
        prompt = rng.randint(1, cfg.vocab_size, (21,)).astype(np.int32)
        other = rng.randint(1, cfg.vocab_size, (5,)).astype(np.int32)
        srv = PagedGenerationServer(
            model, max_slots=1, block_size=8, max_prompt_len=32,
            max_new_tokens=16, enable_prefix_cache=True,
            kv_tier=HostKVTier(capacity_blocks=16, watermark=0.0),
            tier_prefetch=True, prefill_chunk_tokens=16,
            flight_recorder=True).start()
        try:
            first = srv.submit(prompt).result(timeout=600)
            assert srv.cache.demote_cold(16) > 0
            fa = srv.submit(other)   # occupies the single slot
            fb = srv.submit(prompt)  # queued behind it -> prefetched
            fa.result(timeout=600)
            again = fb.result(timeout=600)
            st = srv.stats()
            ring = [e for e in srv._recorder.events()
                    if e["name"] == "tier_promote"]
        finally:
            srv.stop()
        np.testing.assert_array_equal(first, again)
        tp = st["tier_prefetch"]
        assert tp["enabled"] and tp["lookahead"] == 2
        assert tp["issued_blocks"] > 0, "prefetch never fired"
        assert tp["hit_blocks"] == tp["issued_blocks"]
        assert tp["hit_rate"] > 0.8
        assert tp["overlap_promote_s"] > 0.0
        # the overlapped batch recorded its own aggregated event with
        # byte/block accounting (satellite: promote time is no longer
        # silently folded into the admission span)
        ov = [e for e in ring if e.get("overlapped")]
        assert ov and ov[0]["blocks"] > 0 and ov[0]["bytes"] > 0
        assert ov[0]["dur_s"] > 0

    def test_sync_promote_event_split_from_admission(self, tiny_model):
        """Fix satellite: the synchronous promote-on-attach walk now
        emits a dedicated `tier_promote` trace event carrying the
        request id, and the assembler reports it as a parallel
        `tier_promote_ms` annotation (the compile_overlap_ms
        discipline — phase tiling of wall clock is untouched)."""
        from paddle_tpu.observability import tracing as T

        model, cfg = tiny_model
        rng = np.random.RandomState(13)
        prompt = rng.randint(1, cfg.vocab_size, (21,)).astype(np.int32)
        T.TRACER.reset()
        T.enable()
        try:
            srv = PagedGenerationServer(
                model, max_slots=1, block_size=8, max_prompt_len=32,
                max_new_tokens=4, enable_prefix_cache=True,
                kv_tier=HostKVTier(capacity_blocks=16, watermark=0.0),
                prefill_chunk_tokens=16).start()
            try:
                srv.submit(prompt).result(timeout=600)
                assert srv.cache.demote_cold(16) > 0
                srv.submit(prompt).result(timeout=600)
            finally:
                srv.stop()
            evs = T.events()
            proms = [e for e in evs if e.get("name") == "tier_promote"]
            assert proms, "sync attach promoted without the event"
            ev = proms[-1]
            assert ev["blocks"] > 0 and ev["bytes"] > 0
            assert ev["overlapped"] is False
            assert ev.get("request_id"), "promote not attributed"
            traces = T.assemble_request_traces(evs)
            rec = traces[ev["request_id"]]
            assert rec["tier_promote_ms"] > 0
            assert rec["tier_promote_blocks"] == ev["blocks"]
            # parallel annotation: the phase breakdown still tiles the
            # request's wall clock (same approx bar as
            # test_observability) — tier_promote_ms rides alongside, it
            # is not a sixth phase
            assert "tier_promote" not in rec["phases_ms"]
            assert sum(rec["phases_ms"].values()) == \
                pytest.approx(rec["wall_ms"], rel=0.10)
        finally:
            T.disable()
            T.TRACER.reset()

    def test_wasted_on_timeout_expiry(self, tiny_model):
        """A queued request that times out before admission settles its
        prefetched blocks as wasted (the blocks themselves just age in
        prefix-index retention)."""
        model, cfg = tiny_model
        rng = np.random.RandomState(17)
        prompt = rng.randint(1, cfg.vocab_size, (21,)).astype(np.int32)
        other = rng.randint(1, cfg.vocab_size, (5,)).astype(np.int32)
        from paddle_tpu.inference.serving import RequestTimeout

        srv = PagedGenerationServer(
            model, max_slots=1, block_size=8, max_prompt_len=32,
            max_new_tokens=24, enable_prefix_cache=True,
            kv_tier=HostKVTier(capacity_blocks=16, watermark=0.0),
            tier_prefetch=True, prefill_chunk_tokens=16).start()
        try:
            srv.submit(prompt).result(timeout=600)
            assert srv.cache.demote_cold(16) > 0
            fa = srv.submit(other)
            fb = srv.submit(prompt, timeout_s=0.01)
            with pytest.raises(RequestTimeout):
                fb.result(timeout=600)
            fa.result(timeout=600)
            st = srv.stats()
        finally:
            srv.stop()
        tp = st["tier_prefetch"]
        if tp["issued_blocks"]:  # timing-dependent: only assert the
            # settlement bookkeeping when the tick beat the expiry
            assert tp["issued_blocks"] == (tp["hit_blocks"]
                                           + tp["wasted_blocks"])

    def test_stats_schema_zeroed_when_disabled(self, tiny_model):
        model, _ = tiny_model
        srv = PagedGenerationServer(model, max_slots=1,
                                    max_prompt_len=16,
                                    max_new_tokens=4)
        off = srv.stats()["tier_prefetch"]
        assert off["enabled"] is False
        assert all(off[k] == 0 for k in off if k != "enabled")
        assert set(off) == {"enabled", "lookahead", "issued_blocks",
                            "hit_blocks", "wasted_blocks", "hit_rate",
                            "overlap_promote_s"}

    def test_prefetch_fires_under_frontdoor_lane_scheduler(self,
                                                           tiny_model):
        """ROADMAP 5d: with the r12 `LaneScheduler` installed the
        prefetch tick used to return early (it only knew how to read
        the FIFO queue), so fronted deployments silently lost the
        overlap. The scheduler now exposes a non-popping `peek` and the
        tick walks that instead — queued-behind-busy requests promote
        their cold chains under `FrontDoor` exactly as under plain
        FIFO, and lane/tenant accounting is untouched by the peek."""
        from paddle_tpu.frontend import FrontDoor

        model, cfg = tiny_model
        rng = np.random.RandomState(23)
        prompt = rng.randint(1, cfg.vocab_size, (21,)).astype(np.int32)
        other = rng.randint(1, cfg.vocab_size, (5,)).astype(np.int32)
        fd = FrontDoor(
            model, max_slots=1, block_size=8, max_prompt_len=32,
            max_new_tokens=16, enable_prefix_cache=True,
            kv_tier=HostKVTier(capacity_blocks=16, watermark=0.0),
            tier_prefetch=True, prefill_chunk_tokens=16).start()
        try:
            first = fd.submit(prompt, lane="batch").result(timeout=600)
            assert fd.server.cache.demote_cold(16) > 0
            # occupy the single slot, then queue the demoted prompt on
            # a different lane/tenant: only the scheduler (not the
            # FIFO queue) knows it is pending, so a hit here proves
            # the peek-based look-ahead path
            fa = fd.submit(other, lane="interactive", tenant="a")
            fb = fd.submit(prompt, lane="batch", tenant="b")
            fa.result(timeout=600)
            again = fb.result(timeout=600)
            st = fd.stats()
        finally:
            fd.stop()
        np.testing.assert_array_equal(first, again)
        tp = st["tier_prefetch"]
        assert tp["issued_blocks"] > 0, \
            "prefetch never fired under the lane scheduler"
        assert tp["hit_blocks"] == tp["issued_blocks"]
        assert tp["hit_rate"] > 0.8
        # peeking never popped or charged anyone: all three requests
        # completed through normal lane admission with TTFT samples on
        # both lanes, and no tenant was rate-skipped by the look-ahead
        lanes = st["frontdoor"]["lanes"]
        assert lanes["batch"]["ttft"]["n"] == 2
        assert lanes["interactive"]["ttft"]["n"] == 1
        assert st["frontdoor"]["rate_throttled_skips"] == 0
        assert st["requests"] == 3
