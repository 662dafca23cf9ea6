"""PagedGenerationServer: continuous batching over the block-pool KV
cache. CPU-sized tier-1 smoke of the full loop (submit -> prefill ->
ragged decode -> EOS/budget -> slot refill -> block free), correctness
vs solo generate, EOS slot refill, reservation-based admission, and the
slow-marked served-traffic bench axis."""
import json
import os
import subprocess
import sys
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


def _run_served_bench(*args, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "bench.py", "served", *args],
                       env=env, capture_output=True, text=True,
                       timeout=timeout,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    return [json.loads(ln) for ln in lines], r.stdout


@pytest.mark.slow
def test_served_bench_axis_emits_records():
    """`bench.py served` (mixed-length traffic: padded vs paged
    closed-loop, the open-loop Poisson axis, the shared-prefix caching
    axis, the round-11 speculation axis, the round-12 front-door
    axis, the quantization axis, the sharded mesh axis, the r18
    fleet axis, and the r21 long-context axis) must emit all the JSON
    records; slow-marked so tier-1 stays fast."""
    recs, stdout = _run_served_bench()
    assert len(recs) == 15, stdout
    assert any("paged" in rec["metric"] for rec in recs)
    assert any("elastic" in rec["metric"] for rec in recs)
    assert any("fleetprocs" in rec["metric"] for rec in recs)
    assert any("longcontext" in rec["metric"] for rec in recs)
    assert any("quantcollectives" in rec["metric"] for rec in recs)
    assert any("fleet" in rec["metric"] for rec in recs)
    assert any("unifiedround" in rec["metric"] for rec in recs)
    assert any("mixedsampling" in rec["metric"] for rec in recs)
    assert any("openloop" in rec["metric"] for rec in recs)
    assert any("sharedprefix" in rec["metric"] for rec in recs)
    assert any("speculative" in rec["metric"] for rec in recs)
    assert any("frontdoor" in rec["metric"] for rec in recs)
    assert any("quantized" in rec["metric"] for rec in recs)
    assert any("sharded" in rec["metric"] for rec in recs)
    for rec in recs:
        assert rec["value"] > 0
        assert rec.get("degraded") is True
        assert "p99_ms" in rec or "sharedprefix" in rec["metric"]
    # the quantization acceptance bar (CPU-provable form): >= 1.8x
    # worst-case slot reservations at the bf16 pool's byte budget,
    # with near-perfect greedy agreement on the served workload (the
    # >= 1.3x tok/s form needs the chip's int8 MXU — rerun queued)
    qz = next(r for r in recs if "quantized" in r["metric"])
    assert qz["slot_capacity_ratio"] >= 1.8, qz
    assert qz["greedy_token_match"] >= 0.9, qz
    assert qz["greedy_token_match_w8a16"] >= 0.98, qz
    # the speculation acceptance bar: >= 1.5x served tok/s vs plain
    # decode on the repetitive mix (CPU-degraded run of the
    # dispatch-bound proxy; the chip run may beat it)
    spec = next(r for r in recs if "speculative" in r["metric"])
    assert spec["vs_baseline"] >= 1.5, spec
    assert spec["tok_s_ratio_oracle"] >= spec["vs_baseline"] * 0.9
    # the front-door acceptance bars (round 12): under the adversarial
    # bully-burst + bursty-Poisson mix at identical arrivals, the
    # interactive lane's TTFT p99 must be >= 3x better than the
    # single-lane FIFO engine while the batch lane keeps >= 85% of its
    # throughput, with preemption actually exercised
    fd = next(r for r in recs if "frontdoor" in r["metric"])
    assert fd["vs_baseline"] >= 3.0, fd
    assert fd["batch_throughput_ratio"] >= 0.85, fd
    assert fd["preemptions"] >= 1, fd
    assert fd["resumes"] >= 1, fd
    assert fd["preempt_cached_tokens"] > 0, fd
    # the unified-round acceptance bars (r16): exactly ONE attention
    # dispatch per round, >= 1.15x served tok/s and no-worse ITL p99
    # vs the split engine at identical arrivals, with the measured
    # window compile-clean (warm_buckets covered the bucket space)
    un = next(r for r in recs if "unifiedround" in r["metric"])
    assert un["dispatches_per_round"] == 1.0, un
    # the split engine reads > 1 only on rounds that actually mixed
    # prefill with decode — timing-dependent on the decode-heavy pool
    # (the tier-1 dispatch-count test pins the structural claim)
    assert un["dispatches_per_round_split"] >= 1.0, un
    assert un["vs_baseline"] >= 1.15, un
    # ITL p99: no regression on the single-core CPU proxy (run-to-run
    # it straddles parity there — strict improvement is the chip-rerun
    # claim, where the per-dispatch floor the fusion removes is
    # 8-70ms, not ~0.3ms; PERF.md r16)
    assert un["itl_p99_ms"] <= un["itl_p99_ms_split"] * 1.25, un
    assert un["compiles_in_window"] == 0, un
    assert un["overlap_fraction"] > 0.0, un
    # the sharded-serving acceptance bars (serving_dist round): token
    # parity across 1/2/4/8-device host meshes, and >= 3x max
    # concurrent slots at 4 devices vs 1 at fixed per-device pool
    # bytes (capacity is CPU-provable; tok/s scaling is a chip number)
    sh = next(r for r in recs if "sharded" in r["metric"])
    assert sh["token_parity"] is True, sh
    assert sh["slot_capacity_ratio"] >= 3.0, sh
    assert sh["devices"] == [1, 2, 4, 8], sh
    # the quantized-collectives acceptance bars (this round): int8
    # wire bytes per decoded token <= 0.30x the unquantized
    # collectives at the SAME dispatches, greedy parity >= 0.996,
    # the round still one dispatch, measured windows compile-clean
    qc = next(r for r in recs if "quantcollectives" in r["metric"])
    assert qc["devices"] == [1, 2, 4], qc
    assert qc["bytes_ratio_int8"] <= 0.30, qc
    assert qc["bytes_ratio_int4g"] < qc["bytes_ratio_int8"], qc
    # the >= 0.996 pinned-workload bar lives in
    # tests/test_quantized_collectives.py (exact at tp∈{2,4} on the
    # composed parity workloads); the bench's longer mixed stream
    # tolerates a few deterministic near-tie flips at tp=4
    assert qc["greedy_token_match"] >= 0.95, qc
    assert qc["dispatches_per_round"] == 1.0, qc
    assert qc["token_parity"] is True, qc
    assert qc["compiles_in_window"] == 0, qc
    # the degraded-mode acceptance bars (r17): every seam of the
    # fixed-seed FaultPlan fired, the recovery ladder absorbed the
    # faults (recoveries counted, survivors token-identical to the
    # fault-free run), and retention stayed above the floor
    dg = next(r for r in recs if "degradedmode" in r["metric"])
    assert dg["survivor_token_parity"] is True, dg
    assert dg["recoveries"] >= 1, dg
    assert all(v >= 1 for v in dg["faults_by_seam"].values()), dg
    # retention floor: recovery (backoff + replayed prefills) may not
    # eat more than 3/4 of fault-free tok/s at this fault rate
    assert dg["vs_baseline"] >= 0.25, dg
    # the fleet acceptance bars (r18): ZERO token divergence across
    # the forced mid-run replica kill and the live migration — every
    # request's output md5 is identical at every replica count
    fl = next(r for r in recs if "_fleet_" in r["metric"])
    assert fl["survivor_token_parity"] is True, fl
    assert fl["replica_kills"] >= 1, fl
    assert fl["failover_sessions"] >= 1, fl
    assert fl["migrated_sessions"] >= 1, fl
    assert fl["replica_counts"] == [1, 2, 4], fl
    # the fleet-procs acceptance bars (r19): the subprocess fleet's
    # output md5s are IDENTICAL to the in-process twin at every OS
    # process count, and the disaggregated prefill/decode pool
    # streamed its handoffs over the wire token-identically
    fp = next(r for r in recs if "fleetprocs" in r["metric"])
    assert fp["wire_token_parity"] is True, fp
    assert fp["process_counts"] == [1, 2, 4], fp
    assert fp["transport"] == "http", fp
    assert fp["disagg_token_parity"] is True, fp
    assert fp["disagg_handoffs"] >= 1, fp
    # the long-context acceptance bars (r21): sp multiplies the packed
    # prefill chunk budget, so the SAME huge prompts take strictly
    # fewer prefill dispatches at every higher sp degree with
    # md5-identical token streams (the structural/exact half; TTFT
    # wall-clock scaling is a chip number on the shared-core host
    # mesh), and the host-RAM KV tier backs >= 3x the resumable
    # long-context sessions at fixed per-device pool bytes, with the
    # churn mechanism (demote/promote, no recompute on resume, token
    # parity) proven empirically
    lc = next(r for r in recs if "longcontext" in r["metric"])
    assert lc["sp_degrees"] == [1, 2, 4], lc
    assert lc["token_parity"] is True, lc
    d = [lc["prefill_dispatches_by_sp"][str(n)] for n in (1, 2, 4)]
    assert d[0] > d[1] > d[2], lc
    assert lc["sessions_at_itl_bar_tier_on"] \
        > lc["sessions_at_itl_bar_tier_off"], lc
    assert lc["session_capacity_ratio"] >= 3.0, lc
    assert lc["max_resident_context_tokens_tier_on"] \
        > lc["max_resident_context_tokens_tier_off"], lc
    assert lc["resume_prefill_dispatches_tier_on"] \
        < lc["resume_prefill_dispatches_tier_off"], lc
    assert lc["tier_demotions"] >= 1, lc
    assert lc["tier_promotions"] >= 1, lc
    assert lc["tier_hit_tokens"] > 0, lc
    assert lc["tier_token_parity"] is True, lc
    # the ISSUE-18 bars: (a) the ring exchange streams md5-identical
    # tokens to the all-gather on the same prompts while its peak
    # fresh-K/V bytes stay at the O(block) rotating window — at sp=4
    # the all-gather materializes 2x the bytes (and the gap grows with
    # chunk length; the tier-1 analytic sweep pins the 16x case)
    assert lc["sp_attention_token_parity"] is True, lc
    assert lc["sp_attention_peak_bytes_ring"] \
        < lc["sp_attention_peak_bytes_allgather"], lc
    assert lc["sp_attention_peak_bytes_ratio"] >= 1.9, lc
    # (b) tier prefetch-ahead: queued resumes find their history
    # already device-resident (hit rate > 0.8) and the overlapped
    # promote never makes the resume SLOWER than paying it at
    # admission (CPU-degraded: generous noise band on the p50)
    assert lc["tier_prefetch_issued_blocks"] >= 1, lc
    assert lc["tier_prefetch_hit_rate"] > 0.8, lc
    assert lc["tier_prefetch_token_parity"] is True, lc
    assert lc["resume_ttft_p50_ms_tier_prefetch"] \
        <= lc["resume_ttft_p50_ms_tier_sync"] * 1.25, lc
    # the elastic acceptance bars (ISSUE 20): the autoscaled fleet
    # holds the declared p99 TTFT SLO at >= 20% fewer replica-seconds
    # than the best static size that also holds it; the md5 over every
    # request's output tokens is IDENTICAL across every static size
    # AND the autoscaled drive (scale-ups, drain migrations and
    # retires are token-invisible); and the live decision journal
    # replays byte-for-byte from the recorded tick log
    el = next(r for r in recs if "elastic" in r["metric"])
    assert el["slo_met_autoscaled"] is True, el
    assert el["replica_seconds_saved_frac"] >= 0.20, el
    assert el["vs_baseline"] <= 0.80, el
    assert el["scale_ups"] >= 1, el
    assert el["scale_downs"] >= 1, el
    assert el["autoscale_errors"] == 0, el
    assert el["token_parity"] is True, el
    assert len(el["parity_md5"]) == 32, el
    assert el["decision_replay_identical"] is True, el
    assert el["transport"] == "inproc", el
    assert el["pool_topology"] == "pooled", el


def test_served_bench_openloop_tiny_schema():
    """Tier-1 smoke (ISSUE 3 + round-9 satellites): the tiny served
    bench must run fast and its records must carry the schema fields —
    a regression in the record format (including the shared-prefix
    cache-on/off axis) fails loudly here, not in a chip session."""
    recs, stdout = _run_served_bench("--tiny", timeout=900)
    assert len(recs) == 15, stdout
    paged = next(r for r in recs if "openloop" not in r["metric"]
                 and "sharedprefix" not in r["metric"]
                 and "mixedsampling" not in r["metric"]
                 and "speculative" not in r["metric"]
                 and "frontdoor" not in r["metric"]
                 and "quantized" not in r["metric"]
                 and "quantcollectives" not in r["metric"]
                 and "sharded" not in r["metric"]
                 and "unifiedround" not in r["metric"]
                 and "degradedmode" not in r["metric"]
                 and "longcontext" not in r["metric"]
                 and "elastic" not in r["metric"]
                 and "fleet" not in r["metric"])
    mix_rec = next(r for r in recs if "mixedsampling" in r["metric"])
    open_rec = next(r for r in recs if "openloop" in r["metric"])
    sp_rec = next(r for r in recs if "sharedprefix" in r["metric"])
    spec_rec = next(r for r in recs if "speculative" in r["metric"])
    fd_rec = next(r for r in recs if "frontdoor" in r["metric"])
    qz_rec = next(r for r in recs if "quantized" in r["metric"])
    sh_rec = next(r for r in recs if "sharded" in r["metric"])
    qc_rec = next(r for r in recs
                  if "quantcollectives" in r["metric"])
    dg_rec = next(r for r in recs if "degradedmode" in r["metric"])
    fl_rec = next(r for r in recs if "_fleet_" in r["metric"])
    fp_rec = next(r for r in recs if "fleetprocs" in r["metric"])
    lc_rec = next(r for r in recs if "longcontext" in r["metric"])
    el_rec = next(r for r in recs if "elastic" in r["metric"])
    for rec in (paged, mix_rec, open_rec, sp_rec, spec_rec, fd_rec,
                qz_rec, sh_rec, qc_rec, dg_rec, fl_rec, lc_rec,
                fp_rec, el_rec):
        assert rec["value"] > 0
        assert rec.get("degraded") is True
        assert "prefill_dispatches" in rec
        assert "itl_p99_ms" in rec
    # ops plane (ISSUE 10): served records carry the compile-window
    # + goodput fields so a compile-poisoned measurement window is
    # visible in the record instead of discovered post-hoc
    for rec in (paged, open_rec, fd_rec):
        assert "compiles_in_window" in rec, rec
        assert "compiles_in_flight_window" in rec, rec
        assert 0 < rec["goodput_ratio"] <= 1.0, rec
    # attribution + capacity (ISSUE 17): the paged record carries the
    # per-tenant ledger view with ZERO conservation residuals (the
    # ledger's exactness proven on the bench workload, not just unit
    # inputs) plus one capacity snapshot's headline fields
    assert paged["attribution_enabled"] is True, paged
    assert paged["tenant_requests"].get("default", 0) >= 1, paged
    assert paged["tenant_device_s"]["default"] > 0, paged
    assert paged["attribution_device_residual_ns"] == 0, paged
    assert paged["attribution_block_residual_ns"] == 0, paged
    assert paged["capacity_schema_version"] == 1, paged
    assert paged["capacity_free_blocks"] >= 0, paged
    assert paged["capacity_available_blocks"] \
        >= paged["capacity_free_blocks"], paged
    assert "capacity_queue_depth" in paged, paged
    assert "capacity_exhaustion_eta_s" in paged, paged
    # mixed-sampling axis (round 10): fixed-seed 50/50 workload whose
    # record carries the pipeline-overhead fields
    for fld in ("sampling_overhead_pct", "sampled_fraction",
                "sampled_dispatches", "fast_path_dispatches",
                "stop_reasons"):
        assert fld in mix_rec, mix_rec
    assert mix_rec["sampled_fraction"] == 0.5
    assert mix_rec["sampled_dispatches"] >= 1
    assert sum(mix_rec["stop_reasons"].values()) > 0
    # open-loop axis: fixed-seed Poisson arrival accounting
    for fld in ("offered_rps", "achieved_rps", "ttft_p99_ms",
                "itl_p50_ms", "prefills"):
        assert fld in open_rec, open_rec
    assert open_rec["offered_rps"] > 0
    assert open_rec["prefill_dispatches"] >= 1
    # shared-prefix axis: cache-on/off TTFT comparison + pool stats
    for fld in ("ttft_p50_ms_uncached", "ttft_p99_ms",
                "ttft_p99_ms_uncached", "tokens_per_sec",
                "tokens_per_sec_uncached", "prefix_hit_rate",
                "prefix_hit_tokens", "prefix_lookup_tokens",
                "prefix_evictions", "prefix_cow_copies",
                "retained_blocks", "peak_retained_blocks",
                "shared_prefix_len", "offered_rps", "vs_baseline"):
        assert fld in sp_rec, sp_rec
    assert sp_rec["prefix_hit_tokens"] > 0  # the warm prefix must hit
    assert 0 < sp_rec["prefix_hit_rate"] <= 1.0
    # speculation axis (round 11): acceptance accounting + the oracle
    # ceiling must be present; token conservation must hold exactly
    for fld in ("vs_baseline", "tokens_per_sec_plain",
                "acceptance_rate", "proposed_tokens", "accepted_tokens",
                "rolled_back_tokens", "verify_dispatches",
                "decode_steps", "decode_steps_plain",
                "max_draft_tokens", "tok_s_ratio_oracle",
                "acceptance_rate_oracle"):
        assert fld in spec_rec, spec_rec
    assert spec_rec["proposed_tokens"] == (
        spec_rec["accepted_tokens"] + spec_rec["rolled_back_tokens"])
    assert 0.0 <= spec_rec["acceptance_rate"] <= 1.0
    assert spec_rec["verify_dispatches"] >= 1
    # front-door axis (round 12): adversarial mix accounting — lanes,
    # deadlines, preemption/resume conservation, batch-cost fields
    for fld in ("vs_baseline", "interactive_ttft_p50_ms",
                "interactive_ttft_p99_ms_baseline",
                "deadline_miss_rate", "deadline_miss_rate_baseline",
                "deadline_ms", "batch_tokens_per_sec",
                "batch_tokens_per_sec_baseline",
                "batch_throughput_ratio", "preemptions", "resumes",
                "preempt_cached_tokens", "rejected", "n_bully",
                "n_interactive"):
        assert fld in fd_rec, fd_rec
    # the tiny mix preempts (hysteresis pinned off in the smoke) and
    # every preemption must later resume
    assert fd_rec["preemptions"] >= 1, fd_rec
    assert fd_rec["resumes"] == fd_rec["preemptions"], fd_rec
    assert 0.0 <= fd_rec["deadline_miss_rate"] <= 1.0
    assert fd_rec["batch_tokens_per_sec"] > 0
    # quantization axis (quantized-serving round): the record must
    # carry the bf16/W8A16/W8A16+int8-KV comparison, the fixed-byte
    # slot capacity pair, and the accuracy-delta fields
    for fld in ("vs_baseline", "tokens_per_sec_bf16",
                "tokens_per_sec_w8a16", "ttft_p50_ms",
                "ttft_p50_ms_bf16", "itl_p99_ms_bf16",
                "max_slots_at_fixed_bytes",
                "max_slots_at_fixed_bytes_bf16", "slot_capacity_ratio",
                "pool_budget_bytes", "kv_bytes_per_token",
                "kv_bytes_per_token_bf16", "kv_scale_bytes",
                "greedy_token_match", "greedy_token_match_w8a16",
                "logit_mae", "logit_max_abs", "offered_rps"):
        assert fld in qz_rec, qz_rec
    # dtype-aware byte accounting must actually show the halving, and
    # the fixed-byte pool must back strictly more int8 slots
    assert qz_rec["kv_bytes_per_token"] \
        < 0.6 * qz_rec["kv_bytes_per_token_bf16"], qz_rec
    assert qz_rec["slot_capacity_ratio"] >= 1.8, qz_rec
    assert qz_rec["kv_scale_bytes"] > 0
    assert 0.0 <= qz_rec["greedy_token_match"] <= 1.0
    # sharded axis (serving_dist round): per-device-count tok/s + slot
    # capacity at fixed per-device pool bytes, token parity asserted
    # across mesh sizes (the tiny smoke runs 1/2 devices)
    for fld in ("vs_baseline", "devices", "tp_degree", "dp_degree",
                "tokens_per_sec_by_devices", "max_slots_by_devices",
                "slot_capacity_ratio", "pool_budget_bytes",
                "token_parity", "cpu_host_mesh"):
        assert fld in sh_rec, sh_rec
    assert sh_rec["token_parity"] is True, sh_rec
    assert sh_rec["devices"] == [1, 2]
    # 2 devices at fixed per-device bytes back ~2x the blocks
    assert sh_rec["slot_capacity_ratio"] >= 1.9, sh_rec
    # quantized-collectives axis (this round): per-mode wire-byte
    # accounting at tp=2 (the tiny smoke runs the one device count
    # with a wire) — the smoke asserts the schema, the structural
    # byte halving and the parity fields; the slow test asserts the
    # <= 0.30x / >= 0.996 acceptance bars at tp=4 across tp∈{1,2,4}
    for fld in ("vs_baseline", "devices", "tp_degree",
                "tokens_per_sec_bf16", "tokens_per_sec_int4g",
                "bytes_per_token", "bytes_per_token_bf16",
                "bytes_ratio_int8", "bytes_ratio_int4g",
                "by_collective_int8", "greedy_token_match",
                "greedy_token_match_int4g", "parity_md5",
                "token_parity", "dispatches_per_round",
                "compiles_in_window", "offered_rps",
                "cpu_host_mesh"):
        assert fld in qc_rec, qc_rec
    assert qc_rec["devices"] == [2], qc_rec
    assert qc_rec["bytes_ratio_int8"] <= 0.35, qc_rec
    assert qc_rec["bytes_ratio_int4g"] \
        < qc_rec["bytes_ratio_int8"], qc_rec
    assert qc_rec["bytes_per_token"] \
        < qc_rec["bytes_per_token_bf16"], qc_rec
    assert 0.0 <= qc_rec["greedy_token_match"] <= 1.0
    assert qc_rec["dispatches_per_round"] == 1.0, qc_rec
    assert qc_rec["token_parity"] is True, qc_rec
    assert len(qc_rec["parity_md5"]) == 32, qc_rec
    # unified-round axis (r16): the one-dispatch round + async loop
    # vs the split engine at identical arrivals — the tiny smoke
    # asserts schema + the structural invariant (exactly 1 attention
    # dispatch per round), not the tok/s bar (slow test)
    un_rec = next(r for r in recs if "unifiedround" in r["metric"])
    for fld in ("vs_baseline", "tokens_per_sec_split", "itl_p99_ms",
                "itl_p99_ms_split", "ttft_p99_ms", "ttft_p99_ms_split",
                "dispatches_per_round", "dispatches_per_round_split",
                "mixed_rounds", "overlap_seconds", "overlap_fraction",
                "offered_rps", "achieved_rps", "compiles_in_window",
                "compiles_in_flight_window", "goodput_ratio"):
        assert fld in un_rec, un_rec
    assert un_rec["dispatches_per_round"] == 1.0, un_rec
    assert un_rec["dispatches_per_round_split"] >= 1.0, un_rec
    assert 0.0 <= un_rec["overlap_fraction"] <= 1.0, un_rec
    assert un_rec["compiles_in_window"] == 0, un_rec
    assert 0 < un_rec["goodput_ratio"] <= 1.0, un_rec
    # degraded-mode axis (r17): identical fixed-seed arrivals at 0%
    # vs an injected fault rate — the tiny smoke asserts the schema,
    # every FaultPlan seam firing, and the chaos survivor-parity proof
    for fld in ("vs_baseline", "tokens_per_sec_clean", "fault_plan",
                "faults_injected", "faults_by_seam",
                "dispatch_retries", "recoveries", "quarantined",
                "survivor_token_parity", "n_requests",
                "goodput_ratio", "goodput_ratio_clean"):
        assert fld in dg_rec, dg_rec
    assert dg_rec["survivor_token_parity"] is True, dg_rec
    assert dg_rec["recoveries"] >= 1, dg_rec
    assert dg_rec["faults_injected"] >= 3, dg_rec  # min 1 per seam
    assert set(dg_rec["faults_by_seam"]) == {
        "prefill", "decode", "ensure_many"}, dg_rec
    assert 0 < dg_rec["goodput_ratio"] <= 1.0, dg_rec
    # fleet axis (r18): identical fixed-seed arrivals at 1/2 replicas
    # (tiny) with one forced mid-run replica kill + one live
    # migration — schema + the md5 token-parity proof across counts
    for fld in ("vs_baseline", "replica_counts",
                "tokens_per_sec_by_replicas",
                "ttft_p99_ms_by_replicas", "ttft_p99_ms",
                "failover_count", "failover_sessions",
                "replica_kills", "migrated_sessions", "prefix_routed",
                "survivor_token_parity", "parity_md5", "n_requests"):
        assert fld in fl_rec, fl_rec
    assert fl_rec["survivor_token_parity"] is True, fl_rec
    assert fl_rec["replica_counts"] == [1, 2], fl_rec
    assert fl_rec["replica_kills"] >= 1, fl_rec
    assert fl_rec["failover_sessions"] >= 1, fl_rec
    assert fl_rec["migrated_sessions"] >= 1, fl_rec
    assert len(fl_rec["parity_md5"]) == 32, fl_rec
    assert fl_rec["transport"] == "inproc", fl_rec
    assert fl_rec["pool_topology"] == "pooled", fl_rec
    # fleet-procs axis (r19): REAL OS-process workers behind the
    # HTTP wire transport at 1/2 processes (tiny) — schema, the
    # wire md5 parity proof vs the in-process twin fleet, topology
    # provenance, and the disaggregated prefill/decode burst A/B
    for fld in ("vs_baseline", "process_counts",
                "tokens_per_sec_by_procs", "ttft_p99_ms_by_procs",
                "ttft_p99_ms", "tokens_per_sec_inproc_1",
                "wire_token_parity", "parity_md5", "transport",
                "pool_topology", "burst_n_requests",
                "burst_ttft_p99_ms_pooled",
                "burst_ttft_p99_ms_disagg", "disagg_handoffs",
                "disagg_handoffs_failed", "disagg_token_parity",
                "n_requests"):
        assert fld in fp_rec, fp_rec
    assert fp_rec["wire_token_parity"] is True, fp_rec
    assert fp_rec["process_counts"] == [1, 2], fp_rec
    assert fp_rec["transport"] == "http", fp_rec
    assert fp_rec["pool_topology"] == "pooled", fp_rec
    assert fp_rec["disagg_token_parity"] is True, fp_rec
    assert fp_rec["disagg_handoffs"] >= 1, fp_rec
    assert fp_rec["disagg_handoffs_failed"] == 0, fp_rec
    assert len(fp_rec["parity_md5"]) == 32, fp_rec
    # long-context axis (r21): huge prompts at sp∈{1,2} (tiny) — the
    # smoke asserts the schema, the exact prefill-dispatch division,
    # md5 token parity across sp degrees, and the host-RAM KV tier's
    # capacity + churn-mechanism fields
    for fld in ("vs_baseline", "sp_degrees", "prompt_tokens",
                "ttft_p50_ms_by_sp", "prefill_dispatches_by_sp",
                "token_parity", "parity_md5",
                "sessions_at_itl_bar_tier_on",
                "sessions_at_itl_bar_tier_off",
                "session_capacity_ratio",
                "max_resident_context_tokens_tier_on",
                "max_resident_context_tokens_tier_off",
                "pool_budget_bytes", "host_budget_bytes",
                "resume_ttft_p50_ms_tier_on",
                "resume_ttft_p50_ms_tier_off",
                "resume_prefill_dispatches_tier_on",
                "resume_prefill_dispatches_tier_off",
                "tier_demotions", "tier_promotions",
                "tier_hit_tokens", "tier_token_parity",
                "n_sessions", "cpu_host_mesh",
                "sp_attention_modes",
                "sp_attention_peak_bytes_allgather",
                "sp_attention_peak_bytes_ring",
                "sp_attention_peak_bytes_ratio", "ttft_p50_ms_ring",
                "sp_attention_token_parity",
                "resume_ttft_p50_ms_tier_prefetch",
                "resume_ttft_p50_ms_tier_sync",
                "tier_prefetch_hit_rate",
                "tier_prefetch_issued_blocks",
                "tier_prefetch_wasted_blocks",
                "tier_prefetch_overlap_promote_s",
                "tier_prefetch_token_parity"):
        assert fld in lc_rec, lc_rec
    assert lc_rec["sp_degrees"] == [1, 2], lc_rec
    assert lc_rec["token_parity"] is True, lc_rec
    assert len(lc_rec["parity_md5"]) == 32, lc_rec
    assert lc_rec["prefill_dispatches_by_sp"]["2"] \
        < lc_rec["prefill_dispatches_by_sp"]["1"], lc_rec
    assert lc_rec["sessions_at_itl_bar_tier_on"] \
        > lc_rec["sessions_at_itl_bar_tier_off"], lc_rec
    assert lc_rec["resume_prefill_dispatches_tier_on"] \
        < lc_rec["resume_prefill_dispatches_tier_off"], lc_rec
    assert lc_rec["tier_demotions"] >= 1, lc_rec
    assert lc_rec["tier_promotions"] >= 1, lc_rec
    assert lc_rec["tier_hit_tokens"] > 0, lc_rec
    assert lc_rec["tier_token_parity"] is True, lc_rec
    # sp_attention A/B (ISSUE 18): ring streams md5-identical and its
    # O(block) peak never exceeds the all-gather's (equal at sp=2
    # where 2T == 4*block; the slow test pins the sp=4 2x gap)
    assert lc_rec["sp_attention_modes"] == ["allgather", "ring"]
    assert lc_rec["sp_attention_token_parity"] is True, lc_rec
    assert lc_rec["sp_attention_peak_bytes_ring"] \
        <= lc_rec["sp_attention_peak_bytes_allgather"], lc_rec
    assert lc_rec["sp_attention_peak_bytes_ratio"] >= 1.0, lc_rec
    # tier prefetch-ahead A/B: schema + parity in the smoke (the hit
    # rate and TTFT bars are the slow test's)
    assert lc_rec["tier_prefetch_token_parity"] is True, lc_rec
    assert 0.0 <= lc_rec["tier_prefetch_hit_rate"] <= 1.0, lc_rec
    # elastic axis (ISSUE 20): the fixed-seed diurnal + flash-crowd
    # trace through static vs autoscaled fleets — the smoke asserts
    # the record schema (replica-seconds cost fields, scale-event
    # accounting, parity md5, decision-replay identity); the >= 20%
    # replica-seconds saving and the SLO bar are the slow test's
    for fld in ("vs_baseline", "replica_counts", "slo_ttft_ms",
                "ttft_p99_ms_by_static", "ttft_p99_ms",
                "slo_met_autoscaled", "best_static_replicas",
                "replica_seconds_by_static",
                "replica_seconds_best_static",
                "replica_seconds_saved_frac", "scale_ups",
                "scale_downs", "decisions_total", "autoscale_errors",
                "migrated_sessions", "failover_sessions",
                "token_parity", "parity_md5",
                "decision_replay_identical", "n_requests"):
        assert fld in el_rec, el_rec
    assert el_rec["unit"] == "replica_s", el_rec
    assert el_rec["replica_counts"] == [1, 2], el_rec
    assert el_rec["transport"] == "inproc", el_rec
    assert el_rec["pool_topology"] == "pooled", el_rec
    # even the tiny trace forces one full scale-up/scale-down cycle
    # through the warm gate and the drain state machine
    assert el_rec["scale_ups"] >= 1, el_rec
    assert el_rec["scale_downs"] >= 1, el_rec
    assert el_rec["autoscale_errors"] == 0, el_rec
    # the parity + determinism proofs hold even at smoke scale
    assert el_rec["token_parity"] is True, el_rec
    assert len(el_rec["parity_md5"]) == 32, el_rec
    assert el_rec["decision_replay_identical"] is True, el_rec
