"""Fault-tolerant serving (ISSUE 12 / r17): deterministic fault
injection at the engine's hazard seams, the dispatch recovery ladder
(snapshot + requeue + backoff + quarantine), per-request timeouts,
admission shedding, stream-side termination semantics, the
crash-consistent session journal (kill + restart with zero accepted-
request loss), and the chaos parity gate — a fixed-seed FaultPlan
over the composed stack with surviving requests token-identical to
the fault-free run."""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.reliability import (ENV_FAULT_PLAN, SEAMS, AdmissionShed,
                                    Fault, FaultPlan, InjectedFault,
                                    QuarantinedRequest, RecoveryPolicy,
                                    RequestTimeout, SessionJournal,
                                    resolve_fault_plan)


@pytest.fixture(autouse=True)
def _registry_guard():
    """expose_port= enables the process metrics registry by design;
    restore the gate + zero the series afterwards (the ops-plane
    suite's convention)."""
    from paddle_tpu.observability import metrics as M

    was = M.REGISTRY.enabled
    yield
    M.REGISTRY.enabled = was
    M.REGISTRY.reset()


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config

    paddle.seed(100)
    cfg = GPT2Config(vocab_size=512, hidden_size=128, num_layers=2,
                     num_heads=4, max_position=128)
    cfg.dropout = 0.0
    m = GPT2(cfg)
    m.eval()
    return m, cfg


def _server(m, **kw):
    from paddle_tpu.inference import PagedGenerationServer

    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_prompt_len", 24)
    kw.setdefault("max_new_tokens", 6)
    return PagedGenerationServer(m, **kw)


def _detok(toks):
    """Deterministic, prefix-stable toy detokenizer (append a token ->
    append characters), good enough for stop strings and streaming."""
    return "".join(chr(97 + (int(t) % 26)) for t in toks)


def _drive(srv, work, timeout=300):
    """Submit [(ids, kwargs), ...]; returns [("ok", tokens) |
    (ExceptionName, exc)] in submit order."""
    futs = [srv.submit(ids, **kw) for ids, kw in work]
    out = []
    for f in futs:
        try:
            out.append(("ok", f.result(timeout=timeout)))
        except Exception as e:  # noqa: BLE001 — collected for asserts
            out.append((type(e).__name__, e))
    return out


def _run_server(m, work, srv_kw=None, timeout=300):
    srv = _server(m, **(srv_kw or {}))
    srv.start()
    try:
        res = _drive(srv, work, timeout=timeout)
        stats = srv.stats()
        health = srv.health()
    finally:
        srv.stop()
    return res, stats, health


class TestFaultPlan:
    def test_from_seed_is_deterministic(self):
        a = FaultPlan.from_seed(7, rate=0.2, horizon=32)
        b = FaultPlan.from_seed(7, rate=0.2, horizon=32)
        for seam in SEAMS:
            for _ in range(32):
                fa, fb = a.poll(seam), b.poll(seam)
                assert (fa is None) == (fb is None)
                if fa is not None:
                    assert (fa.seam, fa.index, fa.kind) == \
                        (fb.seam, fb.index, fb.kind)

    def test_min_per_seam_guarantees_coverage(self):
        p = FaultPlan.from_seed(3, rate=0.0, horizon=16, min_per_seam=1)
        hit = set()
        for seam in SEAMS:
            for _ in range(16):
                if p.poll(seam) is not None:
                    hit.add(seam)
        assert hit == set(SEAMS)
        assert p.fired() == {s: 1 for s in SEAMS}

    def test_seam_kinds_default_correctly(self):
        p = FaultPlan.parse("ensure_many:0,slow_dispatch:0,decode:0")
        assert p.poll("ensure_many").kind == "exhausted"
        assert p.poll("slow_dispatch").kind == "slow"
        assert p.poll("decode").kind == "raise"

    def test_parse_validation(self):
        with pytest.raises(ValueError, match="unknown fault seam"):
            FaultPlan.parse("warp_core:0")
        with pytest.raises(ValueError, match="seam:occurrence"):
            FaultPlan.parse("decode")
        with pytest.raises(ValueError, match="needs seed="):
            FaultPlan.parse("rate=0.5")
        with pytest.raises(ValueError, match="unknown fault-plan key"):
            FaultPlan.parse("seed=1,frequency=2")
        with pytest.raises(ValueError, match="empty"):
            FaultPlan.parse("  ")

    def test_env_var_resolution(self, monkeypatch):
        monkeypatch.delenv(ENV_FAULT_PLAN, raising=False)
        assert resolve_fault_plan(None) is None
        monkeypatch.setenv(ENV_FAULT_PLAN, "decode:1")
        p = resolve_fault_plan(None)
        assert p is not None and p.poll("decode") is None
        assert p.poll("decode") is not None
        with pytest.raises(TypeError, match="fault_plan"):
            resolve_fault_plan(42)

    def test_reset_counters_replays_the_schedule(self):
        p = FaultPlan([Fault("decode", 0)])
        assert p.poll("decode") is not None
        assert p.poll("decode") is None
        p.reset_counters()
        assert p.poll("decode") is not None


class TestRecoveryPolicy:
    def test_backoff_is_capped_exponential(self):
        pol = RecoveryPolicy(backoff_base_s=0.1, backoff_cap_s=0.5)
        assert pol.backoff_s(1) == pytest.approx(0.1)
        assert pol.backoff_s(2) == pytest.approx(0.2)
        assert pol.backoff_s(3) == pytest.approx(0.4)
        assert pol.backoff_s(4) == pytest.approx(0.5)  # capped
        assert pol.backoff_s(10) == pytest.approx(0.5)

    def test_validation(self):
        with pytest.raises(ValueError, match="quarantine_after"):
            RecoveryPolicy(quarantine_after=0)
        with pytest.raises(ValueError, match="backoff_cap_s"):
            RecoveryPolicy(backoff_base_s=1.0, backoff_cap_s=0.1)


class TestSessionJournalUnit:
    class _FakeReq:
        def __init__(self, rid, ids, budget=4, seed=9, gen0=(),
                     sampling=None, meta=None, timeout_s=None):
            self.rid, self.ids = rid, np.asarray(ids, np.int32)
            self.budget, self.seed = budget, seed
            self.gen0, self.sampling = tuple(gen0), sampling
            self.meta, self.timeout_s = meta, timeout_s

    def test_accept_tokens_done_roundtrip(self, tmp_path):
        j = SessionJournal(tmp_path / "j.jsonl")
        j.record_accept(self._FakeReq("r1", [1, 2, 3]))
        j.record_accept(self._FakeReq("r2", [4, 5]))
        j.record_token("r1", 7)
        j.record_token("r1", 8)
        j.record_done("r2", "eos")
        live = j.interrupted()
        assert [e["rid"] for e in live] == ["r1"]
        assert live[0]["ids"] == [1, 2, 3]
        assert live[0]["gen0"] == [7, 8]
        assert j.stats()["accepted"] == 2
        assert j.stats()["finished"] == 1
        j.close()
        # a fresh loader over the same file sees the same state
        j2 = SessionJournal(tmp_path / "j.jsonl")
        assert [e["rid"] for e in j2.interrupted()] == ["r1"]
        assert j2.interrupted()[0]["gen0"] == [7, 8]

    def test_torn_tail_is_skipped(self, tmp_path):
        p = tmp_path / "j.jsonl"
        j = SessionJournal(p)
        j.record_accept(self._FakeReq("r1", [1]))
        j.record_token("r1", 3)
        j.close()
        with open(p, "a", encoding="utf-8") as f:
            f.write('{"t":"tok","rid":"r1","to')  # crash mid-write
        j2 = SessionJournal(p)
        assert j2.interrupted()[0]["gen0"] == [3]
        assert j2.stats()["torn_lines"] == 1

    def test_compaction_bounds_the_file_and_keeps_live_state(
            self, tmp_path):
        p = tmp_path / "j.jsonl"
        j = SessionJournal(p, max_bytes=2048)
        j.record_accept(self._FakeReq("live", [1, 2]))
        for i in range(40):
            j.record_accept(self._FakeReq(f"d{i}", [i]))
            j.record_token(f"d{i}", i)
            j.record_done(f"d{i}", "budget")
            j.record_token("live", 100 + i)
        assert os.path.getsize(p) <= 2048 + 512  # bounded (one slack
        # line may land past the threshold before compaction runs)
        live = j.interrupted()
        assert [e["rid"] for e in live] == ["live"]
        assert live[0]["gen0"] == [100 + i for i in range(40)]
        j.close()


class TestBlastRadius:
    """Satellite: only requests implicated by a failing dispatch may
    fail — and with the recovery ladder (default) not even they do."""

    def test_transient_decode_fault_nobody_fails(self, tiny_model):
        m, cfg = tiny_model
        work = [(np.array([1, 2, 3], np.int32), {}),
                (np.array([4, 5, 6, 7], np.int32), {})]
        ref, _, _ = _run_server(m, work)
        res, st, health = _run_server(
            m, work, {"fault_plan": FaultPlan.parse("decode:1")})
        assert [r[0] for r in res] == ["ok", "ok"]
        for (_, a), (_, b) in zip(ref, res):
            np.testing.assert_array_equal(a, b)
        rel = st["reliability"]
        assert rel["faults_injected"] == 1
        assert rel["dispatch_retries"] == 1
        assert rel["recoveries"] >= 1
        assert rel["quarantined"] == 0
        assert health[0] == "ok"  # degraded was NOT sticky: recovered
        assert health[1]["last_recovery"]["recovered_from"]

    def test_legacy_blast_radius_spares_unimplicated_coresidents(
            self, tiny_model):
        """Even with recovery=False (the legacy fail-the-dispatch
        path), a prefill fault fails ONLY the chunk's requests: a
        decode-phase co-resident completes with correct tokens."""
        m, cfg = tiny_model
        seen = []
        srv = _server(m, recovery=False,
                      fault_plan=FaultPlan.parse("prefill:1"))
        srv.start()
        try:
            a = srv.submit([1, 2, 3], on_token=lambda t, r:
                           seen.append(t))
            deadline = time.monotonic() + 60
            while not seen and time.monotonic() < deadline:
                time.sleep(0.005)  # a is decoding: prefill occurrence
            assert seen  # 0 is spent, occurrence 1 will be b's
            b = srv.submit([4, 5, 6, 7])
            with pytest.raises(InjectedFault):
                b.result(timeout=300)
            out_a = a.result(timeout=300)
        finally:
            srv.stop()
        ref = _server(m).start()
        try:
            np.testing.assert_array_equal(
                out_a, ref.submit([1, 2, 3]).result(timeout=300))
        finally:
            ref.stop()

    def test_block_pool_exhausted_carries_pressure_fields(self):
        from paddle_tpu.inference.kv_cache import (BlockPoolExhausted,
                                                   PagedKVCache)

        c = PagedKVCache(1, 1, 2, block_size=4, num_blocks=4)
        with pytest.raises(BlockPoolExhausted) as ei:
            c.allocate("a", 100)
        assert ei.value.needed == 25
        assert ei.value.available == 3

    def test_injected_pool_exhaustion_recovers(self, tiny_model):
        m, cfg = tiny_model
        work = [(np.array([1, 2, 3], np.int32), {}),
                (np.array([4, 5, 6, 7], np.int32), {})]
        ref, _, _ = _run_server(m, work)
        res, st, health = _run_server(
            m, work, {"fault_plan": FaultPlan.parse("ensure_many:0")})
        assert [r[0] for r in res] == ["ok", "ok"]
        for (_, a), (_, b) in zip(ref, res):
            np.testing.assert_array_equal(a, b)
        assert st["reliability"]["recoveries"] >= 1
        assert health[0] == "ok"


class TestQuarantine:
    def test_persistent_fault_quarantines_exactly_one(self, tiny_model):
        """Three consecutive prefill failures (the default
        quarantine_after) quarantine ONE request — deterministically
        the lowest implicated slot — with a diagnostic naming the
        seam; the co-resident completes token-identically."""
        m, cfg = tiny_model
        work = [(np.array([1, 2, 3], np.int32), {}),
                (np.array([4, 5, 6, 7], np.int32), {})]
        ref, _, _ = _run_server(m, work)
        res, st, health = _run_server(
            m, work,
            {"fault_plan": FaultPlan.parse(
                "prefill:0,prefill:1,prefill:2")})
        kinds = [r[0] for r in res]
        assert kinds.count("QuarantinedRequest") == 1, kinds
        qi = kinds.index("QuarantinedRequest")
        oi = kinds.index("ok")
        q = res[qi][1]
        assert q.seam == "prefill"
        assert q.failures == 3
        assert "injected fault" in str(q)
        np.testing.assert_array_equal(res[oi][1], ref[oi][1])
        rel = st["reliability"]
        assert rel["quarantined"] == 1
        assert rel["recoveries"] >= 1  # the survivor's dispatch
        assert health[0] == "ok"

    def test_quarantined_stream_reason(self, tiny_model):
        from paddle_tpu.frontend.stream import StreamHandle

        m, cfg = tiny_model
        srv = _server(m, max_slots=1,
                      fault_plan=FaultPlan.parse(
                          "prefill:0,prefill:1,prefill:2"))
        handle = StreamHandle()
        srv.start()
        try:
            fut = srv.submit([1, 2, 3], on_token=handle._on_token)
            handle._bind(fut)
            events = list(handle)
            assert events and events[-1].done
            assert events[-1].stop_reason == "quarantined"
            assert handle.stop_reason == "quarantined"
            with pytest.raises(QuarantinedRequest):
                fut.result(timeout=10)
        finally:
            srv.stop()

    def test_detokenize_fault_implicates_one_request(self, tiny_model):
        """A broken detokenizer (injected at the detokenize seam)
        fails exactly the stop-string request — before r17 the raise
        escaped _slot_token and killed the whole engine thread."""
        from paddle_tpu.sampling import SamplingParams

        m, cfg = tiny_model
        work = [(np.array([1, 2, 3], np.int32),
                 {"sampling": SamplingParams(stop_strings=("zq!",))}),
                (np.array([4, 5, 6, 7], np.int32), {})]
        ref, _, _ = _run_server(m, work, {"detokenize": _detok})
        res, st, _ = _run_server(
            m, work, {"detokenize": _detok,
                      "fault_plan": FaultPlan.parse("detokenize:0")})
        kinds = [r[0] for r in res]
        assert kinds[0] == "QuarantinedRequest"
        assert res[0][1].seam == "detokenize"
        assert kinds[1] == "ok"
        np.testing.assert_array_equal(res[1][1], ref[1][1])
        assert st["reliability"]["quarantined"] == 1

    def test_stream_consumer_death_is_isolated(self, tiny_model):
        """A dying on_token consumer (injected at the stream_consumer
        seam) drops the stream but the request itself completes
        token-identically."""
        m, cfg = tiny_model
        ids = np.array([1, 2, 3], np.int32)
        ref, _, _ = _run_server(m, [(ids, {})])
        got = []
        res, st, health = _run_server(
            m, [(ids, {"on_token": lambda t, r: got.append(t)})],
            {"fault_plan": FaultPlan.parse("stream_consumer:0")})
        assert res[0][0] == "ok"
        np.testing.assert_array_equal(res[0][1], ref[0][1])
        assert got == []  # stream dropped at the first token
        assert health[0] == "ok"
        assert st["reliability"]["quarantined"] == 0


class TestHealthTransitions:
    def test_degraded_then_ok_after_clean_recovery(self, tiny_model):
        """The degraded-sticky satellite: /healthz returns to ok after
        a successful recovery (not only reset_stats), and /statusz
        carries the degradation reason + recovery timestamp."""
        m, cfg = tiny_model
        srv = _server(m, max_slots=1, expose_port=0,
                      fault_plan=FaultPlan.parse(
                          "prefill:0,prefill:1,prefill:2"))
        import urllib.request

        def healthz():
            try:
                r = urllib.request.urlopen(
                    srv.exporter.url + "/healthz", timeout=10)
                return r.status, r.read().decode()
            except urllib.error.HTTPError as e:
                return e.code, e.read().decode()

        srv.start()
        try:
            code0, _ = healthz()
            assert code0 == 200
            assert srv.health()[0] == "ok"
            with pytest.raises(QuarantinedRequest):
                srv.submit([1, 2, 3]).result(timeout=300)
            status, detail = srv.health()
            assert status == "degraded"
            assert "injected fault" in detail["degraded_reason"]
            code1, body1 = healthz()
            assert code1 == 200  # degraded still serves (drainable)
            assert '"degraded"' in body1
            # a successful dispatch is a CLEAN recovery: ok again with
            # the reason + timestamp on record, no reset_stats needed
            srv.submit([4, 5, 6]).result(timeout=300)
            status, detail = srv.health()
            assert status == "ok"
            assert "injected fault" in \
                detail["last_recovery"]["recovered_from"]
            assert detail["last_recovery"]["ts"] <= time.time()
            st = srv.stats()["reliability"]
            assert st["recoveries"] == 1
            assert st["last_recovery"]["failures"] >= 1
        finally:
            srv.stop()

    def test_reset_stats_also_clears_degraded(self, tiny_model):
        m, cfg = tiny_model
        srv = _server(m, max_slots=1,
                      fault_plan=FaultPlan.parse(
                          "prefill:0,prefill:1,prefill:2"))
        srv.start()
        try:
            with pytest.raises(QuarantinedRequest):
                srv.submit([1, 2, 3]).result(timeout=300)
            assert srv.health()[0] == "degraded"
            srv.reset_stats()
            assert srv.health()[0] == "ok"
            assert srv.stats()["reliability"]["quarantined"] == 0
        finally:
            srv.stop()

    def test_slow_dispatch_fault_trips_watchdog_then_recovers(
            self, tiny_model):
        m, cfg = tiny_model
        plan = FaultPlan([Fault("slow_dispatch", 0, "slow",
                                delay_s=1.2)])
        srv = _server(m, expose_port=0, stall_timeout_s=0.25,
                      fault_plan=plan)
        srv.start()
        try:
            out = srv.submit([1, 2, 3]).result(timeout=300)
            assert out.size > 3
            deadline = time.monotonic() + 10
            while srv._watchdog.stalled and time.monotonic() < deadline:
                time.sleep(0.05)
            assert srv._watchdog.stalls >= 1
            assert srv.health()[0] == "ok"
            assert srv.stats()["reliability"]["faults_injected"] == 1
        finally:
            srv.stop()


class TestTimeoutsAndShedding:
    def test_queued_request_times_out(self, tiny_model):
        m, cfg = tiny_model
        srv = _server(m, max_slots=1, max_new_tokens=32)
        srv.start()
        try:
            a = srv.submit([1, 2, 3], max_new_tokens=32)
            b = srv.submit([4, 5, 6], timeout_s=0.005)
            with pytest.raises(RequestTimeout, match="timed out"):
                b.result(timeout=300)
            assert a.result(timeout=300).size == 35
            st = srv.stats()
            assert st["reliability"]["timeouts"] == 1
            assert st["kv_cache"]["sequences"] == 0
        finally:
            srv.stop()

    def test_resident_request_times_out_and_frees_its_slot(
            self, tiny_model):
        from paddle_tpu.frontend.stream import StreamHandle

        m, cfg = tiny_model
        # a huge budget + a short deadline: the request is mid-decode
        # when it expires; its blocks must return to the pool
        srv = _server(m, max_slots=1, max_new_tokens=64,
                      max_prompt_len=32)
        handle = StreamHandle()
        srv.start()
        try:
            fut = srv.submit([1, 2, 3], max_new_tokens=64,
                             timeout_s=0.05, on_token=handle._on_token)
            handle._bind(fut)
            with pytest.raises(RequestTimeout) as ei:
                fut.result(timeout=300)
            assert ei.value.timeout_s == pytest.approx(0.05)
            assert handle.stop_reason == "timeout"
            assert srv.stats()["kv_cache"]["sequences"] == 0
            # the freed slot keeps serving
            assert srv.submit([7, 8], max_new_tokens=2) \
                .result(timeout=300).size == 4
        finally:
            srv.stop()

    def test_timeout_scan_covers_scheduler_queues(self, tiny_model):
        from paddle_tpu.frontend import FrontDoor

        m, cfg = tiny_model
        fd = FrontDoor(m, max_slots=1, block_size=4, max_prompt_len=24,
                       max_new_tokens=16)
        fd.start()
        try:
            a = fd.submit([1, 2, 3], lane="batch", max_new_tokens=16)
            b = fd.submit([4, 5, 6], lane="batch", timeout_s=0.005)
            with pytest.raises(RequestTimeout):
                b.result(timeout=300)
            assert b.stop_reason == "timeout"
            assert a.result(timeout=300).size == 19
        finally:
            fd.stop()

    def test_admission_shedding_with_retry_hint(self, tiny_model):
        m, cfg = tiny_model
        srv = _server(m, shed_queue_depth=2)  # NOT started: queue
        try:                                  # can only grow
            srv.submit([1, 2, 3])
            srv.submit([4, 5, 6])
            with pytest.raises(AdmissionShed) as ei:
                srv.submit([7, 8, 9])
            assert ei.value.retry_after_s > 0
            assert ei.value.depth == 2
            assert srv.stats()["reliability"]["shed"] == 1
            # nothing was enqueued for the shed submit
            assert srv.stats()["queue_depth"] == 2
        finally:
            srv.stop()

    def test_stream_iterator_timeout(self):
        """A dead engine can never hang a consumer thread: iterating a
        stream with timeout_s raises TimeoutError when no event
        arrives."""
        from paddle_tpu.frontend.stream import StreamHandle

        handle = StreamHandle(timeout_s=0.15)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match="no event"):
            for _ in handle:
                pass
        assert time.monotonic() - t0 < 10
        with pytest.raises(ValueError, match="timeout_s"):
            StreamHandle(timeout_s=0.0)


class TestJournalRecovery:
    def test_kill_and_restart_loses_zero_accepted_requests(
            self, tiny_model, tmp_path):
        """The crash-consistency gate: kill() mid-flight, rebuild over
        the same journal, recover_from_journal() re-admits every
        accepted-but-unfinished request, and the union of pre-crash
        and post-restart outputs is token-identical to a run that
        never crashed (prefix cache ON: the composed swap-out/attach
        path)."""
        m, cfg = tiny_model
        prompts = [np.array([1, 2, 3], np.int32),
                   np.array([9, 8, 7, 6], np.int32),
                   np.array([5, 5, 2], np.int32)]
        ref, _, _ = _run_server(
            m, [(p, {}) for p in prompts],
            {"max_slots": 1, "max_new_tokens": 8,
             "enable_prefix_cache": True})
        jp = tmp_path / "session.jsonl"
        a = _server(m, max_slots=1, max_new_tokens=8,
                    enable_prefix_cache=True, journal=str(jp))
        seen = {0: [], 1: [], 2: []}
        a.start()
        futs = [a.submit(p, on_token=(lambda k: lambda t, r:
                                      seen[k].append(t))(i))
                for i, p in enumerate(prompts)]
        # wait until request 0 finished and request 1 is mid-flight,
        # then crash: 2 is (typically) still queued
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not (
                futs[0].done() and len(seen[1]) >= 2):
            time.sleep(0.002)
        assert futs[0].done() and len(seen[1]) >= 2
        out0 = futs[0].result(timeout=1)
        a.kill()
        assert not futs[1].done()  # the crash stranded it
        j = SessionJournal(jp)
        live = {e["rid"]: e for e in j.interrupted()}
        assert len(live) == 2  # 1 (mid-flight) + 2 (queued)
        assert any(e["gen0"] for e in live.values())
        j.close()
        b = _server(m, max_slots=1, max_new_tokens=8,
                    enable_prefix_cache=True, journal=str(jp))
        recovered = b.recover_from_journal()
        assert set(recovered) == set(live)
        b.start()
        try:
            outs = {rid: f.result(timeout=300)
                    for rid, f in recovered.items()}
        finally:
            b.stop()
        # rid order is submit order: map back to prompt indices
        rids = sorted(live, key=lambda r: int(r[1:]))
        got = [out0, outs[rids[0]], outs[rids[1]]]
        for (_, want), have in zip(ref, got):
            np.testing.assert_array_equal(want, have)
        # after completion the journal holds no interrupted requests
        j2 = SessionJournal(jp)
        assert j2.interrupted() == []
        j2.close()

    def test_recovered_request_keeps_seed_and_sampling(
            self, tiny_model, tmp_path):
        """A fixed-seed SAMPLED request interrupted mid-flight resumes
        token-identically: recorded seed + sampling params + PRNG step
        base = len(gen0) reproduce the uninterrupted stream."""
        from paddle_tpu.sampling import SamplingParams

        m, cfg = tiny_model
        sp = SamplingParams(temperature=0.8, top_p=0.9, seed=77)
        ids = np.array([3, 1, 4, 1, 5], np.int32)
        ref, _, _ = _run_server(
            m, [(ids, {"sampling": sp})],
            {"max_slots": 1, "max_new_tokens": 8,
             "enable_prefix_cache": True})
        jp = tmp_path / "s.jsonl"
        a = _server(m, max_slots=1, max_new_tokens=8,
                    enable_prefix_cache=True, journal=str(jp))
        seen = []
        a.start()
        fut = a.submit(ids, sampling=sp,
                       on_token=lambda t, r: seen.append(t))
        deadline = time.monotonic() + 120
        while len(seen) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert len(seen) >= 2
        a.kill()
        b = _server(m, max_slots=1, max_new_tokens=8,
                    enable_prefix_cache=True, journal=str(jp))
        recovered = b.recover_from_journal()
        b.start()
        try:
            out = list(recovered.values())[0].result(timeout=300)
        finally:
            b.stop()
        np.testing.assert_array_equal(out, ref[0][1])
        # and the journaled prefix matches what was streamed pre-kill
        np.testing.assert_array_equal(
            out[ids.size:ids.size + len(seen)], np.asarray(seen))

    def test_completed_request_with_lost_done_record_resolves(
            self, tiny_model, tmp_path):
        """A crash that lost ONLY the terminal record: the recovered
        request's tokens already satisfy its budget, so it resolves
        immediately instead of decoding past its budget."""
        m, cfg = tiny_model
        jp = tmp_path / "s.jsonl"
        j = SessionJournal(jp)
        j.record_accept(TestSessionJournalUnit._FakeReq(
            "p9999", [1, 2], budget=2, seed=5))
        j.record_token("p9999", 11)
        j.record_token("p9999", 12)
        j.close()
        b = _server(m, journal=str(jp))
        recovered = b.recover_from_journal()
        out = recovered["p9999"].result(timeout=5)  # no start() needed
        np.testing.assert_array_equal(out, [1, 2, 11, 12])
        b.stop()

    def test_recover_without_journal_raises(self, tiny_model):
        m, cfg = tiny_model
        srv = _server(m)
        with pytest.raises(ValueError, match="no journal"):
            srv.recover_from_journal()
        srv.stop()


class TestChaosParityGate:
    """Acceptance: a fixed-seed FaultPlan injecting >= 1 fault at
    every applicable seam over the composed stack — all non-
    quarantined requests produce tokens identical to the fault-free
    run."""

    def _work(self, with_stream=True):
        from paddle_tpu.sampling import SamplingParams

        sink = []
        work = [
            # repetitive motif: guarantees n-gram proposals (verify)
            (np.tile(np.array([5, 6, 7], np.int32), 4), {}),
            # random prompt: rounds without proposals (plain decode)
            (np.array([40, 2, 31, 9], np.int32), {}),
            # fixed-seed sampled
            (np.array([8, 8, 1], np.int32),
             {"sampling": SamplingParams(temperature=0.8, top_p=0.9,
                                         seed=77)}),
            # stop-string request (exercises the detokenize seam)
            (np.array([12, 13], np.int32),
             {"sampling": SamplingParams(stop_strings=("zqz!",))}),
        ]
        if with_stream:
            work[1] = (work[1][0],
                       {"on_token": lambda t, r: sink.append(t)})
        return work

    def test_split_composed_stack_survivor_parity(self, tiny_model):
        m, cfg = tiny_model
        kw = {"enable_prefix_cache": True, "speculation": True,
              "detokenize": _detok, "max_new_tokens": 8,
              "max_slots": 3}
        ref, _, _ = _run_server(m, self._work(), kw)
        plan = FaultPlan.parse(
            "prefill:1,decode:0,verify:0,ensure_many:2,"
            "slow_dispatch:0,detokenize:1,stream_consumer:0")
        res, st, health = _run_server(
            m, self._work(), dict(kw, fault_plan=plan))
        fired = plan.fired()
        for seam in ("prefill", "decode", "verify", "ensure_many",
                     "slow_dispatch", "detokenize", "stream_consumer"):
            assert fired.get(seam, 0) >= 1, (seam, fired)
        survivors = parity = 0
        for (_, want), (kind, have) in zip(ref, res):
            if kind != "ok":
                assert kind == "QuarantinedRequest", (kind, have)
                continue
            survivors += 1
            np.testing.assert_array_equal(want, have)
            parity += 1
        assert survivors >= 3 and parity == survivors
        assert health[0] == "ok"
        rel = st["reliability"]
        assert rel["faults_injected"] >= 7
        assert rel["recoveries"] >= 1

    def test_unified_async_quantized_stack_survivor_parity(
            self, tiny_model):
        m, cfg = tiny_model
        kw = {"enable_prefix_cache": True, "unified_round": True,
              "async_rounds": True, "quantization": "w8a16",
              "kv_dtype": "int8", "max_new_tokens": 6, "max_slots": 2}
        work = [(np.array([1, 2, 3], np.int32), {}),
                (np.array([4, 5, 6, 7], np.int32), {})]
        ref, _, _ = _run_server(m, work, kw)
        plan = FaultPlan.parse("unified_round:1,ensure_many:3")
        res, st, health = _run_server(
            m, work, dict(kw, fault_plan=plan))
        assert [r[0] for r in res] == ["ok", "ok"]
        for (_, a), (_, b) in zip(ref, res):
            np.testing.assert_array_equal(a, b)
        assert plan.fired().get("unified_round", 0) >= 1
        assert plan.fired().get("ensure_many", 0) >= 1
        assert st["reliability"]["recoveries"] >= 1
        assert health[0] == "ok"

    def test_frontdoor_preemption_with_faults_survivor_parity(
            self, tiny_model):
        from paddle_tpu.frontend import FrontDoor

        m, cfg = tiny_model

        def run(fault_plan=None):
            fd = FrontDoor(m, max_slots=1, block_size=4,
                           max_prompt_len=24, max_new_tokens=8,
                           preempt_wait_tokens=0,
                           fault_plan=fault_plan)
            fd.start()
            try:
                hb = fd.submit([4, 5, 6, 7], lane="batch",
                               max_new_tokens=8)
                time.sleep(0.05)  # the bully occupies the one slot
                hi = fd.submit([1, 2, 3], lane="interactive",
                               max_new_tokens=4)
                outs = [hb.result(timeout=300), hi.result(timeout=300)]
                st = fd.stats()
            finally:
                fd.stop()
            return outs, st

        ref, st0 = run()
        out, st = run(FaultPlan.parse("decode:2,prefill:1"))
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
        assert st["reliability"]["faults_injected"] == 2
        assert st["reliability"]["recoveries"] >= 1


class TestJournalCompactionConcurrency:
    """r18 satellite: compaction racing appends can never tear or
    lose a record — copy-on-compact snapshots under the lock, writes
    outside it, and replays buffered appends before the atomic
    swap."""

    def test_threaded_append_vs_compact_stress(self, tmp_path):
        import threading

        jp = tmp_path / "stress.jsonl"
        j = SessionJournal(jp, max_bytes=2048)  # tiny: compacts often

        class R:
            timeout_s = None
            sampling = None
            meta = None

            def __init__(self, rid):
                self.rid = rid
                self.ids = [1, 2, 3]
                self.gen0 = ()
                self.budget = 8
                self.seed = 7

        stop = threading.Event()
        truth = {}
        tl = threading.Lock()
        errors = []

        def writer(k):
            try:
                i = 0
                while not stop.is_set():
                    rid = f"w{k}-{i}"
                    j.record_accept(R(rid))
                    with tl:
                        truth[rid] = []
                    for t in range(5):
                        j.record_token(rid, t)
                        with tl:
                            truth[rid].append(t)
                    if i % 2 == 0:  # half the requests finish
                        j.record_done(rid, "budget")
                        with tl:
                            del truth[rid]
                    i += 1
            except Exception as e:  # noqa: BLE001 — collected
                errors.append(e)

        def compactor():
            try:
                while not stop.is_set():
                    j.compact()  # force: races every append above
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(4)]
        threads.append(threading.Thread(target=compactor))
        for t in threads:
            t.start()
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        j.close()
        assert not errors, errors
        # a fresh loader sees ZERO torn lines and EXACTLY the live
        # state the writers produced — token lists intact, finished
        # requests gone
        j2 = SessionJournal(jp)
        assert j2.stats()["torn_lines"] == 0
        live = {e["rid"]: e["gen0"] for e in j2.interrupted()}
        assert live == truth
        assert len(live) > 10  # the stress actually produced work
        j2.close()

    def test_forced_compact_while_appending_single_thread(
            self, tmp_path):
        """compact() between appends folds tokens into gen0 and drops
        finished entries — the copy-on-compact rewrite preserves the
        pre-satellite semantics exactly."""
        jp = tmp_path / "fold.jsonl"
        j = SessionJournal(jp)

        class R:
            rid, ids, gen0, budget, seed = "a", [4, 5], (), 6, 3
            timeout_s = sampling = meta = None

        j.record_accept(R())
        j.record_token("a", 11)
        j.record_token("a", 12)
        j.compact()
        j.record_token("a", 13)
        j.close()
        j2 = SessionJournal(jp)
        (ent,) = j2.interrupted()
        assert ent["gen0"] == [11, 12, 13]
        j2.close()


class TestJournalRecoveryWithPrefixCache:
    """r18 satellite: recovered sessions RE-ATTACH published prefixes
    instead of re-prefilling from scratch — attach counters asserted,
    including the mid-block partial-tail case."""

    def test_recovery_attaches_published_prefix_mid_block(
            self, tiny_model, tmp_path):
        m, cfg = tiny_model
        # block_size 4, prompt length 10: publishing it indexes 2 full
        # blocks + a fill-2 partial tail; attach may serve 9 = 8 + 1
        # tokens (len-1 cap), PROVING the mid-block tail attached
        prompt = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], np.int32)
        ref, _, _ = _run_server(
            m, [(prompt, {})],
            {"max_new_tokens": 6, "enable_prefix_cache": True,
             "max_prompt_len": 16})
        jp = tmp_path / "pfx.jsonl"
        a = _server(m, max_new_tokens=6, enable_prefix_cache=True,
                    max_prompt_len=16, journal=str(jp))
        fut = a.submit(prompt)           # never started: queued
        a.kill()                         # crash before any prefill
        assert not fut.done()

        b = _server(m, max_new_tokens=6, enable_prefix_cache=True,
                    max_prompt_len=16, journal=str(jp))
        b.start()
        try:
            # warm b's content index with the SAME prompt (publishes
            # 2 full blocks + the fill-2 partial tail), then recover
            b.submit(prompt).result(timeout=300)
            pc0 = b.cache.stats()["prefix_cache"]
            pre0 = b.stats()["prefill_dispatches"]
            recovered = b.recover_from_journal()
            (out,) = [f.result(timeout=300)
                      for f in recovered.values()]
            pc1 = b.cache.stats()["prefix_cache"]
            pre1 = b.stats()["prefill_dispatches"]
        finally:
            b.stop()
        np.testing.assert_array_equal(ref[0][1], out)
        # the recovered admission ATTACHED instead of re-prefilling:
        # one lookup, one hit, and 9 = 2 full blocks + 1 mid-block
        # token served from cache (the len-1 cap leaves exactly the
        # final token for the single prefill dispatch)
        assert pc1["lookups"] == pc0["lookups"] + 1
        assert pc1["hits"] == pc0["hits"] + 1
        assert pc1["hit_tokens"] - pc0["hit_tokens"] == 9
        assert pre1 - pre0 == 1  # one chunk for the 1 uncached token

    def test_recovery_warm_attach_with_generated_tokens(
            self, tiny_model, tmp_path):
        """A session interrupted MID-decode re-attaches its own
        swap-out-published prefix on the restarted server when the
        pool arrays survive — here we emulate the fleet shape: the
        prefix is republished on the new server via export/import,
        and the resumed request warm-attaches (zero prefill work for
        the cached positions)."""
        m, cfg = tiny_model
        prompt = np.array([7, 2, 7, 2, 7, 2], np.int32)
        ref, _, _ = _run_server(
            m, [(prompt, {})],
            {"max_new_tokens": 8, "enable_prefix_cache": True})
        jp = tmp_path / "warm.jsonl"
        a = _server(m, max_new_tokens=8, enable_prefix_cache=True,
                    journal=str(jp))
        seen = []

        def on_token(t, _reason):
            seen.append(t)
            # the engine's thread: hold each token long enough that the
            # export below finds the session still mid-decode (at a
            # millisecond a token the 5 left were over before it ran:
            # the test failed one time in two, alone, at the parent too)
            time.sleep(0.02)

        a.start()
        fut = a.submit(prompt, on_token=on_token)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and len(seen) < 3:
            time.sleep(0.002)
        assert len(seen) >= 3
        # export the live session's K/V BEFORE the crash (the fleet
        # router does this for planned migration)
        ent, payload = a.export_session(
            next(e["rid"] for e in SessionJournal(jp).interrupted()))
        assert payload is not None
        a.kill()
        assert not fut.done()

        b = _server(m, max_new_tokens=8, enable_prefix_cache=True)
        b.start()
        try:
            b.import_kv_payload(payload)
            pre0 = b.stats()["prefills"]
            out = b.admit_journal_entry(ent).result(timeout=300)
            pre1 = b.stats()["prefills"]
        finally:
            b.stop()
        np.testing.assert_array_equal(ref[0][1], out)
        assert pre1 - pre0 == 0  # warm attach: ZERO prefill work
