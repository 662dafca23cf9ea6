"""Serving operations plane (ISSUE 10): /metrics · /statusz ·
/healthz endpoint round-trip on an ephemeral port, healthz
transitions through an induced stall, flight-recorder ring bounds +
auto-dump on an injected engine exception, exact compile tracking
under a forced fresh bucket (and warm_buckets' in_flight="false"
compiles), and goodput conservation (decoded = goodput + rolled_back
+ replayed)."""
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import compile_tracker as CT
from paddle_tpu.observability.flight_recorder import (FlightRecorder,
                                                      StallWatchdog)


@pytest.fixture(autouse=True)
def _registry_guard():
    """expose_port= enables the process metrics registry by design;
    restore the pre-test gate and zero the series afterwards so later
    tests (and the telemetry suite's absolute-count assertions) see a
    clean slate."""
    from paddle_tpu.observability import metrics as M

    was = M.REGISTRY.enabled
    yield
    M.REGISTRY.enabled = was
    M.REGISTRY.reset()


def _get(url, timeout=10):
    """(status_code, body) — urllib raises on 503, which /healthz uses
    for 'stalled' on purpose."""
    try:
        r = urllib.request.urlopen(url, timeout=timeout)
        return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _wait_for(pred, timeout=10.0, poll=0.05):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(poll)
    return False


def _model(salt=0, hidden=128):
    """A fresh tiny GPT-2. `hidden` varies the decoder SPEC, which
    varies the process-wide jit cache key — tests that must observe a
    compile pick an unused hidden size so earlier tests (or earlier
    servers in THIS test) can't have warmed their programs."""
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config

    paddle.seed(100 + salt)
    cfg = GPT2Config(vocab_size=512, hidden_size=hidden, num_layers=2,
                     num_heads=4, max_position=128)
    cfg.dropout = 0.0
    m = GPT2(cfg)
    m.eval()
    return m, cfg


def _server(m, **kw):
    from paddle_tpu.inference import PagedGenerationServer

    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_new_tokens", 4)
    return PagedGenerationServer(m, **kw)


class TestFlightRecorderRing:
    def test_bounded_and_ordered(self):
        fr = FlightRecorder(capacity=16, enabled=True)
        for i in range(48):
            fr.record("ev", i=i)
        evs = fr.events()
        assert len(evs) == 16  # ring: bounded at capacity
        # deterministic: monotonic contiguous seq, newest retained
        assert [e["seq"] for e in evs] == list(range(32, 48))
        assert [e["i"] for e in evs] == list(range(32, 48))
        d = fr.dump()
        assert d["trigger"] == "manual" and d["n_events"] == 16
        assert fr.last_dump is d

    def test_disabled_is_noop(self):
        fr = FlightRecorder(capacity=4)  # enabled defaults False
        fr.record("ev")
        assert fr.events() == []
        fr.enable()
        fr.record("ev")
        assert len(fr.events()) == 1

    def test_watchdog_requires_pending_work(self):
        """No pending work = never stalled, however long progress sits
        still; pending + frozen progress = stalled within ~timeout,
        and progress recovery clears it."""
        state = {"progress": 0, "pending": False, "stalls": 0}
        wd = StallWatchdog(lambda: state["progress"],
                           lambda: state["pending"],
                           timeout=0.15, poll=0.03,
                           on_stall=lambda: state.__setitem__(
                               "stalls", state["stalls"] + 1)).start()
        try:
            time.sleep(0.4)
            assert not wd.stalled  # idle engine is healthy
            state["pending"] = True
            assert _wait_for(lambda: wd.stalled, timeout=5)
            # the flag is set just before the callback runs
            assert _wait_for(lambda: state["stalls"] == 1, timeout=5)
            state["progress"] += 1  # dispatch progress clears the stall
            assert _wait_for(lambda: not wd.stalled, timeout=5)
            assert state["stalls"] == 1  # one episode, one dump
        finally:
            wd.stop()


class TestOpsEndpoint:
    def test_roundtrip_and_stall_transitions(self):
        """The acceptance shape: ephemeral-port scrape of all three
        endpoints; an induced stall (work submitted, engine loop never
        started) drives /healthz ok -> stalled (503) with a
        flight-recorder auto-dump whose events reconstruct the
        stalling request; starting the engine drains and recovers."""
        m, cfg = _model(salt=1)
        srv = _server(m, expose_port=0, stall_timeout_s=0.3)
        try:
            url = srv.exporter.url
            assert srv.exporter.port > 0
            code, body = _get(url + "/healthz")
            assert code == 200 and json.loads(body)["status"] == "ok"

            fut = srv.submit([3, 5, 7])  # work pending, engine not
            # started: the definition of a stall
            assert _wait_for(
                lambda: _get(url + "/healthz")[0] == 503, timeout=15)
            code, body = _get(url + "/healthz")
            h = json.loads(body)
            assert code == 503 and h["status"] == "stalled"
            assert h["stalls"] >= 1
            # the auto-dump reconstructs the stalling request
            dump = srv._recorder.last_dump
            assert dump is not None and dump["trigger"] == "stall"
            sub = [e for e in dump["events"] if e["name"] == "submit"]
            assert len(sub) == 1  # exactly the stalling request
            assert sub[0]["request_id"].startswith("p")
            assert sub[0]["prompt_len"] == 3 and sub[0]["budget"] == 4
            stall_evs = [e for e in dump["events"]
                         if e["name"] == "stall"]
            assert stall_evs  # the trip itself is on the record

            srv.start()
            out = fut.result(timeout=300)
            assert list(out[:3]) == [3, 5, 7]
            assert _wait_for(
                lambda: json.loads(_get(url + "/healthz")[1])[
                    "status"] == "ok", timeout=15)

            # /metrics: parseable Prometheus text with the ops metrics
            code, prom = _get(url + "/metrics")
            assert code == 200
            assert "# TYPE serving_xla_compiles_total counter" in prom
            assert "serving_stalls_total" in prom
            # /statusz: the live JSON engine state schema
            code, body = _get(url + "/statusz")
            sz = json.loads(body)
            assert code == 200
            assert sz["server"] == "paged"
            assert sz["health"]["status"] == "ok"
            assert sz["last_dump"]["trigger"] == "stall"
            eng = sz["engine"]
            for key in ("goodput", "compiles", "ops", "speculation",
                        "quantization", "sharding", "frontdoor",
                        "kv_cache", "stop_reasons"):
                assert key in eng, key
            assert eng["ops"]["exporter_port"] == srv.exporter.port
            assert eng["goodput"]["goodput_ratio"] == 1.0
            # unknown path: 404 with the path listing, listener alive
            code, body = _get(url + "/nope")
            assert code == 404 and "/statusz" in body
        finally:
            srv.stop()
        # stop() released the port: nothing is listening anymore
        with pytest.raises(Exception):
            urllib.request.urlopen(url + "/healthz", timeout=2)

    def test_env_port_starts_ops_plane(self, monkeypatch):
        """PADDLE_TPU_METRICS_PORT is the no-code-change production
        switch: the engine picks it up at construction."""
        monkeypatch.setenv("PADDLE_TPU_METRICS_PORT", "0")
        m, cfg = _model(salt=2)
        srv = _server(m)
        try:
            assert srv.exporter is not None
            assert srv._recorder.enabled
            code, _ = _get(srv.exporter.url + "/metrics")
            assert code == 200
        finally:
            srv.stop()

    def test_frontdoor_surfaces_ops(self):
        """FrontDoor forwards expose_port to the engine and surfaces
        the ops plane on the facade; /statusz carries the lane/tenant
        blocks of the installed scheduler."""
        from paddle_tpu.frontend import FrontDoor

        m, cfg = _model(salt=3)
        fd = FrontDoor(m, max_slots=2, block_size=4, max_prompt_len=16,
                       max_new_tokens=4, expose_port=0)
        fd.start()
        try:
            assert fd.ops_url
            h = fd.submit([2, 4, 6], lane="interactive")
            assert h.result(timeout=300) is not None
            sz = fd.statusz()
            assert sz["engine"]["frontdoor"]["enabled"] is True
            assert isinstance(sz["engine"]["lane_queue_depth"], dict)
            assert fd.health()[0] in ("ok", "degraded")
            d = fd.dump_flight_recorder()
            assert d["trigger"] == "manual"
            names = {e["name"] for e in d["events"]}
            assert {"submit", "admit", "prefill_chunk",
                    "request_done"} <= names
        finally:
            fd.stop()


class TestEngineExceptionDump:
    def test_injected_dispatch_exception_autodumps(self):
        """An engine dispatch exception fails the in-flight futures
        (pre-existing behavior) AND leaves a post-hoc record: flight
        recorder auto-dump with trigger='engine_exception', health
        degraded until reset_stats."""
        m, cfg = _model(salt=4)
        srv = _server(m, expose_port=0, stall_timeout_s=30.0)

        class Broken:
            def __getattr__(self, name):
                return getattr(srv.__dict__["_real_decoder"], name)

            def packed_prefill(self, *a, **kw):
                raise RuntimeError("injected prefill failure")

        srv.__dict__["_real_decoder"] = srv._decoder
        srv._decoder = Broken()
        srv.start()
        try:
            fut = srv.submit([1, 2, 3])
            with pytest.raises(RuntimeError, match="injected"):
                fut.result(timeout=300)
            assert _wait_for(
                lambda: srv._recorder.last_dump is not None
                and srv._recorder.last_dump["trigger"]
                == "engine_exception", timeout=10)
            dump = srv._recorder.last_dump
            exc = [e for e in dump["events"]
                   if e["name"] == "engine_exception"]
            assert exc and "injected" in exc[0]["error"]
            assert exc[0]["where"] == "prefill"
            status, detail = srv.health()
            assert status == "degraded"
            assert "injected" in detail["last_error"]
            # a fresh measurement window is healthy again
            srv.reset_stats()
            assert srv.health()[0] == "ok"
        finally:
            srv.stop()


class TestCompileTracker:
    def test_forced_fresh_bucket_counts(self):
        """A prompt long enough to need a NEW packed bucket compiles
        exactly once, attributed to packed_prefill with
        in_flight='true' (the engine was serving it); re-hitting the
        same bucket compiles nothing."""
        m, cfg = _model(salt=5, hidden=96)  # unused spec: fresh jits
        srv = _server(m, prefill_chunk_tokens=16)
        srv.start()
        try:
            srv.submit([1, 2, 3]).result(timeout=300)  # T=8 bucket
            mark = CT.mark()
            # 9 real tokens pack to the T=16 bucket: a fresh compile
            srv.submit(list(range(1, 10))).result(timeout=300)
            evs = [e for e in CT.events_since(mark)
                   if e["program"] == "packed_prefill"]
            assert len(evs) == 1, evs
            assert evs[0]["in_flight"] is True
            assert evs[0]["shard"] == "none"
            assert evs[0]["dur_s"] > 0
            mark2 = CT.mark()
            srv.submit(list(range(2, 11))).result(timeout=300)  # same
            assert CT.count_since(mark2) == 0  # bucket: no compile
        finally:
            srv.stop()

    def test_sharded_compiles_carry_mesh_shard_label(self):
        """Compile metrics from a mesh-sharded engine carry the mesh
        shape as the `shard` label (serving_dist), so a fleet mixing
        mesh configs can tell whose jit cache went cold."""
        from paddle_tpu.serving_dist import ShardedEngineConfig

        m, cfg = _model(salt=10, hidden=80)  # unused spec: fresh jits
        srv = _server(m, sharding=ShardedEngineConfig(tp=2))
        mark = CT.mark()
        srv.start()
        try:
            srv.submit([1, 2, 3]).result(timeout=300)
        finally:
            srv.stop()
        evs = CT.events_since(mark)
        assert evs, "sharded dispatch must have compiled fresh programs"
        assert {e["shard"] for e in evs} == {"mp2xdp1"}, evs

    def test_warm_buckets_compiles_are_not_in_flight(self):
        """warm_buckets() coverage is measurable: its compiles happen
        before any traffic (in_flight='false'), and a measurement
        window on warmed traffic reports zero compiles — the
        stats()['compiles'] block bench records as
        compiles_in_window."""
        m, cfg = _model(salt=6, hidden=64)  # unused spec: fresh jits
        srv = _server(m, prefill_chunk_tokens=16)
        mark = CT.mark()
        n = srv.warm_buckets()
        assert n > 0
        warm_evs = CT.events_since(mark)
        assert len(warm_evs) >= 1
        assert all(e["in_flight"] is False for e in warm_evs)
        srv.start()
        try:
            prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
            for f in [srv.submit(p) for p in prompts]:  # warm traffic:
                f.result(timeout=300)  # decode/step programs compile
            srv.reset_stats()
            for f in [srv.submit(p) for p in prompts]:  # measured
                f.result(timeout=300)
            st = srv.stats()
            assert st["compiles"]["window_total"] == 0, st["compiles"]
            assert st["compiles"]["window_in_flight"] == 0
        finally:
            srv.stop()


class TestGoodput:
    def test_conservation_multistep_overrun(self):
        """steps_per_dispatch=3 with a 6-token budget forces post-stop
        scan discards (token 0 from prefill + 5 scan tokens = two
        3-token scans, one discarded): decoded = goodput + rolled_back
        + replayed holds exactly and the ratio drops below 1."""
        m, cfg = _model(salt=7)
        srv = _server(m, steps_per_dispatch=3, max_new_tokens=6)
        srv.start()
        try:
            rs = np.random.RandomState(0)
            for f in [srv.submit(rs.randint(1, cfg.vocab_size,
                                            (n,)).astype(np.int32))
                      for n in (3, 7, 5)]:
                f.result(timeout=300)
            g = srv.stats()["goodput"]
        finally:
            srv.stop()
        assert g["decoded_tokens"] == (g["goodput_tokens"]
                                       + g["rolled_back_tokens"]
                                       + g["replayed_tokens"])
        assert g["goodput_tokens"] == 3 * 6  # every budget delivered
        assert g["replayed_tokens"] == 3  # one discard per request
        assert 0 < g["goodput_ratio"] < 1.0

    def test_conservation_with_speculation_rollback(self):
        """With the n-gram self-drafter on arbitrary prompts, rejected
        drafts roll back; conservation must still hold exactly."""
        m, cfg = _model(salt=8)
        srv = _server(m, speculation=True, max_new_tokens=6,
                      max_prompt_len=24)
        srv.start()
        try:
            # repetitive prompts so the drafter actually proposes
            for f in [srv.submit([7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]),
                      srv.submit([5, 6, 5, 6, 5, 6, 5, 6, 5, 6])]:
                f.result(timeout=300)
            st = srv.stats()
            g = st["goodput"]
        finally:
            srv.stop()
        assert g["decoded_tokens"] == (g["goodput_tokens"]
                                       + g["rolled_back_tokens"]
                                       + g["replayed_tokens"])
        assert st["speculation"]["proposed_tokens"] > 0
        assert g["goodput_tokens"] == 2 * 6

    def test_conservation_exact_budget_is_lossless(self):
        """k=1 greedy with no speculation/preemption: every decoded
        position is emitted — ratio exactly 1.0."""
        m, cfg = _model(salt=9)
        srv = _server(m)
        srv.start()
        try:
            srv.submit([2, 3, 4]).result(timeout=300)
            g = srv.stats()["goodput"]
        finally:
            srv.stop()
        assert g["decoded_tokens"] == g["goodput_tokens"] == 4
        assert g["goodput_ratio"] == 1.0


class TestSplitHealth:
    """r18 satellite: /healthz split into liveness vs readiness so a
    router can tell 'dead, fail over' from 'drain, don't route' —
    with the legacy /healthz shape untouched."""

    def test_live_and_ready_endpoints_roundtrip(self):
        m, cfg = _model(salt=21)
        srv = _server(m, expose_port=0)
        url = srv.exporter.url
        try:
            # before start(): the loop is NOT alive -> live 503;
            # legacy /healthz still answers its old ok/200 shape
            code, body = _get(url + "/healthz/live")
            assert code == 503 and json.loads(body)["live"] is False
            code, body = _get(url + "/healthz")
            assert code == 200 and json.loads(body)["status"] == "ok"

            srv.start()
            assert _wait_for(
                lambda: _get(url + "/healthz/live")[0] == 200)
            code, body = _get(url + "/healthz/ready")
            r = json.loads(body)
            assert code == 200 and r["ready"] is True
            assert r["draining"] is False

            # draining: ready flips 503, live stays 200, legacy
            # /healthz stays ok — residents finish, nothing routes
            srv.set_draining(True)
            code, body = _get(url + "/healthz/ready")
            r = json.loads(body)
            assert code == 503 and r["ready"] is False
            assert r["draining"] is True
            assert _get(url + "/healthz/live")[0] == 200
            assert _get(url + "/healthz")[0] == 200
            srv.set_draining(False)
            assert _get(url + "/healthz/ready")[0] == 200

            # the 404 listing now names the split endpoints
            code, body = _get(url + "/nope")
            assert code == 404
            assert "/healthz/live" in body and "/healthz/ready" in body

            # /statusz inlines both blocks
            sz = json.loads(_get(url + "/statusz")[1])
            assert sz["liveness"]["live"] is True
            assert sz["readiness"]["ready"] is True
        finally:
            srv.stop()
        # after stop(): dead — the router's fail-over signal
        live, detail = srv.liveness()
        assert live is False

    def test_statusz_carries_structured_pool_exhaustion(self):
        """r18 satellite: BlockPoolExhausted.needed/available and the
        degraded reason are machine-readable in health/statusz — the
        router's passive signal parses fields, not messages."""
        from paddle_tpu.inference.kv_cache import BlockPoolExhausted

        m, cfg = _model(salt=22)
        srv = _server(m)
        try:
            e = BlockPoolExhausted("synthetic", needed=7, available=2)
            srv._engine_exception("ensure_many", e, ["p0"])
            status, detail = srv.health()
            assert status == "degraded"
            info = detail["last_error_info"]
            assert info["where"] == "ensure_many"
            assert info["error_type"] == "BlockPoolExhausted"
            assert info["needed"] == 7 and info["available"] == 2
            sz = srv.statusz()
            assert sz["health"]["last_error_info"]["needed"] == 7
            # reset clears the structured info with the string
            srv.reset_stats()
            status, detail = srv.health()
            assert status == "ok" and "last_error_info" not in detail
        finally:
            srv.stop()

    def test_clean_recovery_clears_structured_info(self):
        """The structured error info follows the degraded->ok
        transition: present while unrecovered, gone after the first
        clean dispatch (r17 recovery semantics, r18 field)."""
        from paddle_tpu.reliability import FaultPlan

        m, cfg = _model(salt=23)
        srv = _server(m, fault_plan=FaultPlan([("ensure_many", 0)]))
        srv.start()
        try:
            out = srv.submit([3, 4, 5]).result(timeout=300)
            assert list(out[:3]) == [3, 4, 5]
            assert _wait_for(lambda: srv.health()[0] == "ok")
            _status, detail = srv.health()
            assert "last_error_info" not in detail
            assert srv.stats()["reliability"]["recoveries"] >= 1
        finally:
            srv.stop()
