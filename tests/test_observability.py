"""Unified runtime telemetry (ISSUE 2): metrics registry semantics,
span nesting/ordering across jit boundaries, the per-request trace
assembler on a real paged-serving run, the TelemetryCallback training
hook, and the profiler satellites (percentile summary, decorator)."""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics as M
from paddle_tpu.observability import tracing as T


@pytest.fixture
def reg():
    return M.Registry(enabled=True)


@pytest.fixture
def telemetry_on():
    """Enable the global stack for one test, fully restored after."""
    from paddle_tpu import observability as obs
    obs.enable()
    T.TRACER.reset()
    M.REGISTRY.reset()  # whatever an earlier file of this worker left
    try:
        yield
    finally:
        obs.disable()
        T.TRACER.configure(path=None)
        T.TRACER.reset()
        M.REGISTRY.reset()


class TestRegistry:
    def test_counter_labels_and_get_or_create(self, reg):
        c = reg.counter("reqs_total", "requests", labelnames=("server",))
        c.labels(server="a").inc()
        c.labels(server="a").inc(2)
        c.labels(server="b").inc()
        assert reg.counter("reqs_total", labelnames=("server",)) is c
        snap = reg.snapshot()["reqs_total"]
        assert snap["kind"] == "counter"
        by = {s["labels"]["server"]: s["value"] for s in snap["series"]}
        assert by == {"a": 3.0, "b": 1.0}
        with pytest.raises(ValueError):
            reg.gauge("reqs_total")  # kind mismatch
        with pytest.raises(ValueError):
            c.labels(wrong="x")
        with pytest.raises(ValueError):
            c.labels(server="a").inc(-1)  # counters only go up

    def test_gauge_and_gauge_fn(self, reg):
        g = reg.gauge("depth", "queue depth")
        g.set(4)
        g.dec()
        assert g.value == 3.0
        reg.gauge_fn("age", "pulled", lambda: 42.5)
        assert reg.snapshot()["age"]["series"][0]["value"] == 42.5

    def test_histogram_buckets_and_percentile(self, reg):
        h = reg.histogram("lat", "latency", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 2.0):
            h.observe(v)
        s = reg.snapshot()["lat"]["series"][0]
        assert s["count"] == 4 and s["sum"] == pytest.approx(2.555)
        assert s["buckets"] == {"0.01": 1, "0.1": 1, "1.0": 1, "+Inf": 1}
        assert 0.01 <= h.percentile(0.5) <= 0.1
        with pytest.raises(ValueError):
            reg.histogram("bad", buckets=(1.0, 0.5))  # not increasing

    def test_disabled_is_noop(self):
        r = M.Registry(enabled=False)
        c = r.counter("n")
        g = r.gauge("g")
        h = r.histogram("h")
        c.inc()
        g.set(9)
        h.observe(1.0)
        assert c.value == 0.0 and g.value == 0.0
        assert r.snapshot()["h"]["series"][0]["count"] == 0
        r.enable()
        c.inc()
        assert c.value == 1.0

    def test_prometheus_text_format(self, reg):
        reg.counter("c_total", "help text", labelnames=("k",)) \
           .labels(k='va"l').inc()
        reg.histogram("h_s", buckets=(0.5,)).observe(0.2)
        text = reg.to_prometheus()
        assert "# HELP c_total help text" in text
        assert "# TYPE c_total counter" in text
        assert 'c_total{k="va\\"l"} 1' in text
        assert 'h_s_bucket{le="0.5"} 1' in text
        assert 'h_s_bucket{le="+Inf"} 1' in text
        assert "h_s_sum 0.2" in text and "h_s_count 1" in text

    def test_reset_keeps_definitions(self, reg):
        c = reg.counter("n")
        c.inc(5)
        reg.reset()
        assert c.value == 0.0
        assert reg.counter("n") is c

    def test_prometheus_conformance_golden(self):
        """Golden-file conformance of the scrape text (ISSUE 10
        satellite): HELP/TYPE lines, label escaping for quotes /
        newlines / backslashes, histogram cumulative buckets with the
        +Inf bucket and _sum/_count — byte-exact, so the new /metrics
        endpoint emits parseable Prometheus text by construction."""
        reg = M.Registry(enabled=True)
        c = reg.counter("scrape_c_total", "a counter",
                        labelnames=("k",))
        c.labels(k='quo"te').inc(3)
        c.labels(k="line\nbreak").inc()
        c.labels(k="back\\slash").inc(2)
        g = reg.gauge("scrape_g", "a gauge")
        g.set(2.5)
        h = reg.histogram("scrape_h_seconds", "a histogram",
                          buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        golden = (
            '# HELP scrape_c_total a counter\n'
            '# TYPE scrape_c_total counter\n'
            'scrape_c_total{k="quo\\"te"} 3\n'
            'scrape_c_total{k="line\\nbreak"} 1\n'
            'scrape_c_total{k="back\\\\slash"} 2\n'
            '# HELP scrape_g a gauge\n'
            '# TYPE scrape_g gauge\n'
            'scrape_g 2.5\n'
            '# HELP scrape_h_seconds a histogram\n'
            '# TYPE scrape_h_seconds histogram\n'
            'scrape_h_seconds_bucket{le="0.1"} 1\n'
            'scrape_h_seconds_bucket{le="1"} 2\n'
            'scrape_h_seconds_bucket{le="+Inf"} 3\n'
            'scrape_h_seconds_sum 5.55\n'
            'scrape_h_seconds_count 3\n'
        )
        assert reg.to_prometheus() == golden


class TestTracing:
    def test_span_nesting_and_order_across_jit(self, tmp_path):
        """Spans around jitted dispatches: nesting is recorded
        (parent/depth) and timestamps are monotonic in completion
        order even with a compile inside the outer span."""
        import jax
        import jax.numpy as jnp

        tr = T.Tracer(enabled=True, path=str(tmp_path / "t.jsonl"))
        f = jax.jit(lambda x: x * 2 + 1)
        with tr.span("outer", request_id="r1"):
            with tr.span("dispatch"):
                f(jnp.ones((4,))).block_until_ready()
            with tr.span("dispatch"):
                f(jnp.ones((4,))).block_until_ready()
        evs = tr.events()
        names = [e["name"] for e in evs]
        assert names == ["dispatch", "dispatch", "outer"]  # completion order
        d1, d2, outer = evs
        assert d1["parent"] == d2["parent"] == "outer"
        assert d1["depth"] == 1 and outer["depth"] == 0
        assert d1["ts"] <= d2["ts"] <= outer["ts"] + outer["dur"]
        # the outer span covers both dispatches
        assert outer["dur"] >= d1["dur"] + d2["dur"] - 1e-9
        # JSONL round-trip preserves every event
        tr.close()
        loaded = T.load_events(str(tmp_path / "t.jsonl"))
        assert [e["name"] for e in loaded] == ["trace_start"] + names

    def test_disabled_span_is_noop(self):
        tr = T.Tracer(enabled=False)
        with tr.span("x"):
            pass
        tr.event("y")
        assert tr.events() == []

    def test_sink_rotates_at_max_bytes(self, tmp_path):
        """Bounded sink (ISSUE 10 satellite): the JSONL file never
        exceeds max_bytes; crossing the cap rotates once to path+'.1'
        so total disk stays ~2x the cap and the most recent events
        survive."""
        path = str(tmp_path / "t.jsonl")
        tr = T.Tracer(enabled=True)
        tr.configure(path=path, max_bytes=2048)
        for i in range(200):
            tr.event("ev", i=i, pad="x" * 40)
        tr.flush()
        assert os.path.getsize(path) <= 2048
        assert os.path.exists(path + ".1")
        assert os.path.getsize(path + ".1") <= 2048
        # the live file starts with a rotation-stamped header and its
        # events parse; the newest event is in the live file
        live = T.load_events(path)
        assert live[0]["name"] == "trace_start"
        assert live[0]["rotation"] >= 1
        assert live[-1]["i"] == 199
        # rotation preserved the immediately-preceding events
        prev = T.load_events(path + ".1")
        assert prev[-1]["i"] == live[1]["i"] - 1
        tr.close()

    def test_wrap_decorates_dispatch(self):
        tr = T.Tracer(enabled=True)
        calls = []
        g = tr.wrap("fn_dispatch", lambda a: calls.append(a) or a + 1)
        assert g(1) == 2
        assert calls == [1]
        assert tr.events()[0]["name"] == "fn_dispatch"


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    paddle.seed(23)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


class TestServingTrace:
    def test_paged_serving_trace_assembles(self, tiny_model, tmp_path,
                                           telemetry_on):
        """Tier-1 smoke (ISSUE 2 acceptance shape): a short paged run
        produces a parseable JSONL trace whose per-request phase sum is
        within 10% of the measured wall-clock, with TTFT populated in
        both the assembled report and server stats()."""
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        path = str(tmp_path / "trace.jsonl")
        T.configure(path=path, truncate=True)
        rs = np.random.RandomState(3)
        srv = PagedGenerationServer(model, max_slots=2, block_size=4,
                                    max_prompt_len=16,
                                    max_new_tokens=4).start()
        t_wall = {}
        try:
            prompts = [rs.randint(1, cfg.vocab_size, (n,))
                       .astype(np.int32) for n in (3, 7, 5, 9)]
            t0 = time.perf_counter()
            futs = [srv.submit(p) for p in prompts]
            for f in futs:
                f.result(timeout=300)
            t_wall["drain"] = time.perf_counter() - t0
            st = srv.stats()
        finally:
            srv.stop()
        # ttft percentiles derived from the spans' samples
        assert 0 < st["ttft_p50_ms"] <= st["ttft_p99_ms"] <= st["p99_ms"]
        T.flush()
        # every line parses as JSON (load_events skips nothing here)
        with open(path) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        assert len(lines) == len(T.load_events(path))
        traces = T.assemble_request_traces(path=path)
        assert len(traces) == 4
        for r in traces.values():
            phase_sum = sum(r["phases_ms"].values())
            assert phase_sum == pytest.approx(r["wall_ms"], rel=0.10)
            assert r["wall_ms"] <= t_wall["drain"] * 1e3 * 1.10
            assert set(r["phases_ms"]) == {"queue_wait", "admission",
                                           "prefill", "decode",
                                           "detokenize"}
            assert 0 < r["ttft_ms"] <= r["wall_ms"] * 1.001
            assert r["new_tokens"] == 4
            assert r["decode_dispatches"] >= 1
        summ = T.summarize_traces(traces)
        assert summ["requests"] == 4
        assert summ["ttft_p50_ms"] > 0
        # pool + serving metrics landed in the registry
        snap = M.snapshot()
        done = {s["labels"]["server"]: s["value"]
                for s in snap["serving_requests_total"]["series"]}
        assert done.get("paged") == 4
        pool_series = snap["kv_pool_used_blocks"]["series"]
        assert all(s["value"] == 0 for s in pool_series)  # drained
        assert all("pool" in s["labels"] for s in pool_series)
        refills = snap["serving_slot_refills_total"]["series"][0]["value"]
        assert refills == 4  # every admission fills an idle slot

    def test_kv_pool_gauges_do_not_alias_across_caches(self,
                                                       telemetry_on):
        """Satellite (round 9): two live caches must land on DISTINCT
        `pool`-labeled series — the pre-label behavior silently showed
        whichever pool mutated last."""
        from paddle_tpu.inference.kv_cache import PagedKVCache

        c1 = PagedKVCache(1, 1, 2, block_size=4, num_blocks=4)
        c2 = PagedKVCache(1, 1, 2, block_size=4, num_blocks=8)
        c1.allocate("a", 4)
        c2.allocate("b", 20)
        assert c1._name != c2._name
        by = {s["labels"]["pool"]: s["value"]
              for s in M.snapshot()["kv_pool_used_blocks"]["series"]}
        assert by[c1._name] == 1.0
        assert by[c2._name] == 5.0

    def test_reset_stats_clears_ttft(self, tiny_model, telemetry_on):
        from paddle_tpu.inference import PagedGenerationServer

        model, cfg = tiny_model
        srv = PagedGenerationServer(model, max_slots=1, block_size=4,
                                    max_prompt_len=8,
                                    max_new_tokens=2).start()
        try:
            srv.submit([3, 5, 7]).result(timeout=300)
            assert srv.stats()["ttft_p50_ms"] > 0
            srv.reset_stats()
            st = srv.stats()
            assert st["ttft_p50_ms"] == 0.0 and st["ttft_p99_ms"] == 0.0
        finally:
            srv.stop()


class TestTelemetryCallback:
    def test_fit_populates_step_histograms(self, telemetry_on):
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi.callbacks import TelemetryCallback

        x = np.random.RandomState(0).rand(8, 4).astype(np.float32)
        y = (x @ np.ones((4, 1), np.float32)).astype(np.float32)
        model = paddle.Model(nn.Linear(4, 1))
        model.prepare(paddle.optimizer.SGD(
            0.01, parameters=model.parameters()), nn.MSELoss())
        model.fit(list(zip(x, y)), batch_size=4, epochs=1, verbose=0,
                  callbacks=[TelemetryCallback()])
        snap = M.snapshot()
        assert snap["train_steps_total"]["series"][0]["value"] == 2
        assert snap["train_step_seconds"]["series"][0]["count"] == 2
        assert snap["train_loss"]["series"][0]["count"] == 2
        # spans landed too (tracing enabled by the fixture)
        steps = [e for e in T.events() if e["name"] == "train_step"]
        assert len(steps) == 2


class TestProfilerSatellites:
    def test_summary_percentiles(self):
        from paddle_tpu.utils import profiler
        profiler.reset()
        for ms in (1, 2, 3, 4, 100):
            profiler._records["ev"].append(ms / 1e3)
        s = profiler.summary()["ev"]
        assert s["count"] == 5
        assert s["min"] == pytest.approx(0.001)
        assert s["max"] == pytest.approx(0.1)
        assert s["p50"] == pytest.approx(0.003)
        assert s["p99"] == pytest.approx(0.1)
        assert s["mean"] == pytest.approx(s["total"] / 5)
        profiler.reset()

    def test_record_event_decorator_forms(self):
        from paddle_tpu.utils import profiler
        profiler.reset()

        @profiler.record_event("named")
        def f():
            return 7

        @profiler.record_event
        def g():
            return 8

        assert f() == 7 and f() == 7 and g() == 8
        s = profiler.summary()
        assert s["named"]["count"] == 2
        gkey = [k for k in s if k.endswith("g")]
        assert len(gkey) == 1 and s[gkey[0]]["count"] == 1
        # context-manager form unchanged
        with profiler.record_event("cm"):
            pass
        assert profiler.summary()["cm"]["count"] == 1
        profiler.reset()


class TestWatchdogGauge:
    def test_heartbeat_age_gauge(self, telemetry_on):
        from paddle_tpu.utils.watchdog import Watchdog
        wd = Watchdog(timeout=60).start()
        try:
            wd.beat()
            age = M.snapshot()["watchdog_heartbeat_age_seconds"][
                "series"][0]["value"]
            assert 0 <= age < 5
        finally:
            wd.stop()
