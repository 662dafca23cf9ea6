"""The serving engine's round on one clock (ISSUE 24): the phase
accounting of `PagedGenerationServer` (`stats()["round_phases"]`), its
`pt:` spans in the profiler's own trace, the span primitive's ids, and
the names the Pallas kernels carry."""
import glob
import re
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import PagedGenerationServer
from paddle_tpu.inference.serving import ROUND_PHASES
from paddle_tpu.observability import tracing as T
from paddle_tpu.reliability.faults import Fault, FaultPlan

PHASE_KEYS = set(ROUND_PHASES) | {"other"}
LOOPS = {"split": {},
         "unified": {"unified_round": True},
         "unified_async": {"unified_round": True, "async_rounds": True}}


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    paddle.seed(23)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


def _server(model, loop, **kw):
    return PagedGenerationServer(model, max_slots=2, block_size=4,
                                 max_prompt_len=16, max_new_tokens=6,
                                 **LOOPS[loop], **kw)


def _prompts(cfg, sizes=(3, 7, 5, 9, 4, 6)):
    rs = np.random.RandomState(3)
    return [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in sizes]


def _serve(srv, prompts):
    for f in [srv.submit(p) for p in prompts]:
        f.result(timeout=300)


@pytest.mark.parametrize("loop", list(LOOPS))
class TestRoundPhases:
    def test_phases_tile_the_engine_threads_time(self, tiny_model, loop):
        model, cfg = tiny_model
        srv = _server(model, loop)
        # before the thread runs: every key there, everything zero
        rp = srv.stats()["round_phases"]
        assert set(rp["seconds"]) == PHASE_KEYS
        assert not any(rp["seconds"].values()) and rp["dispatches"] == 0
        srv.start()
        try:
            _serve(srv, _prompts(cfg))     # compiles: not in the window
            t_a = time.perf_counter()
            srv.reset_stats()
            t_b = time.perf_counter()
            _serve(srv, _prompts(cfg))
            time.sleep(0.4)                # a few idle waits
            t_c = time.perf_counter()
            st = srv.stats()
            t_d = time.perf_counter()
        finally:
            srv.stop()
        rp = st["round_phases"]
        assert set(rp["seconds"]) == PHASE_KEYS
        assert all(v >= 0 for v in rp["seconds"].values())
        # the phases tile the thread's time from the reset to the reading:
        # exactly, so the sum lies between the instants around the two
        total = sum(rp["seconds"].values())
        assert t_c - t_b <= total <= t_d - t_a
        assert total == pytest.approx(st["wall_s"], rel=0.02)
        wall = t_d - t_a
        for name in ("plan", "dispatch", "read_back", "emit", "admit",
                     "idle_wait"):
            assert rp["seconds"][name] > 0, name
        # what no phase covers is the small remainder, not the bulk
        assert rp["seconds"]["other"] < 0.25 * total
        longest = rp["longest_round"]
        assert set(longest["phases_ms"]) == PHASE_KEYS
        assert 0 < longest["ms"] <= wall * 1e3
        assert sum(longest["phases_ms"].values()) == pytest.approx(
            longest["ms"], rel=0.02)
        assert 0 <= longest["at_s"] <= wall
        kinds = set(longest["kind"].split("+"))
        assert kinds and kinds <= ({"prefill", "decode"} if loop == "split"
                                   else {"unified"})

    def test_reset_zeroes_and_a_stopped_thread_adds_nothing(
            self, tiny_model, loop):
        model, cfg = tiny_model
        srv = _server(model, loop).start()
        try:
            _serve(srv, _prompts(cfg, (3, 5)))
            assert srv.stats()["round_phases"]["dispatches"] > 0
        finally:
            srv.stop()
        srv.reset_stats()
        time.sleep(0.02)
        rp = srv.stats()["round_phases"]
        assert set(rp["seconds"]) == PHASE_KEYS
        assert not any(rp["seconds"].values())
        assert rp["dispatches"] == 0
        assert rp["longest_round"]["ms"] == 0.0
        assert rp["longest_round"]["kind"] == ""
        assert not any(rp["longest_round"]["phases_ms"].values())

    def test_dispatches_are_the_engines_own_count(self, tiny_model, loop):
        model, cfg = tiny_model
        srv = _server(model, loop).start()
        try:
            _serve(srv, _prompts(cfg))
            srv.reset_stats()
            _serve(srv, _prompts(cfg))
            st = srv.stats()
        finally:
            srv.stop()
        n = st["round_phases"]["dispatches"]
        assert n > 0
        if loop == "split":   # one program a dispatch, each counted once
            assert n == st["decode_steps"] + st["prefill_dispatches"]
        else:                 # one program a round, whatever rides in it
            assert n == st["rounds"]["rounds"]
            assert n == st["rounds"]["attention_dispatches"]
            assert n <= st["decode_steps"] + st["prefill_dispatches"]

    def test_an_injected_delay_is_the_longest_round(self, tiny_model, loop):
        model, cfg = tiny_model
        delay = 0.4
        srv = _server(model, loop).start()
        try:
            _serve(srv, _prompts(cfg))     # compiles out of the way
            # the seam's fifth occurrence from here on: mid-traffic
            srv._faults = FaultPlan([Fault("slow_dispatch", 4, "slow",
                                           delay_s=delay)])
            srv.reset_stats()
            _serve(srv, _prompts(cfg))
            st = srv.stats()
        finally:
            srv.stop()
        assert st["reliability"]["faults_injected"] == 1
        longest = st["round_phases"]["longest_round"]
        assert longest["ms"] >= delay * 1e3
        # the seam sits at the head of the dispatch span, before the
        # tables grow: the host was planning, not waiting for the device
        assert longest["phases_ms"]["plan"] >= delay * 1e3
        assert longest["phases_ms"]["read_back"] < delay * 1e3 / 2
        assert max(longest["phases_ms"], key=longest["phases_ms"].get) \
            == "plan"
        assert st["round_phases"]["seconds"]["plan"] >= delay


def _stall_record_is_coherent(snap, total):
    """What ISSUE 34 added to a reading, against itself: the starved
    rounds' seconds are part of the thread's, the ring's kinds add up,
    the slowest rounds are in order and their head is the longest."""
    rounds = snap["round_ms"]
    slowest = rounds["slowest"]
    return (min(snap["starved_seconds"].values()) >= 0
            and sum(snap["starved_seconds"].values()) <= total + 1e-6
            and 0 <= snap["starved_rounds"]
            and bool(snap["starved_rounds"])
            == any(snap["starved_seconds"].values())
            and rounds["count"] == sum(v["count"] for v in
                                       rounds["by_kind"].values())
            and rounds["p50_ms"] <= rounds["p99_ms"]
            and len(slowest) <= 8
            and [r["ms"] for r in slowest]
            == sorted((r["ms"] for r in slowest), reverse=True)
            and (slowest[0] == snap["longest_round"] if slowest
                 else snap["longest_round"]["kind"] == "")
            and all(r["gc_ms"] >= 0 and r["compiles"] >= 0
                    for r in slowest))


def test_readers_and_resets_race_the_engine_thread():
    """One writer (the engine thread's role) against readers and resets
    from other threads, more threads than cores' worth of switching: no
    reading ever shows a negative phase, more seconds than have passed
    since the clock was made, or a longest round that is not the sum of
    its own phases."""
    import sys
    import threading

    from paddle_tpu.inference.serving import _RoundPhases

    clock = _RoundPhases()
    t_made = time.perf_counter()
    stop = threading.Event()
    bad = []

    def engine():
        clock.thread_started()
        try:
            while not stop.is_set():
                clock.close_round()
                with clock.phase("admit"):
                    pass
                clock.kind("decode")
                if clock.round % 3 == 0:
                    clock.starved()
                for name in ("plan", "dispatch", "read_back", "emit"):
                    with clock.phase(name):
                        with clock.phase("emit"):   # phases nest
                            pass
        finally:
            clock.thread_stopped()

    def reader(reset):
        while not stop.is_set():
            if reset:
                clock.reset()
            snap = clock.snapshot()
            total = sum(snap["seconds"].values())
            longest = snap["longest_round"]
            if (min(snap["seconds"].values()) < 0
                    or total > time.perf_counter() - t_made + 1e-6
                    or snap["dispatches"] < 0
                    or abs(sum(longest["phases_ms"].values())
                           - longest["ms"]) > 1e-6 * max(1, longest["ms"])
                    or not _stall_record_is_coherent(snap, total)):
                bad.append(snap)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=engine)] + [
        threading.Thread(target=reader, args=(i % 2 == 0,))
        for i in range(6)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.5)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert not bad, bad[0]
    # the thread is gone: a reset now leaves zeros that stay zeros
    clock.reset()
    time.sleep(0.01)
    assert not any(clock.snapshot()["seconds"].values())


def _host_events(trace_dir):
    """{line name: [(name, start_ns, end_ns)]} of the pt: spans on the
    /host:CPU plane, read as benchmark/trace_reduce.py reads a trace."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert files, "the profiler wrote no trace"
    out = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("pt:")]
            if evs:
                out[f"{line.name}#{i}"] = evs
    return out


def test_spans_land_in_the_profilers_trace(tiny_model, tmp_path):
    """Telemetry off, a profiler session on: the engine thread's line of
    /host:CPU holds the phases, the issue of a dispatch nested in its
    span and its read-back after it."""
    import jax

    model, cfg = tiny_model
    assert not T.enabled()
    srv = _server(model, "split").start()
    try:
        _serve(srv, _prompts(cfg))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _serve(srv, _prompts(cfg))
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.stop()
    assert T.events() == []          # the sink stayed off
    lines = _host_events(str(tmp_path))
    engine = [evs for evs in lines.values()
              if any(n == "pt:decode_dispatch" for n, _s, _e in evs)]
    assert len(engine) == 1, sorted(lines)   # one thread runs the rounds
    evs = engine[0]
    names = {n for n, _s, _e in evs}
    assert {"pt:admit", "pt:plan", "pt:dispatch", "pt:read_back",
            "pt:emit", "pt:prefill_chunk", "pt:step_dispatch"} <= names
    parents = [(s, e) for n, s, e in evs
               if n in ("pt:decode_dispatch", "pt:prefill_chunk")]
    reads = [(s, e) for n, s, e in evs if n == "pt:read_back"]
    # one dispatch stays in flight (ISSUE 29): a dispatch span is the
    # issue alone, no read-back lies inside one, and every dispatch is
    # read back once, after it was issued (a decode step a round later)
    for ps, pe in parents:
        assert not any(ps <= s and e <= pe for s, e in reads)
    assert len(reads) == len(parents)
    for (ps, pe), (s, _e) in zip(sorted(parents), sorted(reads)):
        assert s >= pe
    # the jitted call's own span nests inside the dispatch phase
    disp = [(s, e) for n, s, e in evs if n == "pt:dispatch"]
    for n, s, e in evs:
        if n == "pt:step_dispatch":
            assert any(ds <= s and e <= de for ds, de in disp)


# ---- the stall record (ISSUE 34): did the device run dry, every round's
# length by kind, the rounds that stood still with a cause ------------------

def _serve_probed(tiny_model, is_ready=None, late=False, **kw):
    """Serve the prompts twice (compiles out of the way), the second
    time with the probe's answer patched or the host made late at every
    probe (it comes when the device has finished what it holds); returns
    the second window's stats."""
    model, cfg = tiny_model
    srv = _server(model, "split", **kw).start()
    try:
        _serve(srv, _prompts(cfg))
        if is_ready is not None:
            srv._is_ready = is_ready
        if late:
            probe = srv._probe_device

            def too_late(kind, span):
                if srv._newest_out is not None:
                    srv._newest_out.block_until_ready()
                probe(kind, span)

            srv._probe_device = too_late
        srv.reset_stats()
        # budgets that differ: a slot is refilled while the other decodes,
        # so prefills are issued behind a decode step in flight too
        for f in [srv.submit(p, max_new_tokens=n) for p, n in
                  zip(_prompts(cfg), (3, 6, 4, 5, 6, 2))]:
            f.result(timeout=300)
        return srv.stats()
    finally:
        srv.stop()


def test_a_late_host_finds_the_device_idle_at_every_probe(tiny_model):
    st = _serve_probed(tiny_model, late=True)
    da, rp = st["dispatch_ahead"], st["round_phases"]
    assert da["probed"] > 0
    assert da["probed"] == sum(da["probed_by_kind"].values())
    assert da["found_idle"] == da["probed_by_kind"]
    assert set(da["found_idle"]) == {"decode", "prefill", "verify"}
    assert da["found_idle_share"] == 1.0
    assert da["found_idle"]["decode"] > 0 and da["found_idle"]["prefill"] > 0
    # a dispatch is probed when another is unread: every decode step
    # issued ahead was, and no more dispatches than were issued
    assert da["probed_by_kind"]["decode"] == da["issued_ahead"]
    assert da["probed"] <= rp["dispatches"]
    # the rounds that held those issues, and their phases: a part of all
    assert 0 < rp["starved_rounds"] <= rp["round_ms"]["count"]
    assert set(rp["starved_seconds"]) == PHASE_KEYS
    for k, v in rp["starved_seconds"].items():
        assert 0 <= v <= rp["seconds"][k] + 1e-9, k
    assert rp["starved_seconds"]["dispatch"] > 0


def test_a_busy_device_is_never_found_idle(tiny_model):
    st = _serve_probed(tiny_model, is_ready=lambda out: False)
    da, rp = st["dispatch_ahead"], st["round_phases"]
    assert da["probed"] > 0
    assert da["found_idle"] == {"decode": 0, "prefill": 0, "verify": 0}
    assert da["found_idle_share"] == 0.0
    assert rp["starved_rounds"] == 0
    assert not any(rp["starved_seconds"].values())


def test_the_probe_asks_when_the_dispatch_has_landed(tiny_model):
    """One `is_ready()` a dispatch, asked when the jit call has returned
    (the device runs dry while the host uploads and calls, not before):
    a device that was busy when the `dispatch` phase opened and finished
    during it is found idle."""
    model, cfg = tiny_model
    srv = _server(model, "split").start()
    order = []      # "top" of a dispatch phase, "call" returned, "ask"
    try:
        _serve(srv, _prompts(cfg))
        fault = srv._maybe_fault

        def top(where):
            if where in ("decode", "prefill"):
                order.append("top")
            fault(where)

        def returned(fn):
            def call(*a, **kw):
                out = fn(*a, **kw)
                # what was in flight finishes during the phase
                if srv._newest_out is not None:
                    srv._newest_out.block_until_ready()
                order.append("call")
                return out
            return call

        def ask(out):
            order.append("ask")
            return bool(out.is_ready())

        srv._maybe_fault = top
        srv._decoder.step = returned(srv._decoder.step)
        srv._decoder.packed_prefill = returned(srv._decoder.packed_prefill)
        srv._is_ready = ask
        srv.reset_stats()
        for f in [srv.submit(p, max_new_tokens=n) for p, n in
                  zip(_prompts(cfg), (3, 6, 4, 5, 6, 2))]:
            f.result(timeout=300)
        da = srv.stats()["dispatch_ahead"]
    finally:
        srv.stop()
    assert da["probed"] > 0 and da["found_idle"] == da["probed_by_kind"]
    assert order.count("ask") == da["probed"]
    assert order.count("top") == order.count("call")
    for i, what in enumerate(order):
        if what == "ask":
            assert order[i - 1] == "call", order[max(0, i - 3):i + 1]


@pytest.mark.parametrize("why", ["drafter", "steps_per_dispatch"])
def test_an_engine_that_reads_at_once_probes_nothing(tiny_model, why):
    from paddle_tpu.spec_decode import SpecConfig

    kw = (dict(speculation=SpecConfig(max_draft_tokens=2))
          if why == "drafter" else dict(steps_per_dispatch=2))
    st = _serve_probed(tiny_model, late=True, **kw)
    da, rp = st["dispatch_ahead"], st["round_phases"]
    assert rp["dispatches"] > 0
    assert da["probed"] == 0
    assert da["found_idle_share"] == 0.0
    assert da["found_idle"] == da["probed_by_kind"] \
        == {"decode": 0, "prefill": 0, "verify": 0}
    assert rp["starved_rounds"] == 0


def _drive(clock, rounds):
    """Run hand-made rounds through a clock on this thread: each a
    (kind, found idle, {phase: seconds slept}) triple."""
    clock.thread_started()
    try:
        for kind, idle, phases in rounds:
            clock.close_round()
            clock.kind(kind)
            if idle:
                clock.starved()
            for name, secs in phases.items():
                with clock.phase(name):
                    time.sleep(secs)
        clock.close_round()
    finally:
        clock.thread_stopped()


def test_starved_seconds_tile_the_starved_rounds():
    from paddle_tpu.inference.serving import _RoundPhases

    clock = _RoundPhases()
    _drive(clock, [("decode", i % 2 == 1,
                    {"plan": 0.002, "read_back": 0.003}) for i in range(6)])
    snap = clock.snapshot()
    ring = list(clock._ring)
    assert len(ring) == 6 and snap["starved_rounds"] == 3
    starved_ms = sum(ms for i, (_at, ms, _kind) in enumerate(ring) if i % 2)
    assert sum(snap["starved_seconds"].values()) * 1e3 \
        == pytest.approx(starved_ms, rel=1e-6)
    assert snap["starved_seconds"]["read_back"] >= 3 * 0.003
    assert snap["starved_seconds"]["read_back"] \
        < snap["seconds"]["read_back"]
    assert snap["starved_seconds"]["idle_wait"] == 0.0


def test_round_ms_percentiles_against_a_hand_made_list():
    from paddle_tpu.inference.serving import ROUND_RING, _RoundPhases

    clock = _RoundPhases()
    decode = [float(i) for i in range(1, 201)]          # 1..200 ms
    mixed = [300.0, 310.0, 320.0, 330.0]
    for i, ms in enumerate(decode):
        clock._ring.append((i * 1e-3, ms, "decode"))
    for i, ms in enumerate(mixed):
        clock._ring.append((1.0 + i, ms, "prefill+decode"))
    rounds = clock.snapshot()["round_ms"]
    assert rounds["count"] == 204
    # the rank stats() reads its latencies at: sorted[int(p * n)]
    every = sorted(decode + mixed)
    assert rounds["p50_ms"] == every[102] == 103.0
    assert rounds["p99_ms"] == every[201] == 310.0
    assert list(rounds["by_kind"]) == ["decode", "prefill+decode"]
    assert rounds["by_kind"]["decode"] == {
        "count": 200, "p50_ms": 101.0, "p99_ms": 199.0}
    assert rounds["by_kind"]["prefill+decode"] == {
        "count": 4, "p50_ms": 320.0, "p99_ms": 330.0}
    assert rounds["slowest"] == []       # the ring alone was filled
    # the ring keeps the newest ROUND_RING
    for i in range(ROUND_RING):
        clock._ring.append((2.0, 7.0, "decode"))
    assert clock.snapshot()["round_ms"]["by_kind"] == {
        "decode": {"count": ROUND_RING, "p50_ms": 7.0, "p99_ms": 7.0}}
    clock.reset()
    assert clock.snapshot()["round_ms"] == {
        "count": 0, "p50_ms": 0.0, "p99_ms": 0.0, "by_kind": {},
        "slowest": []}


def test_round_ms_counts_the_engines_rounds_by_kind(tiny_model):
    model, cfg = tiny_model
    srv = _server(model, "split").start()
    try:
        _serve(srv, _prompts(cfg))
        srv.reset_stats()
        _serve(srv, _prompts(cfg))
        st = srv.stats()
        srv.reset_stats()
        after = srv.stats()["round_phases"]
    finally:
        srv.stop()
    rounds = st["round_phases"]["round_ms"]
    assert 0 < rounds["count"] <= st["round_phases"]["dispatches"]
    assert set(rounds["by_kind"]) <= {"decode", "prefill",
                                      "prefill+decode"}
    assert "decode" in rounds["by_kind"]
    assert sum(v["count"] for v in rounds["by_kind"].values()) \
        == rounds["count"]
    assert 0 < rounds["p50_ms"] <= rounds["p99_ms"] \
        <= rounds["slowest"][0]["ms"]
    assert len(rounds["slowest"]) == min(8, rounds["count"])
    assert after["round_ms"]["count"] == 0
    assert after["round_ms"]["slowest"] == []
    assert after["starved_rounds"] == 0


def test_an_injected_delay_heads_the_slowest_rounds(tiny_model):
    model, cfg = tiny_model
    delay = 0.4
    srv = _server(model, "split").start()
    try:
        _serve(srv, _prompts(cfg))
        srv._faults = FaultPlan([Fault("slow_dispatch", 4, "slow",
                                       delay_s=delay)])
        srv.reset_stats()
        _serve(srv, _prompts(cfg))
        rp = srv.stats()["round_phases"]
    finally:
        srv.stop()
    slowest = rp["round_ms"]["slowest"]
    assert 1 < len(slowest) <= 8
    assert [r["ms"] for r in slowest] == sorted(
        (r["ms"] for r in slowest), reverse=True)
    head = slowest[0]
    assert head == rp["longest_round"]        # one mechanism, not two
    assert head["ms"] >= delay * 1e3 > slowest[1]["ms"]
    assert max(head["phases_ms"], key=head["phases_ms"].get) == "plan"
    for r in slowest:
        assert set(r) == {"ms", "at_s", "kind", "round", "phases_ms",
                          "gc_ms", "compiles"}
        assert set(r["phases_ms"]) == PHASE_KEYS
        assert r["compiles"] == 0 and r["gc_ms"] >= 0
    assert len({r["round"] for r in slowest}) == len(slowest)


def test_a_collection_inside_a_round_is_named(tiny_model, tmp_path):
    """`gc.collect()` in a token callback: the round that held it says
    how long the collector ran, and under a profiler the collection is
    a `pt:gc` span on the engine thread's line."""
    import gc

    import jax

    from paddle_tpu.observability import gc_tracker

    model, cfg = tiny_model
    collected = []

    def on_token(_tok, _reason):
        if not collected:
            collected.append(gc.collect())
            time.sleep(0.3)      # and the round is the window's longest

    srv = _server(model, "split").start()
    try:
        _serve(srv, _prompts(cfg))
        before = gc_tracker.stats()
        assert before["installed"]
        srv.reset_stats()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            srv.submit(_prompts(cfg)[0], on_token=on_token).result(
                timeout=300)
        finally:
            jax.profiler.stop_trace()
        head = srv.stats()["round_phases"]["round_ms"]["slowest"][0]
    finally:
        srv.stop()
    after = gc_tracker.stats()
    assert collected and after["collections"] > before["collections"]
    assert after["seconds"] > before["seconds"]
    assert after["longest_s"] > 0
    assert head["ms"] >= 300
    assert max(head["phases_ms"], key=head["phases_ms"].get) == "emit"
    assert 0 < head["gc_ms"] <= head["ms"]
    assert head["gc_ms"] <= (after["seconds"] - before["seconds"]) * 1e3 \
        + 1e-6
    engine = [evs for evs in _host_events(str(tmp_path)).values()
              if any(n == "pt:decode_dispatch" for n, _s, _e in evs)]
    assert len(engine) == 1
    spans = [(s, e) for n, s, e in engine[0] if n == "pt:gc"]
    emits = [(s, e) for n, s, e in engine[0] if n == "pt:emit"]
    assert spans
    # the collection the callback asked for lies inside an emit phase
    assert any(es <= s and e <= ee for s, e in spans for es, ee in emits)


def test_dispatch_spans_say_whether_they_found_the_device_idle(
        tiny_model, tmp_path):
    import jax
    from jax.profiler import ProfileData

    model, cfg = tiny_model
    srv = _server(model, "split").start()
    try:
        _serve(srv, _prompts(cfg))
        srv._is_ready = lambda out: False
        srv.reset_stats()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _serve(srv, _prompts(cfg))
        finally:
            jax.profiler.stop_trace()
        da = srv.stats()["dispatch_ahead"]
    finally:
        srv.stop()
    files = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    marks = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("pt:decode_dispatch", "pt:prefill_chunk"):
                    stats = {k: v for k, v in ev.stats}
                    assert "round" in stats, stats
                    marks.append(int(stats["found_idle"]))
    # the patched probe said "busy" wherever it was asked; a dispatch
    # issued with nothing unread is idle by construction, and says so
    assert len(marks) >= da["probed"] > 0
    assert marks.count(0) == da["probed"]
    assert marks.count(1) == len(marks) - da["probed"]


def test_a_round_that_stood_still_is_logged_once_a_second(library_log):
    from paddle_tpu.inference import serving
    from paddle_tpu.inference.serving import _RoundPhases

    def slow_lines():
        return [r for r in library_log if "[slow round]" in r.getMessage()]

    clock = _RoundPhases()
    quick = [("decode", False, {"plan": 0.001})] * serving.SLOW_ROUND_AFTER
    stall = ("prefill+decode", False, {"plan": 0.002, "read_back": 0.2})
    _drive(clock, [stall])          # no median yet: nothing to be 8 x of
    assert slow_lines() == []
    _drive(clock, quick + [stall, stall])
    lines = slow_lines()
    assert len(lines) == 1          # the second one came within a second
    record = lines[0]
    assert record.levelname == "WARNING"
    text = record.getMessage()
    assert "(prefill+decode)" in text
    assert re.search(r"gc \d+\.\d, compiles 0$", text)
    assert f"round {serving.SLOW_ROUND_AFTER + 1}:" in text
    assert text.index("read_back") < text.index("plan")
    clock._warned_at -= serving.SLOW_ROUND_LOG_EVERY_S
    _drive(clock, [stall])
    assert len(slow_lines()) == 2
    # long, but the pace of this engine: not 8 x the median
    steady = _RoundPhases()
    _drive(steady, [("decode", False, {"read_back": 0.06})] * 10)   # > 50 ms
    assert len(slow_lines()) == 2


def test_stop_leaves_the_window_in_the_log(tiny_model, library_log):
    model, cfg = tiny_model
    srv = _server(model, "split").start()
    try:
        _serve(srv, _prompts(cfg))
    finally:
        srv.stop()
    lines = [r for r in library_log
             if r.getMessage().startswith("[rounds]")]
    assert len(lines) == 1 and lines[0].levelname == "INFO"
    text = lines[0].getMessage()
    assert "rounds since the reset" in text and "decode" in text
    assert "found the device idle" in text
    # an engine that never ran a round has nothing to say
    del library_log[:]
    _server(model, "split").start().stop()
    assert not [r for r in library_log
                if r.getMessage().startswith("[rounds]")]


class TestTracerIds:
    def test_id_at_entry_and_parent_id(self):
        tr = T.Tracer(enabled=True)
        with tr.span("outer", round=7) as outer:
            assert outer["id"] == 0          # handed out when it opens
            tr.event("point")
            with tr.span("inner") as inner:
                assert inner["parent_id"] == outer["id"]
                assert inner["parent"] == "outer"
                with tr.span("leaf") as leaf:
                    assert leaf["parent_id"] == inner["id"]
        evs = tr.events()                    # written at exit
        assert [e["name"] for e in evs] == ["point", "leaf", "inner",
                                            "outer"]
        by_id = {e["id"]: e for e in evs}
        assert len(by_id) == 4               # ids are unique
        # the tree can be rebuilt from the events alone
        assert by_id[by_id[leaf["id"]]["parent_id"]]["name"] == "inner"
        assert "parent_id" not in by_id[outer["id"]]
        assert by_id[outer["id"]]["round"] == 7

    def test_nothing_is_buffered_when_off(self):
        tr = T.Tracer(enabled=False)
        with tr.span("outer") as ev:
            assert ev is None
            with tr.span("inner"):
                pass
        assert tr.wrap("f", lambda x: x + 1)(1) == 2
        assert tr.events() == []
        assert getattr(tr._local, "stack", None) is None

    def test_a_span_survives_being_switched_on_inside_it(self):
        tr = T.Tracer(enabled=False)
        with tr.span("outer"):
            tr.enable()
            with tr.span("inner") as inner:
                assert "parent_id" not in inner   # outer was never open
        assert [e["name"] for e in tr.events()] == ["inner"]


def _jaxpr_kernel_names(fn, *args):
    import jax

    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append((str(eqn.params["name"]),
                              str(eqn.source_info.name_stack)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


@pytest.mark.parametrize("seq, want", [
    # a (b, h) row that fits the fused backward's VMEM budget
    (128, {"flash_fwd", "flash_bwd_delta", "flash_bwd_fused"}),
    # and one that does not: the two-kernel backward
    (16384, {"flash_fwd", "flash_bwd_delta", "flash_bwd_dq",
             "flash_bwd_dkv"})])
def test_flash_kernels_carry_their_names(seq, want):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 1, seq, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, True).sum()

    found = _jaxpr_kernel_names(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert {n for n, _stack in found} == want
    for name, stack in found:   # and the scope that names the XLA op
        assert stack.endswith(name), (name, stack)


def test_paged_kernels_carry_their_names():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import unified_attention as ua

    H, DH, BS, M, N = 8, 64, 128, 2, 4
    pool = jnp.zeros((N, BS, H, DH), jnp.float32)
    tables = jnp.zeros((2, M), jnp.int32)

    def decode(q, ctx):
        return ua.paged_decode_attention_kernel(q, pool, pool, tables, ctx,
                                                interpret=True)

    found = _jaxpr_kernel_names(decode, jnp.zeros((2, H, DH)),
                                jnp.ones((2,), jnp.int32))
    assert [n for n, _s in found] == ["paged_attn_decode"]
    assert found[0][1].endswith("paged_attn_decode")

    def stream(q):
        return ua.unified_ragged_attention_kernel(
            q, pool, pool, tables, jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), interpret=True)

    found = _jaxpr_kernel_names(stream, jnp.zeros((ua.Q_TILE, H, DH)))
    assert [n for n, _s in found] == ["paged_attn_prefill"]
    assert found[0][1].endswith("paged_attn_prefill")
