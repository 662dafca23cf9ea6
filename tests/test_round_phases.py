"""The serving engine's round on one clock (ISSUE 24): the phase
accounting of `PagedGenerationServer` (`stats()["round_phases"]`), its
`pt:` spans in the profiler's own trace, the span primitive's ids, and
the names the Pallas kernels carry."""
import glob
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
                           - longest["ms"]) > 1e-6 * max(1, longest["ms"])):
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
