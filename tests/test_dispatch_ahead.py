"""One dispatch in flight (ISSUE 29): the default loop of
`PagedGenerationServer` queues decode step N+1 before it reads step N
back.  Every token must be the one the synchronous order produces, so
each case serves one workload twice: through the engine as it is, and
through `ReadAtOnce`, the same engine told (here, in the test; the
program has no such option) to read every dispatch where it issued it.
One engine, one arithmetic, one host: tokens are compared exactly.

The counters are `stats()["dispatch_ahead"]`: decode dispatches issued,
those issued while the one before was unread, reads with nothing queued
behind them by the seam that asked, rows dropped after a late finish."""
import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.inference import PagedGenerationServer
from paddle_tpu.inference.serving import RequestTimeout
from paddle_tpu.reliability.faults import Fault, FaultPlan
from paddle_tpu.sampling import SamplingParams


class ReadAtOnce(PagedGenerationServer):
    """The synchronous order: nothing stays in flight."""
    _keeps_in_flight = False


ENGINES = {"ahead": PagedGenerationServer, "at_once": ReadAtOnce}
MODES = ("greedy", "sampled")


@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config

    paddle.seed(29)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    return model, cfg


@pytest.fixture(scope="module")
def tiny_kimi():
    from paddle_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig

    paddle.seed(3)
    cfg = KimiLinearConfig.tiny(held_experts=(0, 4))
    model = KimiLinear(cfg)
    model.eval()
    return model, cfg


def detok(toks):
    """A prefix-stable toy detokenizer (tests/test_reliability.py's)."""
    return "".join(chr(97 + (int(t) % 26)) for t in toks)


def sampling(mode, i, **kw):
    """Request i's sampling: greedy, or a seeded stream of its own (one
    request in three with a repetition penalty, so that the sampler's
    count buffer chains from program to program too)."""
    if mode == "greedy":
        return SamplingParams(**kw) if kw else None
    return SamplingParams(temperature=0.9, top_p=0.95, seed=100 + i,
                          repetition_penalty=1.3 if i % 3 == 0 else 1.0,
                          **kw)


def prompts_of(cfg, lengths, seed=5):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def build(engine, model, **kw):
    opts = dict(max_slots=2, block_size=4, max_prompt_len=32,
                max_new_tokens=12, prefill_chunk_tokens=16)
    opts.update(kw)
    return ENGINES[engine](model, **opts)


def serve(engine, model, work, **kw):
    """work: [(prompt, submit kwargs)].  Returns ([tokens or the
    exception], stats, server); every slot a request is admitted into
    was checked to be in no dispatch still in flight."""
    srv = build(engine, model, **kw)
    install = srv._install_slot_locked

    def checked(i, req, worst):
        assert srv._pending is None or i not in srv._pending["rows"], \
            f"slot {i} refilled while a dispatch in flight holds a row of it"
        return install(i, req, worst)

    srv._install_slot_locked = checked
    srv.start()
    try:
        futs = [srv.submit(p, **k) for p, k in work]
        outs = []
        for f in futs:
            try:
                outs.append(np.asarray(f.result(timeout=300)))
            except Exception as e:  # noqa: BLE001 — compared by the test
                outs.append(e)
        stats = srv.stats()
    finally:
        srv.stop()
    return outs, stats, srv


def both(model, work, **kw):
    got, st, _ = serve("ahead", model, work, **kw)
    want, st0, _ = serve("at_once", model, work, **kw)
    assert st0["dispatch_ahead"]["issued_ahead"] == 0
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert not isinstance(w, Exception), w
        assert not isinstance(g, Exception), g
        np.testing.assert_array_equal(g, w)
    return got, st, st0


def assert_conserved(st):
    g = st["goodput"]
    assert g["decoded_tokens"] == (g["goodput_tokens"]
                                   + g["rolled_back_tokens"]
                                   + g["replayed_tokens"])


# ---- tokens: the synchronous order's, whatever ends a request --------------

@pytest.mark.parametrize("mode", MODES)
class TestTokensAreTheSynchronousOrders:
    def test_finish_by_budget_with_slots_reused(self, tiny_model, mode):
        """Six requests through two slots: every finish is by length, so
        a row is simply inactive in the step after its last and nothing
        is thrown away; a budget of 1 ends at the prefill's read."""
        model, cfg = tiny_model
        budgets = (8, 3, 12, 1, 5, 9)
        work = [(p, dict(max_new_tokens=b, sampling=sampling(mode, i)))
                for i, (p, b) in enumerate(zip(
                    prompts_of(cfg, (3, 17, 9, 23, 5, 12)), budgets))]
        got, st, st0 = both(model, work)
        for (p, _k), b, o in zip(work, budgets, got):
            assert len(o) == len(p) + b
        da = st["dispatch_ahead"]
        assert da["issued_ahead"] > 0 and da["dropped_rows"] == 0
        assert st["goodput"]["replayed_tokens"] == 0
        assert st["stop_reasons"] == st0["stop_reasons"]
        assert_conserved(st)

    def test_a_row_joins_from_prefill_while_another_decodes(self,
                                                            tiny_model, mode):
        """A long prompt fed in three chunks beside a row that decodes:
        the prefill is queued behind the step in flight and its row
        joins the decode a step later, with the same tokens."""
        model, cfg = tiny_model
        pa, pb = prompts_of(cfg, (4, 31), seed=8)
        srv_kw = dict(prefill_chunk_tokens=12)
        got, st, _ = both(model, [
            (pa, dict(max_new_tokens=12, sampling=sampling(mode, 0))),
            (pb, dict(max_new_tokens=6, sampling=sampling(mode, 1)))],
            **srv_kw)
        assert st["prefill_dispatches"] >= 3
        assert st["dispatch_ahead"]["issued_ahead"] > 0
        assert_conserved(st)

    def test_a_stop_token_met_in_flight_costs_one_row(self, tiny_model, mode):
        """The stop the read reveals: the row rode in the step already
        queued, its token there is dropped and counted, and its slot is
        not refilled before that step is read (`serve` checks every
        admission)."""
        model, cfg = tiny_model
        pa, pb, pc = prompts_of(cfg, (6, 9, 4), seed=11)
        free = [(pa, dict(max_new_tokens=12, sampling=sampling(mode, 0)))]
        (ref,), _, _ = serve("at_once", model, free)
        new = ref[len(pa):].tolist()
        j = next(j for j in range(2, 10) if new[j] not in new[:j])
        work = [(pa, dict(max_new_tokens=12, sampling=sampling(
                    mode, 0, stop_token_ids=(new[j],)))),
                (pb, dict(max_new_tokens=7, sampling=sampling(mode, 1))),
                (pc, dict(max_new_tokens=5, sampling=sampling(mode, 2)))]
        got, st, st0 = both(model, work, max_slots=1)
        assert got[0].tolist() == ref[:len(pa) + j + 1].tolist()
        assert st["stop_reasons"]["stop_token"] == 1
        assert st["dispatch_ahead"]["dropped_rows"] == 1
        assert st["goodput"]["replayed_tokens"] == 1
        assert st0["goodput"]["replayed_tokens"] == 0
        assert_conserved(st)

    def test_a_stop_string_met_in_flight_costs_one_row(self, tiny_model,
                                                       mode):
        model, cfg = tiny_model
        pa, pb = prompts_of(cfg, (7, 5), seed=13)
        free = [(pa, dict(max_new_tokens=12, sampling=sampling(mode, 0)))]
        (ref,), _, _ = serve("at_once", model, free, detokenize=detok)
        text = detok(ref[len(pa):])
        j = next(j for j in range(3, 10)
                 if text.find(text[j - 1:j + 1]) == j - 1)
        work = [(pa, dict(max_new_tokens=12, sampling=sampling(
                    mode, 0, stop_strings=(text[j - 1:j + 1],)))),
                (pb, dict(max_new_tokens=6, sampling=sampling(mode, 1)))]
        got, st, _ = both(model, work, max_slots=1, detokenize=detok)
        assert got[0].tolist() == ref[:len(pa) + j + 1].tolist()
        assert st["stop_reasons"]["stop_string"] == 1
        assert st["dispatch_ahead"]["dropped_rows"] == 1
        assert_conserved(st)

    def test_a_failed_dispatch_with_one_in_flight(self, tiny_model, mode):
        """The recovery ladder reads the step in flight before it
        snapshots the rows of the one that failed: nothing is lost, the
        requests are retried and end with the tokens of a run that
        never failed."""
        model, cfg = tiny_model
        work = [(p, dict(max_new_tokens=10, sampling=sampling(mode, i)))
                for i, p in enumerate(prompts_of(cfg, (5, 11, 8), seed=17))]
        want, _, _ = serve("at_once", model, work)
        got, st, _ = serve("ahead", model, work,
                           fault_plan=FaultPlan([Fault("decode", 3, "raise")]))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        rel = st["reliability"]
        assert rel["faults_injected"] == 1 and rel["dispatch_retries"] == 1
        assert rel["recoveries"] >= 1 and rel["quarantined"] == 0
        assert st["dispatch_ahead"]["drains"].get("failure") == 1

    def test_preemption_and_resume(self, tiny_model, mode):
        """A victim swapped out mid-decode (the swap-out drains what is
        in flight, so its tokens and its K/V are the host's) and
        resumed: both requests as an uninterrupted run serves them."""
        from paddle_tpu.frontend import FrontDoor

        model, cfg = tiny_model
        pv, pi = prompts_of(cfg, (7, 4), seed=19)

        def door():
            return FrontDoor(model, max_slots=1, block_size=4,
                             max_prompt_len=16, max_new_tokens=24,
                             enable_prefix_cache=True).start()

        fd = door()
        try:
            hv = fd.submit(pv, lane="batch", sampling=sampling(mode, 0),
                           max_new_tokens=24)
            it = iter(hv)
            next(it), next(it)    # the victim decodes
            hi = fd.submit(pi, lane="interactive", max_new_tokens=3)
            out_i, out_v = hi.result(timeout=300), hv.result(timeout=300)
            st = fd.stats()
        finally:
            fd.stop()
        assert st["frontdoor"]["preemptions"] >= 1
        assert st["dispatch_ahead"]["drains"].get("preempt", 0) >= 1
        assert st["dispatch_ahead"]["issued_ahead"] > 0
        want, _, _ = serve(
            "at_once", model,
            [(pv, dict(max_new_tokens=24, sampling=sampling(mode, 0))),
             (pi, dict(max_new_tokens=3))],
            max_slots=1, max_prompt_len=16, max_new_tokens=24)
        np.testing.assert_array_equal(out_v, want[0])
        np.testing.assert_array_equal(out_i, want[1])


def test_a_timeout_takes_its_row_and_no_other(tiny_model):
    """A resident request cancelled by its deadline mid-decode: the scan
    drains first, its slot and blocks are free at once, and the request
    beside it ends with the synchronous order's tokens."""
    model, cfg = tiny_model
    pa, pb = prompts_of(cfg, (6, 10), seed=23)
    (want,), _, _ = serve("at_once", model, [(pa, dict(max_new_tokens=40))],
                          max_new_tokens=64)
    srv = build("ahead", model, max_new_tokens=64)
    seen = threading.Semaphore(0)
    srv.start()
    try:
        fa = srv.submit(pa, max_new_tokens=40)
        fb = srv.submit(pb, max_new_tokens=64, timeout_s=0.2,
                        on_token=lambda *_a: seen.release())
        assert seen.acquire(timeout=120)      # b decodes beside a
        with pytest.raises(RequestTimeout):
            fb.result(timeout=300)
        np.testing.assert_array_equal(fa.result(timeout=300), want)
        st = srv.stats()
        assert st["reliability"]["timeouts"] == 1
        assert st["kv_cache"]["sequences"] == 0
        # the slot serves on
        assert srv.submit(pb, max_new_tokens=2).result(timeout=300).size \
            == pb.size + 2
    finally:
        srv.stop()


# ---- the description: one path for both layouts -----------------------------

@pytest.mark.parametrize("mode", MODES)
def test_a_description_goes_through_the_same_loop(tiny_kimi, mode):
    """The tiny Kimi description: the store chains from program to
    program as the pool does; every request is told its routing once a
    position, in order, with the positions of the dispatch that fed
    them, and the picks of the synchronous order."""
    model, cfg = tiny_kimi
    rs = np.random.default_rng(2)
    prompts = [rs.integers(1, cfg.vocab_size, n, dtype=np.int32)
               for n in (5, 23, 37, 9, 16)]
    budgets = (8, 3, 6, 8, 5)

    def run(engine):
        told = [[] for _ in prompts]

        def note(i):
            def on_routing(position, picks, _slot):
                told[i].append((int(position), np.array(picks)))
            return on_routing

        work = [(p, dict(max_new_tokens=b, sampling=sampling(mode, i),
                         on_routing=note(i)))
                for i, (p, b) in enumerate(zip(prompts, budgets))]
        outs, st, _ = serve(engine, model, work, max_slots=3, block_size=8,
                            num_blocks=64, max_prompt_len=48,
                            max_new_tokens=8, prefill_chunk_tokens=16)
        return outs, st, told

    got, st, told = run("ahead")
    want, st0, told0 = run("at_once")
    assert st["dispatch_ahead"]["issued_ahead"] > 0
    assert st0["dispatch_ahead"]["issued_ahead"] == 0
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        np.testing.assert_array_equal(got[i], want[i])
        # every position fed, once, in order: the prompt's chunks, then
        # one decode position a dispatch (all but the last token)
        at = 0
        for position, picks in told[i]:
            assert position == at
            at += picks.shape[1]
        assert at == len(p) + b - 1
        flat = np.concatenate([k for _p, k in told[i]], axis=1)
        flat0 = np.concatenate([k for _p, k in told0[i]], axis=1)
        np.testing.assert_array_equal(flat, flat0)
    assert st["experts"]["tokens"] == st0["experts"]["tokens"]
    assert_conserved(st)


# ---- the cell's arithmetic: no slack was added ------------------------------

def test_an_engine_at_the_models_last_position_constructs_and_serves(
        tiny_model):
    """max_prompt_len + max_new_tokens == max_position, as the serve
    cell runs GPT-2-medium (768 + 256 = 1,024): a row in flight feeds a
    token the sequence really produced, below prompt + budget, so the
    loop needs no overrun position."""
    model, cfg = tiny_model
    new = 8
    kw = dict(max_slots=2, block_size=8, max_new_tokens=new,
              max_prompt_len=cfg.max_position - new)
    srv = build("ahead", model, **kw)
    assert srv._overrun == 0
    assert srv._m_width * srv.block_size == cfg.max_position
    longest = prompts_of(cfg, (cfg.max_position - new, 9), seed=31)
    work = [(p, dict(max_new_tokens=new)) for p in longest]
    got, st, _ = both(model, work, **kw)
    assert len(got[0]) == cfg.max_position
    assert st["dispatch_ahead"]["issued_ahead"] > 0


# ---- the counter ------------------------------------------------------------

def test_all_but_the_first_step_of_a_chain_are_issued_ahead(tiny_model):
    """One request of N decode steps and nothing that drains: the first
    step has none before it, every other is queued before the one
    before it is read, and the last is read with nothing behind it."""
    model, cfg = tiny_model
    (p,) = prompts_of(cfg, (6,), seed=37)
    _, st, _ = serve("ahead", model, [(p, dict(max_new_tokens=12))])
    da = st["dispatch_ahead"]
    n = st["decode_steps"]
    assert n == 11 and da["decode_dispatches"] == n
    assert da["issued_ahead"] >= n - 2
    assert da["ahead_share"] == pytest.approx(da["issued_ahead"] / n)
    assert da["drains"] == {"no_successor": 1}
    assert da["dropped_rows"] == 0


@pytest.mark.parametrize("why", ["drafter", "steps_per_dispatch"])
def test_an_engine_that_needs_the_hosts_tokens_issues_none_ahead(
        tiny_model, why):
    from paddle_tpu.spec_decode import SpecConfig

    model, cfg = tiny_model
    kw = (dict(speculation=SpecConfig(max_draft_tokens=2))
          if why == "drafter" else dict(steps_per_dispatch=2))
    work = [(p, dict(max_new_tokens=9))
            for p in prompts_of(cfg, (5, 14, 8), seed=41)]
    outs, st, _ = serve("ahead", model, work, max_prompt_len=16, **kw)
    assert all(len(o) == len(p) + 9 for o, (p, _k) in zip(outs, work))
    da = st["dispatch_ahead"]
    assert da["issued_ahead"] == 0 and da["drains"] == {}
    assert da["dropped_rows"] == 0
    if why == "steps_per_dispatch":
        assert da["decode_dispatches"] == st["decode_steps"] > 0


def test_reset_stats_zeroes_the_counters(tiny_model):
    model, cfg = tiny_model
    srv = build("ahead", model).start()
    try:
        (p,) = prompts_of(cfg, (6,), seed=43)
        srv.submit(p, max_new_tokens=6).result(timeout=300)
        assert srv.stats()["dispatch_ahead"]["issued_ahead"] > 0
        srv.reset_stats()
        by_kind = {"decode": 0, "prefill": 0, "verify": 0}
        assert srv.stats()["dispatch_ahead"] == {
            "decode_dispatches": 0, "issued_ahead": 0, "ahead_share": 0.0,
            "drains": {}, "dropped_rows": 0, "probed": 0,
            "probed_by_kind": by_kind, "found_idle": by_kind,
            "found_idle_share": 0.0}
    finally:
        srv.stop()


def test_the_decode_kernel_counter_reads_its_steps(tiny_model, tiny_kimi,
                                                  monkeypatch):
    """stats()["decode_kernel"] (PR 35), counted on the host from the
    contexts each decode dispatch holds: on an engine whose decode steps
    run the paged decode kernel (shapes the kernel's gate takes; the chip
    stood in for while the engine is built, the XLA path serving here), a
    launch a pool layer and step, a row of n blocks (1 for an idle row)
    ceil(n / fan) grid steps at the fan its block's bytes give; zeros after
    a reset; no kernel, fan 0 and no counts where decode runs none (a host
    without the chip).  A latent pool's engine names `mla_decode` (its
    counts: tests/test_kanana2.py)."""
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas import unified_attention as ua

    paddle.seed(35)
    cfg = GPT2Config(vocab_size=1024, hidden_size=256, num_layers=2,
                     num_heads=8, max_position=512)
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    block = 2 * 128 * 256 * 4               # K and V rows of 128, float32
    opts = dict(block_size=128, max_prompt_len=256, max_new_tokens=8,
                prefill_chunk_tokens=128)
    with monkeypatch.context() as m:
        m.setattr(attention, "_on_tpu", lambda: True)
        # a fan of 2 where the engine hands the rule its block's bytes
        m.setattr(ua, "decode_fan", lambda b, _width: 2 if b == block else 1)
        srv = build("ahead", model, **opts)
        idle = build("ahead", tiny_kimi[0], max_prompt_len=16)
    (p,) = prompts_of(cfg, (200,), seed=47)
    srv.start()
    try:
        srv.submit(p, max_new_tokens=8).result(timeout=300)
        st = srv.stats()
        d = st["dispatch_ahead"]["decode_dispatches"]
        # one row at 201-207 positions (2 blocks: 1 step), one idle row
        assert d > 0 and st["decode_kernel"] == {
            "kernel": "paged_attn_decode", "fan": 2, "launches": 2 * d,
            "live_blocks": 2 * d * (2 + 1), "steps": 2 * d * (1 + 1)}
        srv.reset_stats()
        assert srv.stats()["decode_kernel"] == {
            "kernel": "paged_attn_decode", "fan": 2, "launches": 0,
            "live_blocks": 0, "steps": 0}
    finally:
        srv.stop()
    off = {"kernel": None, "fan": 0, "launches": 0, "live_blocks": 0,
           "steps": 0}
    assert idle.stats()["decode_kernel"] == dict(      # mla_decode's pool
        off, kernel="mla_decode", fan=4)
    _, st, _ = serve("ahead", tiny_model[0],
                     [(prompts_of(cfg, (6,))[0], dict(max_new_tokens=4))])
    assert st["decode_kernel"] == off                   # the XLA path here


# ---- a sharded engine takes the same input ----------------------------------

@pytest.mark.skipif(jax.device_count() < 2, reason="needs 2 virtual devices")
def test_a_tensor_parallel_engine_chains_on_one_executable(tiny_model):
    """tp=2: the sharded `decode_step` takes `prev` replicated, as it
    returns its tokens; the first step of a chain (no `prev`) and the
    later ones are one executable, so nothing compiles mid-traffic."""
    from paddle_tpu.observability import compile_tracker
    from paddle_tpu.serving_dist import ShardedEngineConfig

    model, cfg = tiny_model
    work = [(p, dict(max_new_tokens=8))
            for p in prompts_of(cfg, (5, 17, 9), seed=47)]
    kw = dict(sharding=ShardedEngineConfig(tp=2), block_size=8)
    mark = compile_tracker.mark()
    got, st, _ = both(model, work, **kw)
    steps = [e for e in compile_tracker.events_since(mark)
             if e["program"] == "decode_step"]
    assert len(steps) <= 1, [e["program"] for e in steps]
    assert st["dispatch_ahead"]["issued_ahead"] > 0
    assert st["sharding"]["tp_degree"] == 2


# ---- the trace on the new order ---------------------------------------------

def test_request_traces_assemble_on_the_new_order(tiny_model, tmp_path):
    """`assemble_request_traces`: the phases tile each request's wall
    time, its first token is not later than its end, and the decode
    dispatches that carried it are counted at their issue."""
    from paddle_tpu.observability import tracing as T

    model, cfg = tiny_model
    was = T.enabled()
    T.configure(path=str(tmp_path / "trace.jsonl"), truncate=True,
                enabled=True)
    try:
        t0 = time.perf_counter()
        work = [(p, dict(max_new_tokens=5))
                for p in prompts_of(cfg, (3, 7, 5, 9), seed=53)]
        serve("ahead", model, work)
        wall_ms = (time.perf_counter() - t0) * 1e3
        T.flush()
        traces = T.assemble_request_traces(path=str(tmp_path / "trace.jsonl"))
    finally:
        T.TRACER.configure(path=None, enabled=was)
        T.reset()
    assert len(traces) == 4
    for r in traces.values():
        assert sum(r["phases_ms"].values()) == pytest.approx(r["wall_ms"],
                                                             rel=1e-3)
        assert all(v >= 0 for v in r["phases_ms"].values()), r["phases_ms"]
        assert 0 < r["ttft_ms"] <= r["wall_ms"] <= wall_ms
        assert r["new_tokens"] == 5
        # 4 decode tokens: the dispatches that carried the request
        assert r["decode_dispatches"] == 4
