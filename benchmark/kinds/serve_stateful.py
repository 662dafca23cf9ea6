"""The `serve_stateful` kind of cell: `kinds/serve.py`'s closed loop against
one PagedGenerationServer, for a family whose sequences hold recurrent
state beside a paged pool and whose programs hold kernels of several names.

The client, the window, the sampling of `available_block_count`, the traced
slice and the result's keys are `serve.py`'s own (imported, not copied), so
`serve_tokens_per_s`, `itl_p95_ms`, `setup_s` and the `.serve` per-layer
readers mean here what they mean in `gpt2_medium.serve_closed32`.  Three
checks differ, because `serve.py`'s ask for GPT-2's one kernel and for a
reference that forms [4, horizon, vocab] logits at once:

  kernels   each program's Pallas kernels are counted by kernel name
            against the family's own table (`family.serve_kernels`);
  path      the family says which form its decode-side ops take here;
  reference what the TIMED engine did for `sample_for_reference` of the
            requests it served in the window, against the reference's
            full forward of each whole sequence (one layer at a time,
            logits in blocks of positions).  Nothing is run again: the
            engine hands every request what its routers chose at every
            position (`submit(on_routing=)`), and the recurrent-state
            store of the stopped server still holds the last state of
            the sequence that held each slot last, which is where the
            sample is drawn from.  Three readings, each with its limit
            below: (a) every served token must be the reference's argmax
            or lose to it by at most LOGIT_MARGIN; (b) no router's choice
            may lie further than NEAR_TIE from the reference's own; up to
            there the two may order tied scores either way, and the
            reference takes the engine's choice
            (`reference/kimi_linear.expert_ffn`); (c) the first KDA
            layer's state in the engine's store must lie within
            STATE_LIMIT of the reference's after the same tokens.

Traffic parameters: `serve.py`'s.
"""
from __future__ import annotations

import gc
import importlib.util
import os
import re
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load_serve():
    spec = importlib.util.spec_from_file_location(
        "bench_kinds_serve", os.path.join(_HERE, "serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


serve = _load_serve()

# Each limit stands between two readings on the chip (PERF.md section 6,
# PR 26, has the runs): what the engine reads over its seeds, and what a
# fault the limit is there for reads through this same check.
#
# (a) A served token must be the float32 reference's argmax at its
# position, or lose to it by at most LOGIT_MARGIN logit units: `serve.py`'s
# margin, for its reason.  The served path computes in bf16 through 5
# layers, so two near-tied logits legitimately swap, by no more than the
# bf16 noise of a logit; the reference follows the engine's own choice of
# experts wherever (b) lets it, so no position needs a wider margin.
# Read on the chip: at most 0.039 over 6 seeds (0.053 with a bf16 store).
LOGIT_MARGIN = 0.15
# (b) How far a router's choice may lie from the reference's: the most
# that an expert the engine took lies under the reference's 8th selection
# score, or one it left out over the 9th.  Selection scores are sigmoids of
# a 2,304-term dot product that the engine forms from bf16 activations
# (relative step 2^-8) after up to four layers of them; a balanced
# router's 8th and 9th scores lie where the sigmoid is steepest (slope
# 1/4).  A router that ranks wrongly is off by the scores' own spread (the
# 1st over the 8th), which the check's log line gives beside the gap.
# Read on the chip: gaps of at most 0.0083 over 6 seeds (0.0135 with a
# bf16 store, whose drifted state moves scores too); the spread is 0.052.
NEAR_TIE = 2.0 ** -6
# (c) ||S_engine - S_reference|| / ||S_reference|| (Frobenius, over heads)
# of the FIRST KDA layer's state after a request's last fed token; the
# worst sampled request decides.  The engine's state is float32 built from
# bf16 activations: its error is the rounding of one token's q, k, v, not a
# sum of roundings, and it reads the same to a few percent on every
# request, whatever its length.  A store that keeps the state in bf16
# rounds it once a decode token, and the roundings add up over the
# state's memory.  Every later layer adds a block of bf16 activations to
# both readings (the log line has them by layer): the first layer, whose
# input is the embedding itself, tells the two apart best.  All three
# limits see a mixed-up slot or a skipped decay; only this one sees the
# store's precision.  Read on the chip: 0.00403-0.00414 over 6 seeds; the
# whole cell with its store rounded to bf16 after every write
# (scripts/kimi_control_bf16_state.py) reads 0.0145 and is not correct.
STATE_LIMIT = 0.0065


class Recorded:
    """The server as `serve.ClosedLoop` sees it.  Every request it submits
    also records what the engine's routers chose for it and which slot of
    the state store it held (`submit(on_routing=)`): `record[id(request)]`
    = {"routing": [(position, picks)], "slot": state slot}."""

    def __init__(self, server):
        self.server, self.record = server, {}

    def submit(self, prompt, **kw):
        mine = {"routing": [], "slot": 0}
        self.record[id(kw["on_token"].__self__)] = mine   # serve.Request

        def note(position, picks, slot):
            mine["routing"].append((position, picks))
            mine["slot"] = slot

        return self.server.submit(prompt, on_routing=note, **kw)


class Served:
    """One request as the timed engine served it (`sample_of`)."""

    def __init__(self, seq, prompt, picks, state):
        self.seq, self.prompt = seq, prompt
        self.picks, self.state = picks, state


def run(ctx):
    import jax

    import bench_data
    import trace_reduce
    import paddle_tpu as paddle
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.observability import compile_tracker

    log, fail, percentile = ctx["log"], ctx["fail"], serve.percentile
    cfg, traffic, family = ctx["cfg"], ctx["traffic"], ctx["family"]
    seed, seconds, on_tpu = ctx["seed"], ctx["seconds"], ctx["on_tpu"]
    dep = cfg["deployment"]["serve"]
    shape = family.shape(cfg)
    span = jax.profiler.TraceAnnotation

    # ---- set-up: weights, engine, its buckets, the cell's own traffic ----
    paddle.seed(seed % (2 ** 31 - 1))
    model = family.served_model(cfg, dep["dtype"])
    t_model = time.perf_counter()
    log(f"[serve] routers balanced: an expert's largest load over the mean, "
        f"per expert layer: {model.router_balance}")
    mark_all = compile_tracker.mark()
    engine = {k: v for k, v in dep.items() if k not in ("dtype", "sizing")}
    server = PagedGenerationServer(model, **engine)
    num_blocks = server.cache.stats()["num_blocks"]  # usable: no trash block
    n_warm = server.warm_buckets()
    t_warm = time.perf_counter()
    server.start()
    stream = bench_data.RequestStream(traffic, shape["vocab"], seed)
    loop = serve.ClosedLoop(Recorded(server), stream,
                            int(traffic["clients"]),
                            float(traffic["request_timeout_s"]), span)
    loop.start()
    warm_deadline = time.perf_counter() + float(traffic["request_timeout_s"])
    while loop.completed < int(traffic["warm_requests"]):
        if time.perf_counter() > warm_deadline:
            raise fail("the warm-up traffic did not complete")
        time.sleep(0.05)
    log(f"[serve] model built in {t_model - ctx['t_process_start']:.1f}s from "
        f"process start, {n_warm} prefill buckets warmed in "
        f"{t_warm - t_model:.1f}s, {traffic['warm_requests']} requests of "
        f"the cell's traffic in {time.perf_counter() - t_warm:.1f}s; "
        f"{len(compile_tracker.events_since(mark_all))} programs compiled "
        f"or read from the cache")

    # ---- the window (serve.py's, to the letter) --------------------------
    server.reset_stats()
    mark_window = compile_tracker.mark()
    free_min = server.cache.available_block_count
    t_w0 = time.perf_counter()
    setup_s = t_w0 - ctx["t_process_start"]
    t_w1 = t_w0 + seconds
    trace_at = t_w0 + seconds / 3 if ctx["trace"] else None
    trace_seconds = float(traffic.get("trace_seconds", 3.0))
    trace, slice_clock = None, None

    def sample_until(t_stop):
        nonlocal free_min
        while True:
            free_min = min(free_min, server.cache.available_block_count)
            left = t_stop - time.perf_counter()
            if left <= 0:
                return
            time.sleep(min(serve.SAMPLE_EVERY_S, left))

    if trace_at is not None:
        sample_until(trace_at)
        trace_dir = trace_reduce.start(ctx["root"], ctx["cell"]["name"])
        with span(trace_reduce.SLICE_SPAN):
            t_s0 = time.perf_counter()
            sample_until(min(t_s0 + trace_seconds, t_w1))
            t_s1 = time.perf_counter()
        trace = trace_reduce.finish(trace_dir, read=on_tpu)
        slice_clock = (t_s0, t_s1)
    sample_until(t_w1)
    # nothing new from here on: what is in flight now finishes and leaves
    # its last state in the store, where the reference check reads it
    loop.accepting = False
    stats = server.stats()
    compiles_in_window = compile_tracker.count_since(mark_window)
    heard_in_window, stages = ctx["compiles_heard"](t_w0, t_w1)
    memory_peak = ctx["memory_peak"]()
    drained = loop.drain(float(traffic["request_timeout_s"]) + 30)
    events = compile_tracker.events_since(mark_all)
    server.stop()
    if not drained:
        raise fail("requests still in flight long after the window closed")

    # ---- the client's numbers --------------------------------------------
    requests = loop.requests
    due = [r for r in requests if t_w0 <= r.t_due < t_w1]
    tokens_in_window = sum(1 for r in requests for t in r.stamps
                           if t_w0 <= t < t_w1)
    ttft = [r.stamps[0] - r.t_due for r in due if r.stamps]
    gaps_at = [(b - a, b) for r in requests
               for a, b in zip(r.stamps, r.stamps[1:]) if t_w0 <= b < t_w1]
    gaps = [g for g, _end in gaps_at]
    if not ttft or not gaps:
        raise fail(f"nothing completed in the window ({len(due)} due)")
    wrong, bad = [], []
    for r in due:
        if r.error is not None:
            bad.append(f"{r.error}")
        elif (len(r.seq) != len(r.prompt) + r.new
              or not (r.seq[:len(r.prompt)] == r.prompt).all()
              or len(r.stamps) != r.new):
            bad.append(f"a {len(r.prompt)}-token prompt + {r.new} new came "
                       f"back as {len(r.seq)} tokens, {len(r.stamps)} "
                       f"streamed")
    if bad:
        wrong.append(f"{len(bad)} of {len(due)} requests failed, e.g. "
                     f"{bad[0]}")
    rel = stats["reliability"]
    nonzero = {k: rel[k] for k in serve.RELIABILITY_ZERO if rel[k]}
    if nonzero:
        wrong.append(f"engine reliability counters not zero: {nonzero}")
    if compiles_in_window:
        wrong.append(f"{compiles_in_window} compile(s) inside the window")
    stamps = sorted(t for r in requests for t in r.stamps if t_w0 <= t < t_w1)
    stall, stall_at = max((b - a, a - t_w0) for a, b in zip(stamps, stamps[1:]))

    # ---- the programs: which path, and are the kernels in them -----------
    path = family.serving_path(cfg)
    by_program = {}
    for ev in events:
        by_program.setdefault(ev["program"], []).append(ev)
    log("[serve] programs: " + ", ".join(
        f"{n} x{len(v)}" for n, v in sorted(by_program.items()))
        + f"; decode-side ops take the {path} form")
    if on_tpu:
        if path != "pallas":
            wrong.append(f"the decode-side ops take the {path!r} form, not "
                         f"the kernels")
        for name, want in family.serve_kernels(cfg).items():
            if name not in by_program:
                wrong.append(f"program {name} was never dispatched")
                continue
            # one variant of each: re-lowered from its shapes and read
            # back from the compile cache
            compiled = by_program[name][0]["lower"]().compile()
            got = count_kernels(compiled.as_text())
            temp = compiled.memory_analysis().temp_size_in_bytes
            log(f"[serve] {name}: kernels {got} ({want} expected), "
                f"temporaries {temp / 1e9:.3f} GB")
            if got != {k: v for k, v in want.items() if v}:
                wrong.append(f"{name} holds kernels {got}, not {want}")

    # ---- the reference: a seeded sample of what the window served --------
    params, _buffers = model.functional_state()
    params = dict(params)
    done_ok = [r for r in due if r.error is None and r.seq is not None]
    sample = sample_of(requests, loop.server.record, done_ok,
                       server.cache.state["S"], bench_data.rng(seed, 4),
                       int(traffic["sample_for_reference"]))
    del server, loop.server, model
    gc.collect()
    if not sample:
        raise fail(f"no request of the window still has its state in the "
                   f"store ({len(done_ok)} completed of {len(due)} due)")
    found = check_against_reference(family.reference(cfg), params, sample,
                                    log)
    log(f"[check] {len(sample)} requests as the timed engine served them "
        f"vs the float32 reference: {found['exact']}/{found['tokens']} "
        f"tokens are its argmax, worst deficit {found['deficit']:.4f} logit "
        f"units (margin {LOGIT_MARGIN}); the routers' choices lie at most "
        f"{found['gap']:.5f} from the reference's (limit {NEAR_TIE}; the "
        f"1st score lies {found['spread']:.4f} over the 8th at the median "
        f"position) and the reference took the engine's at "
        f"{found['swapped']} of {found['positions']} positions, "
        f"{found['outside']} outside the limit; the first KDA layer's "
        f"state in the engine's store differs from the reference's by "
        f"{found['state']:.5f} of its norm at worst (limit {STATE_LIMIT}; "
        f"every KDA layer's: "
        f"{[round(v, 5) for v in found['state_by_layer']]})")
    wrong.extend(verdict(found))

    serve_tokens_per_s = tokens_in_window / seconds
    result = {
        "correct": not wrong, "wrong": wrong, "attempted": len(due),
        "failed": len(bad), "memory_peak_bytes": memory_peak,
        "end_to_end": {"serve_tokens_per_s": serve_tokens_per_s,
                       "ttft_p95_ms": percentile(ttft, 0.95) * 1e3,
                       "itl_p95_ms": percentile(gaps, 0.95) * 1e3,
                       "setup_s": setup_s},
        "notes": [
            f"window {seconds:.1f}s: {len(due)} requests due "
            f"({len(due) / seconds:.2f}/s), {tokens_in_window} tokens "
            f"streamed ({serve_tokens_per_s:.1f}/s); TTFT median "
            f"{percentile(ttft, 0.5) * 1e3:.1f} p95 "
            f"{percentile(ttft, 0.95) * 1e3:.1f} ms over {len(ttft)}; ITL "
            f"median {percentile(gaps, 0.5) * 1e3:.2f} p95 "
            f"{percentile(gaps, 0.95) * 1e3:.2f} ms over {len(gaps)} gaps; "
            f"set-up {setup_s:.1f}s; {compiles_in_window} compiles in the "
            f"window by compile_tracker, {heard_in_window} compile requests "
            f"heard by jax.monitoring there ({stages[:6]}); longest silence "
            f"of the token stream {stall * 1e3:.0f} ms at {stall_at:.1f}s; "
            f"the window's halves: "
            + " and ".join(
                f"{sum(1 for t in stamps if a <= t < b) / (b - a):.1f} "
                f"tokens/s, ITL p95 "
                f"{percentile([g for g, e in gaps_at if a <= e < b], 0.95) * 1e3:.2f} ms"
                for a, b in ((t_w0, (t_w0 + t_w1) / 2),
                             ((t_w0 + t_w1) / 2, t_w1))),
            f"engine's own clock: ttft p50/p99 {stats['ttft_p50_ms']:.1f}/"
            f"{stats['ttft_p99_ms']:.1f} ms, itl p50/p99 "
            f"{stats['itl_p50_ms']:.2f}/{stats['itl_p99_ms']:.2f} ms, "
            f"{stats['decode_steps']} decode steps, "
            f"{stats['prefill_dispatches']} prefill dispatches, slot fill "
            f"{stats['slot_fill']:.3f}, fewest free blocks {free_min} of "
            f"{num_blocks}; state slots at most "
            f"{stats['state']['peak_used_slots']} of "
            f"{stats['state']['slots']}; experts: "
            f"{stats['experts']['held_picks']} picks held of "
            f"{stats['experts']['tokens']} token-layers routed, mean load "
            f"{stats['experts']['mean_load']:.2f}, max "
            f"{stats['experts']['max_load']}"],
    }
    if ctx["trace"]:
        obs = {
            "kind": "serve", "shape": shape, "peaks": ctx["peaks"],
            "stats": stats, "admitted_in_window": len(due),
            "compiles_in_window": compiles_in_window,
            "free_blocks_min": free_min, "num_blocks": num_blocks,
            "ttft_p95_ms": percentile(ttft, 0.95) * 1e3,
            "memory_peak_bytes": memory_peak, "log": log,
            "slice_clock": slice_clock,
        }
        obs.update(serve.reduce_trace(trace_reduce, trace, slice_clock,
                                      requests, log) if on_tpu
                   else trace_reduce.NOTHING_TRACED)
        result["obs"] = obs
    return result


def count_kernels(compiled_text):
    """{kernel name: launches} of a compiled program's Pallas kernels (a
    `tpu_custom_call` whose instruction carries the kernel's name)."""
    out = {}
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r"%([A-Za-z_][A-Za-z0-9_]*?)(\.\d+)? = ", line)
        name = m.group(1) if m else "?"
        out[name] = out.get(name, 0) + 1
    return out


def sample_of(requests, record, eligible, store, rng, k):
    """At most k of `eligible`, drawn by `rng`, each as the timed engine
    served it (`Served`): `.picks` [expert layers, n - 1, top k], its
    routers' choices at every position fed (the last token is never fed),
    and `.state` [KDA layers, H, Dk, Dv] float32, its slot of `store`
    (the stopped server's own).  `record` is `Recorded.record`.  Only a
    request that held its slot LAST can be drawn: another sequence has
    since overwritten an earlier holder's state.  Where none of those is
    in `eligible` (a window shorter than a request, or one whose end the
    caller overran), they are drawn from whatever else completed."""
    last = {}
    for r in requests:
        slot = record[id(r)]["slot"]
        if r.t_done is not None and slot and (
                slot not in last or r.t_done > last[slot].t_done):
            last[slot] = r
    ok = {id(r) for r in eligible}
    held = [r for r in last.values() if r.error is None]
    pool = sorted([r for r in held if id(r) in ok] or held,
                  key=lambda r: r.t_due)
    sample = []
    for i in rng.permutation(len(pool))[:k]:
        r, mine = pool[i], record[id(pool[i])]
        n = len(r.seq)
        layers, _n, top_k = mine["routing"][0][1].shape
        picks = np.full((layers, n - 1, top_k), -1, np.int32)
        for position, told in mine["routing"]:   # a re-prefill comes later
            picks[:, position:position + told.shape[1]] = told
        if (picks < 0).any():
            raise ValueError(f"the engine told no routing for some of the "
                             f"{n - 1} positions it fed")
        sample.append(Served(r.seq, r.prompt, picks, np.asarray(
            store[:, mine["slot"]], np.float32)))
    return sample


def check_against_reference(reference, params, sample, log):
    """The readings of the module docstring over `sample` (objects with
    `.seq`, `.prompt`, `.picks`, `.state` as `sample_of` leaves them):
    {"deficit", "exact", "tokens", "gap", "spread", "swapped", "outside",
    "positions", "state", "state_by_layer"}."""
    import jax
    import jax.numpy as jnp

    _arch, hidden_fn, head = reference
    hidden = jax.jit(lambda p, ids, picks, n_state: hidden_fn(
        p, ids, served=picks, tie=NEAR_TIE, state_len=n_state))
    head = jax.jit(head)
    block = 512

    @jax.jit
    def deficits(params, rows, nxt):
        lg = head(params, rows)
        return lg.max(-1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]

    out = {"deficit": 0.0, "exact": 0, "tokens": 0, "gap": 0.0,
           "swapped": 0, "outside": 0, "positions": 0, "state": 0.0,
           "state_by_layer": [0.0] * sample[0].state.shape[0]}
    spreads = []
    width = block            # one compiled width for the whole sample
    while width < max(len(r.seq) for r in sample):
        width *= 2
    t0 = time.perf_counter()
    for r in sample:
        n, n_prompt = len(r.seq), len(r.prompt)
        ids = np.zeros((width,), np.int32)
        ids[:n] = r.seq                  # causal: padding cannot reach back
        served = np.zeros(r.picks.shape[:1] + (width,) + r.picks.shape[2:],
                          np.int32)      # past n - 2: never read below
        served[:, :n - 1] = r.picks
        x, found = hidden(params, jnp.asarray(ids), jnp.asarray(served),
                          n - 1)
        # (b) the routers' choices, at every position the engine fed
        out["gap"] = max(out["gap"],
                         float(np.asarray(found["gap"])[:n - 1].max()))
        for key in ("swapped", "outside"):
            out[key] += int((np.asarray(found[key])[:n - 1] > 0).sum())
        out["positions"] += n - 1
        spreads.append(np.asarray(found["spread"])[:n - 1])
        # (a) every generated token under the reference's logits
        for s0 in range(n_prompt - 1, n - 1, block):
            rows = np.arange(s0, s0 + block).clip(max=n - 2)
            d = np.asarray(deficits(params, x[rows],
                                    jnp.asarray(ids[rows + 1])))
            keep = np.arange(s0, s0 + block) <= n - 2
            worst = float(d[keep].max()) if np.isfinite(d[keep]).all() \
                else float("inf")
            out["deficit"] = max(out["deficit"], worst)
            out["exact"] += int((d[keep] == 0).sum())
            out["tokens"] += int(keep.sum())
        # (c) the state the engine left in its store
        want = np.asarray(found["states"])
        for layer in range(want.shape[0]):
            err = float(np.linalg.norm(r.state[layer] - want[layer])
                        / np.linalg.norm(want[layer]))
            err = err if np.isfinite(err) else float("inf")
            out["state_by_layer"][layer] = max(out["state_by_layer"][layer],
                                               err)
    out["state"] = out["state_by_layer"][0]
    out["spread"] = float(np.median(np.concatenate(spreads)))
    log(f"[check] the reference took {time.perf_counter() - t0:.1f}s")
    return out


def verdict(found):
    """What of `check_against_reference`'s readings lies over its limit,
    in words; empty when the engine did what the reference does."""
    wrong = []
    if not found["deficit"] <= LOGIT_MARGIN:
        wrong.append(f"a served token is {found['deficit']:.4f} under the "
                     f"float32 argmax (margin {LOGIT_MARGIN})")
    if found["outside"] or not found["gap"] <= NEAR_TIE:
        wrong.append(f"at {found['outside']} positions a router's choice "
                     f"lies up to {found['gap']:.5f} from the reference's "
                     f"(limit {NEAR_TIE})")
    if not found["state"] <= STATE_LIMIT:
        wrong.append(f"the first KDA layer's state in the engine's store "
                     f"differs from the reference's by "
                     f"{found['state']:.5f} of its norm (limit "
                     f"{STATE_LIMIT})")
    return wrong
