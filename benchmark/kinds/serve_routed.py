"""The `serve_routed` kind of cell: `kinds/serve.py`'s closed loop against
one PagedGenerationServer, for a family whose layers attend paged K/V rows
of fewer K/V heads than query heads through the paged kernels, keep conv
tails in the slot-indexed store beside them, and feed forward through
routed experts.

The client, the window, the sampling of `available_block_count`, the traced
slice and the result's keys are `serve.py`'s own (imported, not copied), so
`serve_tokens_per_s`, `itl_p95_ms`, `setup_s` and the `.serve` per-layer
readers mean here what they mean in `gpt2_medium.serve_closed32`; the
recording server and the kernel count are `serve_stateful.py`'s.  `run` is
`serve_stateful.run` but for what is read from the store and the words of
its log (that file checks a KDA state "S" by name).  The checks:

  kernels   each program's Pallas kernels are counted by kernel name
            against the family's own table (`family.serve_kernels`);
  path      the family says which form its paged attention takes here;
  reference what the TIMED engine did for `sample_for_reference` of the
            requests it served in the window, against the reference's
            full forward of each whole sequence (one layer at a time,
            logits in blocks of positions).  Nothing is run again: the
            engine hands every request what its routers chose at every
            position (`submit(on_routing=)`), and the store of the
            stopped server still holds the conv tails of the sequence
            that held each slot last, which is where the sample is drawn
            from.  Three readings, each with its limit below: (a) every
            served token must be the reference's argmax or lose to it by
            at most LOGIT_MARGIN; (b) no router's choice may lie further
            than NEAR_TIE under the reference's own; up to there the two
            may order tied scores either way, and the reference takes the
            engine's choice (`reference/zaya.expert_ffn`); (c) the first
            layer's conv tails in the engine's store must lie within
            TAIL_LIMIT of the reference's after the same tokens.

Traffic parameters: `serve.py`'s.
"""
from __future__ import annotations

import gc
import importlib.util
import os
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_kinds_{name}", os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


stateful = _load("serve_stateful")
serve = stateful.serve

# Each limit stands between two readings on the chip (PERF.md section 6,
# PR 30, has the runs): what the engine reads over its seeds (7 runs of the
# cell), and what a fault the limit is there for reads through this same
# check (scripts/zaya_faults.py: one changed line of the program at a
# time, on 8-16 requests served at the published widths).
#
# (a) A served token must be the float32 reference's argmax at its
# position, or lose to it by at most LOGIT_MARGIN logit units: `serve.py`'s
# margin, for its reason.  The served path computes in bf16 through 16
# layers, so two near-tied logits legitimately swap, by no more than the
# bf16 noise of a logit; the reference follows the engine's own choice of
# expert wherever (b) lets it, so no position needs a wider margin.
# Read on the chip: at most 0.0265 over 7 seeds; 0.235 with rotary left
# out, 0.240 with the router's carried state dropped, 0.53-0.70 with a tail
# or the value shift wrong, 5.9 with query heads on the wrong K/V head.
LOGIT_MARGIN = 0.15
# (b) How far a router's choice may lie from the reference's: how far the
# expert the engine took lies under the reference's largest selection
# score.  Selection scores are softmax outputs over 16 experts (near 1/16,
# the largest a few times that) of an MLP on a 2,048-term dot product that
# the engine forms from bf16 activations (relative step 2^-8) after up to
# 16 layers of them, plus the state carried from the layers before; the MLP
# itself is float32 in both.  A router that ranks wrongly, or one whose
# carried state is dropped, is off by the spread of a position's 16 scores.
# With random weights a position's largest two selection scores lie only
# 0.0007 apart at the median (the log line's `spread`: the balanced
# routers' scores are nearly flat), so the engine's bf16 noise reorders
# them at one position in ten and the reference takes the engine's choice
# there; what the limit must tell apart is that noise from a wrong router.
# Read on the chip: gaps of at most 0.00149 over 7 seeds (0.0013 with the
# router's MLP in one bf16 pass: this check cannot tell that from
# float32); 0.083 with rotary left out, 0.099-0.112 with a tail zeroed or
# unshifted, 0.112 with the carried state dropped, 0.147 without the value
# shift, 0.246 on the wrong K/V head.  2^-7 lies 5 times over the first
# and 10 times under the others.
NEAR_TIE = 2.0 ** -7
# (c) ||t_engine - t_reference|| / ||t_reference|| of the FIRST layer's
# conv tails (the last inputs of both convolutions and of the shifted
# value) after a request's last fed token; the worst of the three arrays
# and of the sampled requests decides.  The engine's tails are bf16 values
# of a projection of the first layer's normed embedding: their error is one
# rounding of a dot product (2^-9 of a value), the same on every request.
# Every later layer adds 16 layers' bf16 activations to both readings (the
# log line has the worst layer's): the first layer, whose input is the
# embedding itself, tells a wrong tail from a rounded one best.  A tail
# zeroed, left unshifted or read from another slot is off by its whole
# norm.  Read on the chip: 0.00287-0.00307 over 7 seeds (every layer's:
# 0.0081-0.0088); 1.6 with a decode step that leaves the tails it found.
TAIL_LIMIT = 0.02


class Served:
    """One request as the timed engine served it (`sample_of`)."""

    def __init__(self, seq, prompt, picks, tails):
        self.seq, self.prompt = seq, prompt
        self.picks, self.tails = picks, tails


def run(ctx):
    import jax

    import bench_data
    import trace_reduce
    import paddle_tpu as paddle
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.observability import compile_tracker

    log, fail, percentile = ctx["log"], ctx["fail"], serve.percentile
    Recorded, count_kernels = stateful.Recorded, stateful.count_kernels
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
                       server.cache.state, bench_data.rng(seed, 4),
                       int(traffic["sample_for_reference"]))
    del server, loop.server, model
    gc.collect()
    if not sample:
        raise fail(f"no request of the window still has its tails in the "
                   f"store ({len(done_ok)} completed of {len(due)} due)")
    found = check_against_reference(family.reference(cfg), params, sample,
                                    log)
    log(f"[check] {len(sample)} requests as the timed engine served them "
        f"vs the float32 reference: {found['exact']}/{found['tokens']} "
        f"tokens are its argmax, worst deficit {found['deficit']:.4f} logit "
        f"units (margin {LOGIT_MARGIN}); the routers' choices lie at most "
        f"{found['gap']:.5f} from the reference's (limit {NEAR_TIE}; the "
        f"1st score lies {found['spread']:.4f} over the 2nd at the median "
        f"position) and the reference took the engine's at "
        f"{found['swapped']} of {found['positions']} positions, "
        f"{found['outside']} outside the limit; the first layer's conv "
        f"tails in the engine's store differ from the reference's by "
        f"{found['tails']:.5f} of their norm at worst (limit {TAIL_LIMIT}; "
        f"by name, the worst layer's: "
        f"{ {k: round(v, 5) for k, v in found['tails_by_name'].items()} })")
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
            f"{num_blocks}; K/V heads "
            f"{stats['kv_cache']['kv_heads']}, "
            f"{stats['kv_cache']['bytes_per_token']} B a cached token; "
            f"state slots at most "
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


def sample_of(requests, record, eligible, store, rng, k):
    """At most k of `eligible`, drawn by `rng`, each as the timed engine
    served it (`Served`): `.picks` [layers, n - 1, 1], its routers'
    choices at every position fed (the last token is never fed), and
    `.tails` {name: [layers, rows, C]} float32, its slot of every array of
    `store` (the stopped server's own).  `record` is `Recorded.record`.
    Only a request that held its slot LAST can be drawn: another sequence
    has since overwritten an earlier holder's tails.  Where none of those
    is in `eligible` (a window shorter than a request, or one whose end
    the caller overran), they are drawn from whatever else completed."""
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
        sample.append(Served(r.seq, r.prompt, picks, {
            name: np.asarray(a[:, mine["slot"]], np.float32)
            for name, a in store.items()}))
    return sample


def check_against_reference(reference, params, sample, log):
    """The readings of the module docstring over `sample` (objects with
    `.seq`, `.prompt`, `.picks`, `.tails` as `sample_of` leaves them):
    {"deficit", "exact", "tokens", "gap", "spread", "swapped", "outside",
    "positions", "tails", "tails_by_name"}."""
    import jax
    import jax.numpy as jnp

    _arch, hidden, head = reference
    head = jax.jit(head)
    block = 512

    @jax.jit
    def deficits(params, rows, nxt):
        lg = head(params, rows)
        return lg.max(-1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]

    out = {"deficit": 0.0, "exact": 0, "tokens": 0, "gap": 0.0,
           "swapped": 0, "outside": 0, "positions": 0, "tails": 0.0,
           "tails_by_name": {name: 0.0 for name in sample[0].tails}}
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
        x, found = hidden(params, jnp.asarray(ids), served=jnp.asarray(served),
                          tie=NEAR_TIE, tail_len=n - 1)
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
        # (c) the tails the engine left in its store
        for name, mine in r.tails.items():
            want = np.asarray(found["tails"][name])
            err = np.linalg.norm((mine - want).reshape(len(want), -1),
                                 axis=1) \
                / np.linalg.norm(want.reshape(len(want), -1), axis=1)
            err = np.where(np.isfinite(err), err, np.inf)
            out["tails"] = max(out["tails"], float(err[0]))
            out["tails_by_name"][name] = max(out["tails_by_name"][name],
                                             float(err.max()))
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
    if not found["tails"] <= TAIL_LIMIT:
        wrong.append(f"the first layer's conv tails in the engine's store "
                     f"differ from the reference's by "
                     f"{found['tails']:.5f} of their norm (limit "
                     f"{TAIL_LIMIT})")
    return wrong
