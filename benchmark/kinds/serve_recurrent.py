"""The `serve_recurrent` kind of cell: `kinds/serve.py`'s closed loop
against one PagedGenerationServer, for a family whose every layer keeps
its sequence as recurrent state in the slot-indexed store: there is no
paged pool, no routed expert, and one Pallas kernel on the decode side.

The client, the window, the sampling of `available_block_count`, the traced
slice and the result's keys are `serve.py`'s own (imported, not copied), so
`serve_tokens_per_s`, `itl_p95_ms`, `setup_s` and the `.serve` per-layer
readers mean here what they mean in `gpt2_medium.serve_closed32`; the
recording server and the kernel count are `serve_stateful.py`'s.  `run` is
`serve_stateful.run` but for what is read from the store, the family's
having no routers, and the words of its log (that file reads
`model.router_balance` and a KDA state "S" by name, `serve_routed.py`
checks conv tails).  The checks:

  kernels   each program's Pallas kernels are counted by kernel name
            against the family's own table (`family.serve_kernels`);
  path      the family says which form its decode-side ops take here;
  reference what the TIMED engine did for `sample_for_reference` of the
            requests it served in the window, against the reference's
            full forward of each whole sequence (one layer at a time,
            weights formed directly from q . k in blocks of queries,
            logits in blocks of positions).  Nothing is run again: the
            engine tells every request which slot of the store it holds
            (`submit(on_routing=)`), and the store of the stopped server
            still holds the last state of the sequence that held each
            slot last, which is where the sample is drawn from.  Two
            readings, each with its limit below: (a) every served token
            must be the reference's argmax or lose to it by at most
            LOGIT_MARGIN; (b) the first layer's retention state in the
            engine's store, brought to the symmetric tensors the
            reference writes (`family.unpack_state`), must lie within
            STATE_LIMIT of the reference's after the same tokens, S and
            its normaliser z alike.

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
# PR 32, has the runs): what the engine reads over its seeds, and what a
# fault the limit is there for reads through this same check
# (scripts/brumby_faults.py: one changed line of the program at a time, on
# requests served at the published widths).
#
# (a) A served token must be the float32 reference's argmax at its
# position, or lose to it by at most LOGIT_MARGIN logit units: `serve.py`'s
# margin, for its reason.  The served path computes in bf16 through 8
# layers, so two near-tied logits legitimately swap, by no more than the
# bf16 noise of a logit.
# Read on the chip: at most 0.0393 over 6 seeds (0.0256 on the controls'
# own requests); 0.35 with the products of two tiles counted once, 0.70
# with rotary left out, 1.08 with query heads on the wrong K/V head, 1.29
# with a slot's state not zeroed for its next holder, 3.1 with a chunk's
# own weights of degree 1, 6.0 without the normaliser.  A gate left out
# (0.057), a missing q/k norm (0.107: the outputs do not depend on the
# queries' length and only weakly on the keys') and a store kept in bf16
# (0.045) stay under it: those are (b)'s.
LOGIT_MARGIN = 0.15
# (b) ||X_engine - X_reference|| / ||X_reference|| (Frobenius, over the K/V
# heads) of the FIRST layer's state after a request's last fed token, for
# X = S (sum_s e^{..} k_s (x) k_s (x) v_s) and X = z (sum_s e^{..} k_s (x)
# k_s); the worse of the two and the worst sampled request decide.  The
# engine's state is float32 built from bf16 activations: its error is the
# rounding of each token's q, k, v (squared in k: twice a bf16 step), not a
# sum of roundings, and it reads the same on every request whatever its
# length.  A store kept in bf16 rounds the state once a decode token, and
# the roundings add up over the state's memory (the first layer's gate
# holds it for thousands of positions).  Every later layer adds blocks of
# bf16 activations to both readings; the first layer, whose input is the
# embedding itself, tells a rounded store from a float32 one best.  A
# state that is not zeroed for its slot's next holder, a gate left out, a
# wrong K/V head or an off-diagonal weight of 1 is off by a large part of
# its norm.
# Read on the chip: 0.00285-0.00316 over 6 seeds (z: 0.00211-0.00247),
# whatever the requests' lengths (853 to 17,276 positions); 0.00237 on the
# controls' own requests and, through the same check there, 0.0331 with the
# store kept in bf16 (rounded at every write of the decode kernel and of
# the prefill form: 48 decode steps after prompts of 600-1,600; a whole
# request's 128-1,536 steps add more), 0.189 without the gate, 0.222 with
# an off-diagonal weight of 1, 1.16 without the q/k norm, 1.23 without
# rotary, 4.45 with a slot's state not zeroed.  0.01 lies 3 times over the
# first and 3 times under the second.
STATE_LIMIT = 0.01


class Served:
    """One request as the timed engine served it (`sample_of`)."""

    def __init__(self, seq, prompt, state):
        self.seq, self.prompt, self.state = seq, prompt, state


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
    behind = sum(1 for g in gaps if g > 1.5 * percentile(gaps, 0.5)) \
        / len(gaps)
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
    sample = sample_of(
        requests, loop.server.record, done_ok,
        lambda slot: family.unpack_state(server.cache.state, slot, cfg),
        bench_data.rng(seed, 4), int(traffic["sample_for_reference"]))
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
        f"units (margin {LOGIT_MARGIN}); the first layer's retention state "
        f"in the engine's store differs from the reference's by "
        f"{found['state']:.5f} of its norm at worst (S "
        f"{found['state_by_name']['S']:.5f}, z "
        f"{found['state_by_name']['z']:.5f}; limit {STATE_LIMIT}) after "
        f"{found['fed']} positions fed")
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
            f"{num_blocks} (no pool: "
            f"{stats['kv_cache']['bytes_per_token']} B a cached token); "
            f"state slots at most {stats['state']['peak_used_slots']} of "
            f"{stats['state']['slots']}, "
            f"{stats['state']['bytes_per_slot'] / 1e6:.1f} MB a slot "
            f"({ {k: round(v / 1e6, 2) for k, v in stats['state']['entries'].items()} }); "
            f"{100 * behind:.1f}% of the window's token gaps are longer "
            f"than 1.5 medians (they follow a prefill dispatch)"],
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


def sample_of(requests, record, eligible, state_of, rng, k):
    """At most k of `eligible`, drawn by `rng`, each as the timed engine
    served it (`Served`): `.state` {"S", "z"} float32 is the first layer's
    state of its slot of the stopped server's store, as `state_of(slot)`
    brings it to the reference's form.  `record` is `Recorded.record`.
    Only a request that held its slot LAST can be drawn: another sequence
    has since overwritten an earlier holder's state.  Where none of those
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
    return [Served(pool[i].seq, pool[i].prompt,
                   state_of(record[id(pool[i])]["slot"]))
            for i in rng.permutation(len(pool))[:k]]


def check_against_reference(reference, params, sample, log):
    """The readings of the module docstring over `sample` (objects with
    `.seq`, `.prompt`, `.state` as `sample_of` leaves them): {"deficit",
    "exact", "tokens", "state", "state_by_name", "fed"}."""
    import jax
    import jax.numpy as jnp

    _arch, hidden, head, query_block = reference
    head = jax.jit(head)
    block = 512

    @jax.jit
    def deficits(params, rows, nxt):
        lg = head(params, rows)
        return lg.max(-1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]

    out = {"deficit": 0.0, "exact": 0, "tokens": 0, "state": 0.0,
           "state_by_name": {"S": 0.0, "z": 0.0}, "fed": []}
    # one compiled width for the whole sample: whole blocks of the
    # reference's queries and of its SwiGLU's rows
    step = max(2048, query_block)
    longest = max(len(r.seq) for r in sample)
    width = -(-longest // step) * step if longest > step \
        else -(-longest // query_block) * query_block
    t0 = time.perf_counter()
    for r in sample:
        n, n_prompt = len(r.seq), len(r.prompt)
        ids = np.zeros((width,), np.int32)
        ids[:n] = r.seq                  # causal: padding cannot reach back
        # the engine fed positions 0 .. n - 2: the last token is never fed
        x, found = hidden(params, jnp.asarray(ids), state_len=n - 1)
        out["fed"].append(n - 1)
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
        # (b) the state the engine left in its store
        for name, mine in r.state.items():
            want = np.asarray(found["state"][name])
            err = float(np.linalg.norm(mine - want) / np.linalg.norm(want))
            err = err if np.isfinite(err) else float("inf")
            out["state_by_name"][name] = max(out["state_by_name"][name],
                                             err)
            out["state"] = max(out["state"], err)
    log(f"[check] the reference took {time.perf_counter() - t0:.1f}s at "
        f"width {width}")
    return out


def verdict(found):
    """What of `check_against_reference`'s readings lies over its limit,
    in words; empty when the engine did what the reference does."""
    wrong = []
    if not found["deficit"] <= LOGIT_MARGIN:
        wrong.append(f"a served token is {found['deficit']:.4f} under the "
                     f"float32 argmax (margin {LOGIT_MARGIN})")
    if not found["state"] <= STATE_LIMIT:
        wrong.append(f"the first layer's retention state in the engine's "
                     f"store differs from the reference's by "
                     f"{found['state']:.5f} of its norm (S "
                     f"{found['state_by_name']['S']:.5f}, z "
                     f"{found['state_by_name']['z']:.5f}; limit "
                     f"{STATE_LIMIT})")
    return wrong
