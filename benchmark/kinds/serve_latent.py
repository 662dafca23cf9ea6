"""The `serve_latent` kind of cell: `kinds/serve.py`'s closed loop against
one PagedGenerationServer, for a family whose every layer keeps its
sequence as rows of a paged latent pool and nothing else (the store is
empty), with rotary positions in the rows, routed experts, and Pallas
kernels of two names.

The client, the window, the sampling of `available_block_count`, the traced
slice and the result's keys are `serve.py`'s own (imported, not copied), so
`serve_tokens_per_s`, `itl_p95_ms`, `setup_s` and the `.serve` per-layer
readers mean here what they mean in `gpt2_medium.serve_closed32`; the
kernel count and the routers' reading are `serve_stateful.py`'s.  `run` is
`serve_stateful.run` but for what is read after the window: a latent pool
in place of a KDA state "S" (that file reads it by name, `serve_routed.py`
checks conv tails and `serve_recurrent.py` a retention state).  The checks:

  kernels   each program's Pallas kernels are counted by kernel name
            against the family's own table (`family.serve_kernels`);
  path      the family says which form its decode-side ops take here;
  reference what the TIMED engine did for `sample_for_reference` of the
            requests it served in the window, against the reference's
            full forward of each whole sequence (expanded keys and values
            a head, one layer at a time, scores in blocks of queries,
            logits in blocks of positions).  Nothing is run again: the
            engine hands every request what its routers chose at every
            position (`submit(on_routing=)`), and the recorder notes where
            each request's rows lie in the pool; the stopped server's pool
            still holds every row whose block no later request took,
            which is where the sample is drawn from.  Three readings, each
            with its limit below: (a) every served token must be the
            reference's argmax or lose to it by at most LOGIT_MARGIN; (b)
            no router's choice may lie further than NEAR_TIE from the
            reference's own (`serve_stateful.py`'s reading and limit); (c)
            layer 0's latent rows in the pool, [RMSNorm(c) | rope(k_pe)],
            must lie within LATENT_LIMIT of the reference's.

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

# Each limit stands between two readings on the chip (PERF.md section 6 has
# the runs): what the engine reads over its seeds, and what a
# fault the limit is there for reads through this same check
# (scripts/kanana2_faults.py: one changed line of the program at a time, on
# requests served at the published widths).
#
# (a) A served token must be the float32 reference's argmax at its
# position, or lose to it by at most LOGIT_MARGIN logit units.  The served
# path computes in bf16 through 6 layers, so two near-tied logits
# legitimately swap, by no more than the bf16 noise of a logit; the
# reference follows the engine's own choice of experts wherever (b) lets
# it, so no position needs a wider margin.  `serve.py`'s 0.15 would lie
# over this check's precision control, so the margin is set here from its
# own two readings.  Read on the chip: 0.0303-0.0433 over 6 seeds (0.0237
# on the controls' own requests); 0.132 with the latent pool at float8
# (e4m3)'s precision, the precision below the configuration's bf16; 1.54
# with routed_scaling_factor dropped, 1.59 without kv_norm, 2.63 with
# rotary on q_pe only, 2.77 with no rotary, 2.94 with the shared FFN one
# expert wide, 3.06 with rotate-half pairing.  0.08 lies 1.85 times over
# the first and 1.65 times under the control.
LOGIT_MARGIN = 0.08
# (b) How far a router's choice may lie from the reference's: the most that
# an expert the engine took lies under the reference's 6th selection score,
# or one it left out over the 7th.  `serve_stateful.py`'s limit, for its
# reason: sigmoids of a 2,048-term dot product formed from bf16 activations.
# Read on the chip: 0.00656-0.00848 over 6 seeds, the 1st score 0.055 over
# the 6th; 0.128 with the pool at float8's precision, 0.27-0.63 with each
# fault above.
NEAR_TIE = stateful.NEAR_TIE
# (c) ||R_engine - R_reference|| / ||R_reference|| (Frobenius) of layer 0's
# latent rows [RMSNorm(c) | rope(k_pe)] at the sampled request's positions
# whose pool block no later request took; the worst sampled request
# decides.  Layer 0's input is the embedding itself, so the engine's rows
# differ from the reference's by the bf16 rounding of one projection, a
# norm and a turn, whatever the sequence's length.  k_pe turned by another
# pairing, by no position or not at all, or c without its norm, is off by a
# large part of the row; a pool kept at float8 (e4m3)'s 3 mantissa bits,
# the precision below the configuration's bf16, by its rounding.
# Read on the chip: 0.00287 on every seed and request (c and rope(k_pe)
# alike, 7,356 to 35,933 rows); 0.0268 with the pool at float8's
# precision, 0.099 without kv_norm, 0.316 with no rotary or rotary on q_pe
# only, 0.377 with rotate-half pairing (the FFN faults leave layer 0's
# rows as they are: (a) and (b) see them).  0.009 lies 3 times over the
# first and 3 times under the control.
LATENT_LIMIT = 0.009

# A window longer than this many seconds also notes what the windows of its
# first multiples of it would have read: how the end-to-end metrics spread
# with the window's length, from runs of one seed.
SLICE_S = 45.0


class Recorded(stateful.Recorded):
    """`serve_stateful.Recorded`, which also keeps where every request's
    rows lie in the pool: `record[id(request)]["blocks"]`, its block table
    as last seen, and `owner` {block: id of the request that took it
    last}.  The table is read (`state_slot_holder`, `block_table`) when a
    dispatch feeds a position past the blocks seen, once a block."""

    def __init__(self, server):
        super().__init__(server)
        self.owner = {}

    def submit(self, prompt, **kw):
        key = id(kw["on_token"].__self__)                    # serve.Request
        mine = {"routing": [], "slot": 0, "blocks": []}
        self.record[key] = mine
        cache, bs = self.server.cache, self.server.block_size

        def note(position, picks, slot):
            mine["routing"].append((position, picks))
            mine["slot"] = slot
            if position + picks.shape[1] > len(mine["blocks"]) * bs:
                seq = cache.state_slot_holder(slot)
                if seq is not None:
                    mine["blocks"] = cache.block_table(seq)
                    for b in mine["blocks"]:
                        self.owner[b] = key

        return self.server.submit(prompt, on_routing=note, **kw)


class Served:
    """One request as the timed engine served it (`sample_of`)."""

    def __init__(self, seq, prompt, picks, positions, rows):
        self.seq, self.prompt, self.picks = seq, prompt, picks
        self.positions, self.rows = positions, rows


def run(ctx):
    import jax

    import bench_data
    import trace_reduce
    import paddle_tpu as paddle
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.observability import compile_tracker

    log, fail, percentile = ctx["log"], ctx["fail"], serve.percentile
    count_kernels = stateful.count_kernels
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
    # nothing new from here on: what is in flight now finishes, and no
    # later request takes its blocks, where the reference check reads it
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
    median_gap = percentile(gaps, 0.5)
    behind = sum(1 for g in gaps if g > 1.5 * median_gap) / len(gaps)
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
    sample = sample_of(requests, loop.server, done_ok,
                       bench_data.rng(seed, 4),
                       int(traffic["sample_for_reference"]))
    del server, loop.server, model
    gc.collect()
    if not sample:
        raise fail(f"no request of the window still has rows in the pool "
                   f"({len(done_ok)} completed of {len(due)} due)")
    found = check_against_reference(family.reference(cfg), params, sample,
                                    log)
    log(f"[check] {len(sample)} requests as the timed engine served them "
        f"vs the float32 reference: {found['exact']}/{found['tokens']} "
        f"tokens are its argmax, worst deficit {found['deficit']:.4f} logit "
        f"units (margin {LOGIT_MARGIN}); the routers' choices lie at most "
        f"{found['gap']:.5f} from the reference's (limit {NEAR_TIE}; the "
        f"1st score lies {found['spread']:.4f} over the 6th at the median "
        f"position) and the reference took the engine's at "
        f"{found['swapped']} of {found['positions']} positions, "
        f"{found['outside']} outside the limit; layer 0's latent rows in "
        f"the pool differ from the reference's by {found['latent']:.5f} "
        f"of their norm at worst (limit {LATENT_LIMIT}; c "
        f"{found['latent_by_part']['c']:.5f}, rope(k_pe) "
        f"{found['latent_by_part']['k_pe']:.5f}) over {found['rows']} rows")
    wrong.extend(verdict(found))

    serve_tokens_per_s = tokens_in_window / seconds
    kernel = stats["decode_kernel"]
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
                             ((t_w0 + t_w1) / 2, t_w1)))
            + "".join(
                # what a window of the first n x SLICE_S would have read
                f"; its first {n * SLICE_S:.0f}s: "
                f"{sum(1 for t in stamps if t < t_w0 + n * SLICE_S) / (n * SLICE_S):.1f} "
                f"tokens/s, ITL p95 "
                f"{percentile([g for g, e in gaps_at if e < t_w0 + n * SLICE_S], 0.95) * 1e3:.2f} ms"
                for n in range(1, int(seconds // SLICE_S))),
            f"engine's own clock: ttft p50/p99 {stats['ttft_p50_ms']:.1f}/"
            f"{stats['ttft_p99_ms']:.1f} ms, itl p50/p99 "
            f"{stats['itl_p50_ms']:.2f}/{stats['itl_p99_ms']:.2f} ms, "
            f"{stats['decode_steps']} decode steps, "
            f"{stats['prefill_dispatches']} prefill dispatches, slot fill "
            f"{stats['slot_fill']:.3f}, fewest free blocks {free_min} of "
            f"{num_blocks} ({stats['kv_cache']['bytes_per_token']} B a "
            f"cached token over every layer; the store holds "
            f"{stats['state']['bytes_per_slot']} B a slot); experts: "
            f"{stats['experts']['held_picks']} picks of "
            f"{stats['experts']['tokens']} token-layers routed, mean load "
            f"{stats['experts']['mean_load']:.2f}, max "
            f"{stats['experts']['max_load']}; decode kernel "
            f"{kernel.get('kernel')} fan {kernel['fan']}: "
            f"{kernel['live_blocks']} live blocks in {kernel['steps']} grid "
            f"steps of {kernel['launches']} launches; "
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


def sample_of(requests, recorded, eligible, rng, k):
    """At most k of `eligible`, drawn by `rng`, each as the timed engine
    served it (`Served`): `.picks` [expert layers, n - 1, top k], its
    routers' choices at every position fed (the last token is never fed),
    and `.rows` [positions, lora + pe], layer 0's latent rows at
    `.positions`, the fed positions whose block no later request took,
    read from the stopped server's pool.  `recorded` is the `Recorded`
    that served them.  Only a request with such a block can be drawn;
    where none of those is in `eligible` (a window shorter than a request,
    or one whose end the caller overran), they are drawn from whatever
    else completed."""
    record, owner = recorded.record, recorded.owner
    cache = recorded.server.cache
    bs = cache.block_size

    def kept(r):
        """(table index, block) of r's blocks that it took last."""
        return [(j, b) for j, b in enumerate(record[id(r)]["blocks"])
                if owner.get(b) == id(r)]

    ok = {id(r) for r in eligible}
    held = [r for r in requests if r.t_done is not None and r.error is None
            and r.seq is not None and kept(r)]
    pool = sorted([r for r in held if id(r) in ok] or held,
                  key=lambda r: r.t_due)
    latent = cache.k_blocks.shape[-1]
    sample = []
    for i in rng.permutation(len(pool))[:k]:
        r, mine = pool[i], record[id(pool[i])]
        n = len(r.seq)
        layers, _n, top_k = mine["routing"][0][1].shape
        picks = np.full((layers, n - 1, top_k), -1, np.int32)
        for position, told in mine["routing"]:
            picks[:, position:position + told.shape[1]] = told
        if (picks < 0).any():
            raise ValueError(f"the engine told no routing for some of the "
                             f"{n - 1} positions it fed")
        blocks = kept(r)
        rows = np.asarray(cache.k_blocks[0, np.array([b for _j, b in blocks])],
                          np.float32).reshape(-1, latent)
        positions = np.concatenate([np.arange(j * bs, (j + 1) * bs)
                                    for j, _b in blocks])
        fed = positions < n - 1
        sample.append(Served(r.seq, r.prompt, picks, positions[fed],
                             rows[fed]))
    return sample


def check_against_reference(reference, params, sample, log):
    """The readings of the module docstring over `sample` (objects with
    `.seq`, `.prompt`, `.picks`, `.positions`, `.rows` as `sample_of`
    leaves them): {"deficit", "exact", "tokens", "gap", "spread",
    "swapped", "outside", "positions", "latent", "latent_by_part",
    "rows"}."""
    import jax
    import jax.numpy as jnp

    arch, hidden_fn, head, query_block = reference
    hidden = jax.jit(lambda p, ids, picks: hidden_fn(
        p, ids, served=picks, tie=NEAR_TIE))
    head = jax.jit(head)
    block = 512
    lora = arch["lora"]

    @jax.jit
    def deficits(params, rows, nxt):
        lg = head(params, rows)
        return lg.max(-1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]

    out = {"deficit": 0.0, "exact": 0, "tokens": 0, "gap": 0.0,
           "swapped": 0, "outside": 0, "positions": 0, "latent": 0.0,
           "latent_by_part": {"c": 0.0, "k_pe": 0.0}, "rows": 0}
    spreads = []
    # one compiled width for the whole sample: whole blocks of the
    # reference's queries and of its SwiGLU's rows
    step = max(2048, query_block)
    longest = max(len(r.seq) for r in sample)
    width = -(-longest // step) * step
    t0 = time.perf_counter()
    for r in sample:
        n, n_prompt = len(r.seq), len(r.prompt)
        ids = np.zeros((width,), np.int32)
        ids[:n] = r.seq                  # causal: padding cannot reach back
        served = np.zeros(r.picks.shape[:1] + (width,) + r.picks.shape[2:],
                          np.int32)      # past n - 2: never read below
        served[:, :n - 1] = r.picks
        x, found = hidden(params, jnp.asarray(ids), jnp.asarray(served))
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
        # (c) layer 0's latent rows the engine left in its pool
        want = np.asarray(found["latent"])[r.positions]
        mine = r.rows[:, :want.shape[1]]
        out["rows"] += len(r.positions)
        for name, part in (("latent", slice(None)), ("c", slice(0, lora)),
                           ("k_pe", slice(lora, None))):
            err = float(np.linalg.norm(mine[:, part] - want[:, part])
                        / np.linalg.norm(want[:, part]))
            err = err if np.isfinite(err) else float("inf")
            if name == "latent":
                out["latent"] = max(out["latent"], err)
            else:
                out["latent_by_part"][name] = max(
                    out["latent_by_part"][name], err)
    out["spread"] = float(np.median(np.concatenate(spreads)))
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
    if found["outside"] or not found["gap"] <= NEAR_TIE:
        wrong.append(f"at {found['outside']} positions a router's choice "
                     f"lies up to {found['gap']:.5f} from the reference's "
                     f"(limit {NEAR_TIE})")
    if not found["latent"] <= LATENT_LIMIT:
        wrong.append(f"layer 0's latent rows in the pool differ from the "
                     f"reference's by {found['latent']:.5f} of their norm "
                     f"(limit {LATENT_LIMIT})")
    return wrong
