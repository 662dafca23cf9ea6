"""The `serve` kind of cell: a closed loop of clients against one
PagedGenerationServer (default loop, greedy), timed by the benchmark's own
client through `on_token`.

Each of `clients` callers sends its next request the moment its last one
completed, so the load follows the server and there is no queue beyond the
slots.  Sizes are a fixed multiset drawn once from the traffic file's own
seed; --seed shuffles their order and draws the prompt tokens, so runs
differ by order alone.  Latencies are the client's: TTFT from the moment a
request was due (its client's previous completion) to its first `on_token`,
ITL between consecutive `on_token` stamps of one request.

Traffic parameters (benchmark/traffic/<name>.json):
  clients                   concurrent closed-loop callers
  prompt_len, new_tokens    {median, sigma, min, max} of a clipped lognormal
  distinct_sizes, sizes_seed  the fixed multiset of (prompt, output) sizes
  warm_requests             completions of the cell's own traffic before the
                            window opens
  trace_seconds             the traced slice of a --trace 1 run
  sample_for_reference      completed requests checked after the window
  request_timeout_s         a request older than this has failed
The engine's parameters are the configuration's deployment.serve: every
key there but `dtype` and `sizing` is passed to PagedGenerationServer.
"""
from __future__ import annotations

import gc
import math
import queue
import threading
import time

import numpy as np

# A served token must be the argmax of the float32 reference at its
# position, or lose to it by at most this many logit units.  Copied with its
# reason from chip_smoke.LOGIT_MARGIN: the served path computes in bf16 (8
# significant bits) through 24 layers; the reference is the same weights
# upcast to float32 at "highest" matmul precision, so two near-tied logits
# legitimately swap.  With random N(0, 0.02) weights and a tied head the
# logits of one position are ~N(0, 0.64) over 50,257 entries, so a token
# picked by a broken attention path or a wrong cache row is ~2.5 units under
# the top; bf16 rounding noise is two orders below that.  0.15 sits between
# (PR 21 measured a worst deficit of 0.0187).
LOGIT_MARGIN = 0.15
ATTENTION_PROGRAMS = ("packed_prefill", "decode_step")
RELIABILITY_ZERO = ("faults_injected", "dispatch_retries", "recoveries",
                    "quarantined", "timeouts", "shed", "consecutive_failures")
SAMPLE_EVERY_S = 0.1


def percentile(values, p):
    """Nearest-rank percentile of a non-empty list."""
    s = sorted(values)
    return s[max(0, math.ceil(p * len(s)) - 1)]


class Request:
    __slots__ = ("client", "prompt", "new", "t_due", "stamps", "seq",
                 "error", "t_done")

    def __init__(self, client, prompt, new, t_due):
        self.client, self.prompt, self.new = client, prompt, new
        self.t_due = t_due
        self.stamps, self.seq, self.error, self.t_done = [], None, None, None

    def on_token(self, _token, _reason):
        self.stamps.append(time.perf_counter())


class ClosedLoop:
    """One thread that keeps `clients` requests in flight."""

    def __init__(self, server, stream, clients, timeout_s, span):
        self.server, self.stream = server, stream
        self.clients, self.timeout_s, self.span = clients, timeout_s, span
        self.done = queue.Queue()
        self.requests = []
        self.in_flight = 0
        self.completed = 0
        self.accepting = True
        self.thread = threading.Thread(target=self._loop, daemon=True,
                                       name="bench-clients")

    def start(self):
        now = time.perf_counter()
        for c in range(self.clients):
            self._submit(c, now)
        self.thread.start()

    def _submit(self, client, t_due):
        prompt, new = next(self.stream)
        req = Request(client, prompt, new, t_due)
        self.requests.append(req)
        self.in_flight += 1
        with self.span("bench:client submit"):
            fut = self.server.submit(prompt, max_new_tokens=new,
                                     on_token=req.on_token,
                                     timeout_s=self.timeout_s)
        fut.add_done_callback(
            lambda f, r=req: self.done.put((r, f, time.perf_counter())))

    def _loop(self):
        while self.in_flight:
            req, fut, t_done = self.done.get()
            self.in_flight -= 1
            self.completed += 1
            req.t_done = t_done
            try:
                req.seq = np.asarray(fut.result())
            except Exception as e:  # noqa: BLE001 — a failed request is data
                req.error = f"{type(e).__name__}: {e}"
            if self.accepting:
                self._submit(req.client, t_done)

    def drain(self, timeout_s):
        self.accepting = False
        self.thread.join(timeout=timeout_s)
        return not self.thread.is_alive()


def run(ctx):
    import jax

    import bench_data
    import trace_reduce
    import paddle_tpu as paddle
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.observability import compile_tracker
    from paddle_tpu.ops.attention import paged_attention_path

    log, fail = ctx["log"], ctx["fail"]
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
    # every key of deployment.serve but these two is the engine's own
    engine = {k: v for k, v in dep.items() if k not in ("dtype", "sizing")}
    server = PagedGenerationServer(model, **engine)
    num_blocks = server.cache.stats()["num_blocks"]  # usable: no trash block
    n_warm = server.warm_buckets()
    t_warm = time.perf_counter()
    server.start()
    stream = bench_data.RequestStream(traffic, shape["vocab"], seed)
    loop = ClosedLoop(server, stream, int(traffic["clients"]),
                      float(traffic["request_timeout_s"]), span)
    loop.start()
    # a fixed amount of the cell's own traffic, not a fixed time: a run that
    # compiles the decode step here opens its window in the same state
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

    # ---- the window -----------------------------------------------------
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
            time.sleep(min(SAMPLE_EVERY_S, left))

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
    stats = server.stats()
    compiles_in_window = compile_tracker.count_since(mark_window)
    heard_in_window, stages = ctx["compiles_heard"](t_w0, t_w1)
    memory_peak = ctx["memory_peak"]()
    drained = loop.drain(float(traffic["request_timeout_s"]) + 30)
    events = compile_tracker.events_since(mark_all)
    server.stop()
    if not drained:
        raise fail("requests still in flight long after the window closed")

    # ---- the client's numbers -------------------------------------------
    requests = loop.requests
    due = [r for r in requests if t_w0 <= r.t_due < t_w1]
    tokens_in_window = sum(1 for r in requests for t in r.stamps
                           if t_w0 <= t < t_w1)
    ttft = [r.stamps[0] - r.t_due for r in due if r.stamps]
    gaps = [b - a for r in requests
            for a, b in zip(r.stamps, r.stamps[1:]) if t_w0 <= b < t_w1]
    if not ttft or not gaps:
        raise fail(f"nothing completed in the window ({len(due)} due)")
    wrong = []
    bad = []
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
    nonzero = {k: rel[k] for k in RELIABILITY_ZERO if rel[k]}
    if nonzero:
        wrong.append(f"engine reliability counters not zero: {nonzero}")
    if compiles_in_window:
        wrong.append(f"{compiles_in_window} compile(s) inside the window")
    stamps = sorted(t for r in requests for t in r.stamps if t_w0 <= t < t_w1)
    stall, stall_at = max((b - a, a - t_w0) for a, b in zip(stamps, stamps[1:]))

    # ---- the programs: which path, and is the kernel in them ------------
    head_dim = shape["head_dim"]
    path = paged_attention_path(head_dim, dep["block_size"], shape["heads"],
                                mesh=None)
    by_program = {}
    for ev in events:
        by_program.setdefault(ev["program"], []).append(ev)
    log("[serve] programs: " + ", ".join(
        f"{n} x{len(v)}" for n, v in sorted(by_program.items()))
        + f"; attention path {path}")
    if on_tpu:
        if path != "pallas":
            wrong.append(f"attention path is {path!r}, not the paged kernel")
        want = family.KERNELS_PER_LAYER_SERVE * shape["layers"]
        for name in ATTENTION_PROGRAMS:
            if name not in by_program:
                wrong.append(f"program {name} was never dispatched")
                continue
            # one variant of each: re-lowered from its shapes and read
            # back from the compile cache (every variant: minutes)
            text = by_program[name][0]["lower"]().compile().as_text()
            got = text.count("tpu_custom_call")
            log(f"[serve] {name}: {got} tpu_custom_call ({want} expected)")
            if got != want:
                wrong.append(f"{name} holds {got} kernels, not {want}")

    # ---- the reference: a seeded sample of what was served --------------
    params, _buffers = model.functional_state()
    params = dict(params)
    del server, loop.server, model
    gc.collect()
    done_ok = [r for r in due if r.error is None and r.seq is not None]
    pick = bench_data.rng(seed, 4).permutation(len(done_ok))[
        :int(traffic["sample_for_reference"])]
    worst, exact, total = check_against_reference(
        family.reference_logits(cfg), params,
        [done_ok[i] for i in pick],
        dep["max_prompt_len"] + dep["max_new_tokens"])
    log(f"[check] {len(pick)} served requests vs the float32 reference: "
        f"{exact}/{total} tokens are its argmax, worst deficit {worst:.4f} "
        f"logit units (margin {LOGIT_MARGIN})")
    if not worst <= LOGIT_MARGIN:
        wrong.append(f"a served token is {worst:.4f} under the float32 "
                     f"argmax (margin {LOGIT_MARGIN})")

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
            f"of the token stream {stall * 1e3:.0f} ms at {stall_at:.1f}s",
            f"engine's own clock: ttft p50/p99 {stats['ttft_p50_ms']:.1f}/"
            f"{stats['ttft_p99_ms']:.1f} ms, itl p50/p99 "
            f"{stats['itl_p50_ms']:.2f}/{stats['itl_p99_ms']:.2f} ms, "
            f"{stats['decode_steps']} decode steps, "
            f"{stats['prefill_dispatches']} prefill dispatches, slot fill "
            f"{stats['slot_fill']:.3f}, fewest free blocks {free_min} of "
            f"{num_blocks}"],
    }
    if ctx["trace"]:
        obs = {
            "kind": "serve", "shape": shape, "peaks": ctx["peaks"],
            "stats": stats, "admitted_in_window": len(due),
            "compiles_in_window": compiles_in_window,
            "free_blocks_min": free_min, "num_blocks": num_blocks,
            "ttft_p95_ms": percentile(ttft, 0.95) * 1e3,
            "memory_peak_bytes": memory_peak, "log": log,
        }
        obs.update(reduce_trace(trace_reduce, trace, slice_clock, requests,
                                log) if on_tpu
                   else trace_reduce.NOTHING_TRACED)
        result["obs"] = obs
    return result


def check_against_reference(ref_logits, params, sample, width):
    """(worst deficit, exact, total) of the sampled requests' generated
    tokens under the reference's full forward of the whole sequence: token
    t is predicted at position t-1.  Sequences are right-padded to one
    fixed width (causal attention: padding cannot reach back), four to a
    call, so every run compiles the same one program."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def deficits(p, ids):
        lg = ref_logits(p, ids)
        nxt = jnp.take_along_axis(lg[:, :-1], ids[:, 1:, None],
                                  axis=-1)[..., 0]
        return lg[:, :-1].max(-1) - nxt

    worst, exact, total = 0.0, 0, 0
    for i in range(0, len(sample), 4):
        group = sample[i:i + 4]
        ids = np.zeros((4, width), np.int32)
        for j, r in enumerate(group):
            ids[j, :len(r.seq)] = r.seq
        d = np.asarray(deficits(params, jnp.asarray(ids)))
        for j, r in enumerate(group):
            served = d[j, len(r.prompt) - 1:len(r.seq) - 1]
            if not np.isfinite(served).all():
                return float("inf"), exact, total
            worst = max(worst, float(served.max()))
            exact += int((served == 0).sum())
            total += served.size
    return worst, exact, total


def reduce_trace(tr, trace, slice_clock, requests, log):
    """The traced slice on the client's clock: the `bench:slice` span ties
    the trace's nanoseconds to perf_counter, so the tokens the client saw in
    the slice can be set against the device's work in it."""
    marks = [e for e in tr.host_spans(trace) if e[0] == tr.SLICE_SPAN]
    if len(marks) != 1:
        raise RuntimeError(f"{len(marks)} bench:slice spans in the trace")
    _name, s0, dur = marks[0]
    window = (s0, s0 + dur)
    trace = tr.clip(trace, *window)
    busy_s, window_s = tr.busy_and_window_s(trace, window)
    t_s0, t_s1 = slice_clock
    decode_contexts, prompts = [], []
    for r in requests:
        for j, t in enumerate(r.stamps):
            if t_s0 <= t < t_s1:
                if j == 0:
                    prompts.append(len(r.prompt))
                else:
                    decode_contexts.append(len(r.prompt) + j)
    log(f"[trace] slice {window_s:.3f}s (client clock {t_s1 - t_s0:.3f}s), "
        f"device busy {busy_s:.3f}s; the client saw {len(decode_contexts)} "
        f"decode tokens and {len(prompts)} first tokens in it")
    return {"trace": trace, "trace_window": window, "busy_s": busy_s,
            "trace_window_s": window_s,
            "decode_contexts": decode_contexts, "prefill_prompts": prompts,
            "breakdown": tr.breakdown(trace, window,
                                      no_span="engine (no span)")}
