"""The `train` kind of cell: one compiled step per dispatch, fresh seeded
batches from io.DataLoader workers, the loss read back every step.

The wiring is chip_smoke.make_train_step's, the only one proven on this
chip: the family's loss on bf16 casts of float32 master weights, and the
optimizer's functional update, jitted as one program with parameters and
optimizer state donated.

Traffic parameters (benchmark/traffic/<name>.json):
  batch, seq            the global batch: `batch` sequences of `seq` tokens
  steps_per_s_cap       sizes the dataset (the loader hands out a finite
                        list); a run that exhausts it fails loudly
  trace_seconds         length of the traced slice in a --trace 1 run
"""
from __future__ import annotations

import concurrent.futures
import gc
import math
import multiprocessing
import statistics
import time

import numpy as np

WARM_STEPS = 2


# One bfloat16 ulp, relative, at its widest (8 significant bits).  The step
# returns its loss as a bfloat16 number, or (BERT) as the quotient of a
# bfloat16 sum: either way its resolution is 2^-8 to 2^-7 of its value.
BF16_RTOL = 2.0 ** -7


def make_step(loss_fn, optimizer, compute_dtype):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(compute_dtype)

    def amp_loss(p32, batch, key):
        pc = jax.tree_util.tree_map(
            lambda x: x.astype(dt)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, p32)
        return loss_fn(pc, batch, key).astype(jnp.float32)

    def step(params, opt_state, batch, key):
        loss, grads = jax.value_and_grad(amp_loss)(params, batch, key)
        params, opt_state = optimizer.functional_update(params, grads,
                                                        opt_state)
        return loss, params, opt_state

    return step


def run(ctx):
    import jax
    import jax.numpy as jnp

    import bench_data
    import trace_reduce
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.io import DataLoader

    log, fail = ctx["log"], ctx["fail"]
    cfg, traffic, family = ctx["cfg"], ctx["traffic"], ctx["family"]
    seed, seconds, on_tpu = ctx["seed"], ctx["seconds"], ctx["on_tpu"]
    dep = cfg["deployment"]["train"]
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    shape = family.shape(cfg)
    if seq > shape["positions"]:
        raise fail(f"seq {seq} exceeds the model's {shape['positions']} "
                   f"positions")

    # ---- set-up: model, optimizer, loader, compile, warm-up -------------
    paddle.seed(seed % (2 ** 31 - 1))
    opt = dep["optimizer"]
    optimizer = getattr(opt_mod, opt["name"])(
        learning_rate=opt["learning_rate"], weight_decay=opt["weight_decay"])
    loss_fn, init_params = family.train_program(cfg)
    step = make_step(loss_fn, optimizer, dep["compute_dtype"])
    params = init_params()
    opt_state = optimizer.functional_init(params)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    t_model = time.perf_counter()

    n_steps = WARM_STEPS + int(math.ceil(
        seconds * float(traffic["steps_per_s_cap"])))
    dataset = bench_data.SeededSequences(
        seed, n_steps * batch, seq, shape["vocab"], family.OBJECTIVE,
        mask_id=cfg.get("assumed", {}).get("mask_token_id", 103))
    loader = DataLoader(dataset, batch_size=batch, shuffle=False,
                        num_workers=int(dep["loader_workers"]),
                        collate_fn=bench_data.COLLATE[family.OBJECTIVE])
    batches = iter(loader)

    def next_batch():
        try:
            data = next(batches)
        except StopIteration:
            raise fail(f"the loader ran out after {n_steps} batches: raise "
                       f"steps_per_s_cap in the traffic file") from None
        return {k: np.asarray(v) for k, v in data.items()}

    def beside_the_compile():
        """What needs no interpreter lock, done while the main thread traces
        the step: the loader's workers start and hand over the first batch;
        the step's starting weights, as it computes with them (cast to the
        compute dtype), go to the host for the reference after the window."""
        first = next_batch()
        start = {k: np.asarray(v.astype(dep["compute_dtype"]))
                 for k, v in params.items()}
        return first, start

    key = jax.random.key(seed % (2 ** 31 - 1))
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        beside = pool.submit(beside_the_compile)
        t0 = time.perf_counter()
        tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt_state, {"input_ids": tokens, "labels": tokens}, key)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        batch0, params0 = beside.result()
    log(f"[train] {cfg['family']} hidden {shape['hidden']} x "
        f"{shape['layers']} layers, {n_params / 1e6:.1f}M parameters, batch "
        f"{batch} x {seq}; model built in {t_model - ctx['t_process_start']:.1f}s "
        f"from process start, step traced and lowered in {t1 - t0:.1f}s, "
        f"compiled in {t2 - t1:.1f}s, first batch and host copy of the "
        f"weights ready {time.perf_counter() - t2:.1f}s later")
    loss, params, opt_state = compiled(params, opt_state, batch0, key)
    loss0 = float(loss)
    for _ in range(WARM_STEPS - 1):
        loss, params, opt_state = compiled(params, opt_state, next_batch(),
                                           key)
        float(loss)

    span = jax.profiler.TraceAnnotation

    # ---- the window -----------------------------------------------------
    trace_dir, trace, trace_stall_s = None, None, 0.0
    trace_at = seconds / 3 if ctx["trace"] else None
    trace_until = None
    trace_seconds = float(traffic.get("trace_seconds", 3.0))
    losses, step_s, wait_s = [], [], []
    t_w0 = time.perf_counter()
    setup_s = t_w0 - ctx["t_process_start"]
    t_end = t_w0
    while t_end - t_w0 < seconds:
        ta = time.perf_counter()
        if trace_at is not None and ta - t_w0 >= trace_at:
            trace_dir = trace_reduce.start(ctx["root"], ctx["cell"]["name"])
            trace_at, trace_until = None, time.perf_counter() + trace_seconds
            trace_stall_s += time.perf_counter() - ta
            ta = time.perf_counter()
        with span("bench:next(loader)"):
            data = next_batch()
        tb = time.perf_counter()
        with span("bench:step dispatch"):
            loss, params, opt_state = compiled(params, opt_state, data, key)
        with span("bench:loss read"):
            losses.append(float(loss))  # the host read is the barrier
        t_end = time.perf_counter()
        wait_s.append(tb - ta)
        step_s.append(t_end - tb)
        if trace_until is not None and (t_end >= trace_until
                                        or t_end - t_w0 >= seconds):
            trace = trace_reduce.finish(trace_dir, read=on_tpu)
            trace_until = None
            trace_stall_s += time.perf_counter() - t_end
            t_end = time.perf_counter()
    # all the work over all the time; a traced run leaves out the seconds
    # the loop stood still to start and to write out the trace
    window_s = t_end - t_w0 - trace_stall_s
    compiles_in_window, _stages = ctx["compiles_heard"](t_w0, t_end)
    steps = len(losses)
    tokens_per_step = batch * seq
    tokens_per_s = steps * tokens_per_step / window_s

    # ---- after the window: shut the loader, check the outputs -----------
    batches.close()
    del batches, loader
    gc.collect()
    for child in multiprocessing.active_children():
        child.join(timeout=10)
        if child.is_alive():
            child.terminate()
            child.join()
    memory_peak = ctx["memory_peak"]()
    del params, opt_state
    gc.collect()

    n_kernels = compiled.as_text().count("tpu_custom_call")
    want = family.KERNELS_PER_LAYER_TRAIN * shape["layers"]
    wrong = []
    finite = [bool(np.isfinite(x)) for x in losses]
    failed = finite.count(False)
    if failed:
        wrong.append(f"{failed} of {steps} steps returned a non-finite loss")
    tenth = max(1, steps // 10)
    first, last = (statistics.median(losses[:tenth]),
                   statistics.median(losses[-tenth:]))
    if not failed and last > first * (1 + BF16_RTOL):
        wrong.append(f"loss rose over the window: median of the first tenth "
                     f"{first:.4f}, of the last {last:.4f}")
    ref_fn = jax.jit(family.reference_loss(cfg))
    ref0 = float(ref_fn({k: jnp.asarray(v) for k, v in params0.items()},
                        {k: jnp.asarray(v) for k, v in batch0.items()}))
    # Tolerance: rounding the loss to bfloat16 moves it by up to half an
    # ulp (2^-8 relative at most, 0.04 at ~11); bf16 activations through the
    # layers move the unrounded loss by ~1e-3 relative (~0.01).  One ulp
    # covers both and nothing more: the reference gets the same
    # bf16-rounded weights.
    tol = BF16_RTOL * ref0
    log(f"[check] first-step loss {loss0:.4f} vs float32 reference "
        f"{ref0:.4f} (tolerance 2^-7 relative = {tol:.4f}); window losses "
        f"{first:.4f} -> {last:.4f} over {steps} steps; tpu_custom_call "
        f"count {n_kernels} ({want} expected on the chip)")
    if not abs(loss0 - ref0) <= tol:
        wrong.append(f"first-step loss {loss0} differs from the reference "
                     f"{ref0} by more than {tol}")
    if on_tpu and n_kernels != want:
        wrong.append(f"{n_kernels} tpu_custom_call in the compiled step, "
                     f"{want} expected")
    if compiles_in_window:
        wrong.append(f"{compiles_in_window} compile request(s) inside the "
                     f"window")

    result = {
        "correct": not wrong, "wrong": wrong, "attempted": steps,
        "failed": failed, "memory_peak_bytes": memory_peak,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "notes": [f"window {window_s:.3f}s, {steps} steps, step median "
                  f"{statistics.median(step_s) * 1e3:.2f} ms, loader wait "
                  f"median {statistics.median(wait_s) * 1e3:.3f} ms, "
                  f"{tokens_per_s:.1f} tokens/s, set-up {setup_s:.1f}s, "
                  f"{compiles_in_window} compiles in the window"],
    }
    if ctx["trace"]:
        obs = {
            "kind": "train", "shape": shape, "peaks": ctx["peaks"],
            "causal": family.CAUSAL, "batch": batch, "seq": seq,
            "n_params": n_params, "tokens_per_step": tokens_per_step,
            "train_tokens_per_s": tokens_per_s, "step_s": step_s,
            "data_wait_s": wait_s, "compiles_in_window": compiles_in_window,
            "memory_peak_bytes": memory_peak, "log": log,
        }
        obs.update(reduce_trace(trace_reduce, trace, log) if on_tpu
                   else trace_reduce.NOTHING_TRACED)
        result["obs"] = obs
    return result


def reduce_trace(tr, trace, log):
    """The traced slice cut to whole steps: from the start of the first
    program run that lies wholly inside it to the end of the last."""
    plane = next(p for p in tr.device_planes(trace) if tr.op_events(p))
    runs = sorted(tr.module_events(plane), key=lambda e: e[1])
    if len(runs) < 3:
        raise RuntimeError(f"only {len(runs)} program runs in the trace")
    runs = runs[1:-1]  # the slice's edges may have cut the outer two
    window = (runs[0][1], runs[-1][1] + runs[-1][2])
    trace = tr.clip(trace, *window)
    busy_s, window_s = tr.busy_and_window_s(trace, window)
    log(f"[trace] {len(runs)} whole steps in a {window_s:.3f}s slice, "
        f"device busy {busy_s:.3f}s; program {runs[0][0]}")
    return {"trace": trace, "trace_window": window, "traced_steps": len(runs),
            "busy_s": busy_s, "trace_window_s": window_s,
            "breakdown": tr.breakdown(trace, window)}
