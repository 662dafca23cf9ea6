#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, one import of JAX.  It drives the two paths users of the
framework start from, at the full width of GPT-2-medium (hidden 1024, 24
layers, 16 heads of 64, vocab 50,257, 1,024 positions; random weights from
`--seed`), through the entry points a user would call:

  1. build   the native runtime from csrc/native_runtime.cpp (no committed .so)
  2. eager   the README's first snippet: nn.Sequential, backward, opt.step
  3. train   models.gpt2.build_train_step + AdamW functional update, bf16
             compute on f32 masters, batches from io.DataLoader(num_workers=2)
  4. serve   PagedGenerationServer(block_size=128): the default loop, then
             unified_round=True, then kv_dtype="int8"; every served token is
             checked against the cache-free float32 forward of the same model

`--chips 4` runs instead, and only, the path across chips: dp2 x mp2 Fleet
hybrid training against the one-device step, and the tensor-parallel paged
engine (tp=4, and tp=2 x dp=2 where the Pallas kernel runs per device under
shard_map) against the one-device engine and the float32 reference.

It needs a TPU: with `JAX_PLATFORMS=cpu`, or with no chip, it exits non-zero
and prints no result.  Every phase failure exits non-zero.  The last line of
standard output is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.

`--rehearse` runs the same code at a tiny size on whatever backend JAX has
(the CPU, Pallas kernels off), to find wrong paths before chip time is spent.
It asserts no kernel counts, prints no `"ok": true` and exits with code 3.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import sys
import time
import warnings

import numpy as np

# A served token must be the argmax of the float32 reference at its position,
# or lose to it by at most this many logit units.  Why a margin at all: the
# served path computes in bf16 (8 mantissa bits, eps 2^-8) through 24 layers
# and, in the third configuration, reads an int8 KV pool; the reference is the
# same weights upcast to float32 at "highest" matmul precision.  Two
# near-tied logits then legitimately swap, and exact equality against another
# low-precision path is a test of one host's rounding, not of the engine.
# Why this size: with random N(0, 0.02) weights and a tied head the logits of
# one position are ~N(0, 0.64) over 50,257 entries (sqrt(1024) * 0.02), so a
# token picked by a broken attention path or a wrong cache row is ~2.5 units
# under the top; rounding noise of bf16 activations is two orders below that.
# 0.15 sits between them (the measured deficits are printed by every run).
LOGIT_MARGIN = 0.15
# Loss of dp2 x mp2 training against the one-device step, per step, relative.
# The model computes its loss in bf16, so the number compared is a bf16 value:
# 8 significant bits, one ulp = 0.0625 at ~11.  The two programs re-associate
# their reductions (per-device partial sums, then an all-reduce), which moves
# the unrounded loss by ~1e-3 relative, far under an ulp — so after rounding
# they are equal or adjacent bf16 numbers.  One ulp is 2^-7 relative at most.
HYBRID_LOSS_RTOL = 2.0 ** -7

BLOCK_SIZE = 128
SERVE_BLOCKS = 256
SERVE_SLOTS = 8
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 6
ATTENTION_PROGRAMS = ("packed_prefill", "decode_step", "unified_round",
                      "packed_verify", "multistep")


class SmokeFailure(Exception):
    """A phase did not do what it must; the run exits non-zero."""


def log(msg):
    print(msg, flush=True)


def need(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class RepeatedBatch:
    """Map-style dataset whose every batch of `batch` items is the same
    `batch` sequences: the trainer must drive the loss down on it.  Plain
    numpy, so DataLoader workers (spawned; they never see the chip) can
    unpickle it without touching a JAX backend."""

    def __init__(self, seqs, steps):
        self.seqs = seqs
        self.n = len(seqs) * steps

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return self.seqs[i % len(self.seqs)]


def collate_lm(samples):
    ids = np.stack(samples)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def distinct_devices(tree):
    import jax

    return {d for leaf in jax.tree.leaves(tree)
            for d in (s.device for s in leaf.addressable_shards)}


def kernel_count(text):
    return text.count("tpu_custom_call")


# ---- phases 1 + 2 --------------------------------------------------------

def phase_build():
    from paddle_tpu.io import native_loader

    for so in glob.glob(os.path.join(os.path.dirname(native_loader._SRC),
                                     "*.so")):
        os.remove(so)
    t0 = time.perf_counter()
    native_loader.get_lib()  # g++ from csrc/native_runtime.cpp; raises
    need(os.path.exists(native_loader._SO), "native runtime not built")
    log(f"[build] native runtime built from source in "
        f"{time.perf_counter() - t0:.1f}s: "
        f"{os.path.relpath(native_loader._SO)}")


def phase_eager(seed, platform):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    paddle.seed(seed)
    rs = np.random.RandomState(seed)
    x = paddle.to_tensor(rs.rand(64, 784).astype(np.float32))
    y = paddle.to_tensor(rs.randint(0, 10, (64,)).astype(np.int64))
    model = nn.Sequential(nn.Linear(784, 256), nn.ReLU(),
                          nn.Linear(256, 10))
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    losses = []
    for _ in range(5):
        loss = nn.CrossEntropyLoss()(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    where = {d.platform for p in model.parameters()
             for d in p._value.devices()} | \
        {d.platform for d in loss._value.devices()}
    need(where == {platform},
         f"eager parameters/loss live on {where}, not {platform}")
    need(np.isfinite(losses).all() and losses[-1] < losses[0],
         f"eager loss did not fall: {losses}")
    log(f"[eager] nn.Sequential MLP, 5 steps on {platform}: loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}")


# ---- phase 3: train ------------------------------------------------------

def make_train_step(cfg, optimizer):
    """bf16 compute on f32 master weights, the AdamW functional update:
    the wiring of the benchmark's train cells (`benchmark/kinds/train.py`)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt2 import build_train_step

    loss_fn, init_params, _model = build_train_step(cfg, remat=False)

    def amp_loss(p32, batch, key):
        pb = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, p32)
        return loss_fn(pb, batch, key).astype(jnp.float32)

    def step(params, opt_state, batch, key):
        loss, grads = jax.value_and_grad(amp_loss)(params, batch, key)
        params, opt_state = optimizer.functional_update(params, grads,
                                                        opt_state)
        return loss, params, opt_state

    return amp_loss, step, init_params


def train_sequences(cfg, seed, batch, seq):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, (seq + 1,)).astype(np.int32)
            for _ in range(batch)]


def phase_train(cfg, seed, batch, seq, steps, on_tpu):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.io import DataLoader
    from paddle_tpu.io.worker import _mp_context
    from paddle_tpu.ops.attention import flash_attention_path

    paddle.seed(seed)
    optimizer = opt_mod.AdamW(learning_rate=1e-4, weight_decay=0.01)
    _amp_loss, step, init_params = make_train_step(cfg, optimizer)
    params = init_params()
    opt_state = optimizer.functional_init(params)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())

    method = _mp_context().get_start_method()
    need(not on_tpu or method == "spawn",
         f"DataLoader workers would start by {method!r} under a parent "
         f"that holds the chip")
    loader = DataLoader(RepeatedBatch(train_sequences(cfg, seed, batch, seq),
                                      steps),
                        batch_size=batch, shuffle=False, num_workers=2,
                        collate_fn=collate_lm)
    key = jax.random.key(seed)
    compiled, losses, times = None, [], []
    for i, data in enumerate(loader):
        data = {k: np.asarray(v) for k, v in data.items()}
        if compiled is None:
            t0 = time.perf_counter()
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                params, opt_state, data, key)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            n_kernels = kernel_count(compiled.as_text())
            log(f"[train] GPT-2 hidden {cfg.hidden_size} x {cfg.num_layers} "
                f"layers, {n_params / 1e6:.0f}M params, batch "
                f"{batch} x {seq}, traced and lowered in {t1 - t0:.1f}s, "
                f"compiled in {time.perf_counter() - t1:.1f}s; flash "
                f"tpu_custom_call "
                f"count {n_kernels} (3 per layer expected = "
                f"{3 * cfg.num_layers})")
            need(not on_tpu or n_kernels > 0,
                 "no flash kernel in the compiled train step")
            path = flash_attention_path(
                cfg.hidden_size // cfg.num_heads, cfg.num_heads, seq, seq,
                batch, token_major=True)
            log(f"[train] attention path: {path}")
            need(not on_tpu or path == "pallas/token_major",
                 f"GPT2Block's attention takes the {path} path, not the "
                 f"flash kernels on the projection's own layout")
        t0 = time.perf_counter()
        loss, params, opt_state = compiled(params, opt_state, data, key)
        losses.append(float(loss))  # the host read is the barrier
        times.append(time.perf_counter() - t0)
    need(len(losses) >= 5, f"only {len(losses)} train steps ran")
    need(np.isfinite(losses).all() and losses[-1] < losses[0],
         f"train loss not finite and falling: {losses}")
    log(f"[train] {len(losses)} steps through DataLoader(num_workers=2, "
        f"start={method}): loss " +
        " ".join(f"{l:.4f}" for l in losses))
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[train] step time after warm-up {np.median(times[1:]) * 1e3:.1f} "
        f"ms (information only); peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")


# ---- phase 4: serve ------------------------------------------------------

def make_requests(cfg, seed, n, lo, hi, new_lo, new_hi):
    rs = np.random.RandomState(seed + 1)
    return [(rs.randint(1, cfg.vocab_size,
                        (int(rs.randint(lo, hi + 1)),)).astype(np.int32),
             int(rs.randint(new_lo, new_hi + 1))) for _ in range(n)]


def serve(model, requests, label, block_size, num_blocks, max_new,
          on_tpu, pool_devices=1, **server_kw):
    """Serve `requests` through a fresh PagedGenerationServer; returns the
    served sequences.  Fails on any fault/recovery, on weights or a KV pool
    that do not sit on `pool_devices` devices and, on the chip, on an
    attention program without its Pallas kernel where the gate applies."""
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.observability import compile_tracker
    from paddle_tpu.ops.attention import paged_attention_path

    cfg = model.cfg
    mark = compile_tracker.mark()
    t0 = time.perf_counter()
    server = PagedGenerationServer(
        model, max_slots=SERVE_SLOTS, block_size=block_size,
        num_blocks=num_blocks, max_new_tokens=max_new,
        max_prompt_len=max(len(p) for p, _ in requests), **server_kw)
    for name, tree in (("weights", server._params),
                       ("KV pool", (server.cache.k_blocks,
                                    server.cache.v_blocks))):
        n = len(distinct_devices(tree))
        need(n == pool_devices,
             f"[{label}] {name} sit on {n} device(s), not {pool_devices}")
    # one layer's K and V, all devices together, and what an int8 pool's
    # programs still re-lay: its two scale stacks [L, N, BS, H], whose
    # rows of H are padded to the 128 lanes (PERF.md section 7)
    pool = server.cache
    layer_pool = pool.pool_bytes_total // cfg.num_layers
    relaid_scales = 0 if pool.kv_dtype is None else (
        2 * cfg.num_layers * num_blocks * block_size * 128
        * pool.k_blocks.scales.dtype.itemsize)
    server.start()
    try:
        futures = [server.submit(p, max_new_tokens=n) for p, n in requests]
        out = [np.asarray(f.result(timeout=900)) for f in futures]
        stats = server.stats()
    finally:
        server.stop()
    wall = time.perf_counter() - t0
    for (p, n), seq in zip(requests, out):
        need(len(seq) == len(p) + n and (seq[:len(p)] == p).all(),
             f"[{label}] request returned {len(seq)} tokens for a "
             f"{len(p)}-token prompt + {n} new")
    rel = stats["reliability"]
    bad = {k: rel[k] for k in ("faults_injected", "dispatch_retries",
                               "recoveries", "quarantined", "timeouts",
                               "shed", "consecutive_failures") if rel[k]}
    need(not bad, f"[{label}] engine reliability counters not zero: {bad}")
    mesh = getattr(server, "_mesh", None)
    path = paged_attention_path(cfg.hidden_size // cfg.num_heads,
                                block_size, cfg.num_heads, mesh=mesh)
    compiles = compile_tracker.events_since(mark)
    compile_s = sum(e["dur_s"] for e in compiles)
    # re-compile each dispatched program from its shapes and read the
    # compiled HLO (the persistent cache serves what the dispatch compiled)
    t1 = time.perf_counter()
    counts, temps = {}, {}
    for ev in compiles:
        compiled = ev["lower"]().compile()
        counts.setdefault(ev["program"], []).append(
            kernel_count(compiled.as_text()))
        temps.setdefault(ev["program"], []).append(
            compiled.memory_analysis().temp_size_in_bytes)
    recount_s = time.perf_counter() - t1
    for name, ks in sorted(counts.items()):
        log(f"[{label}] program {name}: {len(ks)} compiled variant(s), "
            f"tpu_custom_call per variant {sorted(set(ks))}, attention "
            f"path {path if name in ATTENTION_PROGRAMS else 'xla (dense)'}")
        if name not in ATTENTION_PROGRAMS:
            continue
        # the pool is written and read in place: a program's temporaries
        # hold no layer's slice, no copy and no re-laid copy of it
        log(f"[{label}] program {name}: temporaries up to "
            f"{max(temps[name]) / 1e6:.1f} MB a device (one layer's pool "
            f"{layer_pool / 1e6:.1f} MB, re-laid int8 scale stacks "
            f"{relaid_scales / 1e6:.1f} MB)")
        if on_tpu:
            need((min(ks) > 0) == (path != "xla"),
                 f"[{label}] {name}: path {path!r} but kernel counts {ks}")
            need(max(temps[name]) < layer_pool + relaid_scales,
                 f"[{label}] {name}: {max(temps[name])} bytes of "
                 f"temporaries, as much as one layer's pool ({layer_pool}"
                 f" + {relaid_scales}): the pool is being copied")
    need(not on_tpu or any(n in counts for n in ATTENTION_PROGRAMS),
         f"[{label}] no attention program was dispatched: {list(counts)}")
    log(f"[{label}] {len(out)} requests, {stats['new_tokens']} new tokens, "
        f"{stats['decode_steps']} decode steps, "
        f"{stats['prefill_dispatches']} prefill dispatches in {wall:.1f}s "
        f"wall (of which {compile_s:.1f}s in dispatches that compiled; "
        f"reading the programs back took {recount_s:.1f}s), "
        f"weights and KV pool on {pool_devices} device(s); "
        f"faults/recoveries/requeues/quarantines 0")
    del server
    gc.collect()
    return out


def make_reference(model):
    """(seqs, prompt_lens) -> per-token deficit under the float32 argmax,
    from the model's ordinary cache-free forward: float32 weights (the
    served bf16 weights upcast), "highest" matmul precision, and a padded
    length that is no multiple of 128 so the forward takes the plain XLA
    attention — it shares no kernel with the served path."""
    import jax
    import jax.numpy as jnp

    params, buffers = model.functional_state()
    p32 = {k: v.astype(jnp.float32) if jnp.issubdtype(v.dtype, jnp.floating)
           else v for k, v in params.items()}

    @jax.jit
    def deficits(p, ids):
        with jax.default_matmul_precision("highest"):
            logits = model.functional_call(p, buffers, ids)
        logits = getattr(logits, "_value", logits).astype(jnp.float32)
        nxt = jnp.take_along_axis(logits[:, :-1], ids[:, 1:, None],
                                  axis=-1)[..., 0]
        return logits[:, :-1].max(-1) - nxt, logits.std(-1).mean()

    def check(seqs, prompt_lens, label, margin=LOGIT_MARGIN):
        width = max(len(s) for s in seqs)
        width += width % 128 == 0
        worst, exact, total, spread = 0.0, 0, 0, []
        for i in range(0, len(seqs), 4):
            ids = np.zeros((4, width), np.int32)
            for j, s in enumerate(seqs[i:i + 4]):
                ids[j, :len(s)] = s
            d, sd = deficits(p32, jnp.asarray(ids))
            d = np.asarray(d)
            spread.append(float(sd))
            for j, s in enumerate(seqs[i:i + 4]):
                # token t of the sequence is predicted at position t-1
                served = d[j, prompt_lens[i + j] - 1:len(s) - 1]
                need(np.isfinite(served).all(),
                     f"[{label}] non-finite reference logits")
                worst = max(worst, float(served.max()))
                exact += int((served == 0).sum())
                total += served.size
        log(f"[{label}] vs float32 cache-free forward: {exact}/{total} "
            f"served tokens are its argmax, worst deficit {worst:.4f} "
            f"logit units (margin {margin}, logit std "
            f"{np.mean(spread):.3f})")
        need(worst <= margin,
             f"[{label}] a served token is {worst:.4f} under the float32 "
             f"argmax (margin {margin})")

    return check


def build_served_model(cfg, seed, on_tpu):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt2 import GPT2

    paddle.seed(seed)
    model = GPT2(cfg)
    model.eval()
    if on_tpu:
        model.to(dtype="bfloat16")
    return model


def serving_setup(cfg, seed, sizes, on_tpu):
    """(model, requests, check(seqs, label), serve keywords) shared by the
    one-chip and the four-chip serving phases."""
    model = build_served_model(cfg, seed, on_tpu)
    requests = make_requests(cfg, seed, *sizes["requests"])
    reference = make_reference(model)
    plens = [len(p) for p, _ in requests]
    kw = dict(block_size=sizes["block_size"], num_blocks=sizes["blocks"],
              max_new=sizes["requests"][-1], on_tpu=on_tpu)
    return model, requests, \
        (lambda seqs, label: reference(seqs, plens, label)), kw


def phase_serve(cfg, seed, sizes, on_tpu):
    model, requests, check, kw = serving_setup(cfg, seed, sizes, on_tpu)
    for label, extra in (("serve/default", {}),
                         ("serve/unified_round", {"unified_round": True}),
                         ("serve/int8_kv", {"kv_dtype": "int8"})):
        check(serve(model, requests, label, **kw, **extra), label)


# ---- the path across chips (--chips 4) ------------------------------------

def phase_hybrid_train(cfg, seed, batch, seq, on_tpu):
    """dp2 x mp2 through fleet.build_hybrid_train_step against the same
    three steps of the one-device step, same batch, same seed."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.distributed import fleet
    from paddle_tpu.parallel.mesh import mesh_guard

    data = collate_lm(train_sequences(cfg, seed, batch, seq))
    key = jax.random.key(seed)

    def fresh():
        paddle.seed(seed)
        optimizer = opt_mod.AdamW(learning_rate=1e-4, weight_decay=0.01)
        amp_loss, step, init_params = make_train_step(cfg, optimizer)
        params = init_params()
        return optimizer, amp_loss, step, params, \
            optimizer.functional_init(params)

    optimizer, amp_loss, _step, params, opt_state = fresh()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 1, "sp_degree": 1}
    hstep, mesh = fleet.build_hybrid_train_step(strategy, amp_loss,
                                                optimizer)
    t0 = time.perf_counter()
    compiled = hstep.compile_for(params, data, opt_state).lower(
        params, opt_state, data, key).compile()
    n_kernels = kernel_count(compiled.as_text())
    path = flash_attention_path(cfg.hidden_size // cfg.num_heads,
                                cfg.num_heads, seq, seq, batch, mesh,
                                token_major=True)
    log(f"[4chip/train] dp2 x mp2 step compiled in "
        f"{time.perf_counter() - t0:.1f}s on mesh {dict(mesh.shape)}; "
        f"flash tpu_custom_call count {n_kernels} (attention path: "
        f"{path})")
    need(not on_tpu or (n_kernels > 0 and path == "pallas/shard_map"),
         f"no flash kernel in the dp2 x mp2 train step (attention path "
         f"{path})")
    hybrid = []
    for _ in range(3):
        loss, params, opt_state = compiled(params, opt_state, data, key)
        hybrid.append(float(loss))
    for name, tree in (("parameters", params),
                       ("optimizer state", opt_state)):
        n = len(distinct_devices(tree))
        need(n == 4, f"{name} have shards on {n} devices, not 4")
    del params, opt_state, compiled
    gc.collect()

    optimizer, _amp, step, params, opt_state = fresh()
    one = jax.jit(step, donate_argnums=(0, 1))
    single = []
    with mesh_guard(None):  # fleet left its mesh current: one device here
        for _ in range(3):
            loss, params, opt_state = one(params, opt_state, data, key)
            single.append(float(loss))
    need(len(distinct_devices(params)) == 1,
         "the one-device comparison step ran on more than one device")
    del params, opt_state
    gc.collect()
    log(f"[4chip/train] losses dp2 x mp2 {hybrid} vs one device {single}")
    need(np.isfinite(hybrid).all()
         and np.allclose(hybrid, single, rtol=HYBRID_LOSS_RTOL),
         f"hybrid losses {hybrid} differ from one-device {single} beyond "
         f"rtol {HYBRID_LOSS_RTOL}")
    log(f"[4chip/train] parameters and optimizer state on 4 distinct "
        f"devices; losses agree within rtol {HYBRID_LOSS_RTOL}")


def phase_tp_serve(cfg, seed, sizes, on_tpu):
    from paddle_tpu.serving_dist import ShardedEngineConfig

    model, requests, check, kw = serving_setup(cfg, seed, sizes, on_tpu)
    base = serve(model, requests, "4chip/serve/one-device", **kw)
    check(base, "4chip/serve/one-device")
    for label, shard in (("4chip/serve/tp4", ShardedEngineConfig(tp=4)),
                         ("4chip/serve/tp2xdp2",
                          ShardedEngineConfig(tp=2, dp=2))):
        out = serve(model, requests, label, sharding=shard,
                    pool_devices=4, **kw)
        check(out, label)
        same = sum(int((a == b).all()) for a, b in zip(out, base))
        # token agreement with the one-device engine "up to the margin":
        # both passed the same float32 logit check above, so a differing
        # token is a near-tie the two bf16 reductions resolved differently
        log(f"[{label}] {same}/{len(out)} requests token-identical to the "
            f"one-device engine; the rest differ inside the logit margin")


# ---- main -----------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on any backend; never prints ok")
    args = ap.parse_args(argv)

    # the fallbacks that once hid a dead kernel are errors here
    warnings.filterwarnings("error", message=".*fell back.*",
                            category=RuntimeWarning)
    root = os.path.dirname(os.path.abspath(__file__))
    need(os.path.isdir(os.path.join(root, "paddle_tpu")),
         "chip_smoke.py runs from the root of a paddle_tpu checkout")
    sys.path.insert(0, root)

    import jax

    from paddle_tpu.models.gpt2 import GPT2Config
    from paddle_tpu.utils import enable_persistent_compilation_cache

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    on_tpu = dev.platform == "tpu"
    if not args.rehearse:
        need(on_tpu, f"no TPU: JAX found {device}")
        need(len(devices) >= args.chips,
             f"--chips {args.chips} but JAX found {len(devices)} device(s)")
    cache_dir = enable_persistent_compilation_cache()
    # jax's persistent compile cache: compiles that asked it, and hits
    cache = {"compile_requests_use_cache": 0, "cache_hits": 0}

    def count_cache_event(event, **_):
        name = event.rsplit("/", 1)[-1]
        if name in cache:
            cache[name] += 1

    jax.monitoring.register_event_listener(count_cache_event)
    log(f"[device] {json.dumps(device)}; jax {jax.__version__}; compile "
        f"cache {cache_dir} ({len(os.listdir(cache_dir))} entries at start)")

    if args.rehearse:
        cfg = GPT2Config.tiny()
        batch, seq, steps = 4, 64, TRAIN_STEPS
        sizes = {"block_size": 8, "blocks": 64,
                 "requests": (12, 8, 48, 4, 8)}
    else:
        cfg = GPT2Config.medium()
        batch, seq, steps = TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS
        sizes = {"block_size": BLOCK_SIZE, "blocks": SERVE_BLOCKS,
                 "requests": (12, 64, 768, 32, 64)}
    cfg.dropout = 0.0

    t_start = time.perf_counter()
    if args.chips == 4:
        phase_hybrid_train(cfg, args.seed, batch, seq, on_tpu)
        phase_tp_serve(cfg, args.seed, sizes, on_tpu)
    else:
        phase_build()
        phase_eager(args.seed, dev.platform)
        phase_train(cfg, args.seed, batch, seq, steps, on_tpu)
        gc.collect()
        phase_serve(cfg, args.seed, sizes, on_tpu)
    log(f"[done] all phases passed in {time.perf_counter() - t_start:.0f}s; "
        f"persistent compile cache: {cache['cache_hits']} hits in "
        f"{cache['compile_requests_use_cache']} compile requests, now "
        f"{len(os.listdir(cache_dir))} entries")
    if args.rehearse:
        log("[rehearsal] not a chip run: no result line")
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        code = 1
    sys.exit(code)
