"""Benchmark: every BASELINE axis on one chip, machine-readably.

Default run measures each BASELINE config (gpt2s, bert_large, resnet50,
gpt2m, bert_base, ernie) plus decode (bf16 / W8A16 / int8-KV peak) under
a global time budget, printing ONE JSON line per axis as it lands:
{"metric", "value", "unit", "vs_baseline", "baseline"}; the final line
repeats the headline (gpt2s train) with a "parsed_all" list carrying all
records so the driver's single-parse capture records the full measured
state (VERDICT r4 next #3). `python bench.py <axis>` runs one axis.

vs_baseline for train axes = achieved MFU / 0.40 (A100-class reference
MFU target for transformer pretraining, SURVEY.md §6 — BASELINE.json
publishes no absolute numbers this round); "baseline" records that
denominator's provenance so the ratio can't be mistaken for a
driver-published bar. Decode axes report HBM-roofline utilization.
"""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

# priority order: headline first (guaranteed to land), then the two axes
# BASELINE.json names (BERT-large, ResNet-50), then decode (the serving
# story), then the remaining train configs — ernie last (architecturally
# a bert_large duplicate) so a budget squeeze drops the least news
AXES = ("gpt2s", "bert_large", "resnet50", "decode", "served", "gpt2m",
        "bert_base", "ernie")
_BUDGET_S = float(os.environ.get("PADDLE_TPU_BENCH_BUDGET_S", "520"))
_T0 = time.time()


def _remaining():
    return _BUDGET_S - (time.time() - _T0)


# Peaks of ONE chip, keyed by `jax.devices()[0].device_kind`.  A kind that is
# not here is an error, never a default.  Source for "TPU v5 lite": Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).
# "cpu" is the CPU smoke mode's placeholder: its records are marked
# `degraded` and carry vs_baseline 0, so the value is never a roofline.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "cpu": {"bf16_flops": 1e12, "hbm_bytes_per_s": 50e9},
}


def _device_peaks():
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"bench.py: no peaks recorded for device_kind {kind!r}; add it "
            f"to DEVICE_PEAKS with its source (known: "
            f"{sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]


def _bench_train(model_name, on_tpu):
    """Measure one training axis; returns its record dict."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import optimizer as opt_mod

    if model_name == "resnet50":
        # BASELINE.json's first axis is "samples/sec/chip ... ResNet-50";
        # conv FLOPs counted from XLA's cost model below (6N is
        # meaningless for convs)
        from paddle_tpu.vision.models import resnet50
        from paddle_tpu import ops as P_ops
        from paddle_tpu.core.tensor import Tensor as PTensor
        img = 224 if on_tpu else 32
        batch_candidates, seq = ((256, 128, 64) if on_tpu else (4,)), img
        inner = 30 if on_tpu else 2
        nhwc = os.environ.get("PADDLE_TPU_RESNET_NHWC") == "1"
        if nhwc:  # r5 lever A/B: channels on the lane dim
            from paddle_tpu.vision.models.resnet import (BottleneckBlock,
                                                         ResNet)
            model = ResNet(BottleneckBlock, 50, num_classes=1000,
                           data_format="NHWC")
        else:
            model = resnet50(num_classes=1000)
        model.train()

        def init_params():
            p, _ = model.functional_state()
            return p

        _, _buffers = model.functional_state()

        def loss_fn(params, batch_data, key):
            saved_p, saved_b = model.functional_state()
            model.load_functional_state(params, _buffers)
            try:
                logits = model(PTensor(batch_data["images"]))
                loss = P_ops.cross_entropy(logits, batch_data["labels"])
                return loss._value if hasattr(loss, "_value") else loss
            finally:
                model.load_functional_state(saved_p, saved_b)

        cfg = None
        metric_name = "resnet50_train_samples_per_sec_per_chip"
    elif model_name in ("bert_large", "bert_base", "ernie"):
        from paddle_tpu.models.bert import (BertConfig, ErnieConfig,
                                            build_train_step)
        if on_tpu:
            if model_name == "bert_large":
                cfg, batch_candidates = BertConfig.large(), (16, 8, 4)
            elif model_name == "bert_base":
                cfg, batch_candidates = BertConfig.base(), (32, 16, 8)
            else:
                cfg, batch_candidates = ErnieConfig.large(), (16, 8, 4)
            seq, inner = 512, 30
        else:
            cfg = BertConfig.tiny()
            batch_candidates, seq = (4,), 128
            inner = 3
        metric_name = f"{model_name}_train_tokens_per_sec_per_chip"
    elif model_name == "gpt2m":
        # BASELINE.json's GPT-2 config is MEDIUM ("GPT-2 medium with
        # fused_attention_op -> Pallas flash-attn"); single-chip train
        from paddle_tpu.models.gpt2 import GPT2Config, build_train_step
        if on_tpu:
            cfg = GPT2Config.medium()  # 355M params
            batch_candidates, seq = (8, 4), 1024
            inner = 20
        else:
            cfg = GPT2Config.tiny()
            batch_candidates, seq = (4,), 128
            inner = 3
        metric_name = "gpt2m_train_tokens_per_sec_per_chip"
    else:
        from paddle_tpu.models.gpt2 import GPT2Config, build_train_step
        if on_tpu:
            cfg = GPT2Config()  # GPT-2 small, 124M params
            # measured (scripts/perf_sweep.py --section model, r3): tok/s
            # peaks at batch 16 (90.9k) and REGRESSES at 24 (86.6k) — bigger
            # per-chip batch stops paying once the GEMMs saturate; order the
            # candidates by measured throughput, not size
            batch_candidates, seq = (16, 8), 1024
            inner = 30  # steps per dispatch (lax.scan)
        else:  # CI/smoke fallback
            cfg = GPT2Config.tiny()
            batch_candidates, seq = (4,), 128
            inner = 3
        metric_name = "gpt2s_train_tokens_per_sec_per_chip"
    if os.environ.get("PADDLE_TPU_BENCH_BATCHES"):
        batch_candidates = tuple(
            int(b) for b in
            os.environ["PADDLE_TPU_BENCH_BATCHES"].split(","))
    if model_name != "resnet50":
        cfg.dropout = 0.0
        loss_fn, init_params, model = build_train_step(cfg, remat=False)
    params0 = init_params()
    n_params = sum(int(np.prod(v.shape)) for v in params0.values())

    optimizer = opt_mod.AdamW(learning_rate=1e-4, weight_decay=0.01)

    # Mixed precision (the reference's AMP headline config): f32 master
    # params, forward/backward in bf16 on the MXU, f32 optimizer update.
    def _to_bf16(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(jnp.bfloat16)
        return x

    def amp_loss(p32, batch_data, key):
        pb = jax.tree_util.tree_map(_to_bf16, p32)
        return loss_fn(pb, batch_data, key).astype(jnp.float32)

    rng = np.random.RandomState(0)
    key = jax.random.key(0)

    def make_data(batch):
        if model_name == "resnet50":
            img_shape = (batch, seq, seq, 3) if nhwc else (batch, 3, seq,
                                                           seq)
            return {
                # bf16 images: a f32 image against bf16 conv weights would
                # promote the whole conv to f32 (quarter MXU rate)
                "images": jnp.asarray(rng.rand(
                    *img_shape).astype(np.float32)).astype(jnp.bfloat16),
                "labels": jnp.asarray(rng.randint(
                    0, 1000, (batch,)).astype(np.int32)),
            }
        return {
            "input_ids": jnp.asarray(rng.randint(
                0, cfg.vocab_size, (batch, seq)).astype(np.int32)),
            "labels": jnp.asarray(rng.randint(
                0, cfg.vocab_size, (batch, seq)).astype(np.int32)),
        }

    def run_config(batch):
        """Time `inner` train steps inside ONE jitted lax.scan dispatch:
        a production train loop amortizes dispatch, so device throughput
        is what this bench reports. (Fetching the loss scalar via
        device_get is the completion barrier.)"""
        data = make_data(batch)

        def step(carry, i):
            p, s = carry
            loss, grads = jax.value_and_grad(amp_loss)(
                p, data, jax.random.fold_in(key, i))
            np_, ns = optimizer.functional_update(p, grads, s)
            return (np_, ns), loss

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_n(p, s):
            (p, s), losses = jax.lax.scan(step, (p, s),
                                          jnp.arange(inner))
            return p, s, losses[-1]

        params = init_params()
        opt_state = optimizer.functional_init(params)
        params, opt_state, loss = train_n(params, opt_state)  # compile+warm
        float(jax.device_get(loss))
        dt = float("inf")
        for _ in range(2):  # best-of-2
            t0 = time.perf_counter()
            params, opt_state, loss = train_n(params, opt_state)
            float(jax.device_get(loss))
            dt = min(dt, (time.perf_counter() - t0) / inner)
        return dt, float(loss)

    batch = dt = loss = None
    for cand in batch_candidates:
        try:
            dt, loss = run_config(cand)
            batch = cand
            break
        except Exception as e:  # noqa: BLE001 — OOM etc.: try smaller batch
            msg = str(e)[:140].replace("\n", " ")
            print(f"# bench: batch={cand} failed ({msg}); trying smaller",
                  file=sys.stderr)
    if batch is None:
        raise RuntimeError("no batch candidate ran")

    peak = _device_peaks()["bf16_flops"]
    if model_name == "resnet50":
        units_per_step, unit = batch, "samples/s"
        # conv nets have no 6N rule — take fwd+bwd FLOPs from XLA's own
        # cost model for the exact compiled computation (TPU only: the
        # extra .lower().compile() is a full second compile, pointless on
        # the CPU-degraded path where vs_baseline is 0 anyway)
        flops_per_unit = 3 * 4.1e9  # ResNet-50 @224²: ~4.1 GFLOP fwd
        if on_tpu:
            try:
                ca = jax.jit(lambda p, d: jax.value_and_grad(amp_loss)(
                    p, d, key)).lower(
                        params0, make_data(batch)).compile().cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0]
                flops_per_unit = float(ca["flops"]) / batch
            except Exception:
                pass  # keep the analytic estimate
        mfu_attn = None
    else:
        units_per_step, unit = batch * seq, "tokens/s"
        flops_per_unit = 6 * n_params  # fwd+bwd transformer rule of thumb
    units_per_sec = units_per_step / dt
    mfu = units_per_sec * flops_per_unit / peak
    if model_name != "resnet50":
        # attention-inclusive accounting (PaLM appendix): 12*L*S*d_model
        # per token fwd+bwd, /2 only for causal models (GPT); BERT is
        # bidirectional — reported for honesty, the headline mfu keeps the
        # 6N convention for round-over-round comparison
        causal_discount = 0.5 if model_name.startswith("gpt2") else 1.0
        attn_ft = 12 * cfg.num_layers * seq * cfg.hidden_size \
            * causal_discount
        mfu_attn = units_per_sec * (flops_per_unit + attn_ft) / peak

    record = {
        "metric": metric_name if on_tpu
        else f"{model_name}_tiny_train_CPU_DEGRADED",
        "value": round(units_per_sec, 1),
        "unit": unit,
        "vs_baseline": round(mfu / 0.40, 4) if on_tpu else 0.0,
        # provenance: BASELINE.json `published` is empty, so the
        # denominator is the builder's own 0.40-MFU A100-class stand-in —
        # vs_baseline is "fraction of that self-set bar", not of a
        # driver-published number
        "baseline": ("self-set 0.40 MFU stand-in" if on_tpu
                     else "n/a (CPU_DEGRADED)"),
        "mfu": round(mfu, 4),
    }
    if not on_tpu:
        record["degraded"] = True  # TPU probe failed; see stderr probe log
    print(f"# [{model_name}] loss={float(loss):.4f} "
          f"params={n_params/1e6:.1f}M mfu={mfu:.3f}"
          + (f" mfu_attn_incl={mfu_attn:.3f}" if mfu_attn is not None else "")
          + f" step={dt*1000:.1f}ms batch={batch}"
          + f" backend={jax.default_backend()}", file=sys.stderr)
    return record


def _bench_decode(on_tpu):
    """Serving-side decode: bf16, W8A16 and the int8-KV peak config, each
    as its own record (the r4 bench only printed W8/peak to stderr;
    VERDICT r4 missing #4). Returns the record list."""
    import jax

    from paddle_tpu.models.gpt2 import GPT2, GPT2Config

    if on_tpu:
        cfg, batch, prompt, new = GPT2Config(), 8, 64, 192
    else:
        cfg, batch, prompt, new = GPT2Config.tiny(), 2, 8, 16
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    if on_tpu:
        model.to(dtype="bfloat16")  # serving precision: halves the
        # per-token parameter stream (decode is HBM-bound)
    n_params = sum(int(np.prod(p.shape))
                   for p in model.functional_state()[0].values())
    rng = np.random.RandomState(0)
    bw = _device_peaks()["hbm_bytes_per_s"]

    def _one(ids, n_new, **kw):
        model.generate(ids, n_new, **kw).numpy()  # compile + barrier
        dt = float("inf")
        # best-of-4: the differencing subtracts two minima, so each must
        # actually reach its minimum — best-of-2 left the b8 W8A16 point
        # anywhere in a 2x band (PERF.md, round-5 decode numbers)
        for _ in range(4):
            t0 = time.perf_counter()
            model.generate(ids, n_new, **kw).numpy()
            dt = min(dt, time.perf_counter() - t0)
        return dt

    def timed(ids, n_new, **kw):
        """Per-token-step decode time by DIFFERENCING two lengths: one
        generate() is one dispatch, and (T_full - T_short)/(n_new -
        short) cancels the per-dispatch cost AND the prefill exactly.
        Returns the synthetic full-decode time (seconds) for n_new
        tokens."""
        short = min(max(4, n_new // 3), n_new - 4)
        if short <= 0:  # tiny CPU-smoke decode: differencing has no room
            return _one(ids, n_new, **kw)
        t_full = _one(ids, n_new, **kw)
        t_short = _one(ids, short, **kw)
        if t_full <= t_short:
            # timer noise beat the signal: the raw single measurement is
            # the fallback — SAY so, it still contains the dispatch +
            # prefill the differencing exists to remove
            print(f"# decode timing fell back to a raw (prefill-"
                  f"contaminated) measurement for n_new={n_new} "
                  f"(t_full {t_full*1e3:.1f}ms <= t_short "
                  f"{t_short*1e3:.1f}ms)", file=sys.stderr)
            return t_full
        return (t_full - t_short) / (n_new - short) * n_new

    def hbm_util(dt, n_new, bytes_per_param):
        # decode is HBM-bound: each token-STEP streams all params once ->
        # the roofline is bandwidth, not FLOPs; utilization is
        # (steps/sec) * bytes-per-step / bandwidth, batch-independent
        return (n_new / dt) * n_params * bytes_per_param / bw

    records = []
    ids = rng.randint(0, cfg.vocab_size, (batch, prompt)).astype(np.int32)
    dt = timed(ids, new)
    toks = batch * new
    tok_s = toks / dt
    util = hbm_util(dt, new, 2 if on_tpu else 4)
    rec = {
        "metric": ("gpt2s_decode_tokens_per_sec_per_chip" if on_tpu
                   else "gpt2s_tiny_decode_CPU_DEGRADED"),
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(util, 4) if on_tpu else 0.0,
        "baseline": ("v5e 819GB/s HBM roofline (decode is "
                     "bandwidth-bound)" if on_tpu
                     else "n/a (CPU_DEGRADED)"),
    }
    if not on_tpu:
        rec["degraded"] = True
    records.append(rec)
    print(json.dumps(rec))
    print(f"# decode batch={batch} prompt={prompt} new={new} "
          f"step={dt/new*1000:.2f}ms/token params={n_params/1e6:.1f}M "
          f"hbm_util~{util:.3f} "
          f"backend={jax.default_backend()}", file=sys.stderr)
    if not on_tpu:
        return records

    # weight-only int8 (W8A16): the serving-side lever
    dt8 = timed(ids, new, weight_quant="int8")
    util8 = hbm_util(dt8, new, 1)
    rec8 = {
        "metric": "gpt2s_decode_w8a16_tokens_per_sec_per_chip",
        "value": round(toks / dt8, 1),
        "unit": "tokens/s",
        "vs_baseline": round(util8, 4),
        "baseline": "v5e 819GB/s HBM roofline (int8 weight stream)",
    }
    records.append(rec8)
    print(json.dumps(rec8))
    print(f"# w8a16 decode: {toks/dt8:,.0f} tok/s "
          f"({dt8/new*1e3:.2f} ms/token-step, "
          f"{dt/dt8:.2f}x vs bf16 at this batch)", file=sys.stderr)

    # peak-throughput config: int8 KV + int8 weights at batch 40
    # (PERF.md r4: 28.1k tok/s; batch 32 fallback if 40 OOMs)
    for bpeak in (40, 32):
        try:
            idsp = rng.randint(0, cfg.vocab_size,
                               (bpeak, prompt)).astype(np.int32)
            dtp = timed(idsp, new, weight_quant="int8",
                        kv_quant="int8")
            utilp = hbm_util(dtp, new, 1)
            recp = {
                "metric": "gpt2s_decode_peak_w8_kv8_tokens_per_sec_per_chip",
                "value": round(bpeak * new / dtp, 1),
                "unit": "tokens/s",
                "vs_baseline": round(utilp, 4),
                "baseline": "v5e 819GB/s HBM roofline (int8 streams)",
                "batch": bpeak,
            }
            records.append(recp)
            print(json.dumps(recp))
            print(f"# kv8+w8 batch={bpeak} decode: "
                  f"{bpeak*new/dtp:,.0f} tok/s "
                  f"({dtp/new*1e3:.2f} ms/token-step) — peak config",
                  file=sys.stderr)
            break
        except Exception as e:  # noqa: BLE001
            print(f"# bench decode peak batch={bpeak} failed: "
                  f"{str(e)[:120]}", file=sys.stderr)
    return records


def _bench_served(on_tpu, telemetry=False, tiny=False,
                  timeline=False):
    """Served mixed-length traffic: the SAME uniform(64..1024-class)
    prompt pool driven through (a) the padded static-batch
    GenerationServer — every request padded to the global prompt_len, a
    slot held for the full max_new — and (b) the continuous-batching
    PagedGenerationServer over the block-pool KV cache. Reports tok/s
    and p99 for both; the paged record's vs_baseline is its speedup over
    the padded server on this traffic. Closed-loop drain: all requests
    submitted upfront, wall clock measured to completion (each pass runs
    once unmeasured to compile, then reset_stats + a measured pass).

    A third record is the OPEN-LOOP axis (ISSUE 3): the same warm paged
    server driven at fixed-seed Poisson arrivals (~70% of the
    closed-loop request rate), measuring steady-state admission CHURN —
    requests arriving while others decode, which is where prefill
    stalls live; it carries itl_p99_ms and prefill_dispatches, the two
    numbers the packed/chunked prefill scheduler exists to move.

    telemetry=True (`bench.py served --telemetry`, ISSUE 2): after the
    baseline paged pass, interleaved off/on measured passes run on the
    SAME warm server (_served_telemetry_pass) — a Prometheus-text
    metrics snapshot (TELEMETRY_metrics.prom), the span JSONL
    (TELEMETRY_trace.jsonl), and the assembled per-request phase report
    (TELEMETRY_request_traces.json) land in the gitignored telemetry/
    directory (ISSUE 14 satellite; PADDLE_TPU_TELEMETRY_DIR
    overrides), and the extra record carries the measured overhead vs.
    the telemetry-off passes (acceptance bar: <= 5% with the full
    stack — ops plane + trace contexts + SLO engine). timeline=True
    (`--timeline`, implies telemetry) additionally exports the
    Chrome/Perfetto timeline (TELEMETRY_timeline.json).

    A fourth record is the SHARED-PREFIX axis (round 9): a
    system-prompt workload (one shared prefix + short unique tails)
    driven at identical fixed-seed Poisson arrivals with prefix
    caching OFF then ON on the same warm paged server — TTFT is the
    headline, and the record carries hit-rate / CoW / eviction /
    retained-block stats from the content-addressed pool.

    An eighth record is the QUANTIZATION axis (quantized-serving
    round): identical fixed-seed Poisson arrivals through bf16 /
    W8A16 / W8A16+int8-KV servers — served tok/s, TTFT/ITL, greedy
    token match + logit probe vs bf16, and the slot capacity each kv
    dtype backs at the bf16 pool's byte budget (the CPU-provable
    >= 1.8x bar; tok/s is a chip number, CPU has no int8 MXU).

    A ninth record is the SHARDED axis (serving_dist round): the same
    pinned composed workload served on 1/2/4/8-device forced-host
    meshes (tiny: 1/2), one subprocess per count — token parity across
    mesh sizes asserted, plus max concurrent slots at FIXED per-device
    pool bytes (the >= 3x-at-4-devices acceptance bar; tok/s scaling
    is a chip number, host-mesh collectives run on CPU cores).

    An eleventh record is the DEGRADED-MODE axis (r17): identical
    fixed-seed Poisson arrivals at 0% vs an injected fixed-seed
    FaultPlan rate — tok/s retention under the recovery ladder, the
    recovery/quarantine counts, goodput under replay, and the
    survivor token-parity proof.

    tiny=True (`bench.py served --tiny`): seconds-scale smoke config
    that skips the padded comparison and telemetry — it exists so
    tier-1 can assert the served/open-loop/shared-prefix record SCHEMA
    (the prefill_dispatches/itl_p99_ms/prefix_hit_rate fields) without
    paying the full CPU-degraded sweep."""
    from paddle_tpu.inference import (GenerationServer,
                                      PagedGenerationServer,
                                      measure_poisson_load)
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config

    if tiny:
        cfg = GPT2Config.tiny()
        n_req, new, slots, bs, k = 6, 4, 2, 4, 2
        lo, hi, chunk = 4, 24, 16
    elif on_tpu:
        cfg = GPT2Config()
        n_req, new, slots, bs, k = 32, 64, 8, 128, 8
        lo, hi = 64, 768  # hi + new + k-1 must stay under max_position
        chunk = 512
    else:
        # mid-size CPU proxy: big enough that compute dominates dispatch
        # (the regime the chip is always in) — at tiny scale the per-
        # request prefill dispatches drown the padding waste the paged
        # server exists to remove
        cfg = GPT2Config(vocab_size=4096, hidden_size=256, num_layers=4,
                         num_heads=8, max_position=512)
        n_req, new, slots, bs, k = 16, 16, 4, 16, 8
        lo, hi = 32, 384
        chunk = 96
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    if on_tpu:
        model.to(dtype="bfloat16")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (int(rng.randint(lo, hi + 1)),)).astype(np.int32)
               for _ in range(n_req)]

    def drain(server):
        for f in [server.submit(p) for p in prompts]:  # warm/compile pass
            f.result(timeout=900)
        server.reset_stats()
        for f in [server.submit(p) for p in prompts]:  # measured pass
            f.result(timeout=900)
        return server.stats()

    # (a) padded static batcher over the in-process dense-cache decode
    # (skipped in tiny mode: the smoke asserts schema, not the speedup)
    st_pad = None
    if not tiny:
        def prog(ids, seed, temp, eos, top_p, pad):
            return model.generate(
                ids, new, temperature=float(temp), seed=int(seed),
                eos_token_id=None if int(eos) < 0 else int(eos),
                top_p=float(top_p),
                pad_token_id=None if int(pad) < 0 else int(pad)).numpy()

        srv = GenerationServer(prog, batch_size=slots, prompt_len=hi,
                               pad_token_id=0, max_wait_ms=5.0).start()
        try:
            st_pad = drain(srv)
        finally:
            srv.stop()
    # (b) continuous batching over the paged KV cache. With
    # --telemetry the server carries the FULL ops plane (ephemeral
    # /metrics endpoint + stall watchdog + flight recorder) so the
    # telemetry pass measures the whole enabled stack; the ctor
    # enables the metrics registry, so switch it back off until the
    # interleaved on/off passes of _served_telemetry_pass
    # full measured stack: ops plane + the SLO burn-rate engine
    # (ISSUE 14) — the overhead bar covers both
    ops_kw = ({"expose_port": 0, "slos": True}
              if telemetry and not tiny else {})
    psrv = PagedGenerationServer(model, max_slots=slots, block_size=bs,
                                 max_prompt_len=hi, max_new_tokens=new,
                                 steps_per_dispatch=k,
                                 prefill_chunk_tokens=chunk,
                                 attribution=True,  # ISSUE 17: the
                                 # record proves the cost ledger's
                                 # conservation on the measured window
                                 **ops_kw).start()
    if ops_kw:
        from paddle_tpu import observability as _obs
        _obs.disable()
        psrv._recorder.disable()
    rec_tel = None
    try:
        st_paged = drain(psrv)

        # (b2) mixed-sampling axis (round 10): the SAME prompt pool,
        # 50% greedy / 50% sampled (varied top-p, fixed per-request
        # seeds), closed-loop drain on the same warm server — the
        # tok/s delta vs the all-greedy pass (b) is the vectorized
        # sampling pipeline's per-step overhead (every decode dispatch
        # leaves the argmax fast path once one sampled slot is
        # resident).
        from paddle_tpu.sampling import SamplingParams

        def mix_sp(i):
            if i % 2 == 0:
                return None  # greedy
            return SamplingParams(temperature=0.8,
                                  top_p=(0.7, 0.85, 0.95)[(i // 2) % 3],
                                  seed=1000 + i)

        def drain_mixed(server):
            for f in [server.submit(p, sampling=mix_sp(i))  # warm pass:
                      for i, p in enumerate(prompts)]:  # compiles the
                f.result(timeout=900)                  # sampled variants
            server.reset_stats()
            for f in [server.submit(p, sampling=mix_sp(i))
                      for i, p in enumerate(prompts)]:
                f.result(timeout=900)
            return server.stats()

        st_mix = drain_mixed(psrv)
        if telemetry and not tiny:
            rec_tel = _served_telemetry_pass(psrv, prompts, on_tpu,
                                             timeline=timeline)
        # (c) open-loop Poisson churn on the same warm server, offered
        # at ~70% of the closed-loop request rate (fixed arrival seed)
        rps = 0.7 * st_paged["requests"] / max(st_paged["wall_s"], 1e-9)
        psrv.reset_stats()
        st_open = measure_poisson_load(psrv, prompts, rps, n_req,
                                       seed=1234, timeout=900)
        # (d) chunking lever isolated: SAME arrivals, chunk budget =
        # whole prompt (still packed, no chunk/decode interleaving) —
        # the ITL-p99 delta vs (c) is what chunked prefill buys under
        # churn. One unmeasured pass first: the wider packed buckets
        # compile here, not inside the measured window.
        psrv.prefill_chunk_tokens = hi
        measure_poisson_load(psrv, prompts, rps, n_req, seed=1234,
                             timeout=900)
        psrv.reset_stats()
        st_unchunked = measure_poisson_load(psrv, prompts, rps, n_req,
                                            seed=1234, timeout=900)
        # (e) shared-prefix axis (round 9): a system-prompt workload —
        # every prompt is ONE shared prefix + a short unique tail —
        # driven at IDENTICAL fixed-seed Poisson arrivals with prefix
        # caching OFF then ON on the same warm server. Warm passes are
        # unmeasured (compile + seed the content index); the measured
        # pool uses fresh tails, so cache-ON hits are the shared prefix
        # blocks only, not whole-prompt resubmission.
        psrv.prefill_chunk_tokens = chunk
        if tiny:
            sp_len, tlo, thi = 16, 2, 6
        elif on_tpu:
            sp_len, tlo, thi = 512, 32, 96
        else:
            sp_len, tlo, thi = 256, 16, 48
        sp_new = min(new, 4)  # TTFT axis: keep decode short
        sp_prefix = rng.randint(1, cfg.vocab_size,
                                (sp_len,)).astype(np.int32)

        def sp_pool(salt):
            r2 = np.random.RandomState(salt)
            return [np.concatenate([sp_prefix, r2.randint(
                1, cfg.vocab_size, (int(r2.randint(tlo, thi + 1)),))
                .astype(np.int32)]) for _ in range(n_req)]

        warm_pool, warm2_pool, meas_pool = (sp_pool(21), sp_pool(23),
                                            sp_pool(22))

        def sp_warm(pool):
            for f in [psrv.submit(p, max_new_tokens=sp_new)
                      for p in pool]:
                f.result(timeout=900)

        def sp_drive(pool):
            return measure_poisson_load(psrv, pool, sp_rps, n_req,
                                        seed=4321, timeout=900,
                                        max_new_tokens=sp_new)

        psrv.enable_prefix_cache = False
        t_w0 = time.time()
        sp_warm(warm_pool)
        # offer BOTH measured passes at ~30% of the UNCACHED closed-loop
        # drain rate (closed-loop overestimates open-loop capacity —
        # Poisson arrivals rarely fill every slot): TTFT then reflects
        # prefill latency + mild queueing, not deep queue saturation
        # (which would measure the backlog, not the prefix cache). Same
        # rate + fixed seed = identical arrivals for the off/on pair.
        sp_rps = 0.3 * n_req / max(time.time() - t_w0, 1e-6)
        # unmeasured Poisson warm on a separate fresh-tail pool: churn
        # packs DIFFERENT (T, rows, width) prefill buckets than the
        # closed-loop drain, and those compiles must not land in the
        # measured window
        sp_drive(warm2_pool)
        psrv.reset_stats()
        st_sp_off = sp_drive(meas_pool)
        psrv.enable_prefix_cache = True
        sp_warm(warm_pool)   # seeds the content index with the prefix
        sp_drive(warm2_pool)  # compiles the cache-hit churn buckets
        psrv.reset_stats()
        pc0 = psrv.cache.stats()["prefix_cache"]
        st_sp_on = sp_drive(meas_pool)
        kv_sp = psrv.cache.stats()
        pc1 = kv_sp["prefix_cache"]
    finally:
        psrv.stop()

    # (f) SPECULATION axis (round 11): a repetitive/agentic traffic
    # mix — prompts whose greedy continuations the self-drafting
    # n-gram drafter can actually predict — drained closed-loop on a
    # plain server and on a speculation-enabled server (same config,
    # steps_per_dispatch=1 both). The record's vs_baseline is the
    # served tok/s ratio; it also carries the acceptance accounting
    # and the ORACLE ceiling (a replay drafter with acceptance 1.0 —
    # the verification engine's amortization limit, independent of
    # drafter quality). Off TPU this axis runs on the tiny config:
    # speculation amortizes the per-dispatch floor (the chip's decode
    # regime — decode is bandwidth/dispatch-bound, PERF.md), and the
    # compute-bound hs256 CPU proxy would measure XLA matmul width
    # instead of the dispatch amortization it exists to show.
    st_spec = _bench_served_speculation(model, cfg, on_tpu, tiny)

    # (g) FRONT DOOR axis (round 12): adversarial open-loop mix —
    # a long-prompt bully burst + bursty-Poisson interactive arrivals
    # from two tenants at IDENTICAL fixed-seed schedules through the
    # single-lane FIFO engine and through the front door (lanes +
    # deadlines + preemption). Interactive TTFT measured client-side
    # the same way in both runs.
    st_fd = _bench_served_frontdoor(model, cfg, on_tpu, tiny)

    # (h) QUANTIZATION axis (quantized-serving round): identical
    # fixed-seed Poisson arrivals through bf16 / W8A16 / W8A16+int8-KV
    # servers — tok/s + TTFT/ITL + accuracy delta, plus the slot
    # capacity each kv dtype backs at the bf16 pool's byte budget (the
    # CPU-provable bar: no int8 MXU off-chip, so the tok/s headline is
    # a chip number).
    st_qz = _bench_served_quantization(model, cfg, prompts, slots, bs,
                                       hi, new, k, chunk, on_tpu, tiny)

    # (i) SHARDED axis (serving_dist round): the tensor-parallel paged
    # engine at 1/2/4/8 forced-host devices — subprocesses, because the
    # device count must be fixed before jax initializes. Token parity
    # across counts is asserted by the record's token_parity field.
    st_sh = _bench_served_sharded(on_tpu, tiny)

    # (i2) QUANTIZED-COLLECTIVES axis (13th record): identical
    # fixed-seed Poisson arrivals through the composed sharded stack
    # at tp∈{1,2,4} forced-host devices, bf16 vs int8 vs int4-group
    # collective wires — analytic per-device wire bytes (actual vs
    # the unquantized baseline for the SAME dispatches), greedy-token
    # parity, dispatches-per-round and the compile-window proof.
    st_cq = _bench_served_collectives(on_tpu, tiny)

    # (j) UNIFIED-ROUND axis (r16): the whole scheduler round fused
    # into ONE attention dispatch + the async double-buffered loop,
    # vs the split engine at IDENTICAL fixed-seed open-loop Poisson
    # arrivals (both sides bucket-warmed; the record carries
    # dispatches-per-round, overlap fraction and the compile-window
    # proof).
    st_un = _bench_served_unified(model, cfg, on_tpu, tiny)

    # (k) DEGRADED-MODE axis (r17): identical fixed-seed Poisson
    # arrivals at 0% vs an injected fixed-seed fault rate — the
    # recovery ladder's tok/s retention, recovery/quarantine counts,
    # goodput under replay, and the survivor token-parity proof.
    st_dg = _bench_served_degraded(model, cfg, on_tpu, tiny)

    # (l) FLEET axis (r18): IDENTICAL fixed-seed Poisson arrivals
    # through 1/2/4-replica fleets with one forced mid-run replica
    # kill (the replica_kill seam) and one planned live migration —
    # aggregate tok/s, p99 TTFT, failover/migration counts, and the
    # survivor token-parity md5 proof across replica counts.
    st_fl = _bench_served_fleet(model, cfg, on_tpu, tiny)

    # (m) LONG-CONTEXT axis (r21): fixed-seed huge prompts through the
    # sequence-parallel packed prefill at sp∈{1,2,4} forced-host
    # devices (tiny: 1/2) — subprocesses, because the device count must
    # precede jax init. Reports prefill TTFT scaling with sp (the
    # dispatch division is the structural/exact half; the wall-clock
    # ratio is a chip number on the shared-core host mesh) plus the
    # host-RAM KV tier's long-context session capacity: resumable
    # sessions per device at the no-recompute ITL bar and FIXED pool
    # bytes, tier ON vs OFF, with the churn mechanism proven
    # empirically (demotion/promotion counts + resume parity).
    st_lc = _bench_served_longctx(on_tpu, tiny)

    # (n) FLEET-PROCS axis (r19): the fleet at REAL OS-process
    # granularity — subprocess worker replicas behind the stdlib
    # HTTP wire transport at 1/2/4 processes (tiny: 1/2), identical
    # fixed-seed arrivals through the composed stack (prefix cache +
    # speculation + int8 KV wire), md5 parity vs an in-process twin
    # fleet, plus a prefill-heavy burst A/B through a disaggregated
    # 1-prefill + 1-decode pool vs the same two workers pooled.
    st_fp = _bench_served_fleet_procs(on_tpu, tiny)

    # (o) ELASTIC axis (ISSUE 20): a fixed-seed diurnal + flash-crowd
    # trace through static fleets of every candidate size vs an
    # autoscaled fleet (queue-pressure policy, warm-gated scale-up,
    # drain-migrate-retire scale-down) — p99 TTFT vs the declared SLO,
    # replica-seconds for each, the md5 token-parity proof across
    # every scale/migration event, and byte-identical decision-journal
    # replay from the recorded tick log.
    st_el = _bench_served_elastic(model, cfg, on_tpu, tiny)

    base = "gpt2tiny_served" if tiny else "gpt2s_served"
    suffix = "" if on_tpu else "_CPU_DEGRADED"
    rec_paged = {
        "metric": f"{base}_mixed_paged_tokens_per_sec{suffix}",
        "value": round(st_paged["tokens_per_sec"], 1),
        "unit": "tokens/s",
        "p99_ms": round(st_paged["p99_ms"], 1),
        "itl_p99_ms": round(st_paged["itl_p99_ms"], 2),
        "prefill_dispatches": st_paged["prefill_dispatches"],
        "slot_fill": round(st_paged["slot_fill"], 3),
        "kv_block_fill": round(st_paged["kv_block_fill"], 3),
        # ops plane (ISSUE 10): the measured window proves itself
        # compile-clean (or not) in the record instead of post-hoc,
        # and carries the decoded-vs-emitted goodput ratio
        "compiles_in_window": st_paged["compiles"]["window_total"],
        "compiles_in_flight_window":
            st_paged["compiles"]["window_in_flight"],
        "goodput_ratio": round(st_paged["goodput"]["goodput_ratio"],
                               4),
    }
    # attribution + capacity (ISSUE 17): the measured window's
    # per-tenant ledger (all traffic is tenant "default" here) plus
    # the conservation residuals — zero by construction — and one
    # fresh pressure snapshot. compare_bench.py treats the per-tenant
    # breakdowns as non-gating metadata.
    attr = st_paged["attribution"]
    cap = psrv.capacity_snapshot()
    rec_paged.update({
        "attribution_enabled": attr["enabled"],
        "tenant_device_s": {t: a["device_s"]
                            for t, a in attr["tenants"].items()},
        "tenant_kv_block_s": {t: a["kv_block_s"]
                              for t, a in attr["tenants"].items()},
        "tenant_requests": {t: a["requests"]
                            for t, a in attr["tenants"].items()},
        "attribution_device_residual_ns":
            attr["conservation"]["device_residual_ns"],
        "attribution_block_residual_ns":
            attr["conservation"]["block_residual_ns"],
        "capacity_schema_version": cap["schema_version"],
        "capacity_free_blocks": cap["pool"]["free_blocks"],
        "capacity_available_blocks": cap["pool"]["available_blocks"],
        "capacity_queue_depth": cap["queues"]["queue_depth"],
        "capacity_exhaustion_eta_s":
            cap["forecast"]["exhaustion_eta_s"],
    })
    rec_open = {
        "metric": f"{base}_openloop_paged_tokens_per_sec{suffix}",
        "value": round(st_open["tokens_per_sec"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(st_open["tokens_per_sec"]
                             / max(st_paged["tokens_per_sec"], 1e-9), 3),
        "baseline": "same paged server, closed-loop all-upfront drain",
        "p99_ms": round(st_open["p99_ms"], 1),
        "ttft_p99_ms": round(st_open["ttft_p99_ms"], 1),
        "itl_p50_ms": round(st_open["itl_p50_ms"], 2),
        "itl_p99_ms": round(st_open["itl_p99_ms"], 2),
        "prefills": st_open["prefills"],
        "prefill_dispatches": st_open["prefill_dispatches"],
        "offered_rps": round(st_open["offered_rps"], 3),
        "achieved_rps": round(st_open["achieved_rps"], 3),
        # same arrivals with chunking OFF (budget = whole prompt):
        # the chunk budget's ITL-vs-TTFT trade, measured
        "itl_p99_ms_unchunked": round(st_unchunked["itl_p99_ms"], 2),
        "ttft_p99_ms_unchunked": round(st_unchunked["ttft_p99_ms"], 1),
        "compiles_in_window": st_open["compiles"]["window_total"],
        "compiles_in_flight_window":
            st_open["compiles"]["window_in_flight"],
        "goodput_ratio": round(st_open["goodput"]["goodput_ratio"], 4),
    }
    rec_mix = {
        "metric": f"{base}_mixedsampling_paged_tokens_per_sec{suffix}",
        "value": round(st_mix["tokens_per_sec"], 1),
        "unit": "tokens/s",
        # <1 = the sampling pipeline costs that fraction of all-greedy
        # throughput at 50% sampled traffic
        "vs_baseline": round(st_mix["tokens_per_sec"]
                             / max(st_paged["tokens_per_sec"], 1e-9), 3),
        "baseline": "same prompts all-greedy on the same warm server",
        "sampling_overhead_pct": round(
            (st_paged["tokens_per_sec"]
             / max(st_mix["tokens_per_sec"], 1e-9) - 1) * 100, 2),
        "sampled_fraction": 0.5,
        "p99_ms": round(st_mix["p99_ms"], 1),
        "itl_p99_ms": round(st_mix["itl_p99_ms"], 2),
        "prefill_dispatches": st_mix["prefill_dispatches"],
        "sampled_dispatches": st_mix["sampling_sampled_dispatches"],
        "fast_path_dispatches": st_mix["sampling_fast_path_dispatches"],
        "stop_reasons": st_mix["stop_reasons"],
    }
    sp_lookup = max(pc1["lookup_tokens"] - pc0["lookup_tokens"], 1)
    rec_sp = {
        "metric": f"{base}_sharedprefix_cached_ttft_p50_ms{suffix}",
        "value": round(st_sp_on["ttft_p50_ms"], 2),
        "unit": "ms",
        # >1 = cached TTFT is that many times better at the SAME
        # fixed-seed Poisson arrivals
        "vs_baseline": round(st_sp_off["ttft_p50_ms"]
                             / max(st_sp_on["ttft_p50_ms"], 1e-9), 2),
        "baseline": "same arrivals/prompts, prefix caching off",
        "ttft_p50_ms_uncached": round(st_sp_off["ttft_p50_ms"], 2),
        "ttft_p99_ms": round(st_sp_on["ttft_p99_ms"], 2),
        "ttft_p99_ms_uncached": round(st_sp_off["ttft_p99_ms"], 2),
        "tokens_per_sec": round(st_sp_on["tokens_per_sec"], 1),
        "tokens_per_sec_uncached": round(st_sp_off["tokens_per_sec"], 1),
        "itl_p99_ms": round(st_sp_on["itl_p99_ms"], 2),
        "prefill_dispatches": st_sp_on["prefill_dispatches"],
        "prefill_dispatches_uncached": st_sp_off["prefill_dispatches"],
        "prefix_hit_rate": round(
            (pc1["hit_tokens"] - pc0["hit_tokens"]) / sp_lookup, 4),
        "prefix_hit_tokens": pc1["hit_tokens"] - pc0["hit_tokens"],
        "prefix_lookup_tokens": pc1["lookup_tokens"]
                                - pc0["lookup_tokens"],
        "prefix_evictions": pc1["evictions"] - pc0["evictions"],
        "prefix_cow_copies": pc1["cow_copies"] - pc0["cow_copies"],
        "retained_blocks": kv_sp["retained_blocks"],
        "peak_retained_blocks": kv_sp["peak_retained_blocks"],
        "shared_prefix_len": sp_len,
        "offered_rps": round(st_sp_on["offered_rps"], 3),
    }
    sp_plain, sp_on, sp_orc = (st_spec["plain"], st_spec["spec"],
                               st_spec["oracle"])
    spec_stats = sp_on["speculation"]
    rec_spec = {
        "metric": f"{base}_speculative_tokens_per_sec{suffix}",
        "value": round(sp_on["tokens_per_sec"], 1),
        "unit": "tokens/s",
        # the headline of the axis: served tok/s with the self-drafting
        # n-gram drafter vs plain decode on the same repetitive mix
        "vs_baseline": round(sp_on["tokens_per_sec"]
                             / max(sp_plain["tokens_per_sec"], 1e-9), 3),
        "baseline": "same repetitive mix + server config, "
                    "speculation off",
        "tokens_per_sec_plain": round(sp_plain["tokens_per_sec"], 1),
        "acceptance_rate": round(spec_stats["acceptance_rate"], 4),
        "proposed_tokens": spec_stats["proposed_tokens"],
        "accepted_tokens": spec_stats["accepted_tokens"],
        "rolled_back_tokens": spec_stats["rolled_back_tokens"],
        "verify_dispatches": spec_stats["verify_dispatches"],
        "decode_steps": sp_on["decode_steps"],
        "decode_steps_plain": sp_plain["decode_steps"],
        "max_draft_tokens": st_spec["K"],
        # acceptance-1.0 ceiling (replay oracle): what the packed
        # verification engine delivers when every draft is right —
        # separates engine amortization from drafter quality
        "tok_s_ratio_oracle": round(
            sp_orc["tokens_per_sec"]
            / max(sp_plain["tokens_per_sec"], 1e-9), 3),
        "acceptance_rate_oracle": round(
            sp_orc["speculation"]["acceptance_rate"], 4),
        "p99_ms": round(sp_on["p99_ms"], 1),
        "itl_p99_ms": round(sp_on["itl_p99_ms"], 2),
        "prefill_dispatches": sp_on["prefill_dispatches"],
    }
    qz_b, qz_w, qz_q = (st_qz["modes"]["bf16"], st_qz["modes"]["w8a16"],
                        st_qz["modes"]["w8a16_kv8"])
    rec_qz = {
        "metric": f"{base}_quantized_tokens_per_sec{suffix}",
        "value": round(qz_q["tokens_per_sec"], 1),
        "unit": "tokens/s",
        # >1 = W8A16+int8-KV serves that many times the bf16 tok/s at
        # IDENTICAL fixed-seed arrivals (chip bar: >= 1.3x; CPU runs
        # lack an int8 MXU, so the CPU-provable bar is
        # slot_capacity_ratio >= 1.8 below)
        "vs_baseline": round(qz_q["tokens_per_sec"]
                             / max(qz_b["tokens_per_sec"], 1e-9), 3),
        "baseline": "same arrivals/prompts, bf16 weights + bf16 KV",
        "tokens_per_sec_bf16": round(qz_b["tokens_per_sec"], 1),
        "tokens_per_sec_w8a16": round(qz_w["tokens_per_sec"], 1),
        "ttft_p50_ms": round(qz_q["ttft_p50_ms"], 2),
        "ttft_p50_ms_bf16": round(qz_b["ttft_p50_ms"], 2),
        "itl_p99_ms": round(qz_q["itl_p99_ms"], 2),
        "itl_p99_ms_bf16": round(qz_b["itl_p99_ms"], 2),
        "p99_ms": round(qz_q["p99_ms"], 1),
        "prefill_dispatches": qz_q["prefill_dispatches"],
        # capacity at FIXED pool bytes (the bf16 pool's budget): the
        # admission-reservation slot count each kv dtype backs
        "max_slots_at_fixed_bytes": st_qz["slots_int8"],
        "max_slots_at_fixed_bytes_bf16": st_qz["slots_bf16"],
        "slot_capacity_ratio": round(
            st_qz["slots_int8"] / max(st_qz["slots_bf16"], 1), 3),
        "pool_budget_bytes": st_qz["pool_budget_bytes"],
        "kv_bytes_per_token": round(qz_q["bytes_per_token"], 2),
        "kv_bytes_per_token_bf16": round(qz_b["bytes_per_token"], 2),
        "kv_scale_bytes": qz_q["quant"]["kv_scale_bytes"],
        # accuracy delta vs the bf16 outputs on this workload
        "greedy_token_match": round(qz_q["token_match"], 4),
        "greedy_token_match_w8a16": round(qz_w["token_match"], 4),
        "logit_mae": round(st_qz["logit_mae"], 6),
        "logit_max_abs": round(st_qz["logit_max_abs"], 5),
        "offered_rps": round(qz_q["offered_rps"], 3),
    }
    sh_counts = sorted(st_sh)
    sh_head = st_sh[4 if 4 in st_sh else max(st_sh)]  # acceptance point
    sh_one = st_sh[1]
    sh_sigs = {r["token_sig"] for r in st_sh.values()}
    rec_sh = {
        "metric": f"{base}_sharded_served_tokens_per_sec{suffix}",
        "value": round(sh_head["tokens_per_sec"], 1),
        "unit": "tokens/s",
        # CPU host-mesh: collectives run on host cores, so tok/s
        # scaling is a chip number — the CPU-provable halves of the
        # axis are token parity and slot capacity at fixed bytes
        "vs_baseline": round(sh_head["tokens_per_sec"]
                             / max(sh_one["tokens_per_sec"], 1e-9), 3),
        "baseline": "same pinned composed workload, 1-device mesh "
                    "worker (CPU host-mesh)",
        "devices": sh_counts,
        "tp_degree": sh_head["tp"],
        "dp_degree": sh_head["dp"],
        "tokens_per_sec_by_devices": {
            str(n): round(st_sh[n]["tokens_per_sec"], 1)
            for n in sh_counts},
        "max_slots_by_devices": {str(n): st_sh[n]["max_slots"]
                                 for n in sh_counts},
        # >= 3x at 4 devices is the acceptance bar (slow test asserts)
        "slot_capacity_ratio": round(
            sh_head["max_slots"] / max(sh_one["max_slots"], 1), 3),
        "pool_budget_bytes": sh_head["pool_budget_bytes"],
        "token_parity": len(sh_sigs) == 1,
        "p99_ms": round(sh_head["p99_ms"], 1),
        "itl_p99_ms": round(sh_head["itl_p99_ms"], 2),
        "prefill_dispatches": sh_head["prefill_dispatches"],
        "cpu_host_mesh": True,
        "degraded": True,  # host-mesh numbers even on a chip session
    }
    cq_counts = sorted(st_cq)
    cq_head = st_cq[max(st_cq)]        # largest tp = acceptance point
    cq_m = cq_head["modes"]
    cq_bf = cq_m["bf16"]
    cq_i8 = cq_m.get("int8", cq_bf)   # tp=1 smoke has no wire
    cq_i4 = cq_m.get("int4g", cq_bf)
    cq_sigs = {st_cq[n]["modes"]["bf16"]["token_sig"]
               for n in cq_counts}
    rec_cq = {
        "metric": f"{base}_quantcollectives_served_tokens_per_sec"
                  f"{suffix}",
        "value": round(cq_i8["tokens_per_sec"], 1),
        "unit": "tokens/s",
        # ~1.0 on the shared-core host mesh is expected: collectives
        # are function calls there, so the latency win is a chip
        # number (EQuARX ~2x) — the CPU-provable halves are the wire
        # bytes and token parity below
        "vs_baseline": round(cq_i8["tokens_per_sec"]
                             / max(cq_bf["tokens_per_sec"], 1e-9), 3),
        "baseline": "same fixed-seed Poisson arrivals, same mesh, "
                    "unquantized (bf16-wire) collectives",
        "devices": cq_counts,
        "tp_degree": cq_head["tp"],
        "tokens_per_sec_bf16": round(cq_bf["tokens_per_sec"], 1),
        "tokens_per_sec_int4g": round(cq_i4["tokens_per_sec"], 1),
        # per-device analytic wire bytes per decoded token, actual vs
        # the unquantized collectives on the SAME dispatches — the
        # <= 0.30x acceptance bar (int8)
        "bytes_per_token": round(cq_i8["bytes_per_decoded_token"], 1),
        "bytes_per_token_bf16": round(
            cq_i8["bytes_baseline"] / cq_i8["decoded_tokens"], 1),
        "bytes_ratio_int8": round(cq_i8["bytes_ratio"], 4),
        "bytes_ratio_int4g": round(cq_i4["bytes_ratio"], 4),
        "by_collective_int8": cq_i8["by_collective"],
        # greedy-stream agreement vs the bf16 wire, worst across tps
        "greedy_token_match": round(min(
            st_cq[n]["modes"].get("int8", st_cq[n]["modes"]["bf16"])
            ["greedy_token_match"] for n in cq_counts), 4),
        "greedy_token_match_int4g": round(
            cq_i4["greedy_token_match"], 4),
        # md5 proof: the bf16 wire is mesh-parity across tps (the r14
        # guarantee, re-asserted under the new code path)
        "parity_md5": cq_bf["token_sig"],
        "token_parity": len(cq_sigs) == 1,
        "dispatches_per_round": round(
            cq_i8["dispatches_per_round"], 4),
        "compiles_in_window": cq_i8["compiles_in_window"],
        "offered_rps": round(cq_head["offered_rps"], 3),
        "p99_ms": round(cq_i8["p99_ms"], 1),
        "itl_p99_ms": round(cq_i8["itl_p99_ms"], 2),
        "prefill_dispatches": cq_i8["prefill_dispatches"],
        "cpu_host_mesh": True,
        "degraded": True,  # host-mesh numbers even on a chip session
    }
    un_s, un_u = st_un["split"], st_un["uni"]
    rec_uni = {
        "metric": f"{base}_unifiedround_tokens_per_sec{suffix}",
        "value": round(un_u["tokens_per_sec"], 1),
        "unit": "tokens/s",
        # >1 = the one-dispatch round + async loop serve that many
        # times the split engine's tok/s at IDENTICAL arrivals
        # (CPU-degraded bar: >= 1.15x; chip rerun queued)
        "vs_baseline": round(un_u["tokens_per_sec"]
                             / max(un_s["tokens_per_sec"], 1e-9), 3),
        "baseline": "same fixed-seed Poisson arrivals, split engine "
                    "(separate chunk-prefill/decode dispatches, "
                    "steps_per_dispatch=1)",
        "tokens_per_sec_split": round(un_s["tokens_per_sec"], 1),
        "itl_p99_ms": round(un_u["itl_p99_ms"], 2),
        "itl_p99_ms_split": round(un_s["itl_p99_ms"], 2),
        "ttft_p99_ms": round(un_u["ttft_p99_ms"], 2),
        "ttft_p99_ms_split": round(un_s["ttft_p99_ms"], 2),
        "p99_ms": round(un_u["p99_ms"], 1),
        # the headline STRUCTURE numbers: the fused engine must read
        # exactly 1.0 here, the split engine > 1 on mixed rounds
        "dispatches_per_round": round(
            un_u["rounds"]["dispatches_per_round"], 4),
        "dispatches_per_round_split": round(
            un_s["rounds"]["dispatches_per_round"], 4),
        "mixed_rounds": un_u["rounds"]["mixed_rounds"],
        "overlap_seconds": round(un_u["rounds"]["overlap_seconds"], 4),
        "overlap_fraction": round(
            un_u["rounds"]["overlap_fraction"], 4),
        "prefill_dispatches": un_u["prefill_dispatches"],
        "offered_rps": round(un_u["offered_rps"], 3),
        "achieved_rps": round(un_u["achieved_rps"], 3),
        "compiles_in_window": un_u["compiles"]["window_total"],
        "compiles_in_flight_window":
            un_u["compiles"]["window_in_flight"],
        "goodput_ratio": round(un_u["goodput"]["goodput_ratio"], 4),
    }
    fd_base, fd_on, fd_stats = (st_fd["base"], st_fd["front"],
                                st_fd["stats"])
    fdd = fd_stats["frontdoor"]
    rec_fd = {
        "metric": f"{base}_frontdoor_interactive_ttft_p99_ms{suffix}",
        "value": round(fd_on["ttft_p99_ms"], 2),
        "unit": "ms",
        # >1 = the interactive lane's TTFT p99 is that many times
        # better than the single-lane FIFO engine at IDENTICAL
        # adversarial arrivals (acceptance bar: >= 3x)
        "vs_baseline": round(fd_base["ttft_p99_ms"]
                             / max(fd_on["ttft_p99_ms"], 1e-9), 2),
        "baseline": "same arrivals/prompts, single-lane FIFO engine "
                    "(no front door)",
        "interactive_ttft_p50_ms": round(fd_on["ttft_p50_ms"], 2),
        "interactive_ttft_p99_ms_baseline":
            round(fd_base["ttft_p99_ms"], 2),
        "deadline_miss_rate": round(fd_on["miss_rate"], 4),
        "deadline_miss_rate_baseline": round(fd_base["miss_rate"], 4),
        "deadline_ms": st_fd["deadline_ms"],
        # lane priority must not strand the batch lane: >= 0.85 of the
        # baseline's bully throughput (acceptance: within 15%)
        "batch_tokens_per_sec": round(fd_on["batch_tok_s"], 1),
        "batch_tokens_per_sec_baseline":
            round(fd_base["batch_tok_s"], 1),
        "batch_throughput_ratio": round(
            fd_on["batch_tok_s"] / max(fd_base["batch_tok_s"], 1e-9),
            3),
        "preemptions": fdd["preemptions"],
        "resumes": fdd["resumes"],
        "preempt_cached_tokens": fdd["preempt_cached_tokens"],
        "rejected": fdd["rejected"],
        "n_bully": st_fd["n_bully"],
        "n_interactive": st_fd["n_inter"],
        "p99_ms": round(fd_stats["p99_ms"], 1),
        "itl_p99_ms": round(fd_stats["itl_p99_ms"], 2),
        "prefill_dispatches": fd_stats["prefill_dispatches"],
        # ops-plane acceptance (ISSUE 10): with warm_buckets() both
        # sides, the measured front-door window must be compile-clean
        # — in_flight compiles here mean the scheduling signal was
        # polluted by an XLA compile (the PERF.md r12/r13 incident)
        "compiles_in_window": fd_stats["compiles"]["window_total"],
        "compiles_in_flight_window":
            fd_stats["compiles"]["window_in_flight"],
        "goodput_ratio": round(fd_stats["goodput"]["goodput_ratio"],
                               4),
    }
    dg_c, dg_f, dg_plan = (st_dg["clean"], st_dg["faulted"],
                           st_dg["plan"])
    dg_rel = dg_f["reliability"]
    rec_dg = {
        "metric": f"{base}_degradedmode_tokens_per_sec{suffix}",
        "value": round(dg_f["tokens_per_sec"], 1),
        "unit": "tokens/s",
        # <1 = serving under the injected fault rate retains that
        # fraction of fault-free tok/s at IDENTICAL arrivals (the
        # recovery ladder's cost: replayed prefills + backoff)
        "vs_baseline": round(dg_f["tokens_per_sec"]
                             / max(dg_c["tokens_per_sec"], 1e-9), 3),
        "baseline": "same fixed-seed arrivals/prompts, no fault plan",
        "tokens_per_sec_clean": round(dg_c["tokens_per_sec"], 1),
        "fault_plan": dg_plan["name"],
        "faults_injected": dg_rel["faults_injected"],
        "faults_by_seam": dg_plan["fired_by_seam"],
        "dispatch_retries": dg_rel["dispatch_retries"],
        "recoveries": dg_rel["recoveries"],
        "quarantined": dg_rel["quarantined"],
        # the chaos parity proof: every non-quarantined request's
        # output md5-matches the fault-free run
        "survivor_token_parity": st_dg["survivor_parity"],
        "n_requests": st_dg["n_req"],
        "goodput_ratio": round(dg_f["goodput"]["goodput_ratio"], 4),
        "goodput_ratio_clean": round(
            dg_c["goodput"]["goodput_ratio"], 4),
        "p99_ms": round(dg_f["p99_ms"], 1),
        "itl_p99_ms": round(dg_f["itl_p99_ms"], 2),
        "prefill_dispatches": dg_f["prefill_dispatches"],
    }
    fl_max = max(st_fl["replica_counts"])
    rec_fl = {
        "metric": f"{base}_fleet_tokens_per_sec{suffix}",
        "value": round(st_fl["tokens_per_sec_by_replicas"]
                       [str(fl_max)], 1),
        "unit": "tokens/s",
        # aggregate tok/s at the max replica count (with one forced
        # mid-run replica kill absorbed) vs the clean single replica.
        # On the single-core CPU proxy replicas share the core, so
        # ~1.0x is expected; scaling is a chip/multi-host number.
        "vs_baseline": round(
            st_fl["tokens_per_sec_by_replicas"][str(fl_max)]
            / max(st_fl["tokens_per_sec_by_replicas"]["1"], 1e-9), 3),
        "baseline": "same fixed-seed arrivals, 1 replica, no kill",
        # topology provenance (r19 bench hygiene): compare_bench.py
        # refuses to diff fleet records across transports/topologies
        "transport": "inproc",
        "pool_topology": "pooled",
        "replica_counts": st_fl["replica_counts"],
        "tokens_per_sec_by_replicas":
            st_fl["tokens_per_sec_by_replicas"],
        "ttft_p99_ms_by_replicas": st_fl["ttft_p99_ms_by_replicas"],
        "ttft_p99_ms": round(st_fl["ttft_p99_ms_by_replicas"]
                             [str(fl_max)], 2),
        "failover_count": st_fl["failover_count"],
        "failover_sessions": st_fl["failover_sessions"],
        "replica_kills": st_fl["replica_kills"],
        "migrated_sessions": st_fl["migrated_sessions"],
        "prefix_routed": st_fl["prefix_routed"],
        # the chaos parity proof: every request's output md5 is
        # IDENTICAL at every replica count, across the forced kill
        # and the live migration
        "survivor_token_parity": st_fl["survivor_token_parity"],
        "parity_md5": st_fl["parity_md5"],
        "n_requests": st_fl["n_req"],
        # schema-congruence fields shared by every served record
        # (worst replica's ITL, fleet-total prefill dispatches at the
        # max replica count)
        "p99_ms": round(st_fl["ttft_p99_ms_by_replicas"]
                        [str(fl_max)], 2),
        "itl_p99_ms": round(st_fl["itl_p99_ms"], 2),
        "prefill_dispatches": st_fl["prefill_dispatches"],
    }
    lc_counts = sorted(st_lc)
    lc1, lc_hi = st_lc[lc_counts[0]], st_lc[lc_counts[-1]]
    lc_tier = lc1["tier"]
    lc_sigs = {st_lc[n]["token_sig"] for n in lc_counts}
    rec_lc = {
        "metric": f"{base}_longcontext_ttft_p50_ms{suffix}",
        "value": round(lc_hi["ttft_p50_ms"], 2),
        "unit": "ms",
        # >1 = sp=max prefills the same fixed-seed huge prompts that
        # many times faster (TTFT p50) than the unsharded chunk
        # stream. The dispatch division below is the exact structural
        # half; this wall-clock ratio is the chip half — the forced
        # host mesh shares one core across sp shards, so ~1.0x is
        # expected off TPU (rerun queued)
        "vs_baseline": round(lc1["ttft_p50_ms"]
                             / max(lc_hi["ttft_p50_ms"], 1e-9), 3),
        "baseline": "same fixed-seed huge prompts, sp=1 "
                    "(unsharded packed prefill stream)",
        "sp_degrees": lc_counts,
        "prompt_tokens": lc1["prompt_tokens"],
        "ttft_p50_ms_by_sp": {str(n): round(st_lc[n]["ttft_p50_ms"], 2)
                              for n in lc_counts},
        # the structural proof: sp multiplies the per-dispatch chunk
        # budget, so the SAME prompts take ~1/sp the prefill
        # dispatches — exact, deterministic, asserted by the slow test
        "prefill_dispatches_by_sp": {
            str(n): st_lc[n]["prefill_dispatches"] for n in lc_counts},
        # md5 proof: identical token streams at every sp degree
        "token_parity": len(lc_sigs) == 1,
        "parity_md5": lc1["token_sig"],
        # ---- sp_attention A/B (ISSUE 18): the highest-sp worker runs
        # the SAME prompts again through the memory-flat ring exchange.
        # peak bytes = the engine's per-dispatch fresh-K/V gauge; the
        # ratio is the memory the all-gather materializes beyond ring's
        # O(block) rotating window (grows with chunk length; flat for
        # ring). Token parity proves the exchange rewrite is exact.
        "sp_attention_modes": ["allgather", "ring"],
        "sp_attention_peak_bytes_allgather":
            lc_hi["sp_ab"]["allgather_peak_bytes"],
        "sp_attention_peak_bytes_ring":
            lc_hi["sp_ab"]["ring_peak_bytes"],
        "sp_attention_peak_bytes_ratio": round(
            lc_hi["sp_ab"]["allgather_peak_bytes"]
            / max(lc_hi["sp_ab"]["ring_peak_bytes"], 1), 3),
        "ttft_p50_ms_ring": round(
            lc_hi["sp_ab"]["ring_ttft_p50_ms"], 2),
        "sp_attention_token_parity":
            lc_hi["sp_ab"]["ring_token_sig"] == lc_hi["token_sig"],
        # ---- host-RAM KV tier half: long-context session capacity.
        # "sessions at the ITL bar" = sessions whose history stays
        # RESIDENT (device or host tier), so a resume re-attaches the
        # prefix instead of recomputing it — recompute is the ITL/TTFT
        # cliff the churn probe measures. Capacity is the
        # reservation-backed count at FIXED per-device pool bytes
        # (host tier provisioned at 4x the device budget); the
        # mechanism (demote on churn, promote on resume, token parity)
        # is proven empirically on a deliberately small pool.
        "sessions_at_itl_bar_tier_on": lc_tier["sessions_at_bar_on"],
        "sessions_at_itl_bar_tier_off": lc_tier["sessions_at_bar_off"],
        "session_capacity_ratio": round(
            lc_tier["sessions_at_bar_on"]
            / max(lc_tier["sessions_at_bar_off"], 1), 2),
        "max_resident_context_tokens_tier_on":
            lc_tier["max_ctx_tokens_on"],
        "max_resident_context_tokens_tier_off":
            lc_tier["max_ctx_tokens_off"],
        "pool_budget_bytes": lc_tier["pool_budget_bytes"],
        "host_budget_bytes": lc_tier["host_budget_bytes"],
        # churn-probe empirics: resuming n_sessions round-robin
        # histories through a pool sized for ~1.5 of them
        "resume_ttft_p50_ms_tier_on":
            round(lc_tier["resume_ttft_p50_ms_on"], 2),
        "resume_ttft_p50_ms_tier_off":
            round(lc_tier["resume_ttft_p50_ms_off"], 2),
        "resume_prefill_dispatches_tier_on":
            lc_tier["resume_prefill_dispatches_on"],
        "resume_prefill_dispatches_tier_off":
            lc_tier["resume_prefill_dispatches_off"],
        "tier_demotions": lc_tier["demotions"],
        "tier_promotions": lc_tier["promotions"],
        "tier_hit_tokens": lc_tier["hit_tokens"],
        # tier ON streams byte-identical to tier OFF on the resumes
        "tier_token_parity": lc_tier["sig_on"] == lc_tier["sig_off"],
        # ---- tier prefetch-ahead A/B (ISSUE 18): queued-behind-busy
        # resumes, promote overlapped with the occupier's rounds vs
        # paid synchronously at admission (same fixed-seed busy work)
        "resume_ttft_p50_ms_tier_prefetch":
            round(lc_tier["resume_ttft_p50_ms_prefetch"], 2),
        "resume_ttft_p50_ms_tier_sync":
            round(lc_tier["resume_ttft_p50_ms_sync"], 2),
        "tier_prefetch_hit_rate":
            round(lc_tier["prefetch"]["hit_rate"], 3),
        "tier_prefetch_issued_blocks":
            lc_tier["prefetch"]["issued_blocks"],
        "tier_prefetch_wasted_blocks":
            lc_tier["prefetch"]["wasted_blocks"],
        "tier_prefetch_overlap_promote_s":
            round(lc_tier["prefetch"]["overlap_promote_s"], 4),
        "tier_prefetch_token_parity":
            lc_tier["sig_prefetch"] == lc_tier["sig_sync"]
            == lc_tier["sig_on"],
        "n_sessions": lc_tier["n_sessions"],
        # schema-congruence fields shared by every served record
        "tokens_per_sec": round(lc_hi["tokens_per_sec"], 1),
        "p99_ms": round(lc_hi["p99_ms"], 1),
        "itl_p99_ms": round(lc_hi["itl_p99_ms"], 2),
        "prefill_dispatches": lc_hi["prefill_dispatches"],
        "cpu_host_mesh": True,
        "degraded": True,  # host-mesh numbers even on a chip session
    }
    fp_max = max(st_fp["process_counts"])
    rec_fp = {
        "metric": f"{base}_fleetprocs_tokens_per_sec{suffix}",
        "value": round(st_fp["tokens_per_sec_by_procs"]
                       [str(fp_max)], 1),
        "unit": "tokens/s",
        # aggregate tok/s at the max OS-process count. On a shared
        # single-core host the processes contend for the core, so
        # ~1.0x is expected off TPU; real scaling is a chip/multi-host
        # number. The structural proofs (wire parity, disagg handoff)
        # hold everywhere.
        "vs_baseline": round(
            st_fp["tokens_per_sec_by_procs"][str(fp_max)]
            / max(st_fp["tokens_per_sec_by_procs"]["1"], 1e-9), 3),
        "baseline": "same fixed-seed arrivals, 1 OS-process worker",
        # topology provenance (r19 bench hygiene): compare_bench.py
        # refuses to diff fleet records across transports/topologies
        "transport": "http",
        "pool_topology": "pooled",
        "process_counts": st_fp["process_counts"],
        "tokens_per_sec_by_procs":
            st_fp["tokens_per_sec_by_procs"],
        "ttft_p99_ms_by_procs": st_fp["ttft_p99_ms_by_procs"],
        "ttft_p99_ms": round(st_fp["ttft_p99_ms_by_procs"]
                             [str(fp_max)], 2),
        # the in-process twin fleet's tok/s on the same arrivals:
        # the wire-transport overhead reference
        "tokens_per_sec_inproc_1": round(
            st_fp["tokens_per_sec_inproc_1"], 1),
        # the wire parity proof: every request's output md5 is
        # IDENTICAL to the in-process twin at every process count —
        # submit, token stream, and the int8 KV codec hop are exact
        "wire_token_parity": st_fp["wire_token_parity"],
        "parity_md5": st_fp["parity_md5"],
        # prefill-heavy burst A/B: disaggregated 1-prefill+1-decode
        # pool vs the SAME two workers pooled (finished KV blocks
        # stream prefill->decode over the wire through the codec)
        "burst_n_requests": st_fp["burst_n_req"],
        "burst_ttft_p99_ms_pooled": round(
            st_fp["burst_ttft_p99_ms_pooled"], 2),
        "burst_ttft_p99_ms_disagg": round(
            st_fp["burst_ttft_p99_ms_disagg"], 2),
        "disagg_handoffs": st_fp["disagg_handoffs"],
        "disagg_handoffs_failed": st_fp["disagg_handoffs_failed"],
        "disagg_token_parity": st_fp["disagg_token_parity"],
        "n_requests": st_fp["n_req"],
        # schema-congruence fields shared by every served record
        "p99_ms": round(st_fp["ttft_p99_ms_by_procs"]
                        [str(fp_max)], 2),
        "itl_p99_ms": round(st_fp["itl_p99_ms"], 2),
        "prefill_dispatches": st_fp["prefill_dispatches"],
    }
    rec_el = {
        "metric": f"{base}_elastic_replica_seconds{suffix}",
        "value": round(st_el["replica_seconds_autoscaled"], 3),
        "unit": "replica_s",
        # <1.0 = the autoscaled fleet spent FEWER replica-seconds on
        # the same fixed-seed trace than the best (smallest) static
        # size that holds the TTFT SLO — the elastic cost win
        "vs_baseline": round(
            st_el["replica_seconds_autoscaled"]
            / max(st_el["replica_seconds_best_static"], 1e-9), 3),
        "baseline": "best static fleet meeting the TTFT SLO, "
                    "same fixed-seed diurnal+flash-crowd trace",
        # topology provenance (r19 bench hygiene)
        "transport": "inproc",
        "pool_topology": "pooled",
        "replica_counts": st_el["replica_counts"],
        "n_requests": st_el["n_req"],
        # the declared SLO and who holds it
        "slo_ttft_ms": round(st_el["slo_ttft_ms"], 2),
        "ttft_p99_ms_by_static": {
            k: round(v, 2)
            for k, v in st_el["ttft_p99_ms_by_static"].items()},
        "ttft_p99_ms": round(st_el["ttft_p99_ms_autoscaled"], 2),
        "slo_met_autoscaled": st_el["slo_met_autoscaled"],
        "best_static_replicas": st_el["best_static_replicas"],
        # the cost axis: replica-seconds per drive
        "replica_seconds_by_static": {
            k: round(v, 3)
            for k, v in st_el["replica_seconds_by_static"].items()},
        "replica_seconds_best_static": round(
            st_el["replica_seconds_best_static"], 3),
        "replica_seconds_saved_frac": round(
            st_el["replica_seconds_saved_frac"], 3),
        # scale-event accounting on the autoscaled drive
        "scale_ups": st_el["scale_ups"],
        "scale_downs": st_el["scale_downs"],
        "decisions_total": st_el["decisions_total"],
        "autoscale_errors": st_el["autoscale_errors"],
        "migrated_sessions": st_el["migrated_sessions"],
        "failover_sessions": st_el["failover_sessions"],
        # the elastic parity proof: every request's output md5 is
        # IDENTICAL across every static size AND the autoscaled drive
        # — scale-ups, drain migrations and retires are token-invisible
        "token_parity": st_el["token_parity"],
        "parity_md5": st_el["parity_md5"],
        # the determinism proof: the live decision journal replays
        # byte-for-byte from the recorded (now, snapshot) tick log
        "decision_replay_identical": st_el["decision_replay_identical"],
        # schema-congruence fields shared by every served record
        "p99_ms": round(st_el["ttft_p99_ms_autoscaled"], 2),
        "tokens_per_sec": round(
            st_el["new_tokens"]
            / max(st_el["wall_s_autoscaled"], 1e-9), 1),
        "itl_p99_ms": round(st_el["itl_p99_ms"], 2),
        "prefill_dispatches": st_el["prefill_dispatches"],
    }
    if st_pad is not None:
        rec_pad = {
            "metric": f"{base}_mixed_padded_tokens_per_sec{suffix}",
            "value": round(st_pad["tokens_per_sec"], 1),
            "unit": "tokens/s",
            "vs_baseline": 1.0,
            "baseline": "self (the padded static-batch server IS the bar)",
            "p99_ms": round(st_pad["p99_ms"], 1),
        }
        rec_paged["vs_baseline"] = round(
            st_paged["tokens_per_sec"]
            / max(st_pad["tokens_per_sec"], 1e-9), 3)
        rec_paged["baseline"] = \
            "padded static-batch GenerationServer, same traffic"
        records = [rec_pad, rec_paged, rec_mix, rec_open, rec_sp,
                   rec_spec, rec_fd, rec_qz, rec_sh, rec_cq, rec_uni,
                   rec_dg, rec_fl, rec_lc, rec_fp, rec_el]
    else:
        rec_paged["vs_baseline"] = 1.0
        rec_paged["baseline"] = "self (tiny schema smoke)"
        records = [rec_paged, rec_mix, rec_open, rec_sp, rec_spec,
                   rec_fd, rec_qz, rec_sh, rec_cq, rec_uni, rec_dg,
                   rec_fl, rec_lc, rec_fp, rec_el]
    if rec_tel is not None:
        records.append(rec_tel)
    if not on_tpu:
        for rec in records:
            rec["degraded"] = True
    for rec in records:
        print(json.dumps(rec))
    if st_pad is not None:
        print(f"# served mixed({lo}-{hi})x{n_req} new={new} "
              f"slots={slots}: padded {st_pad['tokens_per_sec']:,.0f} "
              f"tok/s p99 {st_pad['p99_ms']:.0f}ms | paged "
              f"{st_paged['tokens_per_sec']:,.0f} tok/s "
              f"p99 {st_paged['p99_ms']:.0f}ms "
              f"({rec_paged['vs_baseline']:.2f}x)", file=sys.stderr)
    print(f"# served mixed-sampling(50% greedy/50% sampled): "
          f"{st_mix['tokens_per_sec']:,.0f} tok/s vs "
          f"{st_paged['tokens_per_sec']:,.0f} all-greedy "
          f"({rec_mix['sampling_overhead_pct']:+.1f}% overhead), "
          f"{rec_mix['sampled_dispatches']} sampled / "
          f"{rec_mix['fast_path_dispatches']} fast-path dispatches",
          file=sys.stderr)
    print(f"# served open-loop: {st_open['offered_rps']:.2f} rps offered "
          f"({st_open['achieved_rps']:.2f} achieved), "
          f"{st_open['tokens_per_sec']:,.0f} tok/s, "
          f"itl p99 {st_open['itl_p99_ms']:.1f}ms "
          f"(unchunked {st_unchunked['itl_p99_ms']:.1f}ms), "
          f"ttft p99 {st_open['ttft_p99_ms']:.0f}ms "
          f"(unchunked {st_unchunked['ttft_p99_ms']:.0f}ms), "
          f"{st_open['prefill_dispatches']} prefill dispatches for "
          f"{st_open['prefills']} prefills", file=sys.stderr)
    print(f"# served shared-prefix({sp_len}+{tlo}-{thi})x{n_req}: "
          f"ttft p50 {st_sp_on['ttft_p50_ms']:.1f}ms cached vs "
          f"{st_sp_off['ttft_p50_ms']:.1f}ms uncached "
          f"({rec_sp['vs_baseline']:.2f}x), hit rate "
          f"{rec_sp['prefix_hit_rate']:.2f}, "
          f"{rec_sp['prefix_cow_copies']} CoW, "
          f"{rec_sp['prefix_evictions']} evictions, "
          f"{rec_sp['retained_blocks']} retained blocks",
          file=sys.stderr)
    print(f"# served speculative(repetitive x{st_spec['pool_size']}, "
          f"K={st_spec['K']}, new={st_spec['new']}): "
          f"{sp_on['tokens_per_sec']:,.0f} tok/s vs "
          f"{sp_plain['tokens_per_sec']:,.0f} plain "
          f"({rec_spec['vs_baseline']:.2f}x), acceptance "
          f"{rec_spec['acceptance_rate']:.2f}, "
          f"{rec_spec['verify_dispatches']} verify + "
          f"{rec_spec['decode_steps']} decode dispatches vs "
          f"{rec_spec['decode_steps_plain']} plain decode steps; "
          f"oracle ceiling {rec_spec['tok_s_ratio_oracle']:.2f}x",
          file=sys.stderr)
    print(f"# served frontdoor({st_fd['n_bully']} bullies + "
          f"{st_fd['n_inter']} interactive): interactive ttft p99 "
          f"{fd_on['ttft_p99_ms']:.0f}ms vs {fd_base['ttft_p99_ms']:.0f}ms "
          f"single-lane ({rec_fd['vs_baseline']:.1f}x), miss rate "
          f"{rec_fd['deadline_miss_rate']:.2f} vs "
          f"{rec_fd['deadline_miss_rate_baseline']:.2f}, batch "
          f"throughput ratio {rec_fd['batch_throughput_ratio']:.2f}, "
          f"{rec_fd['preemptions']} preemptions "
          f"({rec_fd['preempt_cached_tokens']} toks kept cached)",
          file=sys.stderr)
    print(f"# served sharded(devices {sh_counts}, host mesh): tok/s "
          f"{' / '.join(str(rec_sh['tokens_per_sec_by_devices'][str(n)]) for n in sh_counts)}, "
          f"max slots at fixed {rec_sh['pool_budget_bytes']} B/device "
          f"{' -> '.join(str(rec_sh['max_slots_by_devices'][str(n)]) for n in sh_counts)} "
          f"({rec_sh['slot_capacity_ratio']:.2f}x), token parity "
          f"{rec_sh['token_parity']}", file=sys.stderr)
    print(f"# served quant-collectives(devices {cq_counts}, "
          f"tp={rec_cq['tp_degree']}): bytes/token "
          f"{rec_cq['bytes_per_token_bf16']:.0f} bf16 -> "
          f"{rec_cq['bytes_per_token']:.0f} int8 "
          f"({rec_cq['bytes_ratio_int8']:.3f}x; int4g "
          f"{rec_cq['bytes_ratio_int4g']:.3f}x), greedy match "
          f"{rec_cq['greedy_token_match']:.4f} "
          f"(int4g {rec_cq['greedy_token_match_int4g']:.4f}), "
          f"dispatches/round {rec_cq['dispatches_per_round']:.2f}, "
          f"{rec_cq['compiles_in_window']} compiles in window",
          file=sys.stderr)
    print(f"# served unified-round({st_un['n_req']} req @ "
          f"{rec_uni['offered_rps']:.2f} rps, new={st_un['new']}): "
          f"{rec_uni['value']:,.0f} tok/s vs "
          f"{rec_uni['tokens_per_sec_split']:,.0f} split "
          f"({rec_uni['vs_baseline']:.2f}x), itl p99 "
          f"{rec_uni['itl_p99_ms']:.1f}ms vs "
          f"{rec_uni['itl_p99_ms_split']:.1f}ms, dispatches/round "
          f"{rec_uni['dispatches_per_round']:.2f} vs "
          f"{rec_uni['dispatches_per_round_split']:.2f}, overlap "
          f"{rec_uni['overlap_fraction']:.2f}, "
          f"{rec_uni['compiles_in_window']} compiles in window",
          file=sys.stderr)
    print(f"# served quantized(bf16/w8a16/w8a16+kv8 @ "
          f"{rec_qz['offered_rps']:.2f} rps): "
          f"{rec_qz['tokens_per_sec_bf16']:,.0f} / "
          f"{rec_qz['tokens_per_sec_w8a16']:,.0f} / "
          f"{rec_qz['value']:,.0f} tok/s "
          f"({rec_qz['vs_baseline']:.2f}x), slots at fixed bytes "
          f"{rec_qz['max_slots_at_fixed_bytes_bf16']} -> "
          f"{rec_qz['max_slots_at_fixed_bytes']} "
          f"({rec_qz['slot_capacity_ratio']:.2f}x), token match "
          f"{rec_qz['greedy_token_match']:.4f}, logit mae "
          f"{rec_qz['logit_mae']:.4g}", file=sys.stderr)
    fl_counts = rec_fl["replica_counts"]
    print(f"# served fleet(replicas {fl_counts}, 1 forced kill + 1 "
          f"live migration): tok/s "
          f"{' / '.join(str(round(rec_fl['tokens_per_sec_by_replicas'][str(n)], 1)) for n in fl_counts)}, "
          f"ttft p99 "
          f"{' / '.join(str(round(rec_fl['ttft_p99_ms_by_replicas'][str(n)], 1)) for n in fl_counts)}ms, "
          f"{rec_fl['failover_sessions']} sessions failed over "
          f"({rec_fl['replica_kills']} kills), "
          f"{rec_fl['migrated_sessions']} migrated, token parity "
          f"{rec_fl['survivor_token_parity']}", file=sys.stderr)
    print(f"# served long-context(sp {lc_counts}): ttft p50 "
          f"{' / '.join(str(rec_lc['ttft_p50_ms_by_sp'][str(n)]) for n in lc_counts)}ms, "
          f"prefill dispatches "
          f"{' / '.join(str(rec_lc['prefill_dispatches_by_sp'][str(n)]) for n in lc_counts)}, "
          f"token parity {rec_lc['token_parity']} | tier sessions@bar "
          f"{rec_lc['sessions_at_itl_bar_tier_on']} on vs "
          f"{rec_lc['sessions_at_itl_bar_tier_off']} off "
          f"({rec_lc['session_capacity_ratio']:.1f}x), resume prefill "
          f"dispatches {rec_lc['resume_prefill_dispatches_tier_on']} vs "
          f"{rec_lc['resume_prefill_dispatches_tier_off']}, "
          f"{rec_lc['tier_demotions']} demotions / "
          f"{rec_lc['tier_promotions']} promotions, tier parity "
          f"{rec_lc['tier_token_parity']}", file=sys.stderr)
    print(f"# served elastic(static {rec_el['replica_counts']}): "
          f"ttft p99 "
          f"{' / '.join(str(rec_el['ttft_p99_ms_by_static'][str(n)]) for n in rec_el['replica_counts'])}ms "
          f"static vs {rec_el['ttft_p99_ms']}ms autoscaled "
          f"(SLO {rec_el['slo_ttft_ms']}ms, met "
          f"{rec_el['slo_met_autoscaled']}), replica-s "
          f"{rec_el['replica_seconds_best_static']} best-static vs "
          f"{rec_el['value']} autoscaled "
          f"({rec_el['replica_seconds_saved_frac']:.0%} saved), "
          f"{rec_el['scale_ups']} ups / {rec_el['scale_downs']} downs "
          f"/ {rec_el['migrated_sessions']} migrations, parity "
          f"{rec_el['token_parity']}, replay identical "
          f"{rec_el['decision_replay_identical']}", file=sys.stderr)
    return records


def _bench_served_speculation(model, cfg, on_tpu, tiny):
    """Speculation sub-axis of `bench.py served` (round 11). Builds a
    REPETITIVE/AGENTIC mix empirically: candidate prompts are tiled
    short motifs (tool-call-loop shaped), their greedy continuations
    are recorded once, and the candidates whose continuations the
    n-gram drafter predicts best (fewest simulated rounds) form the
    measured pool — "repetitive traffic" for a synthetic-weights model
    IS traffic whose continuations actually repeat. Returns the
    measurement dict the served record is assembled from."""
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.spec_decode import NgramDrafter, SpecConfig

    if tiny:
        spec_model = model
        new, n_req, slots, bs, K, mp, chunk = 6, 4, 2, 4, 3, 16, 16
        passes = 1
    elif on_tpu:
        spec_model = model  # gpt2s bf16: the serving config
        new, n_req, slots, bs, K, mp, chunk = 64, 16, 8, 128, 8, 256, 512
        passes = 2
    else:
        scfg = GPT2Config.tiny()  # dispatch-bound CPU proxy (see (f))
        scfg.dropout = 0.0
        spec_model = GPT2(scfg)
        spec_model.eval()
        new, n_req, slots, bs, K, mp, chunk = 48, 8, 4, 4, 7, 32, 64
        passes = 2
    vocab = spec_model.cfg.vocab_size
    rng = np.random.RandomState(11)
    cands = []
    # candidate lengths bucket to a coarse grid: the recording pass
    # below runs one dense generate per DISTINCT length (jit shape),
    # and free-length candidates would compile one variant each
    step = max(4, mp // 8)
    for _ in range(4 * n_req):
        motif = rng.randint(1, vocab,
                            (int(rng.randint(2, 6)),)).astype(np.int32)
        n = int(rng.randint(max(4, mp // 3), mp - 3))
        n = max(step, n // step * step)
        cands.append(np.tile(motif, -(-n // motif.size))[:n])
    drafter = NgramDrafter(max_match=3, min_match=1)
    refs, scored = [], []
    for p in cands:
        out = spec_model.generate(p[None], new).numpy()[0]
        refs.append(out)
        n = p.size
        pos, rounds = 1, 0
        while pos < new:  # simulate the drafter against the recording
            prop = drafter.propose(out[:n + pos],
                                   min(K, new - pos - 1) or 1)
            rounds += 1
            hits = 0
            for j, t in enumerate(prop):
                if int(t) == int(out[n + pos + j]):
                    hits += 1
                else:
                    break
            pos += hits + 1
        scored.append((rounds, p))
    pool = [p for _, p in sorted(scored, key=lambda x: x[0])[:n_req]]

    class _ReplayOracle:
        """Acceptance-1.0 ceiling drafter: replays the recorded greedy
        continuations (measures the verify engine, not the drafter)."""

        def propose(self, ctx, max_tokens):
            ctx = np.asarray(ctx, np.int32)
            for ref in refs:
                if ctx.size < ref.size and np.array_equal(
                        ref[:ctx.size], ctx):
                    return ref[ctx.size:ctx.size + int(max_tokens)]
            return np.empty((0,), np.int32)

    def drain(spec):
        srv = PagedGenerationServer(
            spec_model, max_slots=slots, block_size=bs,
            max_prompt_len=mp, max_new_tokens=new,
            prefill_chunk_tokens=chunk, speculation=spec).start()
        try:
            best = None
            for f in [srv.submit(p) for p in pool]:  # warm/compile
                f.result(timeout=900)
            for _ in range(passes):  # best-of-N: ratio-of-minima is
                srv.reset_stats()    # stabler than one noisy pass
                for f in [srv.submit(p) for p in pool]:
                    f.result(timeout=900)
                st = srv.stats()
                if best is None or (st["tokens_per_sec"]
                                    > best["tokens_per_sec"]):
                    best = st
            return best
        finally:
            srv.stop()

    st_plain = drain(None)
    st_spec = drain(SpecConfig(max_draft_tokens=K))
    st_oracle = drain(SpecConfig(max_draft_tokens=K,
                                 drafter=_ReplayOracle()))
    return {"plain": st_plain, "spec": st_spec, "oracle": st_oracle,
            "K": K, "pool_size": len(pool), "new": new}


def _bench_served_unified(model, cfg, on_tpu, tiny):
    """Unified-round sub-axis of `bench.py served` (r16): IDENTICAL
    fixed-seed open-loop Poisson arrivals through the SPLIT engine
    (separate chunk-prefill / decode dispatches per round,
    steps_per_dispatch=1 — the dispatch-structure baseline) and the
    UNIFIED+ASYNC engine (one fused attention dispatch per round,
    double-buffered loop chaining tokens on device). Off TPU this axis
    runs the tiny dispatch-bound proxy for the same reason the
    speculation axis does: the win IS dispatch/round overhead, which
    the compute-bound hs256 CPU proxy would bury under XLA matmul
    width. `warm_buckets()` + an unmeasured Poisson churn pass on BOTH
    sides keep the measured windows compile-clean (the record carries
    the r15 tracker proof)."""
    from paddle_tpu.inference import (PagedGenerationServer,
                                      measure_poisson_load)
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config

    # decode-heavy pool (short prompts, long budgets): the regime the
    # round fusion targets — decode is the bandwidth/dispatch-bound
    # phase (PERF.md), and at saturation nearly every round is the
    # steady decode round whose host planning the async loop hides
    if tiny:
        umodel = model
        n_req, new, slots, bs, mp, chunk = 6, 6, 2, 4, 12, 12
        passes = 1
    elif on_tpu:
        umodel = model  # gpt2s bf16: the serving config
        n_req, new, slots, bs, mp, chunk = 32, 128, 8, 128, 256, 256
        passes = 3
    else:
        ucfg = GPT2Config.tiny()  # dispatch-bound CPU proxy (see (f))
        ucfg.dropout = 0.0
        umodel = GPT2(ucfg)
        umodel.eval()
        # n_req >> slots so the measured window is dominated by the
        # full-occupancy steady state, not the low-occupancy drain tail
        n_req, new, slots, bs, mp, chunk = 32, 128, 4, 4, 12, 12
        passes = 3
    vocab = umodel.cfg.vocab_size
    rng = np.random.RandomState(17)
    pool = [rng.randint(1, vocab,
                        (int(rng.randint(max(4, mp // 4), mp + 1)),))
            .astype(np.int32) for _ in range(n_req)]

    def build(**extra):
        srv = PagedGenerationServer(
            umodel, max_slots=slots, block_size=bs, max_prompt_len=mp,
            max_new_tokens=new, steps_per_dispatch=1,
            prefill_chunk_tokens=chunk, **extra)
        srv.warm_buckets()
        return srv.start()

    split = build()
    uni = build(async_rounds=True)
    try:
        # offered rate from a throwaway closed drain on the split
        # side, then 8x it: a strongly SATURATING arrival stream keeps
        # the queue deep on both sides for the whole window, so the
        # tok/s headline measures engine CAPACITY on identical
        # arrivals in the steady decode regime the fusion targets (an
        # unsaturated drive is arrival-limited and reads ~1.0
        # regardless of engine — the r8/r9 latency axes already cover
        # that regime, and at mild saturation the admission-spread and
        # drain-tail rounds dilute the structural difference)
        t0 = time.time()
        for f in [split.submit(p) for p in pool]:
            f.result(timeout=900)
        rps = 8.0 * n_req / max(time.time() - t0, 1e-6)
        # warm the async side's closed shape, then an unmeasured
        # Poisson churn pass per side (admission-timing buckets the
        # closed drain never packs), then INTERLEAVED best-of-N
        # measured passes at the SAME arrival seed — alternating A/B
        # cancels machine-load drift between the two engines (the
        # front-door axis lesson), and ratio-of-best is stabler than
        # one noisy pass each
        for f in [uni.submit(p) for p in pool]:
            f.result(timeout=900)
        for srv in (split, uni):
            measure_poisson_load(srv, pool, rps, n_req, seed=977,
                                 timeout=900)
        pairs = []
        for _ in range(passes):
            pair = []
            for srv in (split, uni):
                srv.reset_stats()
                pair.append(measure_poisson_load(
                    srv, pool, rps, n_req, seed=978, timeout=900))
            pairs.append(pair)
        # MEDIAN-of-pairs: each interleaved (split, unified) pair ran
        # back to back under the same machine-load profile, so its
        # ratio is drift-free; the median pair is robust to one noisy
        # pass in a way best-of-per-side is not
        pairs.sort(key=lambda p: (p[1]["tokens_per_sec"]
                                  / max(p[0]["tokens_per_sec"], 1e-9)))
        st_split, st_uni = pairs[len(pairs) // 2]
    finally:
        split.stop()
        uni.stop()
    return {"split": st_split, "uni": st_uni, "rps": rps,
            "n_req": n_req, "new": new}


def _bench_served_degraded(model, cfg, on_tpu, tiny):
    """Degraded-mode sub-axis of `bench.py served` (r17): IDENTICAL
    fixed-seed Poisson arrivals through a fault-free server and
    through an identical server running a fixed-seed FaultPlan
    (>= 1 fault at each dispatch-path seam). The recovery ladder
    absorbs every fault — implicated requests are snapshotted through
    the swap-out/publish machinery and retried — so the axis measures
    what degradation COSTS: tok/s retention at the same arrivals, the
    recovery/quarantine counts, goodput under replayed work, and the
    survivor token-parity proof (every non-quarantined request's
    output md5-matches the fault-free run)."""
    import hashlib

    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.reliability import FaultPlan, QuarantinedRequest

    # per-seam fault horizons: scheduled occurrence indices must land
    # BELOW the number of times the run actually reaches the seam
    # (admission waves bound prefill dispatches; decode/ensure_many
    # are reached every round), or a scheduled fault never fires
    if tiny:
        dmodel = model
        n_req, new, slots, bs, mp, chunk = 6, 6, 2, 4, 12, 12
        rate, horizons = 0.2, {"prefill": 3, "decode": 12,
                               "ensure_many": 12}
    elif on_tpu:
        dmodel = model  # gpt2s bf16: the serving config
        n_req, new, slots, bs, mp, chunk = 24, 48, 8, 128, 256, 256
        rate, horizons = 0.05, {"prefill": 3, "decode": 96,
                                "ensure_many": 96}
    else:
        dcfg = GPT2Config.tiny()  # dispatch-bound CPU proxy (see (f))
        dcfg.dropout = 0.0
        dmodel = GPT2(dcfg)
        dmodel.eval()
        n_req, new, slots, bs, mp, chunk = 16, 24, 4, 4, 12, 12
        rate, horizons = 0.08, {"prefill": 4, "decode": 48,
                                "ensure_many": 48}
    vocab = dmodel.cfg.vocab_size
    rng = np.random.RandomState(23)
    pool = [rng.randint(1, vocab,
                        (int(rng.randint(max(4, mp // 4), mp + 1)),))
            .astype(np.int32) for _ in range(n_req)]
    gaps = np.random.RandomState(31).exponential(0.01, size=n_req)

    def drive(fault_plan=None):
        srv = PagedGenerationServer(
            dmodel, max_slots=slots, block_size=bs, max_prompt_len=mp,
            max_new_tokens=new, prefill_chunk_tokens=chunk,
            enable_prefix_cache=True, fault_plan=fault_plan).start()
        try:
            if fault_plan is None:  # warm/compile pass (fault-free
                for f in [srv.submit(p) for p in pool]:  # side only:
                    f.result(timeout=900)  # same process jit cache)
            srv.reset_stats()
            t0 = time.time()
            futs, arrival = [], 0.0
            for i, p in enumerate(pool):
                arrival += gaps[i]
                dt = arrival - (time.time() - t0)
                if dt > 0:
                    time.sleep(dt)
                futs.append(srv.submit(p))
            outs = []
            for f in futs:
                try:
                    outs.append(hashlib.md5(
                        np.ascontiguousarray(f.result(timeout=900))
                        .tobytes()).hexdigest())
                except QuarantinedRequest:
                    outs.append(None)
            st = srv.stats()
        finally:
            srv.stop()
        return outs, st

    out0, st0 = drive()
    prng = np.random.RandomState(41)
    entries = []
    for seam, hor in sorted(horizons.items()):
        idx = set(np.flatnonzero(prng.rand(hor) < rate).tolist())
        while not idx:  # >= 1 fault per seam (the chaos-gate floor)
            idx.add(int(prng.randint(hor)))
        entries.extend((seam, i) for i in sorted(idx))
    plan = FaultPlan(entries, name=f"seed=41,rate={rate}")
    out1, st1 = drive(plan)
    survivors = [i for i, h in enumerate(out1) if h is not None]
    parity = all(out0[i] == out1[i] for i in survivors)
    return {"clean": st0, "faulted": st1, "plan": plan.stats(),
            "survivor_parity": parity, "n_req": n_req,
            "quarantined_requests": n_req - len(survivors)}


def _bench_served_fleet(model, cfg, on_tpu, tiny):
    """Fleet sub-axis of `bench.py served` (r18): IDENTICAL fixed-seed
    Poisson arrivals driven through 1/2/4-replica fleets (tiny: 1/2).
    At every count >= 2 one replica is hard-killed mid-run by the
    router's replica_kill fault seam (its sessions fail over via
    router-journal replay) and one live session is migrated between
    replicas through the KV wire format. The proof carried by the
    record: the md5 over every request's output tokens is IDENTICAL
    at every replica count — failover and migration are
    token-invisible."""
    import hashlib
    import tempfile

    from paddle_tpu.fleet import FleetRouter, Replica
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.reliability import FaultPlan
    from paddle_tpu.sampling import SamplingParams

    if tiny:
        fmodel = model
        counts = [1, 2]
        n_req, new, slots, bs, mp, chunk = 6, 8, 2, 4, 12, 12
        mig_budget = 16
    elif on_tpu:
        fmodel = model
        counts = [1, 2, 4]
        n_req, new, slots, bs, mp, chunk = 24, 32, 4, 128, 256, 256
        mig_budget = 64
    else:
        fcfg = GPT2Config.tiny()  # dispatch-bound CPU proxy
        fcfg.dropout = 0.0
        fmodel = GPT2(fcfg)
        fmodel.eval()
        counts = [1, 2, 4]
        n_req, new, slots, bs, mp, chunk = 12, 16, 2, 4, 12, 12
        mig_budget = 48
    vocab = fmodel.cfg.vocab_size
    rng = np.random.RandomState(57)
    pool = [rng.randint(1, vocab,
                        (int(rng.randint(4, mp + 1)),)).astype(np.int32)
            for _ in range(n_req)]
    # half greedy, half fixed-seed sampled: parity must hold for both
    samplings = [None if i % 2 == 0 else
                 SamplingParams(temperature=0.8, top_p=0.9,
                                seed=1000 + i)
                 for i in range(n_req)]
    gaps = np.random.RandomState(61).exponential(0.01, size=n_req)
    max_budget = max(new, mig_budget)

    def drive(n_replicas):
        reps = [Replica(f"b{i}", PagedGenerationServer(
            fmodel, max_slots=slots, block_size=bs, max_prompt_len=mp,
            max_new_tokens=max_budget, prefill_chunk_tokens=chunk,
            enable_prefix_cache=True)) for i in range(n_replicas)]
        plan = (FaultPlan([("replica_kill", n_req // 3)],
                          name="bench-kill") if n_replicas >= 2
                else None)
        jpath = tempfile.NamedTemporaryFile(
            suffix=".journal", delete=False).name
        router = FleetRouter(reps, journal=jpath, fault_plan=plan,
                             probe_interval_s=0.25, seed=5).start()
        try:
            t0 = time.time()
            futs, arrival = [], 0.0
            mig_first = threading.Event()
            for i, p in enumerate(pool):
                arrival += gaps[i]
                dt = arrival - (time.time() - t0)
                if dt > 0:
                    time.sleep(dt)
                # request 0 is the migration candidate: a longer
                # budget keeps it live until the mid-run migrate call
                kw = {}
                if i == 0:
                    kw = {"max_new_tokens": mig_budget,
                          "on_token":
                              lambda t, r: mig_first.set()}
                else:
                    kw = {"max_new_tokens": new}
                futs.append(router.submit(
                    p, sampling=samplings[i], **kw))
                if i == n_req // 2 and n_replicas >= 2:
                    # planned live migration mid-run (first token
                    # already streamed, so the session is resident)
                    mig_first.wait(timeout=120)
                    try:
                        router.migrate_session(
                            list(router._sessions)[0])
                    except KeyError:
                        pass  # finished early: nothing to migrate
            hashes = [hashlib.md5(np.ascontiguousarray(
                f.result(timeout=900)).tobytes()).hexdigest()
                for f in futs]
            st = router.stats()
            eng = [rep.server.stats() for rep in reps
                   if not rep.dead]
        finally:
            router.stop()
            try:
                os.unlink(jpath)
            except OSError:
                pass
        return hashes, st, eng

    drive(counts[0])  # discarded warm pass: compiles stay out of the
    # measured windows (every drive shares the in-process jit caches)
    by_tok, by_ttft = {}, {}
    parity = True
    base_hashes = None
    fail_ct = fail_sess = kills = migs = prefix_routed = 0
    itl_p99 = 0.0
    prefill_disp = 0
    for n in counts:
        hashes, st, eng = drive(n)
        if base_hashes is None:
            base_hashes = hashes
        elif hashes != base_hashes:
            parity = False
        by_tok[str(n)] = st["new_tokens"] / max(st["wall_s"], 1e-9)
        by_ttft[str(n)] = st["ttft_p99_ms"]
        if n == counts[-1]:
            fail_ct = st["failovers"]
            fail_sess = st["failover_sessions"]
            kills = st["replica_kills"]
            migs = st["migrations"]
            prefix_routed = st["prefix_routed"]
            itl_p99 = max((e["itl_p99_ms"] for e in eng), default=0.0)
            prefill_disp = sum(e["prefill_dispatches"] for e in eng)
    return {
        "replica_counts": counts,
        "n_req": n_req,
        "tokens_per_sec_by_replicas": by_tok,
        "ttft_p99_ms_by_replicas": by_ttft,
        "failover_count": fail_ct,
        "failover_sessions": fail_sess,
        "replica_kills": kills,
        "migrated_sessions": migs,
        "prefix_routed": prefix_routed,
        "survivor_token_parity": parity,
        "parity_md5": hashlib.md5(
            "".join(base_hashes).encode()).hexdigest(),
        "itl_p99_ms": itl_p99,
        "prefill_dispatches": prefill_disp,
    }


def _bench_served_elastic(model, cfg, on_tpu, tiny):
    """Elastic sub-axis of `bench.py served` (ISSUE 20): a fixed-seed
    diurnal + flash-crowd arrival trace (calm shoulder, a burst of
    near-simultaneous arrivals, calm shoulder) driven through STATIC
    fleets of every candidate size and through an AUTOSCALED fleet
    that starts at 1 replica and follows the queue-pressure policy
    (scale up into the crowd behind the warm readiness gate, drain +
    migrate + retire back down after it).

    The record carries the elastic acceptance bars: the autoscaled
    fleet's p99 TTFT holds the declared SLO at materially fewer
    replica-seconds than the best static size that also holds it; the
    md5 over every request's output tokens is IDENTICAL across all
    drives — every scale-up, drain migration and retire is
    token-invisible; and the live run's decision journal replays
    byte-for-byte from its recorded (now, snapshot) tick log."""
    import concurrent.futures
    import hashlib
    import tempfile

    from paddle_tpu.fleet import (Autoscaler, AutoscalePolicy,
                                  FleetRouter, Replica)
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.sampling import SamplingParams

    if tiny:
        emodel = model
        counts = [1, 2]
        calm_n, peak_n, new, slots, bs, mp, chunk = 2, 6, 6, 2, 4, 12, 12
        calm_gap, peak_gap = 0.05, 0.002
        slo_floor_ms = 50.0
    elif on_tpu:
        emodel = model
        counts = [1, 2, 4]
        calm_n, peak_n, new, slots, bs, mp, chunk = \
            8, 24, 24, 4, 128, 256, 256
        calm_gap, peak_gap = 0.25, 0.002
        slo_floor_ms = 100.0
    else:
        ecfg = GPT2Config.tiny()  # dispatch-bound CPU proxy
        ecfg.dropout = 0.0
        emodel = GPT2(ecfg)
        emodel.eval()
        counts = [1, 2]
        calm_n, peak_n, new, slots, bs, mp, chunk = 8, 24, 12, 2, 4, 12, 12
        calm_gap, peak_gap = 0.3, 0.002
        slo_floor_ms = 50.0
    vocab = emodel.cfg.vocab_size
    n_req = calm_n + peak_n + calm_n
    rng = np.random.RandomState(73)
    pool = [rng.randint(1, vocab,
                        (int(rng.randint(4, mp + 1)),)).astype(np.int32)
            for _ in range(n_req)]
    # half greedy, half EXPLICIT-seed sampled: parity must hold for
    # both, independent of router seed resolution
    samplings = [None if i % 2 == 0 else
                 SamplingParams(temperature=0.8, top_p=0.9,
                                seed=2000 + i)
                 for i in range(n_req)]
    g = np.random.RandomState(79)
    gaps = np.concatenate([
        g.exponential(calm_gap, size=calm_n),
        g.exponential(peak_gap, size=peak_n),  # the flash crowd
        g.exponential(calm_gap, size=calm_n),
    ])

    def _engine():
        return PagedGenerationServer(
            emodel, max_slots=slots, block_size=bs, max_prompt_len=mp,
            max_new_tokens=new, prefill_chunk_tokens=chunk,
            enable_prefix_cache=True)

    policy = AutoscalePolicy(
        min_replicas=1, max_replicas=max(counts),
        up_headroom_frac=0.0, down_headroom_frac=0.0,
        up_queue_per_slot=1.0, up_after=1, up_cooldown_s=0.0,
        down_queue_per_slot=0.0, down_after=3, down_cooldown_s=0.0)

    def drive(n_replicas, autoscale=False):
        reps = [Replica(f"e{i}", _engine())
                for i in range(n_replicas)]
        jpath = tempfile.NamedTemporaryFile(
            suffix=".journal", delete=False).name
        router = FleetRouter(reps, journal=jpath,
                             probe_interval_s=0.25, seed=5).start()
        auto = None
        if autoscale:
            # pre-warm the spawn pool OUTSIDE the measured window
            # (same discipline as the discarded warm drives elsewhere
            # in this file: bucket compiles never land in a measured
            # trace).  The warm readiness gate still verifies
            # `_warm_ran` on every admit — actuation just doesn't
            # compile mid-flash-crowd.
            spares = []
            for _ in range(policy.max_replicas - n_replicas):
                e = _engine()
                e.warm_buckets()
                spares.append(e)

            def _spawn(name):
                if spares:
                    return spares.pop()
                e = _engine()  # re-up after a retire: warm is cached
                e.warm_buckets()
                return e

            auto = Autoscaler(router, policy, spawn=_spawn)
        last_tick = [0.0]

        def maybe_tick():
            # 0.25 s cadence: plenty for the hysteresis windows, and
            # capacity federation stays off the CPU the engines need
            now = time.monotonic()
            if auto is not None and now - last_tick[0] >= 0.25:
                last_tick[0] = now
                auto.tick(now=now)

        try:
            t0 = time.monotonic()
            futs, arrival = [], 0.0
            for i, p in enumerate(pool):
                arrival += gaps[i]
                while True:
                    dt = arrival - (time.monotonic() - t0)
                    if dt <= 0:
                        break
                    maybe_tick()
                    time.sleep(min(dt, 0.02))
                futs.append(router.submit(p, sampling=samplings[i],
                                          max_new_tokens=new))
                if auto is not None and \
                        i == calm_n + min(peak_n, 2 * slots + 1) - 1:
                    # the crowd's head has provably over-filled the
                    # single replica (2 slots busy + a queue past the
                    # pressure bar): take one unthrottled tick so the
                    # scale-up lands EARLY and the rest of the crowd
                    # routes to the surge replica (the throttled
                    # cadence can step clean over a burst that
                    # submits in a few milliseconds)
                    last_tick[0] = time.monotonic()
                    auto.tick(now=last_tick[0])
                else:
                    maybe_tick()
            hashes = []
            for f in futs:
                while True:
                    try:
                        out = f.result(timeout=0.05 if auto else 600)
                        break
                    except concurrent.futures.TimeoutError:
                        maybe_tick()
                hashes.append(hashlib.md5(np.ascontiguousarray(
                    out).tobytes()).hexdigest())
            wall_s = time.monotonic() - t0
            if auto is not None:
                # post-crowd ticks: the calm hysteresis drains +
                # retires the surge replicas back to min (bounded —
                # metering keeps running, so a lazy tail COSTS)
                for _ in range(200):
                    auto.tick(now=time.monotonic())
                    if len(router.replicas) <= policy.min_replicas:
                        break
                    time.sleep(0.02)
            st = router.stats()
            eng = [r.server.stats() for r in router.replicas
                   if not r.dead]
            itl = max((e.get("itl_p99_ms", 0.0) for e in eng),
                      default=0.0)
            pfd = sum(e.get("prefill_dispatches", 0) for e in eng)
            ablk = auto.stats_block() if auto is not None else None
            replay_ok = True
            if auto is not None:
                recorded = json.loads(json.dumps(auto.recorded))
                replay_ok = (Autoscaler.replay(policy, recorded)
                             == auto.decisions)
        finally:
            if auto is not None:
                auto.stop()
            router.stop()
            try:
                os.unlink(jpath)
            except OSError:
                pass
        return {"hashes": hashes, "wall_s": wall_s,
                "ttft_p99_ms": st["ttft_p99_ms"],
                "migrations": st["migrations"],
                "failover_sessions": st["failover_sessions"],
                "replicas_added": st.get("replicas_added", 0),
                "auto": ablk, "replay_ok": replay_ok,
                "itl_p99_ms": itl, "prefill_dispatches": pfd,
                "stats": st}

    drive(counts[0])  # discarded warm pass: compiles stay out of the
    # measured windows (every drive shares the in-process jit caches)
    static = {n: drive(n) for n in counts}
    elastic = drive(1, autoscale=True)

    # the declared TTFT SLO: a floor, or 1.5x the best static p99 —
    # generous enough for the best static size AND a well-behaved
    # autoscaled fleet, tight enough that the undersized static
    # shoulder (queueing through the flash crowd) misses it
    best_static_p99 = min(s["ttft_p99_ms"] for s in static.values())
    slo_ttft_ms = max(slo_floor_ms, 1.5 * best_static_p99)
    static_rs = {n: n * s["wall_s"] for n, s in static.items()}
    meeting = [n for n in counts
               if static[n]["ttft_p99_ms"] <= slo_ttft_ms]
    best_n = min(meeting) if meeting else max(counts)
    rs_best = static_rs[best_n]
    rs_auto = elastic["auto"]["replica_seconds"]
    all_hashes = [s["hashes"] for s in static.values()] \
        + [elastic["hashes"]]
    parity = all(h == all_hashes[0] for h in all_hashes[1:])
    return {
        "replica_counts": counts,
        "n_req": n_req,
        "slo_ttft_ms": slo_ttft_ms,
        "ttft_p99_ms_by_static": {
            str(n): static[n]["ttft_p99_ms"] for n in counts},
        "ttft_p99_ms_autoscaled": elastic["ttft_p99_ms"],
        "slo_met_autoscaled":
            elastic["ttft_p99_ms"] <= slo_ttft_ms,
        "best_static_replicas": best_n,
        "replica_seconds_by_static": {
            str(n): static_rs[n] for n in counts},
        "replica_seconds_best_static": rs_best,
        "replica_seconds_autoscaled": rs_auto,
        "replica_seconds_saved_frac": 1.0 - rs_auto / max(rs_best,
                                                          1e-9),
        "scale_ups": elastic["auto"]["scale_ups"],
        "scale_downs": elastic["auto"]["scale_downs"],
        "decisions_total": elastic["auto"]["decisions"],
        "autoscale_errors": elastic["auto"]["errors"],
        "migrated_sessions": elastic["migrations"],
        "failover_sessions": elastic["failover_sessions"],
        "token_parity": parity,
        "parity_md5": hashlib.md5(
            "".join(elastic["hashes"]).encode()).hexdigest(),
        "decision_replay_identical": elastic["replay_ok"],
        "new_tokens": elastic["stats"]["new_tokens"],
        "wall_s_autoscaled": elastic["wall_s"],
        "itl_p99_ms": elastic["itl_p99_ms"],
        "prefill_dispatches": elastic["prefill_dispatches"],
    }


def _bench_served_fleet_procs(on_tpu, tiny):
    """Fleet-procs sub-axis of `bench.py served` (r19): the fleet at
    REAL OS-process granularity. Worker replicas are spawned with
    `RemoteReplica.spawn` (each builds the model from the shared seed
    recipe — no weight shipping) and driven over the stdlib HTTP wire
    transport at 1/2/4 processes (tiny: 1/2) with IDENTICAL fixed-seed
    Poisson arrivals through the COMPOSED stack (prefix cache +
    speculation + int8 KV pool, so every wire hop rides the r20 int8
    codec bit-exactly). The proofs carried by the record: (a) every
    request's output md5 is IDENTICAL to an in-process twin fleet at
    every process count — the wire is token-invisible; (b) a
    prefill-heavy burst A/B through a disaggregated 1-prefill +
    1-decode pool vs the same two workers pooled, with the handoff
    count and the cross-topology token-parity md5."""
    import hashlib
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from paddle_tpu.fleet import (DisaggRouter, FleetRouter, Replica,
                                  RemoteReplica)
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.sampling import SamplingParams
    import paddle_tpu as paddle

    if tiny:
        counts = [1, 2]
        n_req, new, slots, bs, mp, chunk = 6, 8, 2, 4, 12, 12
        mcfg = {"vocab_size": 512, "hidden_size": 128,
                "num_layers": 2, "num_heads": 4, "max_position": 128,
                "dropout": 0.0}
        n_burst, burst_new = 4, 4
    elif on_tpu:
        counts = [1, 2, 4]
        n_req, new, slots, bs, mp, chunk = 24, 32, 4, 64, 64, 64
        mcfg = {"vocab_size": 2048, "hidden_size": 256,
                "num_layers": 4, "num_heads": 8, "max_position": 512,
                "dropout": 0.0}
        n_burst, burst_new = 8, 4
    else:
        counts = [1, 2, 4]
        n_req, new, slots, bs, mp, chunk = 12, 16, 2, 4, 12, 12
        mcfg = {"vocab_size": 512, "hidden_size": 128,
                "num_layers": 2, "num_heads": 4, "max_position": 128,
                "dropout": 0.0}
        n_burst, burst_new = 6, 4
    mseed = 100
    # one burst request holds a long decode budget so the disagg
    # handoff loop reliably catches it live on the prefill pool (the
    # same designated-candidate pattern the fleet axis uses for its
    # mid-run migration)
    burst_hold = new * 3
    srv_kw = {"max_slots": slots, "block_size": bs,
              "max_prompt_len": mp,
              "max_new_tokens": max(new, burst_hold),
              "prefill_chunk_tokens": chunk,
              "enable_prefix_cache": True, "speculation": True,
              "quantization": "w8a16", "kv_dtype": "int8"}
    vocab = mcfg["vocab_size"]
    rng = np.random.RandomState(71)
    pool = [rng.randint(1, vocab,
                        (int(rng.randint(4, mp + 1)),)).astype(np.int32)
            for _ in range(n_req)]
    samplings = [None if i % 2 == 0 else
                 SamplingParams(temperature=0.8, top_p=0.9,
                                seed=2000 + i)
                 for i in range(n_req)]
    gaps = np.random.RandomState(73).exponential(0.01, size=n_req)
    brng = np.random.RandomState(79)
    burst_pool = [brng.randint(1, vocab, (mp,)).astype(np.int32)
                  for _ in range(n_burst)]

    # the in-process twin: same seed recipe the workers rebuild from,
    # so weights match bit-for-bit without shipping them
    paddle.seed(mseed)
    tmodel = GPT2(GPT2Config(**mcfg))
    tmodel.eval()

    wcfg = {"model": {"kind": "gpt2", "seed": mseed, "config": mcfg},
            "server": srv_kw}
    with ThreadPoolExecutor(max_workers=max(counts)) as ex:
        workers = list(ex.map(
            lambda i: RemoteReplica.spawn(
                f"w{i}", wcfg, keep_alive_on_stop=True),
            range(max(counts))))
    try:
        def run(router, prompts, spars, budgets, arrivals):
            t0 = time.time()
            futs, arrival = [], 0.0
            for i, p in enumerate(prompts):
                if arrivals is not None:
                    arrival += arrivals[i]
                    dt = arrival - (time.time() - t0)
                    if dt > 0:
                        time.sleep(dt)
                futs.append(router.submit(
                    p, sampling=spars[i], max_new_tokens=budgets[i]))
            hashes = [hashlib.md5(np.ascontiguousarray(
                f.result(timeout=900)).tobytes()).hexdigest()
                for f in futs]
            return hashes, router.stats()

        def drive(reps):
            jpath = tempfile.NamedTemporaryFile(
                suffix=".journal", delete=False).name
            router = FleetRouter(reps, journal=jpath,
                                 probe_interval_s=0.5, seed=5).start()
            try:
                return run(router, pool, samplings, [new] * n_req,
                           gaps)
            finally:
                router.stop()
                try:
                    os.unlink(jpath)
                except OSError:
                    pass

        def burst(router):
            # prefill-heavy burst: full-length prompts, tiny decode
            # budgets, all submitted at once — TTFT-bound by design.
            # Request 0 carries the long hold budget (handoff window).
            spars = [None] * n_burst
            budgets = [burst_hold] + [burst_new] * (n_burst - 1)
            return run(router, burst_pool, spars, budgets, None)

        # in-process twin fleet: the parity baseline AND the
        # transport-overhead reference (discarded first pass warms
        # the parent-process jit caches)
        def inproc_reps(n):
            return [Replica(f"t{i}", PagedGenerationServer(
                tmodel, **srv_kw)) for i in range(n)]

        drive(inproc_reps(1))  # discarded warm pass
        # discarded warm pass PER WORKER: every worker process takes
        # the full workload once so its first-dispatch compiles
        # (prefill buckets, decode, speculation) stay out of every
        # measured window, matching the warmed in-process twin
        for w in workers:
            drive([w])
        base_hashes, st_in = drive(inproc_reps(1))
        tok_inproc = st_in["new_tokens"] / max(st_in["wall_s"], 1e-9)

        by_tok, by_ttft = {}, {}
        parity = True
        for n in counts:
            hashes, st = drive(workers[:n])
            if hashes != base_hashes:
                parity = False
            by_tok[str(n)] = st["new_tokens"] / max(st["wall_s"],
                                                    1e-9)
            by_ttft[str(n)] = st["ttft_p99_ms"]

        # prefill-heavy burst A/B: the SAME two workers pooled vs
        # disaggregated (w0 = prefill pool, w1 = decode pool; finished
        # KV blocks stream over the wire through the int8 codec)
        def pooled_burst():
            jpath = tempfile.NamedTemporaryFile(
                suffix=".journal", delete=False).name
            router = FleetRouter(workers[:2], journal=jpath,
                                 probe_interval_s=0.5,
                                 seed=5).start()
            try:
                return burst(router)
            finally:
                router.stop()
                os.unlink(jpath)

        def disagg_burst():
            jpath = tempfile.NamedTemporaryFile(
                suffix=".journal", delete=False).name
            drouter = DisaggRouter(
                [workers[0]], [workers[1]], journal=jpath,
                handoff_poll_s=0.002,
                probe_interval_s=0.5, seed=5).start()
            try:
                return burst(drouter)
            finally:
                drouter.stop()
                os.unlink(jpath)

        # discarded warm passes on BOTH sides: the burst prompts'
        # prefill shapes INCLUDING the prefix-hit suffix buckets of a
        # repeat pass (and the disagg handoff path) compile outside
        # the measured A/B windows — otherwise whichever side runs
        # first eats the compiles and the A/B measures XLA, not
        # topology
        pooled_burst()
        pooled_burst()
        disagg_burst()
        pooled_hashes, st_pooled = pooled_burst()
        disagg_hashes, st_disagg = disagg_burst()

        eng = [w.server.stats() for w in workers[:counts[-1]]]
        itl_p99 = max((e["itl_p99_ms"] for e in eng), default=0.0)
        prefill_disp = sum(e["prefill_dispatches"] for e in eng)
    finally:
        for w in workers:
            w.terminate()

    return {
        "process_counts": counts,
        "n_req": n_req,
        "tokens_per_sec_by_procs": by_tok,
        "ttft_p99_ms_by_procs": by_ttft,
        "tokens_per_sec_inproc_1": tok_inproc,
        "wire_token_parity": parity,
        "parity_md5": hashlib.md5(
            "".join(base_hashes).encode()).hexdigest(),
        "burst_n_req": n_burst,
        "burst_ttft_p99_ms_pooled": st_pooled["ttft_p99_ms"],
        "burst_ttft_p99_ms_disagg": st_disagg["ttft_p99_ms"],
        "disagg_handoffs": st_disagg["disagg"]["handoffs"],
        "disagg_handoffs_failed":
            st_disagg["disagg"]["handoffs_failed"],
        "disagg_token_parity": disagg_hashes == pooled_hashes,
        "itl_p99_ms": itl_p99,
        "prefill_dispatches": prefill_disp,
    }


def _bench_served_quantization(model, cfg, prompts, slots, bs, hi, new,
                               k, chunk, on_tpu, tiny):
    """Quantization sub-axis of `bench.py served` (quantized-serving
    round): the SAME fixed-seed Poisson arrival schedule driven through
    three fresh servers — bf16, W8A16 weights, and W8A16 + int8 KV
    pool — measuring served tok/s, TTFT/ITL, and the accuracy delta
    (greedy token match vs the bf16 outputs, plus a decoder-level
    logit probe on a fixed batch). The axis also reports MAX CONCURRENT
    SLOTS AT FIXED POOL BYTES: holding the bf16 pool's byte budget
    constant, how many worst-case requests each kv dtype's pool can
    reserve — the capacity lever int8 KV exists for, and the one a
    CPU run can prove exactly (CPU has no int8 MXU, so the tok/s
    headline is chip-only; the record self-describes which bar it
    meets)."""
    import jax.numpy as jnp

    from paddle_tpu.inference import (PagedGenerationServer,
                                      PagedKVCache,
                                      measure_poisson_load)
    from paddle_tpu.inference.kv_cache import blocks_for
    from paddle_tpu.nn.decode import PagedDecoder
    from paddle_tpu.sampling.buffers import greedy_args

    n_req = len(prompts)
    modes = (("bf16", None, None), ("w8a16", "w8a16", None),
             ("w8a16_kv8", "w8a16", "int8"))
    results = {}
    rps = None
    for name, quant, kvd in modes:
        srv = PagedGenerationServer(
            model, max_slots=slots, block_size=bs, max_prompt_len=hi,
            max_new_tokens=new, steps_per_dispatch=k,
            prefill_chunk_tokens=chunk, quantization=quant,
            kv_dtype=kvd).start()
        try:
            t_w0 = time.time()
            outs = [f.result(timeout=900) for f in
                    [srv.submit(p) for p in prompts]]  # warm + outputs
            if rps is None:  # one rate for ALL modes: identical
                # arrivals make the A/B/C comparison the dtype alone
                rps = 0.7 * n_req / max(time.time() - t_w0, 1e-6)
            # unmeasured Poisson warm (the shared-prefix-axis lesson):
            # churn packs different (T, rows, width) prefill buckets
            # than the closed-loop drain, and the quantized servers'
            # param/pool pytrees are fresh jit cache keys — those
            # compiles must not land in the measured window
            measure_poisson_load(srv, prompts, rps, n_req,
                                 seed=778, timeout=900)
            srv.reset_stats()
            st = measure_poisson_load(srv, prompts, rps, n_req,
                                      seed=777, timeout=900)
            st["quant"] = srv.stats()["quantization"]
            st["bytes_per_token"] = srv.cache.bytes_per_token
            st["pool_bytes"] = srv.cache.pool_bytes_total
            st["outs"] = outs
        finally:
            srv.stop()
        results[name] = st

    # accuracy delta vs bf16: greedy served outputs are deterministic
    # per prompt, so the warm-drain outputs compare token-for-token
    ref = results["bf16"]["outs"]
    for name in ("w8a16", "w8a16_kv8"):
        outs = results[name]["outs"]
        tot = sum(o.size for o in ref)
        match = sum((a[:min(a.size, b.size)] ==
                     b[:min(a.size, b.size)]).sum()
                    for a, b in zip(ref, outs))
        results[name]["token_match"] = match / max(tot, 1)

    # decoder-level logit probe: ONE prefill on a fixed batch per mode
    params, _ = model.functional_state()
    wq = model.quantize_weights(params)
    rngp = np.random.RandomState(3)
    B, S = min(4, slots), min(24, hi)
    ids = rngp.randint(1, cfg.vocab_size, (B, S)).astype(np.int32)
    lens = jnp.asarray(np.full((B,), S, np.int32))

    def probe_logits(p, kvd):
        cache = PagedKVCache(cfg.num_layers, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads,
                             block_size=bs,
                             num_blocks=B * blocks_for(S, bs) + 1,
                             dtype=p["ln_f.weight"].dtype, kv_dtype=kvd,
                             name=f"qprobe-{kvd}")
        for b in range(B):
            cache.allocate(b, S)
        dec = PagedDecoder.for_config(cfg, bs, return_logits=True,
                                      kv_dtype=kvd)
        out = dec.prefill(p, jnp.asarray(ids), lens,
                          jnp.asarray(cache.table_array(range(B))),
                          cache.k_blocks, cache.v_blocks,
                          greedy_args(B))
        return np.asarray(out[-1], np.float32)

    l_ref = probe_logits(params, None)
    l_q = probe_logits(wq, "int8")
    logit_mae = float(np.abs(l_q - l_ref).mean())
    logit_max = float(np.abs(l_q - l_ref).max())

    # slot capacity at FIXED pool bytes: hold the bf16 serving pool's
    # byte budget constant and count worst-case reservations each kv
    # dtype can back (blocks are the unit admission reasons about)
    m_width = blocks_for(hi + new + max(k - 1, 0), bs) + 0
    budget = results["bf16"]["pool_bytes"]

    def max_slots_at(kvd):
        probe = PagedKVCache(cfg.num_layers, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads,
                             block_size=bs, num_blocks=2,
                             dtype=params["ln_f.weight"].dtype,
                             kv_dtype=kvd, name=f"qcap-{kvd}")
        per_block = probe.pool_bytes_total / 2
        n_blocks = int(budget // per_block)
        return max(0, (n_blocks - 1) // m_width)

    return {"modes": results, "rps": rps, "logit_mae": logit_mae,
            "logit_max_abs": logit_max,
            "slots_bf16": max_slots_at(None),
            "slots_int8": max_slots_at("int8"),
            "pool_budget_bytes": budget}


def _served_sharded_worker(ndev, tiny):
    """Subprocess body of the sharded-serving axis: THIS process was
    spawned with `--xla_force_host_platform_device_count=ndev` (the
    multichip-dryrun trick), builds the pinned composed workload
    (greedy + fixed-seed sampled, prefix cache ON, speculation ON,
    int8 KV + W8A16) on a tp x dp mesh over those devices, and prints
    ONE JSON dict: measured tok/s + latency, the reservation-backed
    max concurrent slots at a FIXED per-device pool byte budget, and a
    signature of every emitted token stream (the parent asserts the
    signatures agree across device counts — mesh parity)."""
    import hashlib

    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.inference.kv_cache import blocks_for
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.sampling import SamplingParams
    from paddle_tpu.serving_dist import (ShardedEngineConfig,
                                         pool_blocks_for_budget)
    import paddle_tpu as paddle

    paddle.seed(0)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    tp = min(int(ndev), cfg.num_heads)
    dp = int(ndev) // tp
    sharding = (ShardedEngineConfig(tp=tp, dp=dp) if ndev > 1 else None)
    rng = np.random.RandomState(3)
    n_req = 6 if tiny else 12
    prompts = [rng.randint(1, cfg.vocab_size,
                           (int(rng.randint(4, 40)),)).astype(np.int32)
               for _ in range(n_req)]
    sps = [None if i % 2 == 0 else SamplingParams(
        temperature=0.8, top_p=(0.7, 0.85, 0.95)[i % 3],
        seed=1000 + i) for i in range(n_req)]
    new, slots, bs, chunk = 8, 2, 8, 16
    srv = PagedGenerationServer(
        model, max_slots=slots, block_size=bs, max_prompt_len=48,
        max_new_tokens=new, prefill_chunk_tokens=chunk,
        enable_prefix_cache=True, speculation=True, kv_dtype="int8",
        quantization="w8a16", sharding=sharding).start()
    try:
        def drain():
            return [f.result(timeout=600) for f in
                    [srv.submit(p, sampling=s)
                     for p, s in zip(prompts, sps)]]

        drain()  # warm/compile pass
        srv.reset_stats()
        outs = drain()
        st = srv.stats()
    finally:
        srv.stop()
    sig = hashlib.md5(
        b"|".join(np.asarray(o, np.int64).tobytes()
                  for o in outs)).hexdigest()
    # capacity at FIXED per-device pool bytes: the pool shards heads
    # over tp and blocks over dp, so the same per-HBM budget backs
    # tp*dp times the blocks (the CPU-provable half of the axis)
    budget = 1 << 20
    nb = pool_blocks_for_budget(cfg, bs, budget, tp=tp, dp=dp,
                                kv_dtype="int8")
    per_req = blocks_for(48 + new + 3, bs) + 1  # spec slack + CoW spare
    max_slots = (nb - 1) // per_req
    print(json.dumps({
        "devices": int(ndev), "tp": tp, "dp": dp,
        "tokens_per_sec": st["tokens_per_sec"],
        "p99_ms": st["p99_ms"],
        "itl_p99_ms": st["itl_p99_ms"],
        "prefill_dispatches": st["prefill_dispatches"],
        "max_slots": int(max_slots),
        "pool_budget_bytes": budget,
        "token_sig": sig,
        "sharding": st["sharding"],
    }))


def _bench_served_sharded(on_tpu, tiny):
    """Sharded-serving axis (serving_dist round): the SAME pinned
    composed workload served at 1/2/4/8 forced-host CPU devices
    (tiny: 1/2), one subprocess per device count so each gets its own
    `--xla_force_host_platform_device_count`.  Reports tok/s and the
    reservation-backed max concurrent slots at FIXED per-device pool
    bytes per count, and asserts token parity across counts.  Always a
    CPU host-mesh measurement — collectives run on host cores, so
    capacity is the CPU-provable number and tok/s scaling is a chip
    number (rerun queued with the r9-r13 carry-over)."""
    counts = (1, 2) if tiny else (1, 2, 4, 8)
    results = {}
    for n in counts:
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
        args = [sys.executable, os.path.abspath(__file__),
                "served-sharded-worker", str(n)]
        if tiny:
            args.append("--tiny")
        r = subprocess.run(args, env=env, capture_output=True,
                           text=True, timeout=900,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
        if r.returncode != 0:
            raise RuntimeError(
                f"sharded worker ({n} devices) failed:\n"
                f"{r.stderr[-2000:]}")
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("{")][-1]
        results[n] = json.loads(line)
    return results


def _longctx_tier_probe(model, cfg, tiny):
    """Host-RAM KV tier half of the long-context axis (runs inside the
    sp=1 worker). n_sessions long-history conversations resume
    round-robin through a device pool deliberately sized for ~1.5 of
    them: with the tier OFF the pool must EVICT an idle session's
    retained history to serve the next one, so its resume recomputes
    the whole prefix (the ITL/TTFT cliff); with the tier ON the same
    churn DEMOTES the history to host RAM and the resume PROMOTES it
    back — no recompute, byte-identical tokens. Returns the empirical
    churn numbers plus the reservation-backed session capacity at a
    FIXED per-device pool byte budget (host tier provisioned at 4x the
    device budget), the CPU-provable half of the capacity claim."""
    import hashlib
    import time as _time

    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.inference.kv_cache import blocks_for
    from paddle_tpu.inference.kv_tier import HostKVTier
    from paddle_tpu.serving_dist import pool_blocks_for_budget

    rng = np.random.RandomState(23)
    n_sess = 3 if tiny else 4
    hist_len, bs, new, chunk = 40, 8, 6, 16
    histories = [rng.randint(1, cfg.vocab_size,
                             (hist_len,)).astype(np.int32)
                 for _ in range(n_sess)]
    tails = [rng.randint(1, cfg.vocab_size, (5,)).astype(np.int32)
             for _ in range(n_sess)]
    nb = 16  # ~1.5 sessions' retained blocks + the active working set

    def run(tier):
        srv = PagedGenerationServer(
            model, max_slots=1, block_size=bs, max_prompt_len=64,
            max_new_tokens=new, prefill_chunk_tokens=chunk,
            num_blocks=nb, enable_prefix_cache=True, kv_dtype="int8",
            kv_tier=tier, temperature=0.0).start()
        try:
            # turn 1: each session's history lands in the prefix cache
            turn1 = [np.asarray(srv.submit(h).result(timeout=600))
                     for h in histories]
            srv.reset_stats()
            # turn 2: round-robin resumes — every resume follows the
            # OTHER sessions' turns, so the churn already displaced
            # this session's retained blocks (evicted vs demoted)
            t_res, outs = [], []
            for i in range(n_sess):
                p = np.concatenate([turn1[i], tails[i]])
                t0 = _time.perf_counter()
                outs.append(np.asarray(
                    srv.submit(p).result(timeout=600)))
                t_res.append((_time.perf_counter() - t0) * 1e3)
            st = srv.stats()
        finally:
            srv.stop()
        sig = hashlib.md5(
            b"|".join(o.astype(np.int64).tobytes()
                      for o in outs)).hexdigest()
        return {"resume_ms": sorted(t_res),
                "prefill_dispatches": st["prefill_dispatches"],
                "itl_p99_ms": st["itl_p99_ms"],
                "tier": st["kv_cache"]["tier"], "sig": sig}

    off = run(None)
    on = run(HostKVTier(capacity_blocks=64, watermark=0.5))

    def run_queued(prefetch):
        """Prefetch A/B half (ISSUE 18): the same churned resumes, but
        each resume is submitted while a short busy request still
        occupies the single slot — the round the engine is computing
        IS the window the tier prefetch-ahead promotes into. Sync
        (prefetch off) pays the promote at admission instead; the busy
        work is fixed-seed identical either way, so the resume-wall
        delta is exactly the promote cost hidden vs exposed."""
        srv = PagedGenerationServer(
            model, max_slots=1, block_size=bs, max_prompt_len=64,
            max_new_tokens=new, prefill_chunk_tokens=chunk,
            num_blocks=nb, enable_prefix_cache=True, kv_dtype="int8",
            kv_tier=HostKVTier(capacity_blocks=64, watermark=0.5),
            tier_prefetch=(True if prefetch else None),
            temperature=0.0).start()
        try:
            turn1 = [np.asarray(srv.submit(h).result(timeout=600))
                     for h in histories]
            srv.reset_stats()
            t_res, outs = [], []
            for i in range(n_sess):
                p = np.concatenate([turn1[i], tails[i]])
                busy = srv.submit(tails[(i + 1) % n_sess])
                t0 = _time.perf_counter()
                fut = srv.submit(p)
                busy.result(timeout=600)
                outs.append(np.asarray(fut.result(timeout=600)))
                t_res.append((_time.perf_counter() - t0) * 1e3)
            st = srv.stats()
        finally:
            srv.stop()
        sig = hashlib.md5(
            b"|".join(o.astype(np.int64).tobytes()
                      for o in outs)).hexdigest()
        return {"resume_ms": sorted(t_res), "sig": sig,
                "prefetch": st["tier_prefetch"]}

    pf_sync = run_queued(False)
    pf_on = run_queued(True)
    # reservation-backed capacity at FIXED per-device pool bytes: a
    # session is "at the ITL bar" when its history is resident
    # (device or host), so a resume re-attaches instead of recomputing
    budget = 1 << 20
    host_x = 4
    nbb = pool_blocks_for_budget(cfg, bs, budget, kv_dtype="int8")
    sess_blocks = blocks_for(hist_len, bs)
    active = blocks_for(64 + new + 3, bs) + 1  # working set + spare
    resident_off = max(0, nbb - 1 - active) // sess_blocks
    resident_on = resident_off + host_x * (nbb - 1) // sess_blocks
    return {
        "n_sessions": n_sess, "history_tokens": hist_len,
        "device_blocks": nb,
        "resume_ttft_p50_ms_on": on["resume_ms"][len(on["resume_ms"])
                                                 // 2],
        "resume_ttft_p50_ms_off": off["resume_ms"][
            len(off["resume_ms"]) // 2],
        "resume_prefill_dispatches_on": on["prefill_dispatches"],
        "resume_prefill_dispatches_off": off["prefill_dispatches"],
        "itl_p99_ms_on": on["itl_p99_ms"],
        "itl_p99_ms_off": off["itl_p99_ms"],
        "demotions": on["tier"]["demotions"],
        "promotions": on["tier"]["promotions"],
        "hit_tokens": on["tier"]["hit_tokens"],
        "sig_on": on["sig"], "sig_off": off["sig"],
        "resume_ttft_p50_ms_prefetch":
            pf_on["resume_ms"][len(pf_on["resume_ms"]) // 2],
        "resume_ttft_p50_ms_sync":
            pf_sync["resume_ms"][len(pf_sync["resume_ms"]) // 2],
        "prefetch": pf_on["prefetch"],
        "sig_prefetch": pf_on["sig"], "sig_sync": pf_sync["sig"],
        "pool_budget_bytes": budget,
        "host_budget_bytes": host_x * budget,
        "sessions_at_bar_on": int(resident_on),
        "sessions_at_bar_off": int(resident_off),
        "max_ctx_tokens_on": int((nbb - 1) * bs
                                 + host_x * (nbb - 1) * bs),
        "max_ctx_tokens_off": int((nbb - 1) * bs),
    }


def _served_longctx_worker(sp, tiny):
    """Subprocess body of the long-context axis: THIS process was
    spawned with `--xla_force_host_platform_device_count=sp`, serves
    the SAME fixed-seed huge prompts (each several chunk budgets long,
    so prefill cost IS the TTFT) sequentially through the
    sequence-parallel packed prefill at that sp degree, and prints ONE
    JSON dict: client-side TTFT percentiles, prefill dispatch count
    (sp multiplies the chunk budget, so dispatches divide by ~sp —
    exact), tok/s + latency, and the md5 stream signature the parent
    asserts across sp degrees. The sp=1 worker also runs the host-RAM
    KV tier churn probe (`_longctx_tier_probe`)."""
    import hashlib
    import time as _time

    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.serving_dist import ShardedEngineConfig
    import paddle_tpu as paddle

    paddle.seed(0)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    sp = int(sp)
    rng = np.random.RandomState(17)
    n_req = 3 if tiny else 6
    lens = [int(rng.randint(72, 96)) for _ in range(n_req)]
    prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    new, bs, chunk = 8, 8, 16

    def measure(sp_attention):
        """One server at this sp degree with the given fresh-K/V
        exchange mode; returns (sorted ttfts, md5 sig, stats)."""
        sharding = (ShardedEngineConfig(sp=sp, sp_attention=sp_attention)
                    if sp > 1 else None)
        srv = PagedGenerationServer(
            model, max_slots=2, block_size=bs, max_prompt_len=112,
            max_new_tokens=new, prefill_chunk_tokens=chunk,
            num_blocks=64, sharding=sharding, temperature=0.0).start()
        try:
            def drain(ttfts=None):
                outs = []
                for p in prompts:  # sequential: TTFT is pure prefill
                    first = []

                    def on_tok(_tok, _reason, first=first):
                        if not first:
                            first.append(_time.perf_counter())
                    t0 = _time.perf_counter()
                    outs.append(srv.submit(p, on_token=on_tok)
                                .result(timeout=600))
                    if ttfts is not None:
                        ttfts.append((first[0] - t0) * 1e3)
                return outs

            drain()  # warm/compile pass
            srv.reset_stats()
            ttfts = []
            outs = drain(ttfts)
            st = srv.stats()
        finally:
            srv.stop()
        sig = hashlib.md5(
            b"|".join(np.asarray(o, np.int64).tobytes()
                      for o in outs)).hexdigest()
        ttfts.sort()
        return ttfts, sig, st

    ttfts, sig, st = measure("allgather")
    # sp_attention A/B (ISSUE 18): the SAME prompts through the
    # memory-flat ring exchange — token parity + the peak fresh-K/V
    # bytes both modes report through the engine's per-dispatch gauge
    sp_ab = None
    if sp > 1:
        r_tt, r_sig, r_st = measure("ring")
        sp_ab = {
            "ring_ttft_p50_ms": r_tt[len(r_tt) // 2],
            "ring_token_sig": r_sig,
            "ring_peak_bytes":
                r_st["sharding"]["sp_attention_bytes_peak"],
            "allgather_peak_bytes":
                st["sharding"]["sp_attention_bytes_peak"],
        }
    tier = _longctx_tier_probe(model, cfg, tiny) if sp == 1 else None
    print(json.dumps({
        "sp": sp, "prompt_tokens": lens,
        "ttft_p50_ms": ttfts[len(ttfts) // 2],
        "ttft_p99_ms": ttfts[min(len(ttfts) - 1,
                                 int(0.99 * len(ttfts)))],
        "tokens_per_sec": st["tokens_per_sec"],
        "p99_ms": st["p99_ms"],
        "itl_p99_ms": st["itl_p99_ms"],
        "prefill_dispatches": st["prefill_dispatches"],
        "token_sig": sig,
        "sharding": st["sharding"],
        "sp_ab": sp_ab,
        "tier": tier,
    }))


def _bench_served_longctx(on_tpu, tiny):
    """Long-context axis (r21): the SAME fixed-seed huge prompts
    prefilled at sp∈{1,2,4} forced-host CPU devices (tiny: 1/2), one
    subprocess per sp degree so each gets its own
    `--xla_force_host_platform_device_count`.  Reports TTFT scaling
    with sp, the exact prefill-dispatch division, token parity across
    degrees, and (from the sp=1 worker) the host-RAM KV tier's
    session-capacity numbers.  Always a CPU host-mesh measurement —
    the sp shards share one core, so the dispatch division and the
    tier capacity are the CPU-provable halves and the TTFT wall-clock
    scaling is a chip number (rerun queued)."""
    counts = (1, 2) if tiny else (1, 2, 4)
    results = {}
    for n in counts:
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
        args = [sys.executable, os.path.abspath(__file__),
                "served-longctx-worker", str(n)]
        if tiny:
            args.append("--tiny")
        r = subprocess.run(args, env=env, capture_output=True,
                           text=True, timeout=900,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
        if r.returncode != 0:
            raise RuntimeError(
                f"long-context worker (sp={n}) failed:\n"
                f"{r.stderr[-2000:]}")
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("{")][-1]
        results[n] = json.loads(line)
    return results


def _served_collectives_worker(ndev, tiny):
    """Subprocess body of the quantized-collectives axis: THIS process
    was spawned with `--xla_force_host_platform_device_count=ndev`,
    serves the SAME fixed-seed Poisson arrivals through the composed
    stack (prefix cache, speculation, W8A16 + int8 KV, unified async
    round) on a tp=ndev mesh under each collective wire —
    bf16 (collective_quant=None), int8, int4-group — and prints ONE
    JSON dict: per-mode tok/s, analytic wire bytes (actual + what the
    unquantized collectives would ship for the identical dispatches),
    greedy-token match vs the in-process bf16 run, md5 stream
    signatures, dispatches-per-round and the compile-window proof."""
    import hashlib
    import time as _time

    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config
    from paddle_tpu.sampling import SamplingParams
    from paddle_tpu.serving_dist import ShardedEngineConfig
    import paddle_tpu as paddle

    paddle.seed(0)
    cfg = GPT2Config.tiny()
    cfg.dropout = 0.0
    model = GPT2(cfg)
    model.eval()
    tp = min(int(ndev), cfg.num_heads)
    rng = np.random.RandomState(3)
    n_req = 6 if tiny else 12
    motif = np.array([7, 11, 13, 5], np.int32)
    prompts = []
    for i in range(n_req):
        if i % 3 == 0:  # draftable motifs keep speculation proposing
            prompts.append(np.tile(motif, int(rng.randint(3, 8))))
        else:
            prompts.append(rng.randint(
                1, cfg.vocab_size,
                (int(rng.randint(4, 40)),)).astype(np.int32))
    sps = [None if i % 2 == 0 else SamplingParams(
        temperature=0.8, top_p=(0.7, 0.85, 0.95)[i % 3],
        seed=1000 + i) for i in range(n_req)]
    gaps = np.random.RandomState(11).exponential(0.02, size=n_req)
    new, slots, bs, chunk = 8, 2, 8, 16
    modes = [None, "int8", "int4g"] if tp > 1 else [None]
    per_mode = {}
    greedy_rows = [i for i in range(n_req) if sps[i] is None]
    bf16_outs = None
    for mode in modes:
        sharding = (ShardedEngineConfig(tp=tp, collective_quant=mode)
                    if ndev > 1 else None)
        srv = PagedGenerationServer(
            model, max_slots=slots, block_size=bs, max_prompt_len=48,
            max_new_tokens=new, prefill_chunk_tokens=chunk,
            enable_prefix_cache=True, speculation=True,
            kv_dtype="int8", quantization="w8a16", unified_round=True,
            async_rounds=True, sharding=sharding)
        # bucket pre-compile BEFORE start (the r12 lesson: admission
        # timing makes bucket usage nondeterministic) for BOTH
        # sampling modes the mixed pool hits; the tiny schema smoke
        # skips it (it asserts schema, not compile-window cleanliness)
        if not tiny:
            srv.warm_buckets(modes=((False, False), (True, False)))
        srv.start()
        try:
            def drain():
                futs = []
                for p, s, g in zip(prompts, sps, gaps):
                    _time.sleep(float(g))
                    futs.append(srv.submit(p, sampling=s))
                return [f.result(timeout=600) for f in futs]

            # churn-shaped warm passes at identical arrivals (two on
            # the full axis: async round composition is timing-shaped
            # and the slow test asserts a compile-clean window; the
            # tiny schema smoke skips them — its structural fields
            # (bytes ratio, parity, dispatches/round) are
            # timing-invariant, and compile cleanliness is only
            # asserted on the full axis)
            if not tiny:
                drain()
                drain()
            srv.reset_stats()
            outs = drain()
            st = srv.stats()
        finally:
            srv.stop()
        name = mode or "bf16"
        if bf16_outs is None:
            bf16_outs = outs
        gtoks = [(int(a), int(b))
                 for i in greedy_rows
                 for a, b in zip(outs[i], bf16_outs[i])]
        c = st["collectives"]
        decoded = max(st["goodput"]["decoded_tokens"], 1)
        per_mode[name] = {
            "tokens_per_sec": st["tokens_per_sec"],
            "itl_p99_ms": st["itl_p99_ms"],
            "p99_ms": st["p99_ms"],
            "prefill_dispatches": st["prefill_dispatches"],
            "bytes_total": c["bytes_total"],
            "bytes_baseline": c["bytes_baseline"],
            "decoded_tokens": decoded,
            "bytes_per_decoded_token": c["bytes_total"] / decoded,
            "bytes_ratio": (c["bytes_total"]
                            / max(c["bytes_baseline"], 1)),
            "by_collective": c["by_collective"],
            "greedy_token_match": (sum(a == b for a, b in gtoks)
                                   / max(len(gtoks), 1)),
            "token_sig": hashlib.md5(
                b"|".join(np.asarray(o, np.int64).tobytes()
                          for o in outs)).hexdigest(),
            "dispatches_per_round":
                st["rounds"]["dispatches_per_round"],
            "compiles_in_window": st["compiles"]["window_total"],
        }
    print(json.dumps({
        "devices": int(ndev), "tp": tp,
        "offered_rps": n_req / max(float(gaps.sum()), 1e-9),
        "modes": per_mode,
    }))


def _bench_served_collectives(on_tpu, tiny):
    """Quantized-collectives axis (13th record): identical fixed-seed
    Poisson arrivals through the composed sharded stack at tp∈{1,2,4}
    forced-host devices (tiny: 1/2), one subprocess per device count,
    each comparing the bf16 / int8 / int4-group collective wires
    in-process. The wire-byte accounting is analytic (per-device bytes
    the shard_map seams ship, with the unquantized baseline counted
    for the SAME dispatches), so the <= 0.30x acceptance bar is a
    structural CPU-provable number; tok/s deltas on the shared-core
    host mesh are noise — the collective-latency win is a chip
    number (EQuARX ~2x, rerun queued). The tiny schema smoke runs the
    ONE device count with a wire (tp=2): tp=1 has no collective to
    quantize, and the cross-count md5 parity proof is the full/slow
    form."""
    counts = (2,) if tiny else (1, 2, 4)
    results = {}
    for n in counts:
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
        args = [sys.executable, os.path.abspath(__file__),
                "served-collectives-worker", str(n)]
        if tiny:
            args.append("--tiny")
        r = subprocess.run(args, env=env, capture_output=True,
                           text=True, timeout=900,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
        if r.returncode != 0:
            raise RuntimeError(
                f"collectives worker ({n} devices) failed:\n"
                f"{r.stderr[-2000:]}")
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("{")][-1]
        results[n] = json.loads(line)
    return results


def _bench_served_frontdoor(model, cfg, on_tpu, tiny):
    """Front-door sub-axis of `bench.py served` (round 12): an
    ADVERSARIAL open-loop mix — long-prompt "bully" batch requests
    land as one burst and monopolize every slot, then short
    interactive requests arrive at bursty fixed-seed Poisson gaps
    (every third gap collapsed to zero) from two tenants while the
    bullies are still decoding. The IDENTICAL arrival schedule drives
    (a) the plain single-lane FIFO engine (no front door) and (b) a
    `FrontDoor` with interactive/batch lanes, TTFT deadlines, and
    preemption. Interactive TTFT is measured CLIENT-SIDE in both runs
    (first `on_token` callback, same engine code path), so the
    comparison is the scheduling policy and nothing else; the record
    carries per-class TTFT, deadline-miss rates, preemption/resume
    counts, and the batch-throughput cost of lane priority.

    Off TPU this axis runs on the tiny dispatch-bound proxy (the
    speculation-axis precedent): the phenomenon being measured is
    QUEUEING — who waits behind whom — and on the hs256 CPU proxy a
    single fresh packed-prefill bucket costs a ~0.7-1.5s XLA compile,
    drowning the scheduling signal (preemption/attach timing changes
    the (T, rows, width) buckets between passes); both servers
    therefore pre-compile the whole bucket space via warm_buckets().
    Each pass uses FRESH same-length prompt pools so the measured
    pass's prefix cache serves only its own swap-outs, not
    whole-prompt reruns; base/front measured passes are INTERLEAVED
    on the same pool salts and reduced by per-field medians, so the
    asserted ratios compare like against like under shared machine
    load."""
    import time as _time

    from paddle_tpu.frontend import FrontDoor
    from paddle_tpu.inference import PagedGenerationServer
    from paddle_tpu.inference.kv_cache import blocks_for
    from paddle_tpu.models.gpt2 import GPT2, GPT2Config

    if tiny:
        fmodel, fcfg = model, cfg
        n_bully, n_inter, new, slots, bs = 2, 4, 4, 2, 4
        blo, bhi, ilo, ihi, ibudget = 10, 14, 3, 5, 2
        chunk, mp, deadline_ms = 16, 16, 2000.0
    elif on_tpu:
        fmodel, fcfg = model, cfg  # gpt2s bf16: the serving config
        n_bully, n_inter, new, slots, bs = 8, 24, 64, 8, 128
        blo, bhi, ilo, ihi, ibudget = 512, 700, 32, 64, 8
        chunk, mp, deadline_ms = 512, 768, 100.0
    else:
        fcfg = GPT2Config.tiny()  # dispatch-bound CPU proxy
        fcfg.dropout = 0.0
        fmodel = GPT2(fcfg)
        fmodel.eval()
        n_bully, n_inter, new, slots, bs = 4, 10, 96, 4, 8
        blo, bhi, ilo, ihi, ibudget = 96, 140, 8, 16, 3
        chunk, mp, deadline_ms = 32, 144, 300.0
    rng = np.random.RandomState(31)

    def pools(salt):
        """Fresh fixed-seed prompt pools (same length mix per pass)."""
        r2 = np.random.RandomState(salt)
        bl = [r2.randint(1, fcfg.vocab_size, (int(r2.randint(
            blo, bhi + 1)),)).astype(np.int32) for _ in range(n_bully)]
        il = [r2.randint(1, fcfg.vocab_size, (int(r2.randint(
            ilo, ihi + 1)),)).astype(np.int32) for _ in range(n_inter)]
        return bl, il

    # pool with RETENTION HEADROOM: the default pool covers max_slots
    # worst cases only, so n_bully swapped-out victims (~a worst case
    # of retained blocks each) would get LRU-evicted by live
    # allocations and every resume would degenerate to a full
    # re-prefill — a production pool holds headroom for the swap-out
    # working set. Both servers get the same pool for a fair compare.
    nb = (slots + n_bully) * (blocks_for(mp + new, bs) + 2) + 1

    def build_plain():
        return PagedGenerationServer(
            fmodel, max_slots=slots, block_size=bs, max_prompt_len=mp,
            max_new_tokens=new, prefill_chunk_tokens=chunk,
            num_blocks=nb)

    # bully wall clock (closed-loop, warm) anchors the arrival window.
    # BOTH servers pre-compile the full packed-prefill bucket space
    # (warm_buckets): preemption/cache-hit timing decides which (T,
    # rows, width) buckets a pass hits, so traffic-driven warming is
    # non-deterministic and a mid-window XLA compile (~0.7-1.5s on the
    # CPU proxy) would bury the scheduling signal being measured.
    srv = build_plain()
    srv.warm_buckets()
    srv.start()
    try:
        wb, wi = pools(41)
        for f in [srv.submit(p) for p in wb]:      # compile bully
            f.result(timeout=900)                  # shapes
        for f in [srv.submit(p, max_new_tokens=ibudget)
                  for p in wi]:                     # compile short
            f.result(timeout=900)                  # shapes
        t_w = _time.perf_counter()
        for f in [srv.submit(p) for p in pools(42)[0]]:
            f.result(timeout=900)
        bully_wall = _time.perf_counter() - t_w

        # bursty Poisson interactive arrivals INSIDE the bully window:
        # fixed seed, every 3rd gap collapsed to zero (burst pairs)
        gaps = rng.exponential(0.5 * bully_wall / max(n_inter, 1),
                               size=n_inter)
        gaps[2::3] = 0.0
        arrivals = 0.12 * bully_wall + np.cumsum(gaps)

        def drive(submit_bully, submit_inter, reset, salt):
            """One pass of the shared arrival schedule on a fresh
            fixed-seed pool; returns the per-class client numbers."""
            bullies, inters = pools(salt)
            reset()
            firsts = [None] * n_inter
            t_sub = [None] * n_inter
            b_done = [None] * n_bully

            def first_cb(k):
                def cb(tok, reason):
                    if firsts[k] is None:
                        firsts[k] = _time.perf_counter()
                return cb

            def done_cb(k):
                def cb(_fut):
                    b_done[k] = _time.perf_counter()
                return cb

            t0 = _time.perf_counter()
            ifuts, bfuts = [], []
            for k, p in enumerate(bullies):  # the opening burst
                f = submit_bully(k, p)
                f.add_done_callback(done_cb(k))
                bfuts.append(f)
            for k, p in enumerate(inters):
                target = t0 + arrivals[k]
                now = _time.perf_counter()
                if now < target:
                    _time.sleep(target - now)
                t_sub[k] = _time.perf_counter()
                ifuts.append(submit_inter(k, p, first_cb(k)))
            for f in ifuts + bfuts:
                f.result(timeout=900)
            ttfts = sorted((firsts[k] - t_sub[k]) * 1e3
                           for k in range(n_inter))
            b_toks = sum(int(f.result().size) - p.size
                         for f, p in zip(bfuts, bullies))
            b_wall = max(b_done) - t0
            return {
                "ttft_p50_ms": ttfts[len(ttfts) // 2],
                "ttft_p99_ms": ttfts[min(len(ttfts) - 1,
                                         int(0.99 * len(ttfts)))],
                "miss_rate": sum(t > deadline_ms for t in ttfts)
                             / len(ttfts),
                "batch_tok_s": b_toks / max(b_wall, 1e-9),
            }

        def med(passes):
            """Per-field median over repeated drives: single ~0.5s
            adversarial passes are +-15% noisy on a shared CPU, and
            the axis asserts RATIOS of two of them."""
            import statistics
            return {k: statistics.median(d[k] for d in passes)
                    for k in passes[0]}

        # (a) single-lane FIFO baseline: the plain engine, same warm
        # server; interactive requests take their place in the one
        # queue behind the bully burst
        def p_bully(k, p):
            return srv.submit(p)

        def p_inter(k, p, cb):
            return srv.submit(p, max_new_tokens=ibudget, on_token=cb)

        # (b) the front door: lanes + deadlines + preemption + two
        # interactive tenants (prefix caching on — the swap-out
        # medium). Built BEFORE measuring so base/front passes can be
        # INTERLEAVED (the telemetry-axis precedent): the two sides
        # see the same background-load profile instead of sequential
        # blocks picking up machine drift as phantom scheduling cost.
        # tiny: bully budgets sit inside the default drain-wait window
        # (every resident is always "about to finish"), which would
        # suppress preemption entirely — the schema smoke pins the
        # hysteresis off so the preempt/resume counters stay exercised
        fd = FrontDoor(fmodel, max_slots=slots, block_size=bs,
                       max_prompt_len=mp, max_new_tokens=new,
                       prefill_chunk_tokens=chunk, num_blocks=nb,
                       preempt_wait_tokens=0 if tiny else 8)
        fd.warm()
        fd.start()
        try:
            def fd_bully(k, p):
                return fd.submit(p, lane="batch", tenant="bully",
                                 stream=False)._future

            def fd_inter(k, p, cb):
                return fd.submit(
                    p, lane="interactive",
                    tenant=("alice", "bob")[k % 2],
                    deadline_ms=deadline_ms, max_new_tokens=ibudget,
                    stream=False, on_token=cb)._future

            # one warm drive each: warm_buckets() already compiled
            # every packed bucket deterministically; these passes
            # compile the pinned decode shape and warm the host-side
            # swap-out/resume paths
            drive(p_bully, p_inter, srv.reset_stats, 51)
            drive(fd_bully, fd_inter, fd.reset_stats, 53)
            b_passes, f_passes = [], []
            for r in range(1 if tiny else 3):  # interleaved A/B
                b_passes.append(drive(p_bully, p_inter,
                                      srv.reset_stats, 55 + r))
                f_passes.append(drive(fd_bully, fd_inter,
                                      fd.reset_stats, 55 + r))
            base, front = med(b_passes), med(f_passes)
            st = fd.stats()
        finally:
            fd.stop()
    finally:
        srv.stop()
    return {"base": base, "front": front, "stats": st,
            "n_bully": n_bully, "n_inter": n_inter,
            "deadline_ms": deadline_ms}



def _served_telemetry_pass(psrv, prompts, on_tpu, timeline=False):
    """Measured drains on the already-warm paged server, the ops plane
    off/on INTERLEAVED (4 rounds of one off-pass + one on-pass, best
    pass per side): the overhead being reported is small, well inside
    closed-loop noise, and sequential off-then-on blocks pick up any
    drift in background machine load as phantom overhead — alternating
    passes give both sides the same load profile. The ON side is the
    FULL ops plane (ISSUE 10): metrics + tracing + the flight recorder
    (the /metrics endpoint and stall watchdog threads run in both
    sides — they are construction state of the server). Writes the
    three telemetry artifacts next to the BENCH_*.json files and
    returns the bench record carrying the measured overhead
    (acceptance bar: <= 5% served tok/s)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.observability import tracing as obs_tracing

    # telemetry artifacts land in the gitignored telemetry/ dir, not
    # the repo root (ISSUE 14 satellite); PADDLE_TPU_TELEMETRY_DIR
    # overrides for CI scrapers
    out_dir = os.environ.get("PADDLE_TPU_TELEMETRY_DIR") or \
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "telemetry")
    os.makedirs(out_dir, exist_ok=True)
    trace_path = os.path.join(out_dir, "TELEMETRY_trace.jsonl")
    prom_path = os.path.join(out_dir, "TELEMETRY_metrics.prom")
    report_path = os.path.join(out_dir, "TELEMETRY_request_traces.json")
    timeline_path = os.path.join(out_dir, "TELEMETRY_timeline.json")

    def one_pass():
        psrv.reset_stats()
        for f in [psrv.submit(p) for p in prompts]:
            f.result(timeout=900)
        return psrv.stats()

    def faster(a, b):
        return b if a is None or (b is not None and
                                  b["tokens_per_sec"]
                                  > a["tokens_per_sec"]) else a

    obs_metrics.REGISTRY.reset()
    obs_tracing.configure(path=trace_path, truncate=True)
    obs_tracing.reset()
    st_off = st = None
    try:
        for _ in range(4):
            obs.disable()
            psrv._recorder.disable()
            st_off = faster(st_off, one_pass())
            obs.enable()
            psrv._recorder.enable()
            st = faster(st, one_pass())
    finally:
        obs_tracing.flush()
        obs.disable()
        psrv._recorder.disable()
    with open(prom_path, "w") as f:
        f.write(obs_metrics.to_prometheus())
    traces = obs_tracing.assemble_request_traces(path=trace_path)
    summary = obs_tracing.summarize_traces(traces)
    with open(report_path, "w") as f:
        json.dump({"summary": summary,
                   "requests": sorted(traces.values(),
                                      key=lambda r: r["request_id"])},
                  f, indent=1)
    timeline_events = 0
    if timeline:
        # Perfetto timeline of the measured window (ISSUE 14): the
        # span sink + this server's flight-recorder ring, per track
        timeline_events = psrv.export_timeline(timeline_path)
    obs_tracing.configure(path=None)  # detach the sink for later axes
    base = st_off["tokens_per_sec"]
    ratio = st["tokens_per_sec"] / max(base, 1e-9)
    rec = {
        "metric": "gpt2s_served_paged_telemetry_tokens_per_sec"
                  + ("" if on_tpu else "_CPU_DEGRADED"),
        "value": round(st["tokens_per_sec"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(ratio, 4),
        "baseline": "same paged server/traffic, ops plane disabled",
        "telemetry_overhead_pct": round((1.0 - ratio) * 100, 2),
        # the full ops plane was on for the ON side: metrics + tracing
        # + flight recorder, with the /metrics endpoint and stall
        # watchdog live in both sides (acceptance bar: <= 5%)
        "ops_plane": psrv.exporter is not None,
        "ops_port": psrv.exporter.port if psrv.exporter else None,
        "compiles_in_window": st["compiles"]["window_total"],
        "compiles_in_flight_window":
            st["compiles"]["window_in_flight"],
        "goodput_ratio": round(st["goodput"]["goodput_ratio"], 4),
        "ttft_p50_ms": round(st["ttft_p50_ms"], 1),
        "ttft_p99_ms": round(st["ttft_p99_ms"], 1),
        "slo_worst": psrv.slo_report()["worst"],
        "trace_events": len(obs_tracing.events()),
        "artifacts": [os.path.basename(p) for p in
                      ((prom_path, trace_path, report_path,
                        timeline_path) if timeline else
                       (prom_path, trace_path, report_path))],
        "telemetry_dir": os.path.basename(out_dir),
        "timeline_events": timeline_events,
    }
    print(f"# served telemetry pass: {st['tokens_per_sec']:,.0f} tok/s "
          f"({rec['telemetry_overhead_pct']:+.2f}% overhead vs "
          f"disabled, full ops plane), "
          f"{rec['compiles_in_window']} compiles in window "
          f"({rec['compiles_in_flight_window']} in-flight), goodput "
          f"{rec['goodput_ratio']:.3f}, ttft p50 "
          f"{st['ttft_p50_ms']:.0f}ms p99 {st['ttft_p99_ms']:.0f}ms; "
          f"phase means {summary.get('mean_phase_ms')}; wrote "
          f"{', '.join(rec['artifacts'])}", file=sys.stderr)
    return rec


def main():
    import jax

    from paddle_tpu.utils import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()

    import paddle_tpu  # noqa: F401

    flags = {a for a in sys.argv[1:] if a.startswith("--")}
    unknown = flags - {"--telemetry", "--tiny", "--timeline"}
    if unknown:
        raise SystemExit(f"unknown bench flag(s) {sorted(unknown)}; "
                         "supported: --telemetry, --tiny, --timeline")
    timeline = "--timeline" in flags
    telemetry = "--telemetry" in flags or timeline
    tiny = "--tiny" in flags
    pos = [a for a in sys.argv[1:] if not a.startswith("--")]
    axis = pos[0] if pos else os.environ.get("PADDLE_TPU_BENCH_MODEL")
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    if not on_tpu and os.environ.get(
            "JAX_PLATFORMS", "").split(",")[0] != "cpu":
        # a measurement path that finds no chip fails; the CPU smoke mode
        # (degraded records, the tier-1 schema tests) is asked for by name
        raise SystemExit(
            f"bench.py: no TPU found (jax backend {backend!r}). Set "
            f"JAX_PLATFORMS=cpu explicitly for the CPU smoke mode.")

    if axis:  # single-axis mode (manual runs / tests)
        if axis == "served-sharded-worker":
            # internal: subprocess body of the sharded-serving axis
            # (this process was spawned with the forced-host device
            # count already in XLA_FLAGS)
            _served_sharded_worker(int(pos[1]), tiny)
            return
        if axis == "served-longctx-worker":
            # internal: subprocess body of the long-context axis
            # (forced-host device count = sp already in XLA_FLAGS)
            _served_longctx_worker(int(pos[1]), tiny)
            return
        if axis == "served-collectives-worker":
            # internal: subprocess body of the quantized-collectives
            # axis (forced-host device count already in XLA_FLAGS)
            _served_collectives_worker(int(pos[1]), tiny)
            return
        if axis in ("decode", "gpt2s_gen"):
            _bench_decode(on_tpu)
            return
        if axis == "served":
            _bench_served(on_tpu, telemetry=telemetry, tiny=tiny,
                          timeline=timeline)
            return
        if axis not in AXES:  # a typo must not silently bench gpt2s
            raise SystemExit(
                f"unknown bench axis {axis!r}; choose from "
                f"{AXES + ('gpt2s_gen',)}")
        print(json.dumps(_bench_train(axis, on_tpu)))
        return

    if not on_tpu:
        # CPU-degraded: one tiny smoke record, same shape as before
        print(json.dumps(_bench_train("gpt2s", on_tpu)))
        return

    _run_all_axes(on_tpu, telemetry, timeline)


def _run_all_axes(on_tpu, telemetry=False, timeline=False):
    """Multi-axis default: run each BASELINE config under the global
    budget, headline first; skip (and say so) when the window closes.
    An axis that raises does not stop the others, but the run then
    exits non-zero after printing what landed."""
    records, skipped, failed = [], [], []
    for name in AXES:
        # decode compiles 6 programs (2 lengths x 3 configs when cold);
        # served compiles ~8 (5 prefill buckets + step + verify, plus
        # the round-11 speculation sub-axis drains)
        need = 210 if name == "decode" else (
            240 if name == "served" else (60 if records else 0))
        if _remaining() < need:
            skipped.append(name)
            continue
        t0 = time.time()
        try:
            if name == "decode":
                records.extend(_bench_decode(on_tpu))
            elif name == "served":
                records.extend(_bench_served(on_tpu,
                                             telemetry=telemetry,
                                             timeline=timeline))
            else:
                rec = _bench_train(name, on_tpu)
                records.append(rec)
                print(json.dumps(rec))
            print(f"# bench axis {name} took {time.time() - t0:.0f}s "
                  f"({_remaining():.0f}s budget left)", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — isolate axis failures
            failed.append(name)
            print(f"# bench axis {name} FAILED: "
                  f"{type(e).__name__}: {str(e)[:200]}", file=sys.stderr)
    if skipped:
        print(f"# bench: skipped {skipped} (budget "
              f"{_BUDGET_S:.0f}s exhausted; set PADDLE_TPU_BENCH_BUDGET_S "
              "to widen)", file=sys.stderr)
    if not records:
        raise SystemExit(f"bench.py: no bench axis produced a record "
                         f"(failed: {failed}, skipped: {skipped})")
    # final line: the headline record again, carrying every axis — the
    # driver's JSON-line capture gets the full measured state either way
    headline = dict(records[0])
    if headline.get("metric") != "gpt2s_train_tokens_per_sec_per_chip":
        # the gpt2s axis failed and another axis landed first: flag it so
        # a driver comparing headlines round-over-round can't mistake a
        # different metric for the usual one (ADVICE r5)
        headline["headline_degraded"] = True
    headline["parsed_all"] = records
    print(json.dumps(headline))
    if failed:
        raise SystemExit(f"bench.py: axis/axes {failed} raised")


if __name__ == "__main__":
    main()
