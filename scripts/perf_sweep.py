"""Profile-driven perf sweep on the real TPU chip (VERDICT r2 next #1).

Measures, behind a device_get-scalar barrier (`_bench_util.sync`):
  1. step-time decomposition: fwd / fwd+bwd / full train step
  2. per-chip batch sweep at seq=1024
  3. flash-attention block_q/block_k sweep (microbench, B=8 H=12 S=1024 D=64)
  4. long-sequence (S=16384) flash fwd+bwd — forces the streaming two-kernel
     backward (sq*d*10 > 8MB) to compile and run on hardware
Run: timeout 1800 python scripts/perf_sweep.py [--section N]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from _bench_util import scan_time as _scan_timer, scan_time_args as _scan_timer_args, sync as _sync  # noqa: E402


def section_model(batch_sizes=(8, 16, 24)):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import optimizer as opt_mod

    from _bench_util import gpt2_amp_setup
    _cfg, params0, amp_loss, make_data = gpt2_amp_setup()
    n_params = sum(int(np.prod(v.shape)) for v in params0.values())

    optimizer = opt_mod.AdamW(learning_rate=1e-4, weight_decay=0.01)

    for batch in batch_sizes:
        seq = 1024
        data = make_data(batch, seq)
        key = jax.random.key(0)
        params = params0
        opt_state = optimizer.functional_init(params)
        inner = 10

        # fwd-only: perturb one param leaf by the carry to defeat CSE
        @jax.jit
        def fwd_n(p):
            k0 = next(iter(p))

            def body(c, _):
                p2 = dict(p)
                p2[k0] = p2[k0] + (c * 1e-30).astype(p2[k0].dtype)
                return amp_loss(p2, data, key).astype(jnp.float32), None
            c, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                None, length=inner)
            return c
        fwd_n(params)
        _sync(fwd_n(params))
        t0 = time.perf_counter()
        _sync(fwd_n(params))
        t_fwd = (time.perf_counter() - t0) / inner

        # full train step chained: params/opt flow through the scan carry
        def step(carry, _):
            p, s = carry
            loss, g = jax.value_and_grad(amp_loss)(p, data, key)
            np_, ns = optimizer.functional_update(p, g, s)
            return (np_, ns), loss

        @jax.jit
        def train_n(p, s):
            (p, s), losses = jax.lax.scan(step, (p, s), None, length=inner)
            return p, s, losses[-1]

        params, opt_state, loss = train_n(params, opt_state)
        float(jax.device_get(loss))
        t0 = time.perf_counter()
        params, opt_state, loss = train_n(params, opt_state)
        float(jax.device_get(loss))
        t_step = (time.perf_counter() - t0) / inner

        toks = batch * seq
        mfu = toks / t_step * 6 * n_params / 197e12
        print(f"batch={batch} seq={seq}: fwd={t_fwd*1e3:.1f}ms "
              f"step={t_step*1e3:.1f}ms "
              f"tok/s={toks/t_step:,.0f} MFU={mfu:.3f}", flush=True)


def section_flash_blocks():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    b, h, s, d = 8, 12, 1024, 64
    kq = jax.random.key(1)
    q = jax.random.normal(kq, (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(kq, 1), (b, h, s, d),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(kq, 2), (b, h, s, d),
                          jnp.bfloat16)
    flops_f = 2 * 2 * b * h * s * s * d * 0.5  # causal fwd

    for bq, bk in [(512, 512), (1024, 512), (512, 1024), (1024, 1024),
                   (256, 512), (512, 256), (256, 256), (1024, 256)]:
        try:
            def fwd_step(c, bq=bq, bk=bk):
                qc = q + (c * 1e-30).astype(q.dtype)  # carry-dependence defeats CSE/hoisting
                o = flash_attention(qc, k, v, True, None, bq, bk)
                return o.astype(jnp.float32).mean()

            t_f = _scan_timer(fwd_step, jnp.zeros((), jnp.float32))

            def bwd_step(c, bq=bq, bk=bk):
                qc = q + (c * 1e-30).astype(q.dtype)
                g = jax.grad(lambda qq: flash_attention(
                    qq, k, v, True, None, bq, bk).astype(
                        jnp.float32).sum())(qc)
                return g.astype(jnp.float32).mean()

            t_g = _scan_timer(bwd_step, jnp.zeros((), jnp.float32))
            print(f"blocks=({bq},{bk}): fwd={t_f*1e3:.2f}ms "
                  f"({flops_f/t_f/1e12:.0f}TF/s) "
                  f"fwd+bwd={t_g*1e3:.2f}ms", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"blocks=({bq},{bk}): FAILED {type(e).__name__}: "
                  f"{str(e)[:100]}", flush=True)


def section_longseq():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    b, h, s, d = 1, 8, 16384, 64  # s*d*10 = 10.5MB > 8MB -> two-kernel bwd
    kq = jax.random.key(2)
    q = jax.random.normal(kq, (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(kq, 1), (b, h, s, d),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(kq, 2), (b, h, s, d),
                          jnp.bfloat16)
    def bwd_step(c):
        qc = q + (c * 1e-30).astype(q.dtype)
        gr = jax.grad(lambda qq: flash_attention(
            qq, k, v, True).astype(jnp.float32).sum())(qc)
        return gr.astype(jnp.float32).mean()

    t = _scan_timer(bwd_step, jnp.zeros((), jnp.float32), inner=5)
    # causal flash fwd+bwd ~ 3.5 matmul passes over S^2/2 scores
    flops = 3.5 * 2 * b * h * s * s * d * 0.5
    print(f"longseq S={s}: streaming two-kernel bwd fwd+bwd={t*1e3:.1f}ms "
          f"(~{flops/t/1e12:.1f} TFLOP/s)", flush=True)


def section_ablate(batch=16):
    """Attention-share decomposition: time the GPT-2 fwd and fwd+bwd with
    (a) the Pallas flash path, (b) plain-XLA attention, (c) attention
    replaced by identity (v passthrough). (c)-(a) is the exact wall-clock
    the attention layers cost inside the real model — the number the
    microbenches only estimate."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.ops as P_ops
    from paddle_tpu.ops.attention import scaled_dot_product_attention as sdpa

    from _bench_util import gpt2_amp_setup
    _cfg, params0, amp_loss, make_data = gpt2_amp_setup()
    data = make_data(batch)
    key = jax.random.key(0)

    def identity_attn(q, k, v, attn_mask=None, dropout_p=0.0,
                      is_causal=False, scale=None, **kw):
        return v, None

    def xla_attn(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False,
                 scale=None, **kw):
        from paddle_tpu.ops.attention import _xla_attention
        out, _w = _xla_attention(q, k, v, mask=attn_mask, scale=scale,
                                 causal=is_causal)
        return out, None

    variants = [("flash", sdpa), ("xla", xla_attn),
                ("identity", identity_attn)]
    orig = P_ops.scaled_dot_product_attention
    z = jnp.zeros((), jnp.float32)
    try:
        for name, impl in variants:
            P_ops.scaled_dot_product_attention = impl

            def fwd_step(c, p):
                k0 = next(iter(p))
                p2 = dict(p)
                p2[k0] = p2[k0] + (c * 1e-30).astype(p2[k0].dtype)
                return amp_loss(p2, data, key).astype(jnp.float32)

            t_f = _scan_timer_args(fwd_step, z, params0)

            def bwd_step(c, p):
                k0 = next(iter(p))
                p2 = dict(p)
                p2[k0] = p2[k0] + (c * 1e-30).astype(p2[k0].dtype)
                _, g = jax.value_and_grad(amp_loss)(p2, data, key)
                return g[k0].astype(jnp.float32).mean()

            t_b = _scan_timer_args(bwd_step, z, params0)
            print(f"ablate[{name}] batch={batch}: fwd={t_f*1e3:.1f}ms "
                  f"fwd+bwd={t_b*1e3:.1f}ms", flush=True)
    finally:
        P_ops.scaled_dot_product_attention = orig


def section_profile(batch=16):
    """Per-op time breakdown of ONE fused train step (fwd+bwd+optimizer)
    via utils.profiler.top_ops — the ground truth for where the
    milliseconds go (attention kernels vs GEMMs vs scatter vs optimizer)."""
    import jax

    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.utils import profiler as prof

    from _bench_util import gpt2_amp_setup
    _cfg, params0, amp_loss, make_data = gpt2_amp_setup()
    data = make_data(batch)
    key = jax.random.key(0)
    optimizer = opt_mod.AdamW(learning_rate=1e-4, weight_decay=0.01)
    opt_state = optimizer.functional_init(params0)

    @jax.jit
    def step(p, s):
        loss, g = jax.value_and_grad(amp_loss)(p, data, key)
        np_, ns = optimizer.functional_update(p, g, s)
        return np_, ns, loss

    state = {"p": params0, "s": opt_state}

    def run():
        state["p"], state["s"], loss = step(state["p"], state["s"])
        float(jax.device_get(loss))

    prof.print_top_ops(run, steps=3, k=30)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all",
                    choices=["all", "model", "blocks", "longseq", "ablate",
                             "profile"])
    ap.add_argument("--batches", default=None)
    args = ap.parse_args()
    model_batches = args.batches or "8,16,24"
    import jax
    print(f"backend={jax.default_backend()} devices={jax.devices()}",
          file=sys.stderr)
    if args.section in ("all", "blocks"):
        section_flash_blocks()
    if args.section in ("all", "longseq"):
        section_longseq()
    if args.section in ("all", "model"):
        section_model(tuple(int(x) for x in model_batches.split(",")))
    if args.section in ("all", "ablate"):
        section_ablate()
    if args.section == "profile":  # not in "all": trace files are big
        # default batch 16 = the headline bench config; --batches overrides
        section_profile(int(args.batches.split(",")[0]) if args.batches
                        else 16)


if __name__ == "__main__":
    main()
