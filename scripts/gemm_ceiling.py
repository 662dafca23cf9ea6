"""GEMM ceiling map (VERDICT r4 next #2): M/N/K sweep + 4096^3 anomaly.

Round 4 left a two-point claim: the model's head shape
(16384x768x50257) hit 97 TF/s while square 4096^3 bf16 ran at 34 TF/s —
"a tiling artifact" was a hypothesis, not a result. This sweeps a real
grid (square + skinny + the model's own shapes, ~1-13 TFLOP each) under
the scan-timed methodology (operands as explicit jit args, not closure
constants) and probes the anomaly's
candidate causes directly on the 4096^3 shape:
  * output dtype (bf16 out vs f32 out via preferred_element_type)
  * operand layouts (contracting-dim position: NT/TN via transposes)
  * per-dim scaling (M-sweep and K-sweep at fixed other dims)

Prints one line per config; run on the real chip.
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bench_util import scan_time_args  # noqa: E402


def time_gemm(m, n, k, out_dtype=jnp.bfloat16, layout="nn", seed=0,
              in_dtype=jnp.bfloat16, inners=(8, 40)):
    """Two-inner differencing: a single scan-timed number at a small
    inner count carries the per-dispatch cost spread over its iterations
    (the r4 "34 TF/s square gemm" was largely that, not silicon). Timing
    the same shape at two inner counts and differencing cancels any
    constant per-dispatch cost exactly: t = (T_hi - T_lo) / (hi - lo)."""
    rs = np.random.RandomState(seed)
    a = jnp.asarray(rs.rand(m, k), in_dtype)
    b = jnp.asarray(rs.rand(k, n) if layout in ("nn", "tn")
                    else rs.rand(n, k), in_dtype)
    if layout == "tn":
        a = jnp.asarray(rs.rand(k, m), in_dtype)

    def step(c, ab):
        aa, bb = ab
        if layout == "nn":
            x, y = aa, bb
        elif layout == "nt":  # b arrives [N, K]; contract K on dim 1
            x, y = aa, bb.T
        else:  # "tn": a arrives [K, M]
            x, y = aa.T, bb
        out = jax.lax.dot_general(
            x + c.astype(in_dtype) * 1e-30, y,
            (((1,), (0,)), ((), ())),
            preferred_element_type=out_dtype)
        return jnp.sum(out.astype(jnp.float32)) * 1e-30

    z = jnp.zeros((), jnp.float32)
    lo, hi = inners
    t_lo = scan_time_args(step, z, (a, b), inner=lo, reps=3) * lo
    t_hi = scan_time_args(step, z, (a, b), inner=hi, reps=3) * hi
    t = max((t_hi - t_lo) / (hi - lo), 1e-9)
    tf = 2 * m * n * k / t / 1e12
    return t, tf


def line(tag, m, n, k, **kw):
    t, tf = time_gemm(m, n, k, **kw)
    print(f"{tag:46s} {m:>6d}x{n:>6d}x{k:>6d}  {t*1e3:7.2f}ms "
          f"{tf:6.1f} TF/s", flush=True)
    return tf


def main():
    print(f"# devices: {jax.devices()}", flush=True)
    results = {}

    print("\n## square sweep (bf16 in, bf16 out)", flush=True)
    for s in (1024, 2048, 4096, 8192):
        results[f"sq{s}"] = line("square", s, s, s)

    print("\n## 4096^3 anomaly probes", flush=True)
    results["sq4096_f32out"] = line("square f32-out", 4096, 4096, 4096,
                                    out_dtype=jnp.float32)
    results["sq4096_nt"] = line("square NT layout", 4096, 4096, 4096,
                                layout="nt")
    results["sq4096_tn"] = line("square TN layout", 4096, 4096, 4096,
                                layout="tn")

    print("\n## M-sweep at NxK=4096x4096", flush=True)
    for m in (1024, 8192, 16384, 65536):
        results[f"m{m}_nk4096"] = line("M-sweep", m, 4096, 4096,
                                       inners=((4, 16) if m >= 65536
                                               else (8, 40)))

    print("\n## N-sweep at M=16384, K=768 (the head family)", flush=True)
    for n in (768, 3072, 6144, 12288, 50257):
        results[f"n{n}"] = line("N-sweep", 16384, n, 768)

    print("\n## K-sweep at M=16384, N=4096", flush=True)
    for k in (256, 768, 1536, 4096):
        results[f"k{k}"] = line("K-sweep", 16384, 4096, k)

    print("\n## the model's own shapes", flush=True)
    results["head"] = line("head matmul (f32 out)", 16384, 50257, 768,
                           out_dtype=jnp.float32)
    results["head_bf16o"] = line("head matmul (bf16 out)", 16384, 50257,
                                 768)
    results["mlp1"] = line("MLP up", 16384, 3072, 768)
    results["mlp2"] = line("MLP down", 16384, 768, 3072)
    results["qkv"] = line("QKV proj", 16384, 2304, 768)
    results["headT"] = line("head bwd (dW shape)", 50257, 768, 16384)

    print("\n## non-GEMM probes (same differencing)", flush=True)
    rs = np.random.RandomState(0)
    # int8 MXU rate — carry-dep via an element write (c*0 folds to
    # identity and the whole dot hoists out of the loop: measured!)
    ai = jnp.asarray(rs.randint(-127, 127, (4096, 4096)), jnp.int8)
    bi = jnp.asarray(rs.randint(-127, 127, (4096, 4096)), jnp.int8)

    def mmi(c, ab):
        x, y = ab
        x = x.at[0, 0].set((c * 1e-30).astype(jnp.int8))
        o = jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        return jnp.sum(o).astype(jnp.float32) * 1e-30

    from _bench_util import scan_time
    z = jnp.zeros((), jnp.float32)
    tl = scan_time_args(mmi, z, (ai, bi), inner=8, reps=3) * 8
    th = scan_time_args(mmi, z, (ai, bi), inner=40, reps=3) * 40
    t = max((th - tl) / 32, 1e-9)
    print(f"{'int8 4096^3 -> s32':46s} {'':22s} {t*1e3:7.2f}ms "
          f"{2*4096**3/t/1e12:6.1f} TOP/s", flush=True)

    # HBM stream: the FULL array as loop carry (read+write each iter;
    # slice-consumer probes get DCE'd to nothing: measured!)
    for dt, nm in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
        x = jnp.asarray(rs.rand(101_000_000)).astype(dt)
        step = (lambda v: v + jnp.float32(1e-30).astype(v.dtype))
        tl = scan_time(step, x, inner=8, reps=3) * 8
        th = scan_time(step, x, inner=40, reps=3) * 40
        t = max((th - tl) / 32, 1e-9)
        nbytes = x.size * x.dtype.itemsize
        print(f"{'carry-chain add 101M ' + nm:46s} {'':22s} "
              f"{t*1e3:7.2f}ms {2*nbytes/t/1e9:6.0f} GB/s rd+wr",
              flush=True)

    peak = max(results.values())
    argpeak = max(results, key=results.get)
    print(f"\n## ceiling: {peak:.1f} TF/s at {argpeak} "
          f"({peak/197e12*1e12:.1%} of 197 TF/s book)", flush=True)


if __name__ == "__main__":
    main()
