"""Raw-op throughput on the chip: big GEMM, attention-shaped batch GEMM,
exp, softmax. Establishes the hardware envelope the attention kernel lives in."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from _bench_util import scan_time, scan_time_args


def main():
    key = jax.random.key(0)
    z = jnp.zeros((), jnp.float32)
    t_start = time.time()

    def mark(label):
        print(f"  [t+{time.time()-t_start:.0f}s after {label}]", flush=True)

    # 1. big square GEMM: the MXU ceiling. The carry must be cast to the
    # operand dtype — a f32 0-d array is NOT weakly typed, so `a + c*1e-30`
    # silently promotes the whole GEMM to f32 (the r3 attn_compare bug).
    a = jax.random.normal(key, (4096, 4096), jnp.bfloat16)
    b = jax.random.normal(jax.random.fold_in(key, 1), (4096, 4096),
                          jnp.bfloat16)

    def gemm(c):
        ab = a + c.astype(jnp.bfloat16) * 1e-30
        assert ab.dtype == jnp.bfloat16
        return (ab @ b).astype(jnp.float32).mean()

    fl = 2 * 4096**3
    t = scan_time(gemm, z)
    print(f"gemm 4096^3 bf16: {t*1e3:.3f}ms {fl/t/1e12:.0f}TF/s", flush=True)

    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)

    def gemmf(c):
        # HIGHEST = true f32-equivalent multi-pass path; default precision
        # would run bf16 passes and mislabel the f32 ceiling
        return jnp.matmul(af + c * 1e-30, bf,
                          precision=jax.lax.Precision.HIGHEST).mean()

    t = scan_time(gemmf, z)
    print(f"gemm 4096^3 f32(highest): {t*1e3:.3f}ms {fl/t/1e12:.0f}TF/s",
          flush=True)

    ai = (a * 16).astype(jnp.int8)
    bi = (b * 16).astype(jnp.int8)

    def gemmi(c):
        # int8 zero-add keeps the dot carry-dependent (else XLA hoists the
        # loop-invariant dot out of the scan). v5e book rate is 2x bf16.
        aa = ai + (c * 0).astype(jnp.int8)
        s = jax.lax.dot_general(
            aa, bi, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return s.astype(jnp.float32).mean()

    t = scan_time(gemmi, z)
    print(f"gemm 4096^3 int8: {t*1e3:.3f}ms {fl/t/1e12:.0f}TOP/s", flush=True)
    mark("square gemms")

    # 1b. the model's biggest single GEMM: head matmul [B*S,768]@[768,50257]
    hx = jax.random.normal(key, (16384, 768), jnp.bfloat16)
    hw = jax.random.normal(jax.random.fold_in(key, 9), (768, 50257),
                           jnp.bfloat16)

    def headmm(c):
        s = (hx + c.astype(jnp.bfloat16) * 1e-30) @ hw
        return s.astype(jnp.float32).mean()

    t = scan_time(headmm, z, inner=5)
    fl2 = 2 * 16384 * 768 * 50257
    print(f"gemm 16384x768x50257 bf16 (head): {t*1e3:.3f}ms "
          f"{fl2/t/1e12:.0f}TF/s", flush=True)
    mark("head gemm")

    # 2. attention-shaped batch GEMM: [96,1024,64]x[96,64,1024]
    q = jax.random.normal(key, (96, 1024, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 2), (96, 1024, 64),
                          jnp.bfloat16)

    def bmm(c):
        s = jnp.einsum("bqd,bkd->bqk", q + c.astype(jnp.bfloat16) * 1e-30, k,
                       preferred_element_type=jnp.float32)
        return s.mean()

    t = scan_time(bmm, z)
    fl = 2 * 96 * 1024 * 1024 * 64
    print(f"bmm  96x1024x64x1024 (f32 out): {t*1e3:.3f}ms "
          f"{fl/t/1e12:.0f}TF/s", flush=True)

    # 2b. same but bf16 out (halves the HBM write)
    def bmm16(c):
        s = jnp.einsum("bqd,bkd->bqk", q + c.astype(jnp.bfloat16) * 1e-30, k)
        return s.astype(jnp.float32).mean()

    t = scan_time(bmm16, z)
    print(f"bmm  96x1024x64x1024 (bf16 out): {t*1e3:.3f}ms "
          f"{fl/t/1e12:.0f}TF/s", flush=True)
    mark("bmms")

    # 3. exp throughput on the score-matrix volume. x is 402MB — it must
    # ride as an explicit jit arg, not closure (remote_compile 413 cap).
    x = jax.random.normal(key, (96, 1024, 1024), jnp.float32)

    def expf(c, xx):
        return jnp.exp(xx + c).mean()

    t = scan_time_args(expf, z, x)
    n = 96 * 1024 * 1024
    print(f"exp  f32 {n/1e6:.0f}M elems: {t*1e3:.3f}ms "
          f"{n/t/1e9:.0f}Gexp/s", flush=True)

    xb = x.astype(jnp.bfloat16)

    def expb(c, xx):
        return jnp.exp(xx + c.astype(jnp.bfloat16)).astype(jnp.float32).mean()

    t = scan_time_args(expb, z, xb)
    print(f"exp  bf16: {t*1e3:.3f}ms {n/t/1e9:.0f}Gexp/s", flush=True)
    mark("exp")

    # 4. full softmax on scores
    def sm(c, xx):
        return jax.nn.softmax(xx + c, axis=-1).mean()

    t = scan_time_args(sm, z, x)
    print(f"softmax f32 [96,1024,1024]: {t*1e3:.3f}ms", flush=True)

    # 5. HBM bandwidth probe: copy 402MB
    def cp(c, xx):
        return (xx + c).mean()

    t = scan_time_args(cp, z, x)
    byts = n * 4 * 2
    print(f"add+reduce f32 402MB: {t*1e3:.3f}ms "
          f"~{byts/t/1e9:.0f}GB/s", flush=True)
    mark("hbm")


if __name__ == "__main__":
    main()
