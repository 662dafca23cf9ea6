"""One layer's paged DECODE attention on the chip: the Pallas stream kernel at
several query tiles against the XLA gather path, dense and int8 pool.

This is the measurement behind `DECODE_TILE = 8`
(ops/pallas/unified_attention.py) and behind PERF.md's finding that the
gather path beats the kernel below ~1k context.  GPT-2-medium geometry (16
heads of 64, block 128, table width 8), contexts 300-1,024.  Each reading is
the median of 5 dispatches of 48 chained calls (the output feeds the next
query, so nothing is hoisted), ending in block_until_ready.

    python scripts/decode_tile_bench.py        # needs a TPU; prints a table
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H, DH, BS, M, N = 16, 64, 128, 8, 256
CALLS, READINGS = 48, 5


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.kv_quant import QuantizedKV, kv_encode
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas.unified_attention import (
        unified_ragged_attention_kernel)

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("decode_tile_bench.py: no TPU; a time comes only "
                         "from a chip run")
    rs = np.random.RandomState(0)
    # one layer as the engine holds it: a stack [1, N, BS, H*Dh] of one
    kb = jnp.asarray(rs.randn(1, N, BS, H, DH), jnp.bfloat16)
    vb = jnp.asarray(rs.randn(1, N, BS, H, DH), jnp.bfloat16)

    def rows(x):
        return x.reshape(1, N, BS, H * DH)

    def int8(x):
        codes, scales = kv_encode(x)
        return QuantizedKV(rows(codes), scales)

    pools = {"dense": (rows(kb), rows(vb)), "int8": (int8(kb), int8(vb))}

    def kernel(q, k, v, tables, lens, qt):
        B = q.shape[0]
        stream = jnp.pad(q[:, None], ((0, 0), (0, qt - 1), (0, 0), (0, 0)))
        return unified_ragged_attention_kernel(
            stream.reshape(B * qt, H, DH), k, v, tables,
            jnp.arange(B, dtype=jnp.int32), lens - 1, 0, q_tile=qt)[::qt]

    def gather(q, k, v, tables, lens, _qt):  # the XLA path, chosen by hand
        saved, attention._on_tpu = attention._on_tpu, lambda: False
        try:
            return attention.paged_decode_attention(q, k, v, tables, lens,
                                                    layer=0)
        finally:
            attention._on_tpu = saved

    def per_call_us(fn, q):
        loop = jax.jit(lambda q: jax.lax.fori_loop(
            0, CALLS, lambda _, x: fn(x).astype(x.dtype), q))
        loop(q).block_until_ready()
        ts = []
        for _ in range(READINGS):
            t0 = time.perf_counter()
            loop(q).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return np.median(ts) / CALLS * 1e6

    print(f"device {jax.devices()[0].device_kind}; us per call", flush=True)
    for name, (k, v) in pools.items():
        for B in (8, 32):
            tables = jnp.asarray(rs.randint(1, N, (B, M)), jnp.int32)
            lens = jnp.asarray(rs.randint(300, M * BS, (B,)), jnp.int32)
            q = jnp.asarray(rs.randn(B, H, DH), jnp.bfloat16)
            ref = jax.jit(lambda q: gather(q, k, v, tables, lens, 0))(q)
            us = per_call_us(lambda x: gather(x, k, v, tables, lens, 0), q)
            row = [f"xla-gather {us:.0f}"]
            for qt in (8, 16, 32, 128):
                out = jax.jit(lambda q, qt=qt: kernel(q, k, v, tables, lens,
                                                      qt))(q)
                err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                            - ref.astype(jnp.float32))))
                us = per_call_us(
                    lambda x, qt=qt: kernel(x, k, v, tables, lens, qt), q)
                row.append(f"tile{qt} {us:.0f} (max |err| {err:.3f})")
            print(f"{name} pool, B={B}: " + "; ".join(row), flush=True)


if __name__ == "__main__":
    main()
