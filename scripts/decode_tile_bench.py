"""One layer's paged DECODE attention on the chip: the decode kernel (all
heads of a block in one pass) beside the stream kernel at an 8-row tile (the
decode entry until PR 27) and the XLA gather path, dense and int8 pool.

This is the measurement behind ROADMAP S1.  GPT-2-medium geometry (16 heads
of 64, block 128, table width 8, tables 0-padded past a row's context as the
engine pads them), two context mixes: 300-1,024 uniform (PR 21's), and the
serve cell's (`benchmark/traffic/closed32.json`: prompts lognormal median 256
sigma 1.0 clipped 16-768, plus 0-111 tokens generated so far).  The last rows,
every context 1, price a launch's fixed part: 256 grid steps of which 32 do
anything.  Each reading is the median of 5 dispatches of 48 chained calls (the
output feeds the next query, so nothing is hoisted), ending in
block_until_ready.

    python scripts/decode_tile_bench.py        # needs a TPU; prints a table
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

H, DH, BS, M, N = 16, 64, 128, 8, 256
CALLS, READINGS = 48, 5
STREAM_TILE = 8  # one f32 sublane group: the smallest tile the dots lower at


def contexts(rs, mix, B):
    if mix == "300-1024":
        return rs.randint(300, M * BS, (B,))
    if mix == "cell":
        prompts = np.clip(rs.lognormal(np.log(256), 1.0, B), 16, 768)
        return (prompts + rs.randint(0, 112, B)).astype(np.int64)
    return np.ones((B,), np.int64)  # "ctx=1"


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.kv_quant import QuantizedKV, kv_encode
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas import unified_attention as ua

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

    def decode(q, k, v, tables, lens):
        return ua.paged_decode_attention_kernel(q, k, v, tables, lens, 0)

    def tile8(q, k, v, tables, lens):
        qt, B = STREAM_TILE, q.shape[0]
        stream = jnp.pad(q[:, None], ((0, 0), (0, qt - 1), (0, 0), (0, 0)))
        return ua.unified_ragged_attention_kernel(
            stream.reshape(B * qt, H, DH), k, v, tables,
            jnp.arange(B, dtype=jnp.int32), lens - 1, 0, q_tile=qt)[::qt]

    def gather(q, k, v, tables, lens):  # the XLA path, chosen by hand
        saved, attention._on_tpu = attention._on_tpu, lambda: False
        try:
            return attention.paged_decode_attention(q, k, v, tables, lens,
                                                    layer=0)
        finally:
            attention._on_tpu = saved

    def per_call_us(fn, q, k, v, tables, lens):
        # nothing a call reads may be the same from one call to the next,
        # or XLA moves the gather out of the loop and keeps what it
        # gathered in VMEM (PR 27's first reading: 33.5 MB "read" in 32
        # us): the pool and the tables are arguments (closed over they
        # are constants, folded at compile time), and call i takes the
        # table rolled by i rows, which is the same work
        def chained(q, k, v, tables, lens):
            def call(i, x):
                return fn(x, k, v, jnp.roll(tables, i, 0),
                          jnp.roll(lens, i, 0)).astype(x.dtype)

            return jax.lax.fori_loop(0, CALLS, call, q)

        loop = jax.jit(chained)
        loop(q, k, v, tables, lens).block_until_ready()
        ts = []
        for _ in range(READINGS):
            t0 = time.perf_counter()
            loop(q, k, v, tables, lens).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return np.median(ts) / CALLS * 1e6

    print(f"device {jax.devices()[0].device_kind}; us per call; |err| is "
          "the decode kernel's largest distance from xla-gather",
          flush=True)
    print("| pool | B | contexts | mean ctx | xla-gather | tile8 | decode "
          "| max err |\n|---|---|---|---|---|---|---|---|", flush=True)
    cases = [(p, B, mix) for p in pools for B in (8, 32)
             for mix in ("300-1024", "cell")]
    cases += [("dense", 32, "ctx=1"), ("int8", 32, "ctx=1")]
    for name, B, mix in cases:
        k, v = pools[name]
        ctx = contexts(rs, mix, B)
        live = np.arange(M)[None, :] * BS < ctx[:, None]
        tables = jnp.asarray(np.where(live, rs.randint(1, N, (B, M)), 0),
                             jnp.int32)
        lens = jnp.asarray(ctx, jnp.int32)
        q = jnp.asarray(rs.randn(B, H, DH), jnp.bfloat16)
        us, outs = {}, {}
        for label, fn in (("xla-gather", gather), ("tile8", tile8),
                          ("decode", decode)):
            outs[label] = jax.jit(fn)(q, k, v, tables, lens).astype(
                jnp.float32)
            us[label] = per_call_us(fn, q, k, v, tables, lens)
        err = float(jnp.max(jnp.abs(outs["decode"] - outs["xla-gather"])))
        print(f"| {name} | {B} | {mix} | {ctx.mean():.0f} | "
              f"{us['xla-gather']:.0f} | {us['tile8']:.0f} | "
              f"{us['decode']:.0f} | {err:.3f} |", flush=True)


if __name__ == "__main__":
    main()
