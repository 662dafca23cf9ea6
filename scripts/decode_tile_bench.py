"""One layer's paged DECODE attention on the chip: the decode kernel (all
heads of a block in one pass) beside the stream kernel at an 8-row tile (the
decode entry until PR 27) and the XLA gather path, dense and int8 pool.

This is the measurement behind ROADMAP S1: what a launch costs, how many grid
steps it takes and what one live (row, block) step costs, at the two serve
cells' geometries.

  * `gpt2`: GPT-2-medium (16 heads of 64, block 128, table width 8, a live
    step moves 512 KB of K and V), tables 0-padded past a row's context as the
    engine pads them, context mixes 300-1,024 uniform (PR 21's) and the serve
    cell's (`benchmark/traffic/closed32.json`: prompts lognormal median 256
    sigma 1.0 clipped 16-768, plus 0-111 tokens generated so far).
  * `zaya`: ZAYA1-8B's compressed attention (8 query heads on 2 K/V heads of
    128, table width 40, 128 rows, a live step moves 128 KB), contexts from
    `benchmark/traffic/reason_closed128.json` (a prompt plus a uniform share of
    its output so far) and `deep` (three quarters of the table and more).
  * `kimi`, `kanana`: the latent kernel `mla_decode` (32 heads against a pool
    of [128, 640] bf16 latent blocks, 576 used: a live block moves 160 KB) at
    the Kimi cell's geometry (128 rows, table width 76) and the Kanana cell's
    (64 rows, width 152), contexts from their traffic files
    (`decode_closed128.json`, `longdoc_closed64.json`) as `reason` takes
    them, and every context 1; the XLA gather path of `ops/mla.py` is the
    reference and the `xla-gather` column.

The rows of every context 1 price a launch's fixed part. `live` is the
launch's live (row, block) pairs, `fan` the blocks of a row a grid step
takes and `grid` the steps the kernel of THIS tree takes for them (rows x
table width until PR 31; the live pairs, one for a row that has none, until
PR 35; since then ceil(live blocks / fan) a row). Each case is timed at
every fan in FANS up to the table's width, forced in place of
`decode_fan`, and the row the rule picks is marked `*`: one run is the
sweep behind `DECODE_STEP_BYTES` and the fans the rule takes; for the
latent kernel the fan is `mla_decode.FAN`, forced likewise. Each reading
is the median of 5 dispatches of 48 chained calls (the output feeds the
next query, so nothing is hoisted), ending in block_until_ready. To compare
two trees, copy this file over the other tree's and run it there, in the
same chip call.

    python scripts/decode_tile_bench.py [GEOMETRY ...]   # needs a TPU
"""
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BS = 128
# query heads, K/V heads, head size, table width, pool blocks
GEOMETRY = {"gpt2": (16, 16, 64, 8, 256), "zaya": (8, 2, 128, 40, 2560),
            "gpt2tp2": (8, 8, 64, 8, 256)}
# latent geometries: rows, table width, pool blocks, traffic file
LATENT = {"kimi": (128, 76, 6144, "decode_closed128.json"),
          "kanana": (64, 152, 5120, "longdoc_closed64.json")}
HEADS, ROW, LORA, QK = 32, 640, 512, 192   # a latent row: 512 + 64 + pad
CALLS, READINGS = 48, 5
STREAM_TILE = 8  # one f32 sublane group: the smallest tile the dots lower at
GATHER_ROWS = 8  # rows of a 128-row launch the gather reference is taken on
FANS = (1, 2, 4, 8)


def _lengths(rs, traffic, key, n):
    """n lengths from a traffic file's lognormal `key`, clipped as it says."""
    with open(os.path.join(ROOT, "benchmark", "traffic", traffic)) as f:
        spec = json.load(f)[key]
    return np.clip(rs.lognormal(np.log(spec["median"]), spec["sigma"], n),
                   spec["min"], spec["max"])


def contexts(rs, mix, B, M):
    if mix == "300-1024":
        return rs.randint(300, M * BS, (B,))
    if mix == "deep":
        return rs.randint(3 * M * BS // 4, M * BS, (B,))
    if mix == "cell":      # closed32: a prompt and 0-111 tokens of output
        prompts = _lengths(rs, "closed32.json", "prompt_len", B)
        return (prompts + rs.randint(0, 112, B)).astype(np.int64)
    if mix.endswith(".json") or mix == "reason":
        # a prompt and a uniform share of its own output
        traffic = "reason_closed128.json" if mix == "reason" else mix
        done = rs.uniform(0, 1, B) * _lengths(rs, traffic, "new_tokens", B)
        prompts = _lengths(rs, traffic, "prompt_len", B)
        return np.minimum(prompts + done, M * BS).astype(np.int64)
    return np.ones((B,), np.int64)  # "ctx=1"


def main(geometries):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.kv_quant import QuantizedKV, kv_encode
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas import unified_attention as ua

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("decode_tile_bench.py: no TPU; a time comes only "
                         "from a chip run")
    rs = np.random.RandomState(0)

    def pools(geometry):
        """One layer as the engine holds it: a stack [1, N, BS, Hkv*Dh] of
        one, dense and (a K/V head a query head) int8."""
        H, HKV, DH, _M, N = GEOMETRY[geometry]
        kb = jnp.asarray(rs.randn(1, N, BS, HKV, DH), jnp.bfloat16)
        vb = jnp.asarray(rs.randn(1, N, BS, HKV, DH), jnp.bfloat16)

        def rows(x):
            return x.reshape(1, N, BS, HKV * DH)

        def int8(x):
            codes, scales = kv_encode(x)
            return QuantizedKV(rows(codes), scales)

        out = {"dense": (rows(kb), rows(vb))}
        if H == HKV:
            out["int8"] = (int8(kb), int8(vb))
        return out

    def decode(q, k, v, tables, lens):
        return ua.paged_decode_attention_kernel(q, k, v, tables, lens, 0)

    def tile8(q, k, v, tables, lens):
        qt, (B, H, DH) = STREAM_TILE, q.shape
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

    def per_call_us(fn, q, *args):
        # nothing a call reads may be the same from one call to the next,
        # or XLA moves the gather out of the loop and keeps what it
        # gathered in VMEM (PR 27's first reading: 33.5 MB "read" in 32
        # us): the pools and the tables are arguments (closed over they
        # are constants, folded at compile time), and call i takes the
        # table rolled by i rows, which is the same work; `fn` returns
        # the next call's query
        def chained(q, *args):
            *pools, tables, lens = args

            def call(i, x):
                return fn(x, *pools, jnp.roll(tables, i, 0),
                          jnp.roll(lens, i, 0)).astype(x.dtype)

            return jax.lax.fori_loop(0, CALLS, call, q)

        loop = jax.jit(chained)
        loop(q, *args).block_until_ready()
        ts = []
        for _ in range(READINGS):
            t0 = time.perf_counter()
            loop(q, *args).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return np.median(ts) / CALLS * 1e6

    def grid_steps(lens, tables, fan):
        """Grid steps the decode kernel of this tree takes for the launch."""
        if hasattr(ua, "decode_fan"):
            return int(ua.decode_work_list(lens, tables, BS, fan)[3])
        if hasattr(ua, "decode_work_list"):
            return int(ua.decode_work_list(lens, BS, tables.shape[1])[2])
        return tables.size

    print(f"device {jax.devices()[0].device_kind}; us per call; live = "
          "(row, block) pairs under a context, grid = the decode kernel's "
          "steps, us/live = decode over live; |err| is the decode kernel's "
          "largest distance from xla-gather", flush=True)
    cases = [("gpt2", p, B, mix) for p in ("dense", "int8") for B in (8, 32)
             for mix in ("300-1024", "cell")]
    cases += [("gpt2", "dense", 32, "ctx=1"), ("gpt2", "int8", 32, "ctx=1")]
    cases += [("zaya", "dense", 128, mix)
              for mix in ("reason", "deep", "ctx=1")]
    cases += [("gpt2tp2", p, 32, mix) for p in ("dense", "int8")
              for mix in ("300-1024", "cell", "ctx=1")]
    cases = [c for c in cases if c[0] in geometries]
    if cases:
        print("| geometry | pool | B | contexts | mean ctx | live | fan | "
              "grid | xla-gather | tile8 | decode | us/live | us/step | "
              "max err |\n|---|---|---|---|---|---|---|---|---|---|---|---|"
              "---|---|", flush=True)
    held = {}
    has_fan = hasattr(ua, "decode_fan")       # this tree's kernel takes k
    rule_of = getattr(ua, "decode_fan", None)
    for geometry, name, B, mix in cases:
        H, _HKV, DH, M, N = GEOMETRY[geometry]
        if geometry not in held:
            held = {geometry: pools(geometry)}  # one geometry's at a time
        k, v = held[geometry][name]
        ctx = contexts(rs, mix, B, M)
        live = np.arange(M)[None, :] * BS < ctx[:, None]
        tables = jnp.asarray(np.where(live, rs.randint(1, N, (B, M)), 0),
                             jnp.int32)
        lens = jnp.asarray(ctx, jnp.int32)
        q = jnp.asarray(rs.randn(B, H, DH), jnp.bfloat16)
        few = min(B, GATHER_ROWS)   # the gather repeats K/V a query head
        ref = jax.jit(gather)(q[:few], k, v, tables[:few], lens[:few])
        # the two older paths are timed at the geometry they served
        older = [f"{per_call_us(fn, q, k, v, tables, lens):.0f}"
                 if geometry == "gpt2" else "-" for fn in (gather, tile8)]
        rule = ua.pool_decode_fan(k, v, M, 0) if has_fan else 1
        for fan in [f for f in FANS if f <= M] if has_fan else [1]:
            if has_fan:
                ua.decode_fan = lambda _bytes, _width, fan=fan: fan
            # a new function a fan: jit would keep the first fan's trace
            out = jax.jit(lambda *a: decode(*a))(q, k, v, tables, lens
                                                 ).astype(jnp.float32)
            us = per_call_us(decode, q, k, v, tables, lens)
            err = float(jnp.max(jnp.abs(out[:few]
                                        - ref.astype(jnp.float32))))
            grid = grid_steps(lens, tables, fan)
            print(f"| {geometry} | {name} | {B} | {mix} | {ctx.mean():.0f} | "
                  f"{int(live.sum())} | {fan}{'*' if fan == rule else ''} | "
                  f"{grid} | {older[0]} | {older[1]} | {us:.1f} | "
                  f"{us / live.sum():.3f} | {us / grid:.3f} | {err:.3f} |",
                  flush=True)
            older = ["", ""]
        if has_fan:
            ua.decode_fan = rule_of
    latent([g for g in geometries if g in LATENT], rs, per_call_us)


def latent(geometries, rs, per_call_us):
    """`mla_decode` at every fan in FANS, forced in place of
    `mla_decode.FAN`, beside the XLA gather path, at the latent
    geometries; GB/s is the live blocks' bytes over the kernel's time."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import attention, mla
    from paddle_tpu.ops.pallas import mla_decode
    from paddle_tpu.ops.pallas import unified_attention as ua

    if not geometries:
        return
    kw = dict(scale=QK ** -0.5, lora=LORA)
    walks = hasattr(mla_decode, "decode_work_list")   # a work-list grid

    def decode(q, pool, tables, lens):   # returns the next call's query
        o = mla_decode.mla_decode_kernel(q, pool, 0, tables, lens, **kw)
        return jnp.concatenate([o, q[..., LORA:]], -1)

    def gather(q, pool, tables, lens):   # the XLA path, chosen by hand
        saved, attention._on_tpu = attention._on_tpu, lambda: False
        try:
            o = mla.mla_decode_attention(q, pool, 0, tables, lens, **kw)
        finally:
            attention._on_tpu = saved
        return jnp.concatenate([o, q[..., LORA:]], -1)

    print("| geometry | B | contexts | mean ctx | live | fan | grid | "
          "xla-gather | mla_decode | us/live | us/step | GB/s | max err |\n"
          "|---|---|---|---|---|---|---|---|---|---|---|---|---|",
          flush=True)
    rule = mla_decode.FAN
    for geometry in geometries:
        B, M, N, traffic = LATENT[geometry]
        pool = jax.random.normal(jax.random.PRNGKey(0), (1, N, BS, ROW),
                                 jnp.bfloat16)
        for mix in (traffic, "ctx=1"):
            ctx = contexts(rs, mix, B, M)
            live = np.arange(M)[None, :] * BS < ctx[:, None]
            tables = jnp.asarray(
                np.where(live, rs.randint(1, N, (B, M)), 0), jnp.int32)
            lens = jnp.asarray(ctx, jnp.int32)
            q = jnp.asarray(rs.randn(B, HEADS, ROW), jnp.bfloat16)
            few = min(B, GATHER_ROWS)
            ref = jax.jit(gather)(q[:few], pool, tables[:few], lens[:few])
            xla = f"{per_call_us(gather, q, pool, tables, lens):.0f}"
            for fan in [f for f in FANS if f <= M]:
                mla_decode.FAN = fan
                jax.clear_caches()   # a kernel that reads FAN as it traces
                out = jax.jit(decode)(q, pool, tables, lens)
                us = per_call_us(decode, q, pool, tables, lens)
                err = float(jnp.max(jnp.abs(
                    out[:few, :, :LORA].astype(jnp.float32)
                    - ref[..., :LORA].astype(jnp.float32))))
                grid = (int(ua.decode_steps(ctx, BS, M, fan)[1].sum())
                        if walks else B * -(-M // fan))
                n = int(live.sum())
                print(f"| {geometry} | {B} | {mix} | {ctx.mean():.0f} | {n} | "
                      f"{fan}{'*' if fan == rule else ''} | {grid} | {xla} | "
                      f"{us:.1f} | {us / n:.3f} | {us / grid:.3f} | "
                      f"{n * BS * ROW * 2 / us / 1e3:.0f} | {err:.4f} |",
                      flush=True)
                xla = ""
            mla_decode.FAN = rule


if __name__ == "__main__":
    main(sys.argv[1:] or list(GEOMETRY) + list(LATENT))
