"""Attention on the real chip.

Default: one layer's attention sublayer, forward + backward, PROJECTIONS
AND THEIR GRADIENTS INSIDE the timed region (so the relayout passes between
the GEMMs and the kernels are timed too), at both train cells' shapes:
GPT-2-medium's fused [E, 3E] projection, causal, 8 x 1,024, and BERT-large's
three projections, non-causal, 16 x 512 — 16 heads of 64, bf16. Two forms:

  head-major   heads split and merged around `scaled_dot_product_attention`
               (what GPT2Block and MultiHeadAttention did before PR 33)
  token-major  `token_major_attention` on the projections as they lie

On a tree without `token_major_attention` only the head-major rows print:
to compare two trees, copy this script over the other tree's and run both
in one call.

`--check`: both forms' outputs and gradients against float32 attention, on
this device at the cells' layer shapes.

`--kernels`: ours vs jax stock pallas flash vs plain XLA einsum, kernels
alone, B=8 H=12 S=1024 D=64 bf16 causal (GPT-2 small shapes)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from _bench_util import scan_time, scan_time_args

HEADS, HEAD_DIM = 16, 64


def _head_major(ops, qkv_or_qkv3, causal):
    """The pre-PR-33 form: split heads, attend head-major, merge heads."""
    if isinstance(qkv_or_qkv3, tuple):
        q, k, v = qkv_or_qkv3
    else:
        q, k, v = jnp.split(qkv_or_qkv3, 3, axis=-1)
    b, s, e = q.shape
    q, k, v = (x.reshape(b, s, HEADS, HEAD_DIM).transpose(0, 2, 1, 3)
               for x in (q, k, v))
    o, _ = ops.scaled_dot_product_attention.__raw_fn__(q, k, v,
                                                       is_causal=causal)
    return o.transpose(0, 2, 1, 3).reshape(b, s, e)


def _token_major(ops, qkv_or_qkv3, causal):
    if isinstance(qkv_or_qkv3, tuple):
        q, k, v = qkv_or_qkv3
    else:
        q, k, v = qkv_or_qkv3, None, None
    return ops.token_major_attention.__raw_fn__(q, k, v, num_heads=HEADS,
                                                is_causal=causal)


def layer_ab():
    from paddle_tpu import ops
    from paddle_tpu.ops import attention as A

    e = HEADS * HEAD_DIM
    forms = [("head-major", _head_major)]
    if hasattr(ops, "token_major_attention"):
        forms.append(("token-major", _token_major))
    key = jax.random.key(0)
    for cell, b, s, fused, causal in (("gpt2_medium.train", 8, 1024, True,
                                       True),
                                      ("bert_large.train", 16, 512, False,
                                       False)):
        if hasattr(A, "flash_attention_path"):
            print(f"{cell}: paths",
                  [A.flash_attention_path(HEAD_DIM, HEADS, s, s, b,
                                          token_major=t)
                   for t in (False, True)], flush=True)
        x = jax.random.normal(key, (b, s, e), jnp.bfloat16)
        # the cotangent of the sublayer's output: no constant, which XLA
        # would fold into the out_proj gradients
        g = jax.random.normal(jax.random.fold_in(key, 3), (b, s, e),
                              jnp.bfloat16)
        w = {"qkv": jax.random.normal(jax.random.fold_in(key, 1),
                                      (e, 3 * e), jnp.bfloat16) * e ** -0.5,
             "out": jax.random.normal(jax.random.fold_in(key, 2),
                                      (e, e), jnp.bfloat16) * e ** -0.5}

        def project(w, x):
            if fused:
                return x @ w["qkv"]
            return tuple(x @ w["qkv"][:, i * e:(i + 1) * e]
                         for i in range(3))

        for name, form in forms:
            def layer(w, x, g):
                o = form(ops, project(w, x), causal) @ w["out"]
                return (o * g).astype(jnp.float32).sum()

            def layer_step(c, args):
                w, x, g = args
                gw, gx = jax.grad(layer, argnums=(0, 1))(
                    w, x + (c * 1e-30).astype(x.dtype), g)
                return c + gx.astype(jnp.float32).mean() \
                    + gw["qkv"].astype(jnp.float32).mean() \
                    + gw["out"].astype(jnp.float32).mean()

            t = scan_time_args(layer_step, jnp.zeros((), jnp.float32),
                               (w, x, g))
            print(f"{cell:18s} {name:12s} projections + attention + "
                  f"out_proj, forward + backward: {t * 1e3:7.3f} ms a layer",
                  flush=True)


def check():
    """Outputs and all three gradients of both forms on this device, bf16,
    against plain float32 attention on the same operands, at both cells'
    layer shapes (2 sequences): the worst error over the reference's largest
    value.  A train cell's `correct` reads one loss; this reads what the
    kernels write."""
    from paddle_tpu import ops

    e = HEADS * HEAD_DIM
    key = jax.random.key(7)
    worst = 0.0
    for cell, s, fused, causal in (("gpt2_medium.train", 1024, True, True),
                                   ("bert_large.train", 512, False, False)):
        qkv = jax.random.normal(key, (2, s, 3 * e), jnp.bfloat16)
        g = jax.random.normal(jax.random.fold_in(key, 1), (2, s, e),
                              jnp.bfloat16)

        def reference(qkv):
            q, k, v = (x.astype(jnp.float32).reshape(2, s, HEADS, HEAD_DIM)
                       .transpose(0, 2, 1, 3)
                       for x in jnp.split(qkv, 3, axis=-1))
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * HEAD_DIM ** -0.5
            if causal:
                sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
            o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)
            return o.transpose(0, 2, 1, 3).reshape(2, s, e)

        with jax.default_matmul_precision("highest"):
            want, vjp = jax.vjp(reference, qkv)
            want = (want, *jnp.split(vjp(g.astype(jnp.float32))[0]
                                     .astype(jnp.float32), 3, axis=-1))
        for name, form in (("head-major", _head_major),
                           ("token-major", _token_major)):
            def run(qkv):
                return form(ops, qkv if fused else
                            tuple(jnp.split(qkv, 3, axis=-1)), causal)

            out, vjp = jax.jit(lambda x: jax.vjp(run, x))(qkv)
            got = (out, *jnp.split(vjp(g)[0], 3, axis=-1))
            errs = [float(jnp.abs(a.astype(jnp.float32) - b).max()
                          / jnp.abs(b).max()) for a, b in zip(got, want)]
            worst = max(worst, *errs)
            print(f"{cell:18s} {name:12s} error / largest value: o "
                  f"{errs[0]:.4f} dq {errs[1]:.4f} dk {errs[2]:.4f} dv "
                  f"{errs[3]:.4f}", flush=True)
    # bf16 operands, probabilities and outputs: 2^-8 a rounding, a few deep
    if worst > 0.03:
        raise SystemExit(f"worst error {worst:.4f} over 0.03")


def kernels_alone():
    b, h, s, d = 8, 12, 1024, 64
    kq = jax.random.key(1)
    q = jax.random.normal(kq, (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(kq, 1), (b, h, s, d),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(kq, 2), (b, h, s, d),
                          jnp.bfloat16)
    flops_f = 2 * 2 * b * h * s * s * d * 0.5

    # ---- ours
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def ours(c):
        o = flash_attention(q + (c * 1e-30).astype(q.dtype), k, v, True)
        return o.astype(jnp.float32).mean()

    t = scan_time(ours, jnp.zeros((), jnp.float32))
    print(f"ours            fwd {t*1e3:.2f}ms {flops_f/t/1e12:.1f}TF/s",
          flush=True)

    def ours_g(c):
        g = jax.grad(lambda qq: flash_attention(qq, k, v, True)
                     .astype(jnp.float32).sum())(q + (c * 1e-30).astype(q.dtype))
        return g.astype(jnp.float32).mean()

    t = scan_time(ours_g, jnp.zeros((), jnp.float32))
    print(f"ours            f+b {t*1e3:.2f}ms", flush=True)

    # ---- stock pallas flash attention
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as stock_fa, BlockSizes)

        def stock(c):
            o = stock_fa(q + (c * 1e-30).astype(q.dtype), k, v, causal=True,
                         sm_scale=d ** -0.5)
            return o.astype(jnp.float32).mean()

        t = scan_time(stock, jnp.zeros((), jnp.float32))
        print(f"stock pallas    fwd {t*1e3:.2f}ms {flops_f/t/1e12:.1f}TF/s",
              flush=True)

        def stock_g(c):
            g = jax.grad(lambda qq: stock_fa(qq, k, v, causal=True,
                                             sm_scale=d ** -0.5)
                         .astype(jnp.float32).sum())(q + (c * 1e-30).astype(q.dtype))
            return g.astype(jnp.float32).mean()

        t = scan_time(stock_g, jnp.zeros((), jnp.float32))
        print(f"stock pallas    f+b {t*1e3:.2f}ms", flush=True)
    except Exception as e:  # noqa: BLE001
        print(f"stock pallas FAILED: {type(e).__name__}: {str(e)[:150]}",
              flush=True)

    # ---- plain XLA
    def xla(c):
        qq = q + (c * 1e-30).astype(q.dtype)
        sc = jnp.einsum("bhqd,bhkd->bhqk", qq, k,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        sc = jnp.where(qpos >= kpos, sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1).astype(jnp.bfloat16)
        o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
        return o.astype(jnp.float32).mean()

    t = scan_time(xla, jnp.zeros((), jnp.float32))
    print(f"xla einsum      fwd {t*1e3:.2f}ms {flops_f/t/1e12:.1f}TF/s "
          f"(counting causal-half flops)", flush=True)

    def xla_g(c):
        g = jax.grad(lambda qq: xla_loss(qq))(q + (c * 1e-30).astype(q.dtype))
        return g.astype(jnp.float32).mean()

    def xla_loss(qq):
        sc = jnp.einsum("bhqd,bhkd->bhqk", qq.astype(jnp.bfloat16), k,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        sc = jnp.where(qpos >= kpos, sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1).astype(jnp.bfloat16)
        o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
        return o.astype(jnp.float32).sum()

    t = scan_time(xla_g, jnp.zeros((), jnp.float32))
    print(f"xla einsum      f+b {t*1e3:.2f}ms", flush=True)


if __name__ == "__main__":
    {"--kernels": kernels_alone, "--check": check}.get(
        (sys.argv[1:] or [""])[0], layer_ab)()
