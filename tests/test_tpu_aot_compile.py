"""The main-path Pallas kernels, compiled at real widths for a DESCRIBED
TPU v5e — the chip's own compiler, no chip attached, nothing runs.

Interpret mode cannot see what Mosaic refuses: the (B, M)-grid decode body
passed every interpret-mode test for twenty PRs and never lowered (a
head-batched dot whose left operand is a bare [H, Dh]), the int8 dequant
needed an f32 product, and a kernel under a multi-device `jit` has to sit
inside a shard_map.  These cases guard each of those at no chip time:
every one asserts that the kernel is IN the compiled program
(`tpu_custom_call`).

This is the only file that describes the chip.  The topology is described
inside a module-scoped fixture, after a test of this file has started —
never at import, in a skipif, in parametrize arguments or in conftest.py —
so every xdist worker collects the same tests and only the worker that is
handed this file loads libtpu.  The compiles run in this process.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

H, DH, BS, M, N_BLOCKS = 16, 64, 128, 8, 64  # GPT-2-medium heads, paged pool


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """dp=2 x mp=2 over the four described devices, in the repo's own axis
    order (parallel/mesh.py)."""
    return Mesh(np.array(topo.devices).reshape(2, 1, 2, 1),
                ("dp", "pp", "mp", "sp"))


def _kernels(fn, *args):
    # conftest.py pins "highest" matmul precision for CPU numerics; the
    # chip runs the default, and Mosaic refuses an fp32-contract bf16 dot
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(*args).compile().as_text().count(
            "tpu_custom_call")


def _pool(sharding, quant, spec=None):
    """One layer's K (or V) pool as shapes: dense bf16, or int8 codes with
    per-vector scales in the compute dtype (what a bf16 engine holds)."""
    def s(shape, dt, spec):
        sh = sharding if spec is None else NamedSharding(sharding, spec)
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    if not quant:
        return s((N_BLOCKS, BS, H, DH), jnp.bfloat16, spec)
    from paddle_tpu.inference.kv_quant import QuantizedKV
    return QuantizedKV(
        s((N_BLOCKS, BS, H, DH), jnp.int8, spec),
        s((N_BLOCKS, BS, H), jnp.bfloat16,
          None if spec is None else P(*spec[:3])))


def _flash_args(one_chip, b, h, s, d):
    x = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one_chip)
    return x, x, x


def test_flash_forward_compiles(one_chip):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    assert _kernels(lambda q, k, v: flash_attention(q, k, v, causal=True),
                    *_flash_args(one_chip, 8, 16, 1024, 64)) == 1


def test_flash_grad_compiles(one_chip):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    # forward + delta + fused backward: the 3 per layer of a train step
    assert _kernels(jax.grad(loss, argnums=(0, 1, 2)),
                    *_flash_args(one_chip, 8, 16, 1024, 64)) == 3


def test_flash_bias_grad_compiles(one_chip):
    """Per-key additive bias, the BERT-large padding-mask shape."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_bias

    def loss(q, k, v, bias):
        return flash_attention_bias(q, k, v, bias).astype(
            jnp.float32).sum()

    bias = jax.ShapeDtypeStruct((16, 512), jnp.float32, sharding=one_chip)
    assert _kernels(jax.grad(loss, argnums=(0, 1, 2)),
                    *_flash_args(one_chip, 16, 16, 512, 64), bias) == 3


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
def test_stream_kernel_compiles(one_chip, quant):
    """Packed prefill / unified round attention at the chunk bucket."""
    from paddle_tpu.ops.pallas.unified_attention import (
        Q_TILE, unified_ragged_attention_kernel)

    T, B = 512, 8
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((T, H, DH), jnp.bfloat16, sharding=one_chip)
    assert _kernels(unified_ragged_attention_kernel, q,
                    _pool(one_chip, quant), _pool(one_chip, quant),
                    i32(B, M), i32(T // Q_TILE), i32(T // Q_TILE)) == 1


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
def test_decode_path_compiles(one_chip, quant):
    """The default engine loop's decode step: one token per sequence, the
    stream kernel at DECODE_TILE rows (the path as repaired in PR 21)."""
    from paddle_tpu.ops.pallas.unified_attention import (
        paged_decode_attention_kernel)

    B = 32
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((B, H, DH), jnp.bfloat16, sharding=one_chip)
    assert _kernels(paged_decode_attention_kernel, q,
                    _pool(one_chip, quant), _pool(one_chip, quant),
                    i32(B, M), i32(B)) == 1


def _kernel_op_names(fn, *args):
    """The HLO instruction names of the program's kernels, as the chip's
    compiler gives them — what a device trace shows on `XLA Ops`."""
    with jax.default_matmul_precision("default"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    return sorted(ln.split(" = ", 1)[0].split("%")[-1].rsplit(".", 1)[0]
                  for ln in text.splitlines()
                  if "tpu_custom_call" in ln and " = " in ln)


def test_kernels_are_named_in_the_compiled_program(one_chip):
    """Each kernel's XLA op reads its own name, also under `jax.grad`
    (where an unnamed kernel read `jvp__` / `transpose_jvp___`)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.unified_attention import (
        Q_TILE, paged_decode_attention_kernel,
        unified_ragged_attention_kernel)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    assert _kernel_op_names(jax.grad(loss, argnums=(0, 1, 2)),
                            *_flash_args(one_chip, 8, 16, 1024, 64)) == [
        "flash_bwd_delta", "flash_bwd_fused", "flash_fwd"]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    pool = _pool(one_chip, False)
    q = jax.ShapeDtypeStruct((32, H, DH), jnp.bfloat16, sharding=one_chip)
    assert _kernel_op_names(paged_decode_attention_kernel, q, pool, pool,
                            i32(32, M), i32(32)) == ["paged_attn_decode"]
    q = jax.ShapeDtypeStruct((512, H, DH), jnp.bfloat16, sharding=one_chip)
    assert _kernel_op_names(
        unified_ragged_attention_kernel, q, pool, pool, i32(8, M),
        i32(512 // Q_TILE), i32(512 // Q_TILE)) == ["paged_attn_prefill"]


def test_head_sharded_kernels_compile_on_four_devices(mesh4, monkeypatch):
    """What a tensor-parallel engine hands the attention ops: a pool whose
    heads are split over mp (and blocks over dp) under a plain multi-device
    `jit`.  GSPMD cannot partition a Mosaic kernel, so the ops run it per
    device under shard_map; 8 heads per device pass the kernel gate."""
    from paddle_tpu.ops import attention

    # the ops ask jax.default_backend(), which is the CPU here: steer the
    # platform branch from the test, the mesh is the real argument
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert attention.paged_attention_path(DH, BS, H, 512, mesh4) \
        == "pallas/shard_map"
    assert attention.paged_attention_path(DH, BS, 4, 512, mesh4) == "xla"

    rep = NamedSharding(mesh4, P())
    pool = P("dp", None, "mp", None)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)

    def heads(n):
        return jax.ShapeDtypeStruct(
            (n, H, DH), jnp.bfloat16,
            sharding=NamedSharding(mesh4, P(None, "mp", None)))

    T, B = 512, 8
    kv = _pool(mesh4, False, pool)
    assert _kernels(
        lambda q, k, v, tb, seg, pos: attention.ragged_prefill_attention(
            q, k, v, tb, seg, pos, mesh=mesh4),
        heads(T), kv, kv, i32(B, M), i32(T), i32(T)) == 1
    kv8 = _pool(mesh4, True, pool)
    assert _kernels(
        lambda q, k, v, tb, ctx: attention.paged_decode_attention(
            q, k, v, tb, ctx, mesh=mesh4),
        heads(B), kv8, kv8, i32(B, M), i32(B)) == 1


def test_flash_under_dp_mp_mesh_compiles(mesh4, monkeypatch):
    """Training under Fleet dp x mp: `scaled_dot_product_attention` traced
    with a current mesh runs the flash kernels per device (batch over dp,
    heads over mp) instead of handing GSPMD a kernel it cannot split."""
    from paddle_tpu.core.autograd import functional_trace
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops import attention
    from paddle_tpu.parallel.mesh import mesh_guard

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct(
        (8, 16, 1024, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh4, P("dp", "mp", None, None)))

    def loss(q, k, v):
        with mesh_guard(mesh4), functional_trace():
            out, _ = attention.scaled_dot_product_attention(
                Tensor(q), Tensor(k), Tensor(v), is_causal=True)
        return out._value.astype(jnp.float32).sum()

    assert _kernels(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == 3
