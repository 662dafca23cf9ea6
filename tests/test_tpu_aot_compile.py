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
import re

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


LAYERS = 2  # depth of the pool stacks and programs compiled here


def _pool(sharding, quant, spec=None, heads=H, layers=LAYERS,
          blocks=N_BLOCKS):
    """The K (or V) pool stack as shapes, [L, N, BS, H*Dh] as the engine
    holds it: dense bf16, or int8 codes with per-vector scales [L, N, BS, H]
    in the compute dtype (what a bf16 engine holds)."""
    def s(shape, dt):
        sh = sharding if spec is None else NamedSharding(sharding, spec)
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    rows = (layers, blocks, BS)
    if not quant:
        return s(rows + (heads * DH,), jnp.bfloat16)
    from paddle_tpu.inference.kv_quant import QuantizedKV
    return QuantizedKV(s(rows + (heads * DH,), jnp.int8),
                       s(rows + (heads,), jnp.bfloat16))


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
                    i32(B, M), i32(T // Q_TILE), i32(T // Q_TILE),
                    i32()) == 1


def _decode_kernels(one_chip, quant, heads, kv_heads=None, dh=DH, rows=32,
                    width=M, blocks=N_BLOCKS):
    from paddle_tpu.ops.pallas.unified_attention import (
        paged_decode_attention_kernel)

    kv_heads = heads if kv_heads is None else kv_heads
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((rows, heads, dh), jnp.bfloat16,
                             sharding=one_chip)
    pool = _pool(one_chip, quant, heads=kv_heads * dh // DH, blocks=blocks)
    return _kernels(paged_decode_attention_kernel, q, pool, pool,
                    i32(rows, width), i32(rows), i32())


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
def test_decode_path_compiles(one_chip, quant):
    """The default engine loop's decode step: one token per sequence, all
    16 heads of a [128, 1024] block in one [16, 1024] query tile (PR 27;
    the (B, M)-grid body PR 21 deleted had a bare [H, Dh] left operand),
    over a grid whose one bound, the launch's live (row, block) pairs, is
    read on the device (PR 31)."""
    assert _decode_kernels(one_chip, quant, H) == 1


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
def test_decode_path_compiles_at_eight_heads_a_device(one_chip, quant):
    """What `tp=2` hands each device under shard_map: an [8, 512] tile,
    half a packed bf16 sublane group."""
    assert _decode_kernels(one_chip, quant, H // 2) == 1


def test_decode_path_compiles_at_group_four(one_chip):
    """`zaya1_8b_l16.serve_reason128`'s launch: 8 query heads on 2 K/V
    heads of 128, 128 rows, a table 40 wide over 2,560 blocks: the work
    list's 5,120 rows and columns beside the tables in SMEM."""
    assert _decode_kernels(one_chip, False, 8, kv_heads=2, dh=128, rows=128,
                           width=40, blocks=2560) == 1


def _kernel_names_in(text):
    """The HLO instruction names of a compiled program's kernels, as the
    chip's compiler gives them — what a device trace shows on `XLA Ops`."""
    return sorted(ln.split(" = ", 1)[0].split("%")[-1].rsplit(".", 1)[0]
                  for ln in text.splitlines()
                  if "tpu_custom_call" in ln and " = " in ln)


# what `decode_work_list` is in a program compiled for the described chip:
# one fusion for rows and columns' sums and one each for their bounds
WORK_LIST_FUSIONS = 3


def _work_list_fusions(text, steps):
    """The fusions of a compiled program's entry computation that produce
    the decode launch's work list (the only int32 arrays `steps` = rows x
    table width long): it depends on the contexts alone, so every layer's
    launch must share ONE, not build its own."""
    entry = text[text.index("\nENTRY "):]
    return [ln.split(" = ", 1)[0].strip() for ln in entry.splitlines()
            if " fusion(" in ln
            and f"s32[{steps}]" in ln.split(" = ", 1)[1].split(" fusion(")[0]]


def _kernel_op_names(fn, *args):
    with jax.default_matmul_precision("default"):
        return _kernel_names_in(jax.jit(fn).lower(*args).compile().as_text())


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
                            i32(32, M), i32(32),
                            i32()) == ["paged_attn_decode"]
    q = jax.ShapeDtypeStruct((512, H, DH), jnp.bfloat16, sharding=one_chip)
    assert _kernel_op_names(
        unified_ragged_attention_kernel, q, pool, pool, i32(8, M),
        i32(512 // Q_TILE), i32(512 // Q_TILE),
        i32()) == ["paged_attn_prefill"]


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
    pool = P(None, "dp", None, "mp")
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)

    def heads(n):
        return jax.ShapeDtypeStruct(
            (n, H, DH), jnp.bfloat16,
            sharding=NamedSharding(mesh4, P(None, "mp", None)))

    T, B = 512, 8
    kv = _pool(mesh4, False, pool)
    assert _kernels(
        lambda q, k, v, tb, seg, pos: attention.ragged_prefill_attention(
            q, k, v, tb, seg, pos, mesh=mesh4, layer=1),
        heads(T), kv, kv, i32(B, M), i32(T), i32(T)) == 1
    # the decode kernel builds its work list per device from the replicated
    # contexts and reads its grid bound there (PR 31), dense and int8
    for kvq in (kv, _pool(mesh4, True, pool)):
        assert _kernels(
            lambda q, k, v, tb, ctx: attention.paged_decode_attention(
                q, k, v, tb, ctx, mesh=mesh4, layer=1),
            heads(B), kvq, kvq, i32(B, M), i32(B)) == 1


def _gpt2_medium_params(sharding, layers):
    E = H * DH
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
    p = {"wte.weight": f(50257, E), "wpe.weight": f(1024, E),
         "ln_f.weight": f(E), "ln_f.bias": f(E)}
    for i in range(layers):
        h = f"h.{i}."
        p.update({
            h + "ln_1.weight": f(E), h + "ln_1.bias": f(E),
            h + "ln_2.weight": f(E), h + "ln_2.bias": f(E),
            h + "qkv_proj.weight": f(E, 3 * E), h + "qkv_proj.bias": f(3 * E),
            h + "out_proj.weight": f(E, E), h + "out_proj.bias": f(E),
            h + "fc1.weight": f(E, 4 * E), h + "fc1.bias": f(4 * E),
            h + "fc2.weight": f(4 * E, E), h + "fc2.bias": f(E)})
    return p


def _compile_serving_program(one_chip, monkeypatch, program, quant,
                             layers=LAYERS, blocks=N_BLOCKS):
    """GPT-2-medium's `decode_step` (32 rows) or `packed_prefill` (512
    tokens of 4 rows) through the Pallas path, its pools donated, as the
    chip's compiler schedules it."""
    from paddle_tpu.nn import decode
    from paddle_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    spec = (layers, H, DH, H * DH, 1e-5, True)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    pool = _pool(one_chip, quant, layers=layers, blocks=blocks)
    params = _gpt2_medium_params(one_chip, layers)
    if program == "step":
        _, fn = decode._build_paged_fns(spec, BS, False, (False, False),
                                        quant)
        # with `prev`, as the engine dispatches it (PR 29): a row whose
        # token is negative goes on from the step before's result
        args = (params, i32(32), i32(32),
                jax.ShapeDtypeStruct((32,), jnp.bool_, sharding=one_chip),
                i32(32, M), pool, pool, {"stop": i32(32, 1)}, i32(32))
        donate = (5, 6)
    else:
        fn = decode._build_packed_prefill(spec, BS, False, (False, False),
                                          quant)
        args = (params, i32(512), i32(512), i32(512), i32(4, M), i32(4),
                pool, pool, {"stop": i32(4, 1)})
        donate = (6, 7)
    with jax.default_matmul_precision("default"):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("program", ["step", "packed_prefill"])
def test_serving_program_works_on_the_pool_in_place(one_chip, monkeypatch,
                                                    program, quant):
    """GPT-2-medium's width at depth 2, through the Pallas path, as the
    chip's compiler schedules it: the donated pools are aliased to the
    outputs, one kernel a layer reads the stack, and nothing the program
    produces but the K/V scatters is as large as one layer's pool — no
    slice of a layer, no copy, no re-laid copy of the stack (PR 25: the
    parent's programs held three pools' worth of those)."""
    import re

    from test_pool_in_place import pool_sized_instructions

    compiled = _compile_serving_program(one_chip, monkeypatch, program,
                                        quant)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == LAYERS
    n_pools = 4 if quant else 2   # codes and scales, or the blocks
    assert len(re.findall(r"(?:may|must)-alias",
                          text.split("\n", 1)[0])) == n_pools
    layer_elems = N_BLOCKS * BS * H * DH
    big = [b for b in pool_sized_instructions(text, layer_elems)
           if "scatter" not in b[2]]
    # the head and the embedding are the vocabulary matrix, not the pool
    big = [b[:2] for b in big if "[50257," not in b[2].split(" = ", 1)[1][:24]]
    assert not big, big
    if not quant:
        # an int8 pool's scale stacks [L, N, BS, H] are still re-laid (H
        # is narrower than the lanes): 1/64 of the codes, PERF.md section 7
        layer_bytes = 2 * layer_elems * 2
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


def test_decode_step_at_the_serve_cell_size(one_chip, monkeypatch):
    """The whole `decode_step` of `gpt2_medium.serve_closed32` (24 layers,
    32 rows, 256 blocks): 24 kernels, each `paged_attn_decode`, and no
    temporaries: the decode entry takes q and returns its output as the
    [B, H*Dh] rows they are, so its wrapper adds no padded, transposed or
    re-laid operand (PR 27; the parent's 8-row streams and [H, T, Dh]
    transposes were 22.8 MB here)."""
    layers, blocks = 24, 256
    compiled = _compile_serving_program(one_chip, monkeypatch, "step",
                                        False, layers, blocks)
    text = compiled.as_text()
    assert _kernel_names_in(text) == ["paged_attn_decode"] * layers
    # the 24 launches share one work list (PR 31), and it costs the program
    # no temporary: 0 bytes, as at the parent
    assert len(_work_list_fusions(text, 32 * M)) == WORK_LIST_FUSIONS
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def _sds(one_chip):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def test_kda_decode_kernel_compiles(one_chip):
    """The recurrence on [8 heads, 128, 128] float32 tiles of the store,
    aliased in and out: the transposes and sublane reductions lower."""
    from paddle_tpu.ops.pallas.kda_decode import kda_decode_kernel

    s = _sds(one_chip)
    row = s((16, 32, 128), jnp.float32)
    assert _kernels(
        lambda st, sl, q, k, v, a, b: kda_decode_kernel(st, 1, sl, q, k, v,
                                                        a, b),
        s((2, 9, 32, 128, 128), jnp.float32), s((16,), jnp.int32), row, row,
        row, row, s((16, 32), jnp.float32)) == 1


def test_mla_decode_kernel_compiles(one_chip):
    """32 heads against [128, 640] blocks of latents (576 used), four table
    entries a grid step."""
    from paddle_tpu.ops.pallas.mla_decode import mla_decode_kernel

    s = _sds(one_chip)
    assert _kernels(
        lambda q, pool, tb, cx: mla_decode_kernel(q, pool, 0, tb, cx,
                                                  scale=192 ** -0.5,
                                                  lora=512),
        s((16, 32, 640), jnp.bfloat16), s((1, 64, 128, 640), jnp.bfloat16),
        s((16, 76), jnp.int32), s((16,), jnp.int32)) == 1


@pytest.mark.parametrize("tm,rows", [(16, 368), (128, 3072)],
                         ids=["decode", "prefill"])
def test_moe_gmm_kernel_compiles(one_chip, tm, rows):
    """The grouped SwiGLU at the published expert width (2304 x 1024), at
    the decode tile and at the prefill tile (whose slabs need more VMEM
    than the default scoped limit)."""
    from paddle_tpu.ops.pallas.moe_gmm import moe_gmm_kernel

    s = _sds(one_chip)
    w = s((8, 2304, 1024), jnp.bfloat16)
    assert _kernels(
        lambda x, g, u, d, te, nv: moe_gmm_kernel(x, g, u, d, te, nv, tm=tm),
        s((rows, 2304), jnp.bfloat16), w, w, s((8, 1024, 2304), jnp.bfloat16),
        s((rows // tm,), jnp.int32), s((1,), jnp.int32)) == 1


def test_stateful_decode_step_works_on_both_caches_in_place(one_chip,
                                                            monkeypatch):
    """Kimi-Linear's published widths at 5 layers and 8 held experts: the
    decode step holds its kernels by name, the latent pool and the state
    store are aliased to the outputs, and its temporaries are smaller than
    one KDA layer's state (PR 25's lesson, for two caches: a 576-wide
    latent row was re-laid around every kernel until it was padded to the
    lanes)."""
    import re

    from paddle_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig
    from paddle_tpu.nn.decode_blocks import build_block_programs
    from paddle_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    s = _sds(one_chip)
    model = KimiLinear(KimiLinearConfig(vocab_size=8192, held_layers=5,
                                        held_experts=(0, 8)),
                       dtype="bfloat16")
    desc = model.decoder_description()
    params = {k: s(v.shape, v.dtype)
              for k, v in model.functional_state()[0].items()}
    lay = desc.cache_layout()
    slots, rows, blocks, width = 16, 16, 64, 8
    pool = s((lay["pool_layers"], blocks, BS, lay["row_width"]), jnp.bfloat16)
    store = {name: s((layers, slots + 1) + shape, dt or jnp.bfloat16)
             for name, (layers, shape, dt) in lay["store"].items()}
    _packed, step = build_block_programs(desc, BS, False, (False, False))
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=(5, 6)).lower(
            params, s((rows,), jnp.int32), s((rows,), jnp.int32),
            s((rows,), jnp.bool_), s((rows, 1 + width), jnp.int32), pool,
            store, {"stop": s((rows, 1), jnp.int32)},
            s((rows,), jnp.int32)).compile()   # `prev`, as the engine's
    text = compiled.as_text()
    names = re.findall(r"%(kda_decode|mla_decode|moe_gmm)[.\d]* = ", text)
    assert {n: names.count(n) for n in set(names)} == {
        "kda_decode": 4, "mla_decode": 1, "moe_gmm": 4}
    assert text.count("tpu_custom_call") == 9
    # the pool, the state and the conv tails: in place (what the routers
    # did is a result of its own, written anew)
    assert len(re.findall(r"(?:may|must)-alias",
                          text.split("\n", 1)[0])) == 3
    one_layer_state = (slots + 1) * 32 * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer_state


@pytest.mark.parametrize("entry", ["head_major", "token_major",
                                   "token_major_fused"])
def test_flash_under_dp_mp_mesh_compiles(mesh4, monkeypatch, entry):
    """Training under Fleet dp x mp: the attention ops traced with a
    current mesh run the flash kernels per device (batch over dp, heads
    over mp) instead of handing GSPMD a kernel it cannot split — head-major
    operands through `scaled_dot_product_attention`, and the projections as
    they lie (three of them, or the fused one with every device's heads of
    q, k and v) through `token_major_attention`."""
    from paddle_tpu.core.autograd import functional_trace
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops import attention
    from paddle_tpu.parallel.mesh import mesh_guard

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert attention.flash_attention_path(
        DH, H, 1024, 1024, 8, mesh4,
        token_major=entry != "head_major") == "pallas/shard_map"
    # 8 sequences do not split over a dp of 3, nor 16 heads over an mp of 3
    assert attention.flash_attention_path(DH, 15, 1024, 1024, 8,
                                          mesh4) == "xla"

    def sds(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=NamedSharding(mesh4, spec))

    if entry == "head_major":
        args = (sds((8, H, 1024, DH), P("dp", "mp", None, None)),) * 3
    elif entry == "token_major":
        args = (sds((8, 1024, H * DH), P("dp", None, "mp")),) * 3
    else:
        args = (sds((8, 1024, 3 * H * DH), P("dp", None, None)),)

    def loss(*qkv):
        with mesh_guard(mesh4), functional_trace():
            if entry == "head_major":
                out, _ = attention.scaled_dot_product_attention(
                    *map(Tensor, qkv), is_causal=True)
            else:
                out = attention.token_major_attention(
                    *map(Tensor, qkv), num_heads=H, is_causal=True)
        return out._value.astype(jnp.float32).sum()

    assert _kernels(jax.grad(loss, argnums=tuple(range(len(args)))),
                    *args) == 3


_VIEWS = {"bitcast", "get-tuple-element", "tuple", "copy-start", "copy-done",
          "slice-start", "slice-done", "ConcatBitcast"}
_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u16": 2, "u32": 4, "pred": 1}


def _passes_around_kernels(text, q_bytes):
    """The ops of a compiled program's entry computation that produce a
    kernel's operand or consume its result, hold an array of `q_bytes` or
    more, and are neither a kernel, a GEMM fusion nor a parameter: each is
    a pass of its own over that array (a `copy`, a transpose, a pad, a
    slice, a concatenate, a prescale).  Views and XLA's own moves between
    memory spaces (`copy-start`/`-done`, sliced prefetches) are looked
    through."""
    def largest(shape):
        sizes = [0]
        for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape):
            if dt in _ITEM:
                sizes.append(_ITEM[dt] * int(np.prod(
                    [int(d) for d in dims.split(",") if d])))
        return max(sizes)

    gemms = {m.group(1) for m in re.finditer(
        r"\n(%[\w.\-]+) \([^\n]*\{\n(.*?)\n\}", text, re.S)
        if " convolution(" in m.group(2)}
    ins, users = {}, {}
    for ln in text[text.index("\nENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\((.*)", ln)
        if not m:
            continue
        name, shape, kind, rest = m.groups()
        if kind == "custom-call":
            kind = re.search(r'custom_call_target="([^"]+)"', rest).group(1)
        elif kind == "fusion" and re.search(r"calls=(%[\w.\-]+)",
                                            rest).group(1) in gemms:
            kind = "gemm"
        operands = re.findall(r"%[\w.\-]+", re.split(
            r", (?:calls|custom_call_target|kind)=", rest)[0])
        ins[name] = (kind, largest(shape), operands)
        for o in operands:
            users.setdefault(o, []).append(name)

    def beyond_views(name, step):
        found, todo = [], [name]
        while todo:
            for n in step(todo.pop()):
                (todo if ins[n][0] in _VIEWS else found).append(n)
        return found

    passes = set()
    for name in [n for n, i in ins.items() if i[0] == "tpu_custom_call"]:
        for n in beyond_views(name, lambda n: [o for o in ins[n][2]
                                               if o in ins]) \
                + beyond_views(name, lambda n: users.get(n, [])):
            kind, size, _ = ins[n]
            if size >= q_bytes and kind not in ("tpu_custom_call", "gemm",
                                                "parameter"):
                passes.add(f"{kind} {n}")
    return sorted(passes)


@pytest.mark.parametrize("layer", ["gpt2_block", "encoder_layer"])
def test_no_relayout_stands_around_the_flash_kernels(one_chip, monkeypatch,
                                                     layer):
    """One GPT2Block at 8 x 1,024 and one nn.TransformerEncoderLayer at
    16 x 512 (16 heads of 64, bf16), forward + backward, compiled for the
    described chip: the three kernels under their names, fed by the
    projection GEMMs and feeding the gradient GEMMs with NOTHING between
    them that moves a q-sized array (16.8 MB); the fused projection's
    gradient comes out of the fused backward as one array.  Before PR 33
    eight such passes stood around a layer's kernels (split heads, prescale
    q, merge heads; dO to head-major, dq, dk, dv back, a pad-and-add into
    d(qkv), the prescale's chain rule): 25 ms of a 189 ms train step."""
    from paddle_tpu import nn
    from paddle_tpu.core.autograd import functional_trace
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt2 import GPT2Block, GPT2Config
    from paddle_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    if layer == "gpt2_block":
        batch, seq = 8, 1024
        net = GPT2Block(GPT2Config(hidden_size=H * DH, num_heads=H,
                                   num_layers=1, dropout=0.0))
    else:
        batch, seq = 16, 512
        net = nn.TransformerEncoderLayer(H * DH, H, 4 * H * DH, dropout=0.0,
                                         activation="gelu")
    assert attention.flash_attention_path(
        DH, H, seq, seq, batch, token_major=True) == "pallas/token_major"
    net.train()
    net.to(dtype="bfloat16")
    s = _sds(one_chip)
    params = {k: s(v.shape, v.dtype)
              for k, v in net.functional_state()[0].items()}

    def loss(p, x):
        saved = net.functional_state()
        net.load_functional_state(p, None)
        try:
            with functional_trace():
                return net(Tensor(x))._value.astype(jnp.float32).sum()
        finally:
            net.load_functional_state(*saved)

    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, s((batch, seq, H * DH), jnp.bfloat16)).compile().as_text()
    assert _kernel_names_in(text) == ["flash_bwd_delta", "flash_bwd_fused",
                                      "flash_fwd"]
    assert text.count("tpu_custom_call") == 3
    assert _passes_around_kernels(text, batch * seq * H * DH * 2) == []


def _zaya_programs(one_chip, layers, rows, blocks, width, tokens=512):
    """ZAYA1-8B's `decode_step` and `packed_prefill` at the published
    widths and `layers` deep, as shapes on the described chip: (compiled
    step, compiled packed prefill, one layer's K pool in bytes)."""
    from paddle_tpu.models.zaya import Zaya, ZayaConfig
    from paddle_tpu.nn.decode_blocks import build_block_programs

    s = _sds(one_chip)
    # one layer's weights are built (4 of its experts), the rest are shapes
    model = Zaya(ZayaConfig(vocab_size=8192, held_layers=1,
                            held_experts=(0, 4)), dtype="bfloat16")
    one = model.functional_state()[0]
    params = {}
    for k, v in one.items():
        shape = (16,) + v.shape[1:] if ".experts." in k else v.shape
        for i in range(layers if k.startswith("layers.0.") else 1):
            params[k.replace("layers.0.", f"layers.{i}.")] = s(shape, v.dtype)
    params["embed.weight"] = s((262272, 2048), jnp.bfloat16)
    cfg = ZayaConfig(held_layers=layers)
    model.cfg = cfg
    desc = model.decoder_description()
    lay = desc.cache_layout()
    pool = s((lay["pool_layers"], blocks, BS, lay["row_width"]), jnp.bfloat16)
    store = {name: s((n, rows + 1) + shape, dt or jnp.bfloat16)
             for name, (n, shape, dt) in lay["store"].items()}
    packed, step = build_block_programs(desc, BS, False, (False, False))
    i32 = lambda *sh: s(sh, jnp.int32)
    with jax.default_matmul_precision("default"):
        c_step = jax.jit(step, donate_argnums=(5, 6, 7)).lower(
            params, i32(rows), i32(rows), s((rows,), jnp.bool_),
            i32(rows, 1 + width), pool, pool, store,
            {"stop": i32(rows, 1)}, i32(rows)).compile()
        c_packed = jax.jit(packed, donate_argnums=(6, 7, 8)).lower(
            params, i32(tokens), i32(tokens), i32(tokens),
            i32(rows, 1 + width), i32(rows), pool, pool, store,
            {"stop": i32(rows, 1)}).compile()
    return c_step, c_packed, blocks * BS * lay["row_width"] * 2


def test_grouped_head_programs_work_on_pools_and_tails_in_place(
        one_chip, monkeypatch):
    """ZAYA1-8B's two serving programs at the published widths, 2 layers
    deep, through the Pallas path: a paged kernel and a `moe_gmm` a layer
    by name (8 query heads on the pool's 2 K/V heads), the K pool, the V
    pool and the three tail arrays aliased to the outputs, and
    temporaries under one layer's pool (no pool-sized copy, no re-laid
    stack)."""
    import re

    from paddle_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    layers = 2
    step, packed, one_layer_pool = _zaya_programs(
        one_chip, layers, rows=128, blocks=2560, width=40)
    for compiled, kernel in ((step, "paged_attn_decode"),
                             (packed, "paged_attn_prefill")):
        text = compiled.as_text()
        names = _kernel_names_in(text)
        assert sorted(names) == sorted([kernel, "moe_gmm"] * layers), names
        assert len(re.findall(r"(?:may|must)-alias",
                              text.split("\n", 1)[0])) == 5
        assert compiled.memory_analysis().temp_size_in_bytes \
            < one_layer_pool
    # both layers' decode launches step over one work list (PR 31)
    assert len(_work_list_fusions(step.as_text(), 128 * 40)) \
        == WORK_LIST_FUSIONS


@pytest.mark.parametrize("tile", [32, 16])
def test_power_decode_kernel_compiles(one_chip, tile):
    """Brumby's launch: 16 rows, 40 query heads on 8 K/V heads of 128, a
    K/V head's [D, 128] float32 state a grid step (5.2 MB at tiles of 32,
    4.7 MB at 16, read and written: over the default scoped VMEM limit,
    under the kernel's own), the store aliased in and out: the
    transposes, the one-row loads and the sublane reductions lower."""
    from paddle_tpu.ops.pallas.power_decode import power_decode_kernel
    from paddle_tpu.ops.power_retention import state_dim

    s = _sds(one_chip)
    f32 = jnp.float32
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda st, sl, q, k, v, a: power_decode_kernel(
                st, 1, sl, q, k, v, a, tile=tile),
            donate_argnums=(0,)).lower(
            s((2, 17, 8, state_dim(128, tile), 128), f32),
            s((16,), jnp.int32), s((16, 40, 128), f32),
            s((16, 8, 128), f32), s((16, 8, 128), f32),
            s((16, 8), f32)).compile()
    assert _kernel_names_in(compiled.as_text()) == ["power_decode"]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_poolless_programs_work_on_the_store_in_place(one_chip,
                                                      monkeypatch):
    """Brumby-14B's two serving programs at the published widths, 2 layers
    deep, 16 rows, a table of [state slot] alone: a `power_decode` a layer
    by name in the decode step and no kernel in the packed prefill (its
    chunked form is XLA), the two store arrays (and the pool of no rows)
    aliased to the outputs, and temporaries under half a GB beside a
    store entry of 0.7 GB a layer: no copy of the store, in the scan over
    chunks and heads either (PR 25's check).  A chunk is a whole dispatch
    of 512 tokens, so that is the one packed bucket; the scan has 8 steps
    a chunk (one a K/V head) and stays a loop: a scan of ONE step is
    inlined and the compiler then re-lays the whole store around the
    program (5.3 GB of temporaries: PR 32, a scan over chunks alone at a
    chunk of 128 in the 128-token bucket)."""
    import re

    from paddle_tpu.models.brumby import Brumby, BrumbyConfig
    from paddle_tpu.nn.decode_blocks import build_block_programs
    from paddle_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    s = _sds(one_chip)
    layers, rows, tokens = 2, 16, 512
    model = Brumby(BrumbyConfig(vocab_size=1024, held_layers=1),
                   dtype="bfloat16")
    params = {}
    for k, v in model.functional_state()[0].items():
        for i in range(layers if k.startswith("layers.0.") else 1):
            params[k.replace("layers.0.", f"layers.{i}.")] = s(v.shape,
                                                               v.dtype)
    params["embed.weight"] = s((151936, 5120), jnp.bfloat16)
    params["lm_head.weight"] = s((5120, 151936), jnp.bfloat16)
    model.cfg = BrumbyConfig(held_layers=layers)
    desc = model.decoder_description()
    lay = desc.cache_layout()
    assert lay["pool_layers"] == 0
    pool = s((0, 2, BS, 0), jnp.bfloat16)
    store = {name: s((n, rows + 1) + shape, dt)
             for name, (n, shape, dt) in lay["store"].items()}
    packed, step = build_block_programs(desc, BS, False, (False, False))
    i32 = lambda *sh: s(sh, jnp.int32)
    with jax.default_matmul_precision("default"):
        c_step = jax.jit(step, donate_argnums=(5, 6)).lower(
            params, i32(rows), i32(rows), s((rows,), jnp.bool_),
            i32(rows, 1), pool, store, {"stop": i32(rows, 1)},
            i32(rows)).compile()
        c_packed = jax.jit(packed, donate_argnums=(6, 7)).lower(
            params, i32(tokens), i32(tokens), i32(tokens), i32(rows, 1),
            i32(rows), pool, store, {"stop": i32(rows, 1)}).compile()
    assert desc.chunk == desc.pack_multiple == tokens
    for compiled, kernels in ((c_step, ["power_decode"] * layers),
                              (c_packed, [])):
        text = compiled.as_text()
        assert _kernel_names_in(text) == kernels
        assert len(re.findall(r"(?:may|must)-alias",
                              text.split("\n", 1)[0])) == 3
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
