"""The pool's path through a serving program (PR 25): written in place,
read in place.

The kernels and the attention ops take the pool STACK [L, N, BS, H*Dh]
and a layer index; no builder slices a layer out of it.  These cases hold
the stack form to the one-layer form bit for bit (dense and int8 pools, a
middle layer and the last), and hold the compiled programs to the
structure the change is about: the pools aliased input to output and no
instruction but the K/V scatter producing anything as large as one
layer's pool.  (The same structure, for the chip's compiler and the
Pallas path, is a case of tests/test_tpu_aot_compile.py.)
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

L, N, BS, H, DH = 3, 9, 4, 4, 8
LAYERS = [1, L - 1]          # a middle layer and the last
TABLES = np.array([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 2]], np.int32)


def _stack(quant, seed):
    """(stack, one_layer(i)): a pool stack as the engine holds it, and the
    same values as one layer's [N, BS, H, Dh] pool."""
    from paddle_tpu.inference.kv_quant import QuantizedKV, kv_encode

    x = jnp.asarray(np.random.RandomState(seed).randn(
        L, N, BS, H, DH).astype(np.float32))
    if not quant:
        return x.reshape(L, N, BS, H * DH), lambda i: x[i]
    codes, scales = kv_encode(x)
    return (QuantizedKV(codes.reshape(L, N, BS, H * DH), scales),
            lambda i: QuantizedKV(codes[i], scales[i]))


def _stream():
    """Three segments of one 8-row tile each: a chunk, a decode row and a
    pad tile."""
    seg = np.repeat(np.arange(3, dtype=np.int32), 8)
    pos = np.array(list(range(3, 11)) + [13] + [-1] * 7 + [-1] * 8,
                   np.int32)
    q = np.random.RandomState(0).randn(24, H, DH).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(seg), jnp.asarray(pos)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
def test_stream_kernel_stack_equals_one_layer(quant, layer):
    from paddle_tpu.ops.pallas.unified_attention import (
        unified_ragged_attention_kernel as kernel)

    q, seg, pos = _stream()
    (kc, k_of), (vc, v_of) = _stack(quant, 1), _stack(quant, 2)
    tables = jnp.asarray(TABLES)
    out = kernel(q, kc, vc, tables, seg[::8], pos[::8], layer, q_tile=8,
                 interpret=True)
    # the layer is a traced scalar in a program: one kernel body for all
    traced = jax.jit(lambda ly: kernel(q, kc, vc, tables, seg[::8],
                                       pos[::8], ly, q_tile=8,
                                       interpret=True))(jnp.int32(layer))
    ref = kernel(q, k_of(layer), v_of(layer), tables, seg[::8], pos[::8],
                 q_tile=8, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert np.array_equal(np.asarray(traced), np.asarray(ref))
    assert np.isfinite(np.asarray(ref)).all() and np.asarray(ref).any()


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
def test_decode_entry_stack_equals_one_layer(quant, layer):
    from paddle_tpu.ops.pallas.unified_attention import (
        paged_decode_attention_kernel as kernel)

    q = jnp.asarray(np.random.RandomState(3).randn(3, H, DH)
                    .astype(np.float32))
    lens = jnp.asarray(np.array([11, 0, 16], np.int32))
    (kc, k_of), (vc, v_of) = _stack(quant, 1), _stack(quant, 2)
    tables = jnp.asarray(TABLES)
    out = kernel(q, kc, vc, tables, lens, layer, interpret=True)
    ref = kernel(q, k_of(layer), v_of(layer), tables, lens, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert np.asarray(ref)[0].any() and not np.asarray(ref)[1].any()


def test_stack_without_layer_is_refused():
    from paddle_tpu.ops.pallas.unified_attention import (
        paged_decode_attention_kernel as kernel)

    kc, _ = _stack(False, 1)
    q = jnp.zeros((3, H, DH), jnp.float32)
    with pytest.raises(ValueError, match="takes layer="):
        kernel(q, kc[0], kc[0], jnp.asarray(TABLES),
               jnp.ones((3,), jnp.int32), 0, interpret=True)


def _op_case(op):
    """(call(k, v, **layer) -> out) of one `ops.attention` entry on the
    XLA gather path (what the CPU takes)."""
    from paddle_tpu.ops import attention as A

    tables = jnp.asarray(TABLES)
    if op == "paged_decode_attention":
        q = jnp.asarray(np.random.RandomState(3).randn(3, H, DH)
                        .astype(np.float32))
        lens = jnp.asarray(np.array([11, 1, 16], np.int32))
        return lambda k, v, **kw: A.paged_decode_attention(
            q, k, v, tables, lens, **kw)
    q, seg, pos = _stream()
    if op == "ragged_prefill_attention":
        return lambda k, v, **kw: A.ragged_prefill_attention(
            q, k, v, tables, seg, pos, **kw)
    return lambda k, v, **kw: A.verify_window_attention(
        q.reshape(3, 8, H, DH), k, v, tables, pos.reshape(3, 8), **kw)


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("op", ["paged_decode_attention",
                                "ragged_prefill_attention",
                                "verify_window_attention"])
def test_ops_with_layer_equal_their_one_layer_forms(op, quant):
    call = _op_case(op)
    (kc, k_of), (vc, v_of) = _stack(quant, 1), _stack(quant, 2)
    for layer in LAYERS:
        out = call(kc, vc, layer=layer)
        ref = call(k_of(layer), v_of(layer))
        assert np.array_equal(np.asarray(out), np.asarray(ref))


# ---- the compiled programs ------------------------------------------------

SPEC = (2, 4, 8, 32, 1e-5, True)    # L, H, Dh, E, eps, tied
VOCAB, PBS, PN = 64, 8, 64          # vocabulary, block size, pool blocks


def _params():
    Lr, Hh, Dh, E, _, _ = SPEC
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    p = {"wte.weight": f(VOCAB, E), "wpe.weight": f(64, E),
         "ln_f.weight": f(E), "ln_f.bias": f(E)}
    for i in range(Lr):
        h = f"h.{i}."
        p.update({
            h + "ln_1.weight": f(E), h + "ln_1.bias": f(E),
            h + "ln_2.weight": f(E), h + "ln_2.bias": f(E),
            h + "qkv_proj.weight": f(E, 3 * E), h + "qkv_proj.bias": f(3 * E),
            h + "out_proj.weight": f(E, E), h + "out_proj.bias": f(E),
            h + "fc1.weight": f(E, 4 * E), h + "fc1.bias": f(4 * E),
            h + "fc2.weight": f(4 * E, E), h + "fc2.bias": f(E)})
    return p


def pool_sized_instructions(text, layer_elems):
    """[(name, op, line)] of the optimized HLO's instructions whose result
    holds at least `layer_elems` elements, parameters and bitcasts aside."""
    found = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \(?\w+\[([0-9,]*)\]"
                     r"[^ ]* ([\w\-]+)\(", ln)
        if not m or m.group(3) in ("parameter", "bitcast",
                                   "get-tuple-element"):
            continue
        n = 1
        for d in m.group(2).split(","):
            n *= int(d or 1)
        if n >= layer_elems:
            found.append((m.group(1), m.group(3), ln))
    return found


@pytest.mark.parametrize("program", ["step", "packed_prefill"])
def test_program_writes_and_reads_the_pool_in_place(program):
    """Lowered with donation, a serving program holds nothing of a layer's
    pool size but the pool itself and the K/V scatters into it: no slice of
    a layer, no copy of the stack."""
    from paddle_tpu.nn import decode

    Lr, Hh, Dh, _, _, _ = SPEC
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    pool = jax.ShapeDtypeStruct((Lr, PN, PBS, Hh * Dh), jnp.float32)
    if program == "step":
        _, fn = decode._build_paged_fns(SPEC, PBS, False, (False, False))
        args = (_params(), i32(4), i32(4),
                jax.ShapeDtypeStruct((4,), jnp.bool_), i32(4, 3), pool,
                pool, {"stop": i32(4, 1)})
        donate = (5, 6)
    else:
        fn = decode._build_packed_prefill(SPEC, PBS, False, (False, False))
        args = (_params(), i32(16), i32(16), i32(16), i32(2, 3), i32(2),
                pool, pool, {"stop": i32(2, 1)})
        donate = (6, 7)
    text = jax.jit(fn, donate_argnums=donate).lower(*args).compile() \
        .as_text()
    # where the backend reports aliasing, both pools are aliased to outputs
    header = text.split("\n", 1)[0]
    alias = "input_output_alias" in header
    if alias:
        assert len(re.findall(r"(?:may|must)-alias", header)) == 2, header
    big = pool_sized_instructions(text, PN * PBS * Hh * Dh)
    others = [b[:2] for b in big if "scatter" not in b[2]]
    # a backend without donation copies each donated pool once, before its
    # first scatter; with it, nothing pool-sized is left at all
    allowed = 0 if alias else 2
    assert len([b for b in others if b[1] == "copy"]) <= allowed, others
    assert not [b for b in others if b[1] != "copy"], others
    # K and V once a layer (a backend may wrap each in a fusion besides)
    assert len(big) - len(others) >= 2 * Lr
