"""The flash kernels on the projections' own layout (PR 33): q, k, v and the
output [B, S, H*Dh], `heads_per_block(Dh)` heads to a 128-lane block.

Parity in interpret mode on the CPU, through the op a model calls
(`ops.token_major_attention`, the platform steered to the chip's branch)
against `_reference_attention`: the output and every gradient, for each
number of heads a block (Dh 64: two, 128: one, 32: four), causal and not,
with a per-key bias, with the fused [B, S, 3E] projection and with three
separate ones — and for a head count that does not fill the kernels' lane
blocks, which must take the path the gate names for it and match too."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

B, S = 2, 256


@pytest.fixture
def on_chip_branch(monkeypatch):
    """The ops take the chip's branch; the kernels run interpreted, at
    blocks of 128 so that every grid axis has more than one step."""
    import paddle_tpu.ops.attention as A
    import paddle_tpu.parallel.mesh as mesh_mod
    from paddle_tpu.ops.pallas import flash_attention as FA

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    # one device, whatever mesh an earlier file of this worker left behind
    monkeypatch.setattr(mesh_mod, "_current_mesh", None)
    calls = []
    for name in ("flash_attention_token_major", "flash_attention",
                 "flash_attention_bias"):
        orig = getattr(FA, name)

        def spy(*a, _orig=orig, _name=name, **kw):
            calls.append(_name)
            if len(a) + len(kw) < 9:  # an op's call: blocks left to default
                kw.update(block_q=128, block_k=128, interpret=True)
            return _orig(*a, **kw)

        monkeypatch.setattr(FA, name, functools.wraps(orig)(spy))
    return calls


def _reference(q, k, v, heads, causal, bias):
    """Token-major operands through the head-major float32 reference."""
    from paddle_tpu.ops.pallas.flash_attention import (NEG_INF,
                                                       _reference_attention)

    b, s, e = q.shape
    d = e // heads
    q, k, v = (x.reshape(b, -1, heads, d).transpose(0, 2, 1, 3)
               for x in (q, k, v))
    if bias is None:
        out = _reference_attention(q, k, v, d ** -0.5, causal)
    else:
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
        if causal:
            sc = jnp.where(jnp.tril(jnp.ones(sc.shape[-2:], bool)), sc,
                           NEG_INF)
        w = jax.nn.softmax(sc + bias[:, None, None, :], axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    return out.transpose(0, 2, 1, 3).reshape(b, s, e)


CASES = [
    # heads, head_dim, causal, fused, bias, path
    (4, 64, True, True, False, "pallas/token_major"),    # GPT-2's layer
    (4, 64, False, False, False, "pallas/token_major"),  # BERT's layer
    (4, 64, False, False, True, "pallas/token_major"),   # a padding mask
    (4, 64, True, False, True, "pallas/token_major"),
    (4, 64, False, True, True, "pallas/token_major"),
    (2, 128, True, False, False, "pallas/token_major"),  # one head a block
    (2, 128, False, True, True, "pallas/token_major"),
    (8, 32, True, True, False, "pallas/token_major"),    # four a block
    (8, 32, False, False, True, "pallas/token_major"),
    (1, 256, True, False, False, "pallas/token_major"),  # two lane blocks
    # heads that do not fill a block: the head-major border appends zero
    # heads, as it did for every head count before the kernels took blocks
    (3, 64, True, True, False, "pallas"),
    (3, 64, False, False, True, "pallas"),
    (6, 32, True, False, False, "pallas"),
    # no kernel for this head size: the XLA path
    (4, 16, True, True, False, "xla"),
]


@pytest.mark.parametrize("heads,head_dim,causal,fused,with_bias,path", CASES)
def test_token_major_matches_reference(on_chip_branch, heads, head_dim,
                                       causal, fused, with_bias, path):
    from paddle_tpu import ops
    from paddle_tpu.ops.attention import flash_attention_path

    assert flash_attention_path(head_dim, heads, S, S, B,
                                token_major=True) == path
    e = heads * head_dim
    rng = np.random.RandomState(heads * head_dim + causal + 2 * fused)
    qkv = jnp.asarray(rng.randn(B, S, 3 * e), jnp.float32)
    bias = jnp.asarray(rng.randn(B, S), jnp.float32) if with_bias else None
    g = jnp.asarray(rng.randn(B, S, e), jnp.float32)
    mask = None if bias is None else bias[:, None, None, :]

    def op(qkv, mask):
        q, k, v = (qkv, None, None) if fused else jnp.split(qkv, 3, axis=-1)
        return ops.token_major_attention.__raw_fn__(
            q, k, v, num_heads=heads, attn_mask=mask, is_causal=causal)

    def ref(qkv, bias):
        return _reference(*jnp.split(qkv, 3, axis=-1), heads, causal, bias)

    out, vjp = jax.vjp(op, qkv, mask)
    want, vjp_ref = jax.vjp(ref, qkv, bias)
    kernel = {"pallas/token_major": "flash_attention_token_major",
              "pallas": "flash_attention_bias" if with_bias
              else "flash_attention", "xla": None}[path]
    assert (on_chip_branch[:1] or [None])[0] == kernel
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    d_qkv, d_mask = vjp(g)
    want_qkv, want_bias = vjp_ref(g)
    # dq, dk and dv, each against its own scale
    for got, ref_ in zip(jnp.split(d_qkv, 3, axis=-1),
                         jnp.split(want_qkv, 3, axis=-1)):
        np.testing.assert_allclose(got, ref_, rtol=2e-3,
                                   atol=2e-4 * float(jnp.abs(ref_).max()))
    if with_bias:
        np.testing.assert_allclose(
            d_mask[:, 0, 0, :], want_bias, rtol=2e-3,
            atol=2e-4 * float(jnp.abs(want_bias).max()))


def test_two_kernel_backward_token_major():
    """The dq + dkv pair, which the vjp picks only where the fused kernel
    cannot pin a sequence in VMEM: called itself, on the fused projection."""
    from paddle_tpu.ops.pallas import flash_attention as FA

    heads, d = 4, 64
    rng = np.random.RandomState(5)
    qkv = jnp.asarray(rng.randn(B, S, 3 * heads * d), jnp.float32)
    g = jnp.asarray(rng.randn(B, S, heads * d), jnp.float32)
    ops_ = FA._Operands(qkv, None, None, heads)
    out, lse = FA._fwd(ops_, d ** -0.5, True, 128, 128, True)
    grads = FA._bwd_split(ops_, out, lse, g, d ** -0.5, True, 128, 128,
                          True)[:3]
    want = jax.vjp(lambda x: _reference(*jnp.split(x, 3, axis=-1), heads,
                                        True, None), qkv)[1](g)[0]
    for got, ref_ in zip(grads, jnp.split(want, 3, axis=-1)):
        np.testing.assert_allclose(got, ref_, rtol=2e-3,
                                   atol=2e-4 * float(jnp.abs(ref_).max()))


def test_fused_backward_is_gated_by_vmem():
    from paddle_tpu.ops.pallas import flash_attention as FA

    def fits(s, heads=16, d=64, dtype=jnp.bfloat16):
        x = jax.ShapeDtypeStruct((1, s, heads * d), dtype)
        return FA._fused_fits(FA._Operands(x, x, x, heads))

    assert fits(1024) and fits(2048)
    assert not fits(4096)             # ~17 MB of the core's 16
    assert not fits(2048, dtype=jnp.float32)
    assert fits(1024, 4, 256) and not fits(2048, 4, 256)


@pytest.mark.parametrize("model", ["gpt2_block", "encoder_layer"])
def test_layers_reach_the_token_major_kernels(on_chip_branch, model):
    """GPT2Block and nn.TransformerEncoderLayer hand their projections to
    the kernels as they lie, under an outer jax.grad, and match the same
    layer on the XLA path."""
    import paddle_tpu.ops.attention as A
    from paddle_tpu import nn
    from paddle_tpu.core.autograd import functional_trace
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt2 import GPT2Block, GPT2Config

    if model == "gpt2_block":
        layer = GPT2Block(GPT2Config(hidden_size=128, num_heads=2,
                                     num_layers=1, dropout=0.0))
    else:
        layer = nn.TransformerEncoderLayer(128, 2, 256, dropout=0.0)
    layer.train()
    params, _ = layer.functional_state()
    x = jnp.asarray(np.random.RandomState(3).randn(2, 128, 128), jnp.float32)

    def loss(p, x):
        saved = layer.functional_state()
        layer.load_functional_state(p, None)
        try:
            with functional_trace():
                return (layer(Tensor(x))._value ** 2).sum()
        finally:
            layer.load_functional_state(*saved)

    got = jax.grad(loss, argnums=(0, 1))(params, x)
    assert on_chip_branch == ["flash_attention_token_major"]
    A._on_tpu = lambda: False  # the fixture's monkeypatch restores it
    want = jax.grad(loss, argnums=(0, 1))(params, x)
    # against the largest gradient: k_proj's bias has none but rounding
    scale = max(float(jnp.abs(b).max())
                for b in jax.tree_util.tree_leaves(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-5 * scale)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "separate"])
def test_token_major_under_dp_mp_mesh(on_chip_branch, fused):
    """Under a (dp 2, mp 2) mesh the kernels run per device on its
    sequences and its heads — of the fused projection, on its heads' q, k
    and v — and give what one device gives."""
    import paddle_tpu.ops.attention as A
    from paddle_tpu import ops
    from paddle_tpu.parallel.mesh import make_mesh, mesh_guard

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = make_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    heads, d = 8, 64
    assert A.flash_attention_path(d, heads, S, S, B, mesh,
                                  token_major=True) == "pallas/shard_map"
    rng = np.random.RandomState(11)
    qkv = jnp.asarray(rng.randn(B, S, 3 * heads * d), jnp.float32)
    g = jnp.asarray(rng.randn(B, S, heads * d), jnp.float32)

    def op(qkv):
        q, k, v = (qkv, None, None) if fused else jnp.split(qkv, 3, axis=-1)
        with mesh_guard(mesh):
            return ops.token_major_attention.__raw_fn__(
                q, k, v, num_heads=heads, is_causal=True)

    out, vjp = jax.vjp(jax.jit(op), qkv)
    want, vjp_ref = jax.vjp(lambda x: _reference(
        *jnp.split(x, 3, axis=-1), heads, True, None), qkv)
    assert on_chip_branch == ["flash_attention_token_major"]
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    for got, ref_ in zip(jnp.split(vjp(g)[0], 3, axis=-1),
                         jnp.split(vjp_ref(g)[0], 3, axis=-1)):
        np.testing.assert_allclose(got, ref_, rtol=2e-3,
                                   atol=2e-4 * float(jnp.abs(ref_).max()))
