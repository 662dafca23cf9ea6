"""Per-key-bias flash attention (r4): padding masks / ALiBi-style biases
streamed to the Pallas kernels as a [B, Sk] additive row — the [B,1,1,S]
additive-mask form BERT-class encoders build. Parity vs the XLA path in
interpret mode, on the forward, all three gradients, both backward
variants, and the causal+bias composition; plus the sdpa dispatch."""
import numpy as np
import pytest


def _setup(B=2, H=3, S=256, D=32, seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    bias = np.zeros((B, S), np.float32)
    bias[0, -S // 4:] = -1e30
    bias[1, -S // 8:] = -1e30
    return q, k, v, jnp.asarray(bias)


class TestFlashBias:
    def test_fwd_and_grads_match_xla(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import _xla_attention
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_bias

        q, k, v, bias = _setup()
        mask4 = bias[:, None, None, :]

        ref, _ = _xla_attention(q, k, v, mask=mask4, causal=False)
        out = flash_attention_bias(q, k, v, bias, causal=False,
                                   interpret=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5

        gf = jax.grad(lambda q_, k_, v_, b_: flash_attention_bias(
            q_, k_, v_, b_, False, None, 512, 512, True).sum(),
            argnums=(0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(lambda q_, k_, v_, b_: _xla_attention(
            q_, k_, v_, mask=b_[:, None, None, :],
            causal=False)[0].sum(),
            argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(gf, gr):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-5

    def test_broadcast_batch_bias_grad(self):
        """A (1, Sk) bias broadcast over batch must get a (1, Sk) cotangent
        summed over the batch (r5 review finding)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import _xla_attention
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_bias

        q, k, v, _ = _setup()
        bias1 = jnp.asarray(
            np.random.RandomState(7).randn(1, q.shape[2]), jnp.float32)
        gf = jax.grad(lambda b_: flash_attention_bias(
            q, k, v, b_, False, None, 512, 512, True).sum())(bias1)
        gr = jax.grad(lambda b_: _xla_attention(
            q, k, v, mask=b_[:, None, None, :],
            causal=False)[0].sum())(bias1)
        assert gf.shape == bias1.shape
        assert float(jnp.max(jnp.abs(gf - gr))) < 1e-4

    def test_causal_composes_with_bias(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import _xla_attention
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_bias

        q, k, v, bias = _setup(seed=1)
        ref, _ = _xla_attention(q, k, v, mask=bias[:, None, None, :],
                                causal=True)
        out = flash_attention_bias(q, k, v, bias, causal=True,
                                   interpret=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5

    def test_two_kernel_backward_with_bias(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.attention import _xla_attention
        from paddle_tpu.ops.pallas.flash_attention import (
            _flash_bwd, _flash_fwd_lse)

        from paddle_tpu.ops.pallas.flash_attention import (_bias_grad,
                                                           _bias_rows)

        q, k, v, bias = _setup(seed=2)
        bias3 = _bias_rows(bias, q.shape[0])
        sc = q.shape[-1] ** -0.5
        out, lse = _flash_fwd_lse(q, k, v, sc, False, 128, 128, True, bias3)
        g = jnp.ones_like(out)
        dq, dk, dv, db3 = _flash_bwd(q, k, v, out, lse, g, sc, False, 128,
                                     128, True, bias3)
        gr = jax.grad(lambda q_, k_, v_, b_: _xla_attention(
            q_, k_, v_, mask=b_[:, None, None, :],
            causal=False)[0].sum(),
            argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b2 in zip((dq, dk, dv), gr):
            assert float(jnp.max(jnp.abs(a - b2))) < 1e-5
        # the two-kernel path's bias cotangent (sum of dS over q rows then
        # heads) must match the XLA path's grad wrt the [B, Sk] bias
        assert float(jnp.max(jnp.abs(_bias_grad(db3, bias) - gr[3]))) < 1e-4

    def test_sdpa_dispatches_masked_to_kernel(self, monkeypatch):
        import functools

        import jax
        import jax.numpy as jnp

        import paddle_tpu.ops.attention as A
        from paddle_tpu.core.autograd import functional_trace
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.ops.pallas import flash_attention as FA

        import paddle_tpu.parallel.mesh as mesh_mod

        monkeypatch.setattr(A, "_on_tpu", lambda: True)
        # one device, whatever mesh an earlier file of this worker left
        monkeypatch.setattr(mesh_mod, "_current_mesh", None)
        calls = []
        orig = FA.flash_attention_bias

        @functools.wraps(orig)
        def spy(q, k, v, bias, *a, **kw):
            calls.append(q.shape)
            return orig(q, k, v, bias, *a, **kw, interpret=True)

        monkeypatch.setattr(FA, "flash_attention_bias", spy)

        q, k, v, bias = _setup()
        mask4 = bias[:, None, None, :]
        ref, _ = A._xla_attention(q, k, v, mask=mask4, causal=False)

        def run(qv):
            with functional_trace():
                o, _ = A.scaled_dot_product_attention.__raw_fn__(
                    Tensor(qv), Tensor(k), Tensor(v),
                    attn_mask=Tensor(mask4))
                return o

        out = run(q)
        out = out._value if hasattr(out, "_value") else out
        assert calls, "masked sdpa did not reach the bias kernel"
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5

    def test_sdpa_rejects_keys_broadcast_mask(self, monkeypatch):
        """r4 advisor: a [B,1,1,1] keys-broadcast mask is NOT a per-key
        bias (its last dim != Sk); tiling it into the kernel's BlockSpec
        could read garbage on TPU. It must take the XLA path."""
        import jax.numpy as jnp

        import paddle_tpu.ops.attention as A
        from paddle_tpu.core.autograd import functional_trace
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.ops.pallas import flash_attention as FA

        monkeypatch.setattr(A, "_on_tpu", lambda: True)

        def boom(*a, **kw):
            raise AssertionError("bias kernel reached with a broadcast mask")

        monkeypatch.setattr(FA, "flash_attention_bias", boom)
        q, k, v, _ = _setup()
        mask1 = jnp.zeros((q.shape[0], 1, 1, 1), jnp.float32) - 2.0
        ref, _ = A._xla_attention(q, k, v, mask=mask1, causal=False)
        with functional_trace():
            o, _ = A.scaled_dot_product_attention.__raw_fn__(
                Tensor(q), Tensor(k), Tensor(v), attn_mask=Tensor(mask1))
        o = o._value if hasattr(o, "_value") else o
        assert float(jnp.max(jnp.abs(o - ref))) < 1e-5
