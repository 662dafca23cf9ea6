"""Regression guards for the r4 finding that the Pallas flash kernel was
silently ABSENT from every training trace (fwd-only had 12 tpu_custom_calls,
fwd+bwd had ZERO) for two stacked reasons:

  1. pallas_call abstractification rejects the framework Tensor wrapper, and
     sdpa's flash branch swallowed the failure (`except: pass`);
  2. the op registry's eager-tape jax.vjp consumed flash's custom_vjp rule,
     so an outer grad differentiated the raw pallas forward (no jvp rule).

These tests force the flash dispatch path on CPU (monkeypatched _on_tpu +
interpret-mode pallas) and assert the kernel is actually reached — with raw
arrays, with no fallback warning — from inside an outer jax.grad over the
functional train-step path.
"""
import warnings

import numpy as np
import pytest


class TestFlashEngagement:
    def _spy_flash(self, monkeypatch, calls):
        import functools

        import jax

        import paddle_tpu.ops.attention as A
        from paddle_tpu.ops.pallas import flash_attention as FA

        import paddle_tpu.parallel.mesh as mesh_mod

        orig = FA.flash_attention
        monkeypatch.setattr(A, "_on_tpu", lambda: True)
        # one device, whatever mesh an earlier file of this worker left
        # behind (under a leftover mesh sdpa takes its sharded branch)
        monkeypatch.setattr(mesh_mod, "_current_mesh", None)

        @functools.wraps(orig)
        def spy(q, k, v, *a, **kw):
            assert not hasattr(q, "_value"), \
                "flash_attention received a Tensor wrapper (regression #1)"
            assert isinstance(q, (jax.Array, jax.core.Tracer)) or \
                hasattr(q, "aval")
            calls.append(type(q).__name__)
            return orig(q, k, v, *a, **kw, interpret=True)

        monkeypatch.setattr(FA, "flash_attention", spy)

    def test_sdpa_reaches_kernel_under_outer_grad(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.core.autograd import functional_trace
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu import ops

        calls = []
        self._spy_flash(monkeypatch, calls)

        q0 = jnp.asarray(np.random.RandomState(0).rand(1, 2, 128, 32),
                         jnp.float32)

        def loss(qv):
            with functional_trace():
                o, _ = ops.scaled_dot_product_attention(
                    Tensor(qv), Tensor(q0), Tensor(q0), is_causal=True)
                return (o._value if hasattr(o, "_value") else o).sum()

        with warnings.catch_warnings():
            # a flash->XLA fallback warning here IS the regression
            warnings.simplefilter("error", RuntimeWarning)
            g = jax.grad(loss)(q0)
        assert calls, "flash kernel was never reached under outer grad"
        assert g.shape == q0.shape
        assert bool(jnp.all(jnp.isfinite(g)))

    def test_build_train_step_loss_reaches_kernel(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models.gpt2 import GPT2Config, build_train_step

        calls = []
        self._spy_flash(monkeypatch, calls)

        cfg = GPT2Config(vocab_size=512, hidden_size=64, num_layers=1,
                         num_heads=2, max_position=128, dropout=0.0)
        loss_fn, init_params, _model = build_train_step(cfg)
        params = init_params()
        batch = {
            "input_ids": jnp.zeros((1, 128), jnp.int32),
            "labels": jnp.zeros((1, 128), jnp.int32),
        }
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            grads = jax.grad(loss_fn)(params, batch, jax.random.key(0))
        assert calls, \
            "flash kernel absent from the train-step grad trace (regression)"
        leaves = jax.tree_util.tree_leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(l))) for l in leaves)

    def test_tape_still_records_outside_functional_trace(self):
        # dygraph backward() must keep working in user-managed traces:
        # the functional_trace skip must NOT leak into plain eager code
        import paddle_tpu as paddle
        from paddle_tpu.core.tensor import Tensor

        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        x.stop_gradient = False
        y = (x * 3.0).sum()
        y.backward()
        assert x.grad is not None
        assert float(x.grad._value.sum()) == pytest.approx(12.0)
