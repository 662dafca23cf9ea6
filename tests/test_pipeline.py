"""Pipeline parallelism (GPipe over pp axis) tests."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.parallel.pipeline import make_pipeline_loss, pipeline_apply

pytestmark = pytest.mark.skipif(jax.device_count() < 4,
                                reason="needs 4 virtual devices")


def _mesh_pp(s):
    devs = np.array(jax.devices()[:s])
    return Mesh(devs, ("pp",))


class TestPipeline:
    def test_forward_matches_sequential(self):
        s, m, mb, d = 4, 8, 2, 16
        np.random.seed(0)
        ws = np.random.rand(s, d, d).astype(np.float32) * 0.3
        x = np.random.rand(m, mb, d).astype(np.float32)

        def stage_fn(w, a):
            return jnp.tanh(a @ w)

        # sequential reference
        ref = x.copy()
        for i in range(s):
            ref = np.tanh(ref @ ws[i])

        mesh = _mesh_pp(s)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def run(ws, x):
            def inner(w_local, x):
                return pipeline_apply(stage_fn, w_local[0], x, "pp")
            return shard_map(inner, mesh=mesh, in_specs=(P("pp"), P()),
                             out_specs=P(), check_vma=False)(ws, x)

        out = jax.jit(run)(jnp.asarray(ws), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)

    def test_pipeline_trains(self):
        s, m, mb, d = 4, 4, 4, 8
        np.random.seed(1)
        ws = (np.random.rand(s, d, d).astype(np.float32) - 0.5) * 0.5
        x = np.random.rand(m * mb, d).astype(np.float32)
        y = np.random.rand(m * mb, d).astype(np.float32)

        def stage_fn(w, a):
            return jnp.tanh(a @ w)

        def loss_head(out, labels):
            return jnp.mean((out - labels) ** 2)

        mesh = _mesh_pp(s)
        loss_fn = make_pipeline_loss(stage_fn, loss_head, mesh, m)
        params = jnp.asarray(ws)

        @jax.jit
        def step(params, x, y):
            l, g = jax.value_and_grad(loss_fn)(params, x, y)
            return l, params - 0.5 * g

        losses = []
        for _ in range(15):
            l, params = step(params, jnp.asarray(x), jnp.asarray(y))
            losses.append(float(l))
        assert losses[-1] < losses[0] * 0.9
        assert np.isfinite(losses[-1])


class TestInterleavedPipeline:
    """Circular-interleaved schedule (VERDICT r4 next #5): parity against
    the meshless sequential reference AND against GPipe, forward and
    gradients, plus the analytic bubble accounting."""

    def _setup(self, s=4, v=2, m=8, mb=2, d=16, seed=3):
        np.random.seed(seed)
        n_groups = s * v
        ws = (np.random.rand(n_groups, d, d).astype(np.float32) - 0.5) * 0.5
        x = np.random.rand(m, mb, d).astype(np.float32)
        return ws, x

    @staticmethod
    def _stage_fn(w, a):
        return jnp.tanh(a @ w)

    def _sequential(self, ws, x):
        ref = x.copy()
        for i in range(ws.shape[0]):
            ref = np.tanh(ref @ ws[i])
        return ref

    def test_forward_matches_sequential_and_gpipe(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.pipeline import pipeline_apply_interleaved

        s, v = 4, 2
        ws, x = self._setup(s=s, v=v)
        ref = self._sequential(ws, x)
        mesh = _mesh_pp(s)

        def run_inter(ws, x):
            # [V*S, d, d] layer order -> [V, S, d, d], shard dim 1
            wr = ws.reshape(v, s, *ws.shape[1:])

            def inner(w_local, x):
                return pipeline_apply_interleaved(
                    self._stage_fn, w_local[:, 0], x, "pp")
            return shard_map(inner, mesh=mesh,
                             in_specs=(P(None, "pp"), P()),
                             out_specs=P(), check_vma=False)(wr, x)

        def run_gpipe(ws, x):
            # same 8 groups as 4 stages of 2 consecutive layers each
            wr = ws.reshape(s, v, *ws.shape[1:])

            def stage2(w2, a):
                def body(h, w1):
                    return self._stage_fn(w1, h), None
                out, _ = jax.lax.scan(body, a, w2)
                return out

            def inner(w_local, x):
                return pipeline_apply(stage2, w_local[0], x, "pp")
            return shard_map(inner, mesh=mesh, in_specs=(P("pp"), P()),
                             out_specs=P(), check_vma=False)(wr, x)

        out_i = jax.jit(run_inter)(jnp.asarray(ws), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out_i), ref,
                                   rtol=1e-4, atol=1e-5)
        # NOTE: gpipe's stage = layers [2i, 2i+1]; interleaved's group
        # order is the plain layer order — same network either way
        out_g = jax.jit(run_gpipe)(jnp.asarray(ws), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_g),
                                   rtol=1e-4, atol=1e-5)

    def test_grads_match_meshless_reference(self):
        from paddle_tpu.parallel.pipeline import make_pipeline_loss

        s, v, m, mb, d = 2, 2, 4, 2, 8
        ws, x = self._setup(s=s, v=v, m=m, mb=mb, d=d, seed=4)
        xf = x.reshape(m * mb, d)
        y = np.random.rand(m * mb, d).astype(np.float32)

        def loss_head(out, labels):
            return jnp.mean((out - labels) ** 2)

        def meshless(ws):
            h = jnp.asarray(xf)
            for i in range(ws.shape[0]):
                h = jnp.tanh(h @ ws[i])
            return loss_head(h, jnp.asarray(y))

        l_ref, g_ref = jax.value_and_grad(meshless)(jnp.asarray(ws))

        mesh = _mesh_pp(s)
        loss_fn = make_pipeline_loss(self._stage_fn, loss_head, mesh, m,
                                     schedule="interleaved", num_virtual=v)
        l_i, g_i = jax.jit(jax.value_and_grad(loss_fn))(
            jnp.asarray(ws), jnp.asarray(xf), jnp.asarray(y))
        np.testing.assert_allclose(float(l_i), float(l_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(g_i), np.asarray(g_ref),
                                   rtol=1e-3, atol=1e-5)

    def test_interleaved_trains(self):
        from paddle_tpu.parallel.pipeline import make_pipeline_loss

        # 4 layers total (deeper tanh stacks vanish the grads and stall
        # the fixed-lr loop — parity at depth 8 is covered above)
        s, v, m, mb, d = 2, 2, 4, 4, 8
        np.random.seed(5)
        ws = (np.random.rand(s * v, d, d).astype(np.float32) - 0.5) * 0.5
        x = np.random.rand(m * mb, d).astype(np.float32)
        y = np.random.rand(m * mb, d).astype(np.float32)

        def loss_head(out, labels):
            return jnp.mean((out - labels) ** 2)

        mesh = _mesh_pp(s)
        loss_fn = make_pipeline_loss(self._stage_fn, loss_head, mesh, m,
                                     schedule="interleaved", num_virtual=v)
        params = jnp.asarray(ws)

        @jax.jit
        def step(params, x, y):
            l, g = jax.value_and_grad(loss_fn)(params, x, y)
            return l, params - 0.5 * g

        losses = []
        for _ in range(15):
            l, params = step(params, jnp.asarray(x), jnp.asarray(y))
            losses.append(float(l))
        assert losses[-1] < losses[0] * 0.9
        assert np.isfinite(losses[-1])

    def test_bubble_fraction_accounting(self):
        from paddle_tpu.parallel.pipeline import bubble_fraction

        # at S=2, M=4: gpipe burns 20% by construction,
        # interleaved V=2 burns 11%
        assert abs(bubble_fraction("gpipe", 2, 4) - 1 / 5) < 1e-9
        assert abs(bubble_fraction("interleaved", 2, 4, 2) - 1 / 9) < 1e-9
        # the interleaved bubble is strictly smaller whenever V > 1, S > 1
        for s in (2, 4, 8):
            for m in (4, 8, 16):
                for v in (2, 3, 4):
                    assert bubble_fraction("interleaved", s, m, v) \
                        < bubble_fraction("gpipe", s, m)

    def test_rejects_indivisible_microbatches(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.pipeline import pipeline_apply_interleaved

        s, v = 4, 2
        ws, x = self._setup(s=s, v=v, m=6)  # 6 % 4 != 0
        mesh = _mesh_pp(s)
        wr = jnp.asarray(ws).reshape(v, s, *ws.shape[1:])

        def run(wr, x):
            def inner(w_local, x):
                return pipeline_apply_interleaved(
                    self._stage_fn, w_local[:, 0], x, "pp")
            return shard_map(inner, mesh=mesh,
                             in_specs=(P(None, "pp"), P()),
                             out_specs=P(), check_vma=False)(wr, x)

        with pytest.raises(ValueError, match="divisible"):
            jax.jit(run)(wr, jnp.asarray(x))
