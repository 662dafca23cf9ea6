"""Systematic per-op gradient checks — the reference's OpTest.check_grad
strategy (SURVEY §4): for each differentiable op, the dygraph tape's
backward is compared against central finite differences of a fixed random
projection of the op's output. This exercises the recorded-vjp machinery
op by op (not jax.grad directly), the way the reference checks each C++
grad kernel against numeric gradients.

Inputs are small and placed in smooth regions (away from |x|=0 kinks,
distinct values for min/max) so the finite difference is well-posed in
float32; thresholds follow the reference's max_relative_error ~1e-2.
"""
import numpy as np
import pytest

import paddle_tpu as paddle

EPS = 1e-2
RTOL = 8e-2
ATOL = 8e-3


def _loss_np(fn, arrays, proj):
    ts = [paddle.to_tensor(a) for a in arrays]
    out = fn(*ts)
    o = np.asarray(out.numpy(), np.float64)
    return float((o * proj).sum())


def check_grad(fn, *arrays, diff_idx=None):
    """Tape backward of sum(fn(*xs) * proj) vs central differences."""
    rs = np.random.RandomState(7)
    ts = [paddle.to_tensor(a, stop_gradient=False) for a in arrays]
    out = fn(*ts)
    # np.asarray: 0-d outputs (mean/norm/losses) give rs.rand() a float
    proj = np.asarray(rs.rand(*tuple(out.shape)), np.float64) + 0.5
    loss = (out * paddle.to_tensor(proj.astype(np.float32))).sum()
    loss.backward()
    diff_idx = range(len(arrays)) if diff_idx is None else diff_idx
    for k in diff_idx:
        analytic = np.asarray(ts[k].grad.numpy()
                              if hasattr(ts[k].grad, "numpy")
                              else ts[k].grad, np.float64)
        a = arrays[k]
        numeric = np.zeros_like(a, np.float64)
        flat = a.reshape(-1)
        num_flat = numeric.reshape(-1)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + EPS
            up = _loss_np(fn, arrays, proj)
            flat[i] = orig - EPS
            dn = _loss_np(fn, arrays, proj)
            flat[i] = orig
            num_flat[i] = (up - dn) / (2 * EPS)
        np.testing.assert_allclose(
            analytic, numeric, rtol=RTOL, atol=ATOL,
            err_msg=f"input {k} of {getattr(fn, '__name__', fn)}")


def _pos(shape, lo=0.5, hi=1.5, seed=0):
    return np.random.RandomState(seed).uniform(
        lo, hi, shape).astype(np.float32)


def _any(shape, seed=1):
    return (np.random.RandomState(seed).randn(*shape) * 0.5
            ).astype(np.float32)


def _spread(shape, seed=2):
    """Values pairwise far apart: safe for min/max/sort ops."""
    rs = np.random.RandomState(seed)
    n = int(np.prod(shape))
    vals = (np.arange(n) * 0.37 + 0.1) * rs.choice([-1, 1], n)
    rs.shuffle(vals)
    return vals.reshape(shape).astype(np.float32)


P = paddle


class TestElementwiseGrads:
    @pytest.mark.parametrize("op,args", [
        ("add", (_any((2, 3)), _any((2, 3), 3))),
        ("subtract", (_any((2, 3)), _any((2, 3), 4))),
        ("multiply", (_any((2, 3)), _any((2, 3), 5))),
        ("divide", (_any((2, 3)), _pos((2, 3), seed=6))),
        ("pow", (_pos((2, 3)), 2.0)),
        ("exp", (_any((2, 3)),)),
        ("log", (_pos((2, 3)),)),
        ("sqrt", (_pos((2, 3)),)),
        ("rsqrt", (_pos((2, 3)),)),
        ("tanh", (_any((2, 3)),)),
        ("sin", (_any((2, 3)),)),
        ("cos", (_any((2, 3)),)),
        ("erf", (_any((2, 3)),)),
        ("square", (_any((2, 3)),)),
        ("reciprocal", (_pos((2, 3)),)),
        ("sigmoid", (_any((2, 3)),)),
        ("maximum", (_spread((2, 3)), _spread((2, 3), 9))),
        ("minimum", (_spread((2, 3)), _spread((2, 3), 10))),
        # r4 widening: transcendental/cumulative/shape ops
        ("logsumexp", (_any((2, 3)),)),
        ("cumsum", (_any((2, 3)),)),
        ("cumprod", (_pos((2, 3)),)),
        ("softplus", (_any((2, 3)),)),
        ("expm1", (_any((2, 3)),)),
        ("log1p", (_pos((2, 3)),)),
        ("log2", (_pos((2, 3)),)),
        ("log10", (_pos((2, 3)),)),
        ("atan", (_any((2, 3)),)),
        ("sinh", (_any((2, 3)),)),
        ("cosh", (_any((2, 3)),)),
        ("tan", (_any((2, 3), 11),)),
        ("asinh", (_any((2, 3)),)),
        ("softsign", (_any((2, 3)),)),
        ("celu", (_any((2, 3)),)),
        ("trace", (_any((3, 3)),)),
        ("outer", (_any((3,)), _any((4,), 12))),
        ("kron", (_any((2, 2)), _any((2, 3), 13))),
    ])
    def test_grad(self, op, args):
        fn = getattr(P, op) if hasattr(P, op) \
            else getattr(P.nn.functional, op)
        tensor_args = [a for a in args if isinstance(a, np.ndarray)]
        scalars = [a for a in args if not isinstance(a, np.ndarray)]
        check_grad(lambda *xs: fn(*xs, *scalars), *tensor_args)


class TestReductionShapeGrads:
    @pytest.mark.parametrize("build,arrays", [
        (lambda x: P.mean(x), (_any((3, 4)),)),
        (lambda x: P.sum(x, axis=1), (_any((3, 4)),)),
        (lambda x: P.max(x, axis=1), (_spread((3, 4)),)),
        (lambda x: P.min(x, axis=0), (_spread((3, 4), 5),)),
        (lambda x: P.prod(x, axis=1), (_pos((2, 3)),)),
        (lambda x: P.logsumexp(x, axis=1), (_any((3, 4)),)),
        (lambda x: P.cumsum(x, axis=1), (_any((2, 4)),)),
        (lambda x: P.reshape(x, [4, 3]), (_any((3, 4)),)),
        (lambda x: P.transpose(x, [1, 0]), (_any((3, 4)),)),
        (lambda x: P.squeeze(P.unsqueeze(x, 0), 0), (_any((2, 3)),)),
        (lambda x: P.tile(x, [2, 1]), (_any((2, 3)),)),
        (lambda x: P.flip(x, [1]), (_any((2, 3)),)),
        (lambda x: P.clip(x, -0.4, 0.4) * 1.0,
         (_spread((2, 3)) * 0.1,)),
        (lambda x: P.norm(x, p=2), (_pos((2, 3)),)),
        (lambda x, y: P.concat([x, y], axis=1),
         (_any((2, 2)), _any((2, 3), 8))),
        (lambda x, y: P.stack([x, y], axis=0),
         (_any((2, 3)), _any((2, 3), 9))),
        (lambda x, y: P.where(P.to_tensor(
            np.array([[True, False, True], [False, True, False]])), x, y),
         (_any((2, 3)), _any((2, 3), 11))),
    ])
    def test_grad(self, build, arrays):
        check_grad(build, *arrays)


class TestContractionGrads:
    def test_matmul(self):
        check_grad(lambda a, b: P.matmul(a, b),
                   _any((2, 3)), _any((3, 4), 3))

    def test_bmm(self):
        check_grad(lambda a, b: P.bmm(a, b),
                   _any((2, 2, 3)), _any((2, 3, 2), 4))

    def test_linear_functional(self):
        check_grad(lambda x, w, b: P.nn.functional.linear(x, w, b),
                   _any((2, 3)), _any((3, 4), 5), _any((4,), 6))

    def test_embedding_weight_grad(self):
        ids = np.array([[0, 2], [1, 2]])

        def fn(w):
            return P.nn.functional.embedding(
                P.to_tensor(ids), w)

        check_grad(fn, _any((4, 3)))

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_embedding_weight_grad_repeated_ids(self, dtype):
        """Ids that repeat accumulate in d(weight): it equals the one-hot
        matmul of the upstream cotangent, computed here in float64 — for
        the scatter-add of `jnp.take`'s backward, the one path the lookup
        has."""
        rs = np.random.RandomState(0)
        vocab, width = 31, 7
        ids = rs.randint(0, vocab, (5, 4))
        assert np.unique(ids).size < ids.size      # some id repeats
        w = P.to_tensor(rs.randn(vocab, width).astype(np.float32)) \
            .astype(dtype)
        w.stop_gradient = False
        proj = P.to_tensor(rs.rand(5, 4, width).astype(np.float32) + 0.5) \
            .astype(dtype)
        (P.nn.functional.embedding(P.to_tensor(ids), w) * proj).sum() \
            .backward()
        assert str(w.grad.dtype).endswith(dtype)
        onehot = np.eye(vocab, dtype=np.float64)[ids.reshape(-1)]
        want = onehot.T @ np.asarray(proj.astype("float32").numpy(),
                                     np.float64).reshape(-1, width)
        tol = 1e-6 if dtype == "float32" else 2.0 ** -7
        np.testing.assert_allclose(
            np.asarray(w.grad.astype("float32").numpy(), np.float64), want,
            rtol=tol, atol=tol)

    def test_embedding_negative_padding_idx_normalized(self):
        """padding_idx=-1 names row vocab-1 (the reference's
        lookup_table_v2), whose gradient stays zero; direct op callers
        (static.nn.embedding) pass it through raw."""
        w = P.to_tensor(np.ones((5, 3), np.float32), stop_gradient=False)
        x = P.to_tensor(np.array([[4, 1]], np.int64))
        P.ops.embedding(x, w, padding_idx=-1).sum().backward()
        g = w.grad.numpy()
        np.testing.assert_allclose(g[4], 0.0)
        assert np.abs(g[1]).sum() > 0

    def test_conv2d_functional(self):
        check_grad(
            lambda x, w: P.nn.functional.conv2d(x, w, stride=1, padding=1),
            _any((1, 2, 4, 4)), _any((3, 2, 3, 3), 7))


class TestNormalizationLossGrads:
    def test_softmax(self):
        check_grad(lambda x: P.nn.functional.softmax(x, axis=-1),
                   _any((2, 4)))

    def test_log_softmax(self):
        check_grad(lambda x: P.nn.functional.log_softmax(x, axis=-1),
                   _any((2, 4)))

    def test_layer_norm_functional(self):
        check_grad(
            lambda x, w, b: P.nn.functional.layer_norm(x, (4,), w, b),  # ref signature
            _any((3, 4)), _pos((4,), seed=8), _any((4,), 9))

    def test_gelu(self):
        check_grad(lambda x: P.nn.functional.gelu(x), _any((2, 4)))

    def test_relu_off_kink(self):
        check_grad(lambda x: P.nn.functional.relu(x),
                   _spread((2, 3)))  # no values near 0

    def test_cross_entropy(self):
        labels = np.array([1, 3])

        def fn(logits):
            return P.nn.functional.cross_entropy(
                logits, P.to_tensor(labels))

        check_grad(fn, _any((2, 4)))

    def test_mse_loss(self):
        y = _any((2, 3), 12)
        check_grad(lambda x: P.nn.functional.mse_loss(
            x, P.to_tensor(y)), _any((2, 3)))

    def test_softmax_with_cross_entropy(self):
        labels = np.array([[1], [2]])

        def fn(logits):
            return P.nn.functional.softmax_with_cross_entropy(
                logits, P.to_tensor(labels))

        check_grad(fn, _any((2, 4)))


class TestIndexingGrads:
    def test_gather(self):
        idx = np.array([0, 2])
        check_grad(lambda x: P.gather(x, P.to_tensor(idx)),
                   _any((3, 4)))

    def test_slice(self):
        check_grad(lambda x: x[:, 1:3], (_any((2, 4))))

    def test_index_select(self):
        idx = np.array([2, 0])
        check_grad(lambda x: P.index_select(x, P.to_tensor(idx), axis=1),
                   _any((2, 4)))

    def test_pad(self):
        check_grad(lambda x: P.nn.functional.pad(x, [1, 1, 0, 1]),
                   _any((1, 1, 2, 3)))


class TestDoubleGrads:
    """Second-order: d/dx of (d loss/dx · v) vs finite differences of the
    first-order grad — exercises grad-of-grad through the recorded
    pullbacks (ref: the reference's *_double_grad kernels)."""

    @pytest.mark.parametrize("op,mk", [
        (lambda t: P.tanh(t), lambda: _any((2, 3))),
        (lambda t: P.exp(t), lambda: _any((2, 3))),
        (lambda t: P.square(t), lambda: _any((2, 3))),
        (lambda t: P.nn.functional.sigmoid(t), lambda: _any((2, 3))),
        (lambda t: P.log(t), lambda: _pos((2, 3))),
    ])
    def test_hvp(self, op, mk):
        a = mk()
        v = _any(a.shape, 13).astype(np.float64)

        def grad_np(arr):
            t = paddle.to_tensor(arr.astype(np.float32),
                                 stop_gradient=False)
            loss = op(t).sum()
            loss.backward()
            return np.asarray(t.grad.numpy(), np.float64)

        # analytic HVP via the tape's grad-of-grad
        t = paddle.to_tensor(a, stop_gradient=False)
        out = op(t).sum()
        (g,) = paddle.grad([out], [t], create_graph=True)
        inner = (g * paddle.to_tensor(v.astype(np.float32))).sum()
        inner.backward()
        hvp = np.asarray(t.grad.numpy(), np.float64)
        # numeric HVP: (grad(x + eps v) - grad(x - eps v)) / 2eps
        num = (grad_np(a + EPS * v.astype(np.float32))
               - grad_np(a - EPS * v.astype(np.float32))) / (2 * EPS)
        np.testing.assert_allclose(hvp, num, rtol=RTOL, atol=2e-2)


class TestEagerStaticParity:
    """Same computation eager vs whole-Program executor (SURVEY §4
    static-vs-dygraph parity): identical inputs and seeded params must
    produce identical outputs through both execution paths."""

    @pytest.mark.parametrize("build,expected", [
        (lambda x: paddle.static.nn.fc(x, size=5, activation="relu"),
         lambda h: np.maximum(h, 0)),
        (lambda x: paddle.nn.functional.softmax(
            paddle.static.nn.fc(x, size=4), axis=-1),
         lambda h: np.exp(h - h.max(-1, keepdims=True))
         / np.exp(h - h.max(-1, keepdims=True)).sum(-1, keepdims=True)),
    ])
    def test_parity(self, build, expected):
        from paddle_tpu import fluid
        paddle.enable_static()
        try:
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                xv = fluid.data(name="x", shape=[None, 6],
                                dtype="float32")
                out = build(xv)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            x = _any((3, 6), 21)
            static_out = exe.run(main, feed={"x": x},
                                 fetch_list=[out])[0]
            # rebuild the same math eagerly with the Program's params
            params = {p.name: np.asarray(
                fluid.global_scope().find_var(p.name))
                for p in main.all_parameters()}
        finally:
            paddle.disable_static()
        names = sorted(params)
        w, b = params[names[1]], params[names[0]]
        if w.ndim == 1:
            w, b = b, w
        np.testing.assert_allclose(static_out, expected(x @ w + b),
                                   rtol=1e-5, atol=1e-5)


class TestFunctionalTraceParity:
    """The functional_trace path (ops called directly under an outer
    jax.grad — the r4 fast path that lets custom_vjp kernels engage) must
    produce the same gradients as the eager tape for the same computation."""

    def test_composite_network_grads_match_tape(self):
        import jax
        import jax.numpy as jnp

        import paddle_tpu.nn as nn
        from paddle_tpu.core.autograd import functional_trace
        from paddle_tpu.core.tensor import Tensor

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.LayerNorm(16),
                            nn.Linear(16, 4))
        x = np.random.RandomState(0).rand(4, 8).astype(np.float32)
        t = np.random.RandomState(1).rand(4, 4).astype(np.float32)

        # eager tape
        out = net(paddle.to_tensor(x))
        loss = ((out - paddle.to_tensor(t)) ** 2).mean()
        loss.backward()
        tape_grads = {n: np.asarray(p.grad.numpy())
                      for n, p in net.named_parameters()}

        # functional: same params as explicit args under outer jax.grad
        params, bufs = net.functional_state()

        def loss_fn(p):
            with functional_trace():
                o = net.functional_call(p, bufs, Tensor(jnp.asarray(x)))
                d = o - Tensor(jnp.asarray(t))
                return ((d * d).mean())._value

        fgrads = jax.grad(loss_fn)(params)
        for name, g in tape_grads.items():
            np.testing.assert_allclose(
                np.asarray(fgrads[name]), g, rtol=2e-4, atol=2e-5,
                err_msg=f"functional vs tape grad mismatch for {name}")
