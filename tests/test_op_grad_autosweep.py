"""OpTest-style gradient sweep over the ENTIRE op registry (VERDICT r4
next #4; ref: /root/reference/python/paddle/fluid/tests/unittests/
op_test.py:1324 check_grad and its 987 per-op unittest files).

Every name in `ops.OPS` must be either SPEC'd (finite-difference checked
below) or EXCLUDED with a stated reason — `test_registry_fully_covered`
enforces the partition, so a newly added op without a grad check fails
CI. This harness exercises the recorded-vjp tape per op (the silently
dead flash backward was exactly the class of bug only this catches).

Exclusion categories (each entry states its own reason):
  creation     — no tensor inputs to differentiate
  random       — stochastic output; grad undefined w.r.t. inputs
  integer      — integer/bool outputs or selection indices
  complex      — complex dtype surface, not in the f32 FD harness
  inplace      — mutates its input; covered by the functional twin
  gauge        — decomposition defined up to sign/rotation (checked via
                 the invariant part where possible: eigh/svd values)
  unstable     — selection can flip under the FD probe (mode)
  infra        — needs a process group / device context

A bf16 tier re-runs a representative subset with bfloat16 inputs and
compares the tape grad against the f32 analytic grad at bf16 tolerance —
bf16 is the first-class training dtype, so its grads must track f32.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import ops as ops_mod

P = paddle
EPS = 1e-2
RTOL = 8e-2
ATOL = 8e-3


def _any(shape, seed=1, s=0.5):
    return (np.random.RandomState(seed).randn(*shape) * s).astype(np.float32)


def _pos(shape, lo=0.5, hi=1.5, seed=0):
    return np.random.RandomState(seed).uniform(lo, hi, shape).astype(
        np.float32)


def _spread(shape, seed=2, step=0.37):
    """Values pairwise far apart: safe for min/max/sort/median ops."""
    rs = np.random.RandomState(seed)
    n = int(np.prod(shape))
    vals = (np.arange(n) * step + 0.1) * rs.choice([-1, 1], n)
    rs.shuffle(vals)
    return vals.reshape(shape).astype(np.float32)


def _offint(shape, seed=3):
    """Values far from every integer (for floor/ceil/round/trunc)."""
    rs = np.random.RandomState(seed)
    return (rs.randint(-3, 3, shape) + rs.uniform(0.25, 0.45, shape)
            ).astype(np.float32)


def _psd(n, seed=4):
    a = _any((n, n), seed)
    return (a @ a.T + np.eye(n, dtype=np.float32) * n).astype(np.float32)


def _wellcond(n, seed=5):
    return (_any((n, n), seed) + np.eye(n, dtype=np.float32) * 2.0)


def _t(a, sg=True):
    return paddle.to_tensor(a, stop_gradient=sg)


def _float_outs(out):
    """Flatten op output to the float tensors the projection covers."""
    outs = out if isinstance(out, (tuple, list)) else [out]
    keep = []
    for o in outs:
        if o is None:
            continue
        d = str(getattr(o, "dtype", ""))
        if "int" in d or "bool" in d:
            continue
        keep.append(o)
    return keep


def _loss_np(fn, arrays, projs):
    ts = [paddle.to_tensor(a) for a in arrays]
    outs = _float_outs(fn(*ts))
    total = 0.0
    for o, pr in zip(outs, projs):
        total += float((np.asarray(o.numpy(), np.float64) * pr).sum())
    return total


def check_grad(fn, *arrays, diff_idx=None):
    """Tape backward of sum_i(out_i * proj_i) vs central differences."""
    rs = np.random.RandomState(7)
    ts = [paddle.to_tensor(a, stop_gradient=False) for a in arrays]
    outs = _float_outs(fn(*ts))
    assert outs, "op produced no differentiable output"
    projs = [np.asarray(rs.rand(*tuple(o.shape)), np.float64) + 0.5
             for o in outs]
    loss = None
    for o, pr in zip(outs, projs):
        term = (o * paddle.to_tensor(pr.astype(np.float32))).sum()
        loss = term if loss is None else loss + term
    loss.backward()
    diff_idx = range(len(arrays)) if diff_idx is None else diff_idx
    for k in diff_idx:
        g = ts[k].grad
        analytic = (np.zeros_like(arrays[k], np.float64) if g is None
                    else np.asarray(g.numpy() if hasattr(g, "numpy") else g,
                                    np.float64))
        a = arrays[k]
        numeric = np.zeros_like(a, np.float64)
        flat = a.reshape(-1)
        num_flat = numeric.reshape(-1)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + EPS
            up = _loss_np(fn, arrays, projs)
            flat[i] = orig - EPS
            dn = _loss_np(fn, arrays, projs)
            flat[i] = orig
            num_flat[i] = (up - dn) / (2 * EPS)
        np.testing.assert_allclose(
            analytic, numeric, rtol=RTOL, atol=ATOL,
            err_msg=f"input {k} of {getattr(fn, '__name__', fn)}")


def OP(name):
    return ops_mod.OPS[name]


# --------------------------------------------------------------------------
# SPECS: op name -> builder returning (fn over Tensors, [np diff arrays]).
# Inputs sit in smooth regions (off kinks/ties/poles) so the FD is
# well-posed in f32; indices/masks/labels are closed over (not diffed).
# --------------------------------------------------------------------------
_I = np.array([[0, 2], [1, 0]])


def _sdpa_fn(q, k, v):
    out, _ = OP("scaled_dot_product_attention")(q, k, v)
    return out


SPECS = {
    # ---- unary elementwise (smooth) ----
    "abs": lambda: (OP("abs"), [_spread((2, 3))]),
    "acos": lambda: (OP("acos"), [_any((2, 3), s=0.4)]),
    "acosh": lambda: (OP("acosh"), [_pos((2, 3), 1.5, 2.5)]),
    "asin": lambda: (OP("asin"), [_any((2, 3), s=0.4)]),
    "asinh": lambda: (OP("asinh"), [_any((2, 3))]),
    "atan": lambda: (OP("atan"), [_any((2, 3))]),
    "atanh": lambda: (OP("atanh"), [_any((2, 3), s=0.4)]),
    "cos": lambda: (OP("cos"), [_any((2, 3))]),
    "cosh": lambda: (OP("cosh"), [_any((2, 3))]),
    "digamma": lambda: (OP("digamma"), [_pos((2, 3), 1.0, 3.0)]),
    "erf": lambda: (OP("erf"), [_any((2, 3))]),
    "erfinv": lambda: (OP("erfinv"), [_any((2, 3), s=0.3)]),
    "exp": lambda: (OP("exp"), [_any((2, 3))]),
    "expm1": lambda: (OP("expm1"), [_any((2, 3))]),
    "lgamma": lambda: (OP("lgamma"), [_pos((2, 3), 1.2, 3.0)]),
    "log": lambda: (OP("log"), [_pos((2, 3))]),
    "log10": lambda: (OP("log10"), [_pos((2, 3))]),
    "log1p": lambda: (OP("log1p"), [_pos((2, 3))]),
    "log2": lambda: (OP("log2"), [_pos((2, 3))]),
    "neg": lambda: (OP("neg"), [_any((2, 3))]),
    "reciprocal": lambda: (OP("reciprocal"), [_pos((2, 3))]),
    "rsqrt": lambda: (OP("rsqrt"), [_pos((2, 3))]),
    "sigmoid": lambda: (OP("sigmoid"), [_any((2, 3))]),
    "sin": lambda: (OP("sin"), [_any((2, 3))]),
    "sinh": lambda: (OP("sinh"), [_any((2, 3))]),
    "sqrt": lambda: (OP("sqrt"), [_pos((2, 3))]),
    "square": lambda: (OP("square"), [_any((2, 3))]),
    "tan": lambda: (OP("tan"), [_any((2, 3), s=0.5)]),
    "tanh": lambda: (OP("tanh"), [_any((2, 3))]),
    # piecewise-constant: analytic grad must be exactly the FD's zero
    "ceil": lambda: (OP("ceil"), [_offint((2, 3))]),
    "floor": lambda: (OP("floor"), [_offint((2, 3))]),
    "round": lambda: (OP("round"), [_offint((2, 3))]),
    "trunc": lambda: (OP("trunc"), [_offint((2, 3))]),
    "sign": lambda: (OP("sign"), [_spread((2, 3))]),
    "floor_divide": lambda: (
        lambda x: OP("floor_divide")(x, _t(_pos((2, 3), 0.9, 1.1, 9))),
        [_offint((2, 3))]),
    # ---- activations (off kinks) ----
    "celu": lambda: (OP("celu"), [_spread((2, 3))]),
    "elu": lambda: (OP("elu"), [_spread((2, 3))]),
    "gelu": lambda: (OP("gelu"), [_any((2, 3))]),
    "glu": lambda: (OP("glu"), [_any((2, 4))]),
    "hardshrink": lambda: (OP("hardshrink"), [_spread((2, 3))]),
    "hardsigmoid": lambda: (OP("hardsigmoid"), [_any((2, 3), s=0.7)]),
    "hardswish": lambda: (OP("hardswish"), [_spread((2, 3))]),
    "hardtanh": lambda: (OP("hardtanh"), [_spread((2, 3))]),
    "leaky_relu": lambda: (OP("leaky_relu"), [_spread((2, 3))]),
    "log_sigmoid": lambda: (OP("log_sigmoid"), [_any((2, 3))]),
    "log_softmax": lambda: (OP("log_softmax"), [_any((2, 4))]),
    "maxout": lambda: (
        lambda x: OP("maxout")(x, 2), [_spread((1, 4, 2, 2))]),
    "mish": lambda: (OP("mish"), [_any((2, 3))]),
    "prelu": lambda: (OP("prelu"), [_spread((2, 3)), _pos((1,), seed=8)]),
    "relu": lambda: (OP("relu"), [_spread((2, 3))]),
    "relu6": lambda: (OP("relu6"), [_spread((2, 3))]),
    "selu": lambda: (OP("selu"), [_spread((2, 3))]),
    "softmax": lambda: (OP("softmax"), [_any((2, 4))]),
    "softplus": lambda: (OP("softplus"), [_any((2, 3))]),
    "softshrink": lambda: (OP("softshrink"), [_spread((2, 3))]),
    "softsign": lambda: (OP("softsign"), [_any((2, 3))]),
    "stanh": lambda: (OP("stanh"), [_any((2, 3))]),
    "swish": lambda: (OP("swish"), [_any((2, 3))]),
    "tanhshrink": lambda: (OP("tanhshrink"), [_any((2, 3))]),
    "thresholded_relu": lambda: (OP("thresholded_relu"),
                                 [_spread((2, 3))]),
    # ---- binary / ternary ----
    "add": lambda: (OP("add"), [_any((2, 3)), _any((2, 3), 3)]),
    "add_n": lambda: (
        lambda a, b: OP("add_n")([a, b]), [_any((2, 3)), _any((2, 3), 4)]),
    "atan2": lambda: (OP("atan2"), [_any((2, 3)), _pos((2, 3), seed=6)]),
    "divide": lambda: (OP("divide"), [_any((2, 3)), _pos((2, 3), seed=6)]),
    "fmax": lambda: (OP("fmax"), [_spread((2, 3)), _spread((2, 3), 9)]),
    "fmin": lambda: (OP("fmin"), [_spread((2, 3)),
                                  _spread((2, 3), 10, step=0.29)]),
    "lerp": lambda: (OP("lerp"), [_any((2, 3)), _any((2, 3), 5),
                                  _pos((2, 3), 0.2, 0.8, 7)]),
    "maximum": lambda: (OP("maximum"), [_spread((2, 3)),
                                        _spread((2, 3), 9)]),
    "minimum": lambda: (OP("minimum"), [_spread((2, 3)),
                                        _spread((2, 3), 10)]),
    "multiply": lambda: (OP("multiply"), [_any((2, 3)), _any((2, 3), 5)]),
    "pow": lambda: (lambda x: OP("pow")(x, 2.0), [_pos((2, 3))]),
    "remainder": lambda: (
        lambda x: OP("remainder")(x, _t(_pos((2, 3), 0.9, 1.1, 9))),
        [_offint((2, 3))]),
    "scale": lambda: (lambda x: OP("scale")(x, 2.5, 0.5), [_any((2, 3))]),
    "subtract": lambda: (OP("subtract"), [_any((2, 3)), _any((2, 3), 4)]),
    "nan_to_num": lambda: (OP("nan_to_num"), [_any((2, 3))]),
    "increment": lambda: (OP("increment"), [_any((2, 3))]),
    "assign": lambda: (OP("assign"), [_any((2, 3))]),
    "clone": lambda: (OP("clone"), [_any((2, 3))]),
    "cast": lambda: (lambda x: OP("cast")(x, "float32"), [_any((2, 3))]),
    "clip": lambda: (lambda x: OP("clip")(x, -0.4, 0.4),
                     [_spread((2, 3), step=0.1)]),
    # ---- reductions / stats ----
    "mean": lambda: (OP("mean"), [_any((3, 4))]),
    "sum": lambda: (lambda x: OP("sum")(x, axis=1), [_any((3, 4))]),
    "max": lambda: (lambda x: OP("max")(x, axis=1), [_spread((3, 4))]),
    "min": lambda: (lambda x: OP("min")(x, axis=0), [_spread((3, 4), 5)]),
    "prod": lambda: (lambda x: OP("prod")(x, axis=1), [_pos((2, 3))]),
    "logsumexp": lambda: (OP("logsumexp"), [_any((2, 3))]),
    "std": lambda: (OP("std"), [_spread((2, 3))]),
    "var": lambda: (OP("var"), [_spread((2, 3))]),
    "median": lambda: (lambda x: OP("median")(x, axis=1),
                       [_spread((3, 5))]),
    "quantile": lambda: (lambda x: OP("quantile")(x, 0.5, axis=1),
                         [_spread((3, 5))]),
    "kthvalue": lambda: (lambda x: OP("kthvalue")(x, 2, axis=1),
                         [_spread((3, 5))]),
    "cummax": lambda: (lambda x: OP("cummax")(x, axis=1),
                       [_spread((2, 4))]),
    "cummin": lambda: (lambda x: OP("cummin")(x, axis=1),
                       [_spread((2, 4), 6)]),
    "cumsum": lambda: (lambda x: OP("cumsum")(x, axis=1), [_any((2, 4))]),
    "cumprod": lambda: (lambda x: OP("cumprod")(x, dim=1), [_pos((2, 3))]),
    "topk": lambda: (lambda x: OP("topk")(x, 2, axis=1),
                     [_spread((3, 5))]),
    "sort": lambda: (lambda x: OP("sort")(x, axis=1), [_spread((3, 4))]),
    "cov": lambda: (OP("cov"), [_spread((3, 5))]),
    "corrcoef": lambda: (OP("corrcoef"), [_spread((3, 5))]),
    "count_nonzero": None,  # replaced below (integer output) — kept here
    # ---- linalg ----
    "matmul": lambda: (OP("matmul"), [_any((2, 3)), _any((3, 4), 3)]),
    "mm": lambda: (OP("mm"), [_any((2, 3)), _any((3, 2), 3)]),
    "bmm": lambda: (OP("bmm"), [_any((2, 2, 3)), _any((2, 3, 2), 4)]),
    "mv": lambda: (OP("mv"), [_any((3, 4)), _any((4,), 5)]),
    "dot": lambda: (OP("dot"), [_any((4,)), _any((4,), 6)]),
    "inner": lambda: (OP("inner"), [_any((2, 4)), _any((3, 4), 7)]),
    "outer": lambda: (OP("outer"), [_any((3,)), _any((4,), 12)]),
    "kron": lambda: (OP("kron"), [_any((2, 2)), _any((2, 3), 13)]),
    "cross": lambda: (OP("cross"), [_any((2, 3)), _any((2, 3), 8)]),
    "addmm": lambda: (OP("addmm"), [_any((2, 4)), _any((2, 3), 9),
                                    _any((3, 4), 10)]),
    "multi_dot": lambda: (
        lambda a, b, c: OP("multi_dot")([a, b, c]),
        [_any((2, 3)), _any((3, 4), 3), _any((4, 2), 4)]),
    "einsum": lambda: (
        lambda a, b: OP("einsum")("ij,jk->ik", a, b),
        [_any((2, 3)), _any((3, 4), 3)]),
    "t": lambda: (OP("t"), [_any((2, 3))]),
    "trace": lambda: (OP("trace"), [_any((3, 3))]),
    "norm": lambda: (lambda x: OP("norm")(x, p=2), [_pos((2, 3))]),
    "dist": lambda: (OP("dist"), [_any((2, 3)), _any((2, 3), 11)]),
    "det": lambda: (OP("det"), [_wellcond(3)]),
    "slogdet": lambda: (OP("slogdet"), [_wellcond(3)]),
    "inverse": lambda: (OP("inverse"), [_wellcond(3)]),
    "pinv": lambda: (OP("pinv"), [_wellcond(3)]),
    "matrix_power": lambda: (lambda x: OP("matrix_power")(x, 2),
                             [_any((3, 3))]),
    "cholesky": lambda: (OP("cholesky"), [_psd(3)]),
    "cholesky_solve": lambda: (
        lambda b: OP("cholesky_solve")(
            b, _t(np.linalg.cholesky(_psd(3)).astype(np.float32))),
        [_any((3, 2))]),
    "solve": lambda: (OP("solve"), [_wellcond(3), _any((3, 2), 6)]),
    "triangular_solve": lambda: (
        lambda a, b: OP("triangular_solve")(a, b, upper=False),
        [np.tril(_wellcond(3)).astype(np.float32), _any((3, 2), 7)]),
    "eigh": lambda: (  # eigenvalues only: eigenvectors are gauge-dependent
        lambda x: OP("eigh")((x + x.transpose([1, 0])) / 2)[0],
        [np.diag([1.0, 2.5, 4.0]).astype(np.float32) + _any((3, 3), 8,
                                                            s=0.1)]),
    "eigvalsh": lambda: (
        lambda x: OP("eigvalsh")((x + x.transpose([1, 0])) / 2),
        [np.diag([1.0, 2.5, 4.0]).astype(np.float32) + _any((3, 3), 8,
                                                            s=0.1)]),
    "svd": lambda: (  # singular values only (u/vh gauge-dependent)
        lambda x: OP("svd")(x)[1], [_spread((3, 3), 9, step=0.8)]),
    "lstsq": lambda: (
        lambda b: OP("lstsq")(_t(_wellcond(3)), b)[0], [_any((3, 2), 6)]),
    # ---- manipulation ----
    "broadcast_to": lambda: (lambda x: OP("broadcast_to")(x, [2, 2, 3]),
                             [_any((2, 3))]),
    "broadcast_tensors": lambda: (
        lambda a, b: OP("broadcast_tensors")([a, b]),
        [_any((1, 3)), _any((2, 1), 4)]),
    "expand": lambda: (lambda x: OP("expand")(x, [2, 2, 3]),
                       [_any((1, 3))]),
    "expand_as": lambda: (
        lambda x: OP("expand_as")(x, _t(_any((2, 3), 5))), [_any((1, 3))]),
    "chunk": lambda: (lambda x: OP("chunk")(x, 2, axis=1), [_any((2, 4))]),
    "split": lambda: (lambda x: OP("split")(x, 2, axis=1), [_any((2, 4))]),
    "unstack": lambda: (lambda x: OP("unstack")(x, axis=0),
                        [_any((2, 3))]),
    "concat": lambda: (lambda a, b: OP("concat")([a, b], axis=1),
                       [_any((2, 2)), _any((2, 3), 8)]),
    "stack": lambda: (lambda a, b: OP("stack")([a, b], axis=0),
                      [_any((2, 3)), _any((2, 3), 9)]),
    "reshape": lambda: (lambda x: OP("reshape")(x, [4, 3]), [_any((3, 4))]),
    "transpose": lambda: (lambda x: OP("transpose")(x, [1, 0]),
                          [_any((3, 4))]),
    "moveaxis": lambda: (lambda x: OP("moveaxis")(x, 0, 1), [_any((3, 4))]),
    "swapaxes": lambda: (lambda x: OP("swapaxes")(x, 0, 1), [_any((3, 4))]),
    "squeeze": lambda: (lambda x: OP("squeeze")(x, 0), [_any((1, 3))]),
    "unsqueeze": lambda: (lambda x: OP("unsqueeze")(x, 0), [_any((2, 3))]),
    "flatten": lambda: (OP("flatten"), [_any((2, 3))]),
    "tile": lambda: (lambda x: OP("tile")(x, [2, 1]), [_any((2, 3))]),
    "flip": lambda: (lambda x: OP("flip")(x, [1]), [_any((2, 3))]),
    "roll": lambda: (lambda x: OP("roll")(x, 1, axis=1), [_any((2, 3))]),
    "rot90": lambda: (OP("rot90"), [_any((2, 3))]),
    "tril": lambda: (OP("tril"), [_any((3, 3))]),
    "triu": lambda: (OP("triu"), [_any((3, 3))]),
    "diag": lambda: (OP("diag"), [_any((3,))]),
    "diagflat": lambda: (OP("diagflat"), [_any((3,))]),
    "diag_embed": lambda: (OP("diag_embed"), [_any((2, 3))]),
    "diag_embed_f": lambda: (OP("diag_embed_f"), [_any((2, 3))]),
    "crop": lambda: (lambda x: OP("crop")(x, [1, 2], offsets=[0, 1]),
                     [_any((2, 4))]),
    "meshgrid": lambda: (OP("meshgrid"), [_any((3,)), _any((2,), 4)]),
    "repeat_interleave": lambda: (
        lambda x: OP("repeat_interleave")(x, 2, axis=1), [_any((2, 3))]),
    "pad": lambda: (lambda x: OP("pad")(x, [1, 1, 0, 1]),
                    [_any((1, 1, 2, 3))]),
    "slice": lambda: (
        lambda x: OP("slice")(x, [1], [1], [3]), [_any((2, 4))]),
    "strided_slice": lambda: (
        lambda x: OP("strided_slice")(x, [1], [0], [4], [2]),
        [_any((2, 4))]),
    "getitem": lambda: (lambda x: OP("getitem")(x, (slice(0, 2),
                                                    slice(1, 3))),
                        [_any((3, 4))]),
    "setitem": lambda: (
        lambda x, v: OP("setitem")(x, (slice(0, 1),), v),
        [_any((3, 4)), _any((1, 4), 5)]),
    "gather": lambda: (lambda x: OP("gather")(x, _t(np.array([0, 2]))),
                       [_any((3, 4))]),
    "gather_nd": lambda: (
        lambda x: OP("gather_nd")(x, _t(np.array([[0, 1], [2, 0]]))),
        [_any((3, 4))]),
    "index_select": lambda: (
        lambda x: OP("index_select")(x, _t(np.array([2, 0])), axis=1),
        [_any((2, 4))]),
    "index_sample": lambda: (
        lambda x: OP("index_sample")(x, _t(_I)), [_any((2, 4))]),
    "take_along_axis": lambda: (
        lambda x: OP("take_along_axis")(x, _t(_I), 1), [_any((2, 4))]),
    "put_along_axis": lambda: (
        lambda x, v: OP("put_along_axis")(x, _t(_I), v, 1),
        [_any((2, 4)), _any((2, 2), 5)]),
    "scatter": lambda: (
        lambda x, u: OP("scatter")(x, _t(np.array([0, 2])), u),
        [_any((3, 4)), _any((2, 4), 5)]),
    "scatter_nd": lambda: (
        lambda u: OP("scatter_nd")(_t(np.array([[0], [2]])), u, [3, 4]),
        [_any((2, 4), 5)]),
    "scatter_nd_add": lambda: (
        lambda x, u: OP("scatter_nd_add")(x, _t(np.array([[0], [2]])), u),
        [_any((3, 4)), _any((2, 4), 5)]),
    "masked_fill": lambda: (
        lambda x: OP("masked_fill")(
            x, _t(np.array([[True, False, True], [False, True, False]])),
            0.5),
        [_any((2, 3))]),
    "masked_select": lambda: (
        lambda x: OP("masked_select")(
            x, _t(np.array([[True, False, True], [False, True, False]]))),
        [_any((2, 3))]),
    "where": lambda: (
        lambda x, y: OP("where")(
            _t(np.array([[True, False, True], [False, True, False]])), x,
            y),
        [_any((2, 3)), _any((2, 3), 11)]),
    "shuffle": None,  # replaced below (random) — placeholder
    # ---- nn ops ----
    "linear": lambda: (OP("linear"), [_any((2, 3)), _any((3, 4), 5),
                                      _any((4,), 6)]),
    "embedding": lambda: (
        lambda w: OP("embedding")(_t(np.array([[0, 2], [1, 2]])), w),
        [_any((4, 3))]),
    "conv1d": lambda: (
        lambda x, w: OP("conv1d")(x, w, padding=1),
        [_any((1, 2, 5)), _any((3, 2, 3), 7)]),
    "conv2d": lambda: (
        lambda x, w: OP("conv2d")(x, w, padding=1),
        [_any((1, 2, 4, 4)), _any((3, 2, 3, 3), 7)]),
    "conv3d": lambda: (
        lambda x, w: OP("conv3d")(x, w, padding=1),
        [_any((1, 1, 3, 3, 3)), _any((2, 1, 2, 2, 2), 7)]),
    "conv1d_transpose": lambda: (
        lambda x, w: OP("conv1d_transpose")(x, w),
        [_any((1, 2, 4)), _any((2, 3, 3), 7)]),
    "conv2d_transpose": lambda: (
        lambda x, w: OP("conv2d_transpose")(x, w),
        [_any((1, 2, 3, 3)), _any((2, 3, 2, 2), 7)]),
    "conv3d_transpose": lambda: (
        lambda x, w: OP("conv3d_transpose")(x, w),
        [_any((1, 1, 2, 2, 2)), _any((1, 2, 2, 2, 2), 7)]),
    "max_pool1d": lambda: (lambda x: OP("max_pool1d")(x, 2),
                           [_spread((1, 2, 4))]),
    "max_pool2d": lambda: (lambda x: OP("max_pool2d")(x, 2),
                           [_spread((1, 1, 4, 4))]),
    "max_pool3d": lambda: (lambda x: OP("max_pool3d")(x, 2),
                           [_spread((1, 1, 2, 4, 4))]),
    "avg_pool1d": lambda: (lambda x: OP("avg_pool1d")(x, 2),
                           [_any((1, 2, 4))]),
    "avg_pool2d": lambda: (lambda x: OP("avg_pool2d")(x, 2),
                           [_any((1, 1, 4, 4))]),
    "avg_pool3d": lambda: (lambda x: OP("avg_pool3d")(x, 2),
                           [_any((1, 1, 2, 4, 4))]),
    "adaptive_avg_pool1d": lambda: (
        lambda x: OP("adaptive_avg_pool1d")(x, 2), [_any((1, 2, 4))]),
    "adaptive_avg_pool2d": lambda: (
        lambda x: OP("adaptive_avg_pool2d")(x, 2), [_any((1, 1, 4, 4))]),
    "adaptive_avg_pool3d": lambda: (
        lambda x: OP("adaptive_avg_pool3d")(x, 2),
        [_any((1, 1, 2, 4, 4))]),
    "adaptive_max_pool1d": lambda: (
        lambda x: OP("adaptive_max_pool1d")(x, 2), [_spread((1, 2, 4))]),
    "adaptive_max_pool2d": lambda: (
        lambda x: OP("adaptive_max_pool2d")(x, 2),
        [_spread((1, 1, 4, 4))]),
    "batch_norm": lambda: (
        # project only `out`: the returned running stats are deliberately
        # stop-gradiented (reference semantics), which FD can't see
        lambda x, w, b: OP("batch_norm")(
            x, _t(np.zeros(2, np.float32)), _t(np.ones(2, np.float32)),
            w, b, training=True)[0],
        [_any((3, 2)), _pos((2,), seed=8), _any((2,), 9)]),
    "instance_norm": lambda: (
        lambda x, w, b: OP("instance_norm")(x, w, b),
        [_any((2, 2, 4)), _pos((2,), seed=8), _any((2,), 9)]),
    "group_norm": lambda: (
        lambda x, w, b: OP("group_norm")(x, 2, w, b),
        [_any((2, 4, 3)), _pos((4,), seed=8), _any((4,), 9)]),
    "layer_norm": lambda: (
        OP("layer_norm"),
        [_any((3, 4)), _pos((4,), seed=8), _any((4,), 9)]),
    "rms_norm": lambda: (
        lambda x, w: OP("rms_norm")(x, w), [_any((3, 4)),
                                            _pos((4,), seed=8)]),
    "local_response_norm": lambda: (
        lambda x: OP("local_response_norm")(x, 3), [_any((1, 4, 3, 3))]),
    "normalize": lambda: (lambda x: OP("normalize")(x, axis=1),
                          [_pos((2, 3))]),
    "cosine_similarity": lambda: (
        OP("cosine_similarity"), [_pos((2, 3)), _pos((2, 3), seed=6)]),
    "pairwise_distance": lambda: (
        OP("pairwise_distance"), [_any((2, 3)), _any((2, 3), 11)]),
    "dropout": None,  # replaced below (random) — placeholder
    "pixel_shuffle": lambda: (lambda x: OP("pixel_shuffle")(x, 2),
                              [_any((1, 4, 2, 2))]),
    "pixel_unshuffle": lambda: (lambda x: OP("pixel_unshuffle")(x, 2),
                                [_any((1, 1, 4, 4))]),
    "unfold": lambda: (lambda x: OP("unfold")(x, 2), [_any((1, 1, 3, 3))]),
    "interpolate": lambda: (
        lambda x: OP("interpolate")(x, size=[4, 4], mode="bilinear",
                                    align_corners=True),
        [_any((1, 1, 3, 3))]),
    "grid_sample": lambda: (
        # grid points chosen so the bilinear sample coords sit well off
        # the integer lattice (floor() kinks) under the FD probe
        lambda x, g: OP("grid_sample")(x, g, align_corners=True),
        [_any((1, 1, 4, 4)),
         np.array([[[[-0.6, -0.2], [0.25, 0.55]],
                    [[-0.35, 0.6], [0.15, -0.55]]]], np.float32)]),
    "affine_grid": lambda: (
        lambda th: OP("affine_grid")(th, [1, 1, 3, 3]),
        [_any((1, 2, 3))]),
    "temporal_shift": lambda: (
        lambda x: OP("temporal_shift")(x, 2), [_any((2, 4, 2, 2))]),
    "label_smooth": lambda: (OP("label_smooth"),
                             [_pos((2, 4), 0.1, 0.9)]),
    "sequence_mask": None,  # replaced below (integer) — placeholder
    "rnn_scan_simple": lambda: (
        OP("rnn_scan_simple"),
        [_any((2, 3, 2)), _any((2, 3), 3), _any((3, 2), 4),
         _any((3, 3), 5), _any((3,), 6), _any((3,), 7)]),
    "lstm_scan": lambda: (
        OP("lstm_scan"),
        [_any((1, 2, 2)), _any((1, 3), 3), _any((1, 3), 4),
         _any((12, 2), 5), _any((12, 3), 6), _any((12,), 7),
         _any((12,), 8)]),
    "gru_scan": lambda: (
        OP("gru_scan"),
        [_any((1, 2, 2)), _any((1, 3), 3), _any((9, 2), 5),
         _any((9, 3), 6), _any((9,), 7), _any((9,), 8)]),
    "scaled_dot_product_attention": lambda: (
        _sdpa_fn,
        [_any((1, 2, 3, 4)), _any((1, 2, 3, 4), 3),
         _any((1, 2, 3, 4), 4)]),
    "token_major_attention": lambda: (
        lambda qkv: OP("token_major_attention")(qkv, num_heads=2,
                                                is_causal=True),
        [_any((1, 3, 24))]),
    "fused_multi_head_attention": lambda: (
        lambda x, qkv_w, out_w: OP("fused_multi_head_attention")(
            x, qkv_w, None, out_w, None, 2),
        [_any((1, 3, 4)), _any((4, 12), 3), _any((4, 4), 4)]),
    "fused_feedforward": lambda: (
        lambda x, w1, w2: OP("fused_feedforward")(x, w1, None, w2, None),
        [_any((1, 3, 4)), _any((4, 6), 3), _any((6, 4), 4)]),
    # ---- losses ----
    "binary_cross_entropy": lambda: (
        lambda x: OP("binary_cross_entropy")(
            x, _t(_pos((2, 3), 0.1, 0.9, 6))),
        [_pos((2, 3), 0.2, 0.8)]),
    "binary_cross_entropy_with_logits": lambda: (
        lambda x: OP("binary_cross_entropy_with_logits")(
            x, _t(_pos((2, 3), 0.1, 0.9, 6))),
        [_any((2, 3))]),
    "cross_entropy": lambda: (
        lambda x: OP("cross_entropy")(x, _t(np.array([1, 3]))),
        [_any((2, 4))]),
    "softmax_with_cross_entropy": lambda: (
        lambda x: OP("softmax_with_cross_entropy")(
            x, _t(np.array([[1], [2]]))),
        [_any((2, 4))]),
    "nll_loss": lambda: (
        lambda x: OP("nll_loss")(x, _t(np.array([1, 3]))),
        [_any((2, 4))]),
    "kl_div": lambda: (
        lambda x: OP("kl_div")(x, _t(_pos((2, 3), 0.1, 0.9, 6))),
        [_any((2, 3))]),
    "mse_loss": lambda: (
        lambda x: OP("mse_loss")(x, _t(_any((2, 3), 12))), [_any((2, 3))]),
    "l1_loss": lambda: (
        lambda x: OP("l1_loss")(x, _t(_spread((2, 3), 12))),
        [_spread((2, 3))]),
    "smooth_l1_loss": lambda: (
        lambda x: OP("smooth_l1_loss")(x, _t(_spread((2, 3), 12))),
        [_spread((2, 3))]),
    "huber_loss": lambda: (
        lambda x: OP("huber_loss")(x, _t(_spread((2, 3), 12))),
        [_spread((2, 3))]),
    "log_loss": lambda: (
        lambda x: OP("log_loss")(x, _t(_pos((2, 1), 0.1, 0.9, 6))),
        [_pos((2, 1), 0.2, 0.8)]),
    "hinge_loss": lambda: (
        lambda x: OP("hinge_loss")(
            x, _t(np.array([[1.0], [-1.0]], np.float32))),
        [_any((2, 1), s=0.3)]),
    "square_error_cost": lambda: (
        lambda x: OP("square_error_cost")(x, _t(_any((2, 3), 12))),
        [_any((2, 3))]),
    "margin_ranking_loss": lambda: (
        lambda a, b: OP("margin_ranking_loss")(
            a, b, _t(np.array([[1.0], [-1.0]], np.float32))),
        [_spread((2, 1)), _spread((2, 1), 9)]),
    "cosine_embedding_loss": lambda: (
        lambda a, b: OP("cosine_embedding_loss")(
            a, b, _t(np.array([1, -1]))),
        [_pos((2, 3)), _pos((2, 3), seed=6)]),
    "triplet_margin_loss": lambda: (
        OP("triplet_margin_loss"),
        [_any((2, 3)), _any((2, 3), 5) + 2.0, _any((2, 3), 6) - 2.0]),
    "npair_loss": lambda: (
        lambda a, p: OP("npair_loss")(a, p, _t(np.array([0, 1]))),
        [_any((2, 3)), _any((2, 3), 5)]),
    "sigmoid_focal_loss": lambda: (
        lambda x: OP("sigmoid_focal_loss")(
            x, _t(np.array([[1.0, 0.0], [0.0, 1.0]], np.float32))),
        [_any((2, 2))]),
    "ctc_loss": lambda: (
        lambda lp: OP("ctc_loss")(
            lp, _t(np.array([[1, 2], [1, 1]])),
            _t(np.array([4, 4])), _t(np.array([2, 2]))),
        [np.log(_pos((4, 2, 3), 0.2, 0.8, 6)
                / _pos((4, 2, 3), 0.2, 0.8, 6).sum(-1, keepdims=True))]),
    # ---- vision/detection ----
    "box_area": lambda: (
        OP("box_area"),
        [np.array([[0.0, 0.0, 2.0, 3.0], [1.0, 1.0, 4.0, 2.0]],
                  np.float32)]),
    "box_iou": lambda: (
        lambda a: OP("box_iou")(
            a, _t(np.array([[0.5, 0.5, 2.5, 2.5]], np.float32))),
        [np.array([[0.0, 0.0, 2.0, 3.0], [1.0, 1.0, 4.0, 2.0]],
                  np.float32)]),
    "roi_align": lambda: (
        lambda x: OP("roi_align")(
            x, _t(np.array([[0.4, 0.4, 2.6, 2.6]], np.float32)),
            output_size=2),
        [_any((1, 1, 4, 4))]),
    "yolo_box_decode": lambda: (
        lambda p: OP("yolo_box_decode")(p, [2, 3], class_num=1),
        [_any((1, 6, 2, 2))]),
}
# placeholders that belong in EXCLUDED (kept as None above for locality)
for _n in [k for k, v in SPECS.items() if v is None]:
    del SPECS[_n]

EXCLUDED = {
    # creation — no tensor inputs
    "arange": "creation", "empty": "creation", "empty_like": "creation",
    "eye": "creation", "full": "creation", "full_like": "creation",
    "linspace": "creation", "logspace": "creation", "ones": "creation",
    "ones_like": "creation", "zeros": "creation", "zeros_like": "creation",
    # random — stochastic output
    "bernoulli": "random", "dropout": "random", "dropout2d": "random",
    "alpha_dropout": "random", "exponential": "random",
    "gumbel_softmax": "random", "multinomial": "random", "normal": "random",
    "normal_like": "random", "poisson": "random", "rand": "random",
    "randint": "random", "randint_like": "random", "randn": "random",
    "randperm": "random", "shuffle": "random",
    "standard_normal": "random", "truncated_normal": "random",
    "uniform": "random", "uniform_random_like": "random",
    # integer/bool outputs or selection indices
    "all": "integer", "any": "integer", "allclose": "integer",
    "argmax": "integer", "argmin": "integer", "argsort": "integer",
    "bincount": "integer", "bitwise_and": "integer",
    "bitwise_not": "integer", "bitwise_or": "integer",
    "bitwise_xor": "integer", "bucketize": "integer",
    "count_nonzero": "integer", "equal": "integer", "equal_all": "integer",
    "greater_equal": "integer", "greater_than": "integer",
    "histogram": "integer", "isclose": "integer", "isfinite": "integer",
    "isinf": "integer", "isnan": "integer", "less_equal": "integer",
    "less_than": "integer", "logical_and": "integer",
    "logical_not": "integer", "logical_or": "integer",
    "logical_xor": "integer", "matrix_rank": "integer", "nms": "integer",
    "nonzero": "integer", "not_equal": "integer", "one_hot": "integer",
    "searchsorted": "integer", "sequence_mask": "integer",
    "shard_index": "integer", "unique": "integer",
    "unique_consecutive": "integer",
    # complex dtype surface
    "as_complex": "complex", "as_real": "complex", "complex_": "complex",
    "conj": "complex", "imag": "complex", "real": "complex",
    # inplace twins (functional twin is SPEC'd)
    "increment_inplace": "inplace", "nan_to_num_": "inplace",
    # gauge-dependent decompositions (value parts SPEC'd via eigh/svd)
    "qr": "gauge",
    # selection can flip under the FD probe
    "mode": "unstable",
    # needs a process group / device context
    "sync_batch_norm": "infra (single-proc twin batch_norm is SPEC'd)",
}


def test_registry_fully_covered():
    """Every registered op is either grad-checked or excluded with a
    reason — the OpTest-harness contract."""
    reg = set(ops_mod.OPS)
    spec = set(SPECS)
    excl = set(EXCLUDED)
    assert not (spec & excl), f"both SPEC'd and EXCLUDED: {spec & excl}"
    missing = reg - spec - excl
    assert not missing, (
        f"{len(missing)} registry ops have neither a grad check nor a "
        f"documented exclusion: {sorted(missing)}")
    stale = (spec | excl) - reg
    assert not stale, f"SPEC/EXCLUDED entries not in the registry: {stale}"
    # the point of the sweep: the checked surface must stay wide
    assert len(spec) >= 200, f"grad-checked op count fell to {len(spec)}"


@pytest.mark.parametrize("name", sorted(SPECS))
def test_grad(name):
    fn, arrays = SPECS[name]()
    check_grad(fn, *arrays)


# --------------------------------------------------------------------------
# bf16 tier: representative ops re-run with bfloat16 inputs; the tape grad
# must track the f32 analytic grad at bf16 tolerance (~2^-8 relative).
# --------------------------------------------------------------------------
BF16_OPS = [
    "add", "multiply", "divide", "matmul", "bmm", "linear", "embedding",
    "softmax", "log_softmax", "layer_norm", "rms_norm", "gelu", "relu",
    "sigmoid", "tanh", "exp", "log", "sqrt", "mean", "sum", "logsumexp",
    "cross_entropy", "mse_loss", "conv2d", "scaled_dot_product_attention",
]


def _grads_with_dtype(name, cast_bf16):
    import jax.numpy as jnp
    fn, arrays = SPECS[name]()
    ts = []
    for a in arrays:
        t = paddle.to_tensor(a, stop_gradient=False)
        if cast_bf16:
            t = paddle.to_tensor(
                t._value.astype(jnp.bfloat16), stop_gradient=False)
        ts.append(t)
    outs = _float_outs(fn(*ts))
    loss = None
    for o in outs:
        term = o.astype("float32").sum()
        loss = term if loss is None else loss + term
    loss.backward()
    gs = []
    for t in ts:
        g = t.grad
        gs.append(None if g is None
                  else np.asarray(g._value.astype(jnp.float32)))
    return gs


@pytest.mark.parametrize("name", BF16_OPS)
def test_bf16_grad_tracks_f32(name):
    g32 = _grads_with_dtype(name, cast_bf16=False)
    g16 = _grads_with_dtype(name, cast_bf16=True)
    for k, (a, b) in enumerate(zip(g32, g16)):
        if a is None or b is None:
            assert a is None and b is None
            continue
        scale = max(1e-3, float(np.abs(a).max()))
        np.testing.assert_allclose(
            b / scale, a / scale, rtol=0.06, atol=0.06,
            err_msg=f"bf16 grad diverged from f32 for input {k} of {name}")
