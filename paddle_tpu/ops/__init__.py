"""Unified op namespace + Tensor method patching.

Reference: python/paddle/fluid/dygraph/math_op_patch.py — the reference
monkey-patches arithmetic onto VarBase; we do the same onto Tensor so
`x + y`, `x.mean()`, `x @ w` all route through registered ops (and thus
through autograd + static-graph capture).
"""
from __future__ import annotations

from ._registry import OPS, apply_op, as_jax, defop, raw  # noqa: F401
from .attention import (  # noqa: F401
    fused_feedforward, fused_multi_head_attention,
    paged_decode_attention, ragged_prefill_attention,
    scaled_dot_product_attention, token_major_attention,
)
from .control import case, cond, fori_loop, scan, switch_case, while_loop  # noqa: F401
from .creation import *  # noqa: F401,F403
from .linalg import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .manipulation import *  # noqa: F401,F403
from .math import *  # noqa: F401,F403
from .nn_ops import *  # noqa: F401,F403
from .random import *  # noqa: F401,F403
from .search import *  # noqa: F401,F403

from ..core.tensor import Tensor

# names whose op version shadows a python builtin get aliases
from .math import abs as abs_  # noqa: F401
from .math import max as max_  # noqa: F401
from .math import min as min_  # noqa: F401
from .math import sum as sum_  # noqa: F401


def _patch_tensor():
    import builtins

    from . import linalg, manipulation, math, nn_ops, search

    def binop(fn, reverse=False):
        def method(self, other):
            if reverse:
                return fn(other, self)
            return fn(self, other)
        return method

    T = Tensor
    T.__add__ = binop(math.add)
    T.__radd__ = binop(math.add, True)
    T.__sub__ = binop(math.subtract)
    T.__rsub__ = binop(math.subtract, True)
    T.__mul__ = binop(math.multiply)
    T.__rmul__ = binop(math.multiply, True)
    T.__truediv__ = binop(math.divide)
    T.__rtruediv__ = binop(math.divide, True)
    T.__floordiv__ = binop(math.floor_divide)
    T.__rfloordiv__ = binop(math.floor_divide, True)
    T.__mod__ = binop(math.remainder)
    T.__pow__ = binop(math.pow)
    T.__rpow__ = binop(math.pow, True)
    T.__matmul__ = binop(linalg.matmul)
    T.__rmatmul__ = binop(linalg.matmul, True)
    T.__neg__ = lambda self: math.neg(self)
    T.__abs__ = lambda self: math.abs(self)
    T.__invert__ = lambda self: math.logical_not(self)
    T.__lt__ = binop(math.less_than)
    T.__le__ = binop(math.less_equal)
    T.__gt__ = binop(math.greater_than)
    T.__ge__ = binop(math.greater_equal)
    T.__eq__ = binop(math.equal)
    T.__ne__ = binop(math.not_equal)
    T.__and__ = binop(math.logical_and)
    T.__or__ = binop(math.logical_or)
    T.__xor__ = binop(math.logical_xor)

    def _getitem(self, idx):
        def unwrap_idx(i):
            if isinstance(i, Tensor):
                return i._value
            if isinstance(i, tuple):
                return tuple(unwrap_idx(e) for e in i)
            return i
        return manipulation.getitem(self, unwrap_idx(idx))

    def _setitem(self, idx, value):
        def unwrap_idx(i):
            if isinstance(i, Tensor):
                return i._value
            if isinstance(i, tuple):
                return tuple(unwrap_idx(e) for e in i)
            return i
        out = manipulation.setitem(self, unwrap_idx(idx), value)
        # in-place semantics: replace payload, adopt autograd node
        self._value = out._value
        self._node = out._node
        self.stop_gradient = out.stop_gradient and self.stop_gradient

    T.__getitem__ = _getitem
    T.__setitem__ = _setitem

    # attach op methods (paddle Tensor method surface)
    method_ops = {
        "add": math.add, "subtract": math.subtract, "multiply": math.multiply,
        "divide": math.divide, "pow": math.pow, "matmul": linalg.matmul,
        "mm": linalg.mm, "bmm": linalg.bmm, "dot": linalg.dot,
        "maximum": math.maximum, "minimum": math.minimum, "mod": math.remainder,
        "remainder": math.remainder, "floor_divide": math.floor_divide,
        "abs": math.abs, "exp": math.exp, "log": math.log, "log2": math.log2,
        "log10": math.log10, "log1p": math.log1p, "sqrt": math.sqrt,
        "rsqrt": math.rsqrt, "square": math.square, "reciprocal": math.reciprocal,
        "sin": math.sin, "cos": math.cos, "tan": math.tan, "tanh": math.tanh,
        "asin": math.asin, "acos": math.acos, "atan": math.atan,
        "sinh": math.sinh, "cosh": math.cosh, "erf": math.erf,
        "ceil": math.ceil, "floor": math.floor, "round": math.round,
        "trunc": math.trunc, "sign": math.sign, "clip": math.clip,
        "neg": math.neg, "digamma": math.digamma, "lgamma": math.lgamma,
        "isnan": math.isnan, "isinf": math.isinf, "isfinite": math.isfinite,
        "sum": math.sum, "mean": math.mean, "max": math.max, "min": math.min,
        "prod": math.prod, "all": math.all, "any": math.any, "std": math.std,
        "var": math.var, "logsumexp": math.logsumexp, "cumsum": math.cumsum,
        "cumprod": math.cumprod, "trace": math.trace,
        "equal": math.equal, "not_equal": math.not_equal,
        "less_than": math.less_than, "less_equal": math.less_equal,
        "greater_than": math.greater_than, "greater_equal": math.greater_equal,
        "equal_all": math.equal_all, "allclose": math.allclose,
        "is_complex": math.is_complex,
        "is_floating_point": math.is_floating_point,
        "is_integer": math.is_integer,
        "isclose": math.isclose, "logical_and": math.logical_and,
        "logical_or": math.logical_or, "logical_not": math.logical_not,
        "logical_xor": math.logical_xor, "scale": math.scale,
        "reshape": manipulation.reshape, "transpose": manipulation.transpose,
        "t": manipulation.t, "concat": manipulation.concat,
        "split": manipulation.split, "chunk": manipulation.chunk,
        "squeeze": manipulation.squeeze, "unsqueeze": manipulation.unsqueeze,
        "flatten": manipulation.flatten, "gather": manipulation.gather,
        "gather_nd": manipulation.gather_nd, "scatter": manipulation.scatter,
        "tile": manipulation.tile, "expand": manipulation.expand,
        "expand_as": manipulation.expand_as,
        "broadcast_to": manipulation.broadcast_to, "flip": manipulation.flip,
        "roll": manipulation.roll, "cast": manipulation.cast,
        "index_select": manipulation.index_select,
        "index_sample": manipulation.index_sample,
        "masked_fill": search.masked_fill,
        "masked_select": search.masked_select, "where": manipulation.where,
        "unbind": manipulation.unstack, "repeat_interleave":
            manipulation.repeat_interleave,
        "take_along_axis": manipulation.take_along_axis,
        "put_along_axis": manipulation.put_along_axis,
        "argmax": search.argmax, "argmin": search.argmin,
        "argsort": search.argsort, "sort": search.sort, "topk": search.topk,
        "kthvalue": search.kthvalue, "mode": search.mode,
        "median": search.median, "quantile": search.quantile,
        "nonzero": search.nonzero, "unique": search.unique,
        "norm": linalg.norm, "dist": linalg.dist, "cholesky": linalg.cholesky,
        "inverse": linalg.inverse, "matrix_power": linalg.matrix_power,
        "bincount": linalg.bincount,
        "softmax": nn_ops.softmax, "log_softmax": nn_ops.log_softmax,
        "sigmoid": nn_ops.sigmoid, "relu": nn_ops.relu,
        "tril": tril, "triu": triu, "diag": diag,
        "zero_": None, "fill_": None,
    }
    for name, fn in method_ops.items():
        if fn is None:
            continue
        if not hasattr(T, name):
            setattr(T, name, (lambda f: lambda self, *a, **k: f(self, *a, **k))(fn))


_patch_tensor()
del _patch_tensor
