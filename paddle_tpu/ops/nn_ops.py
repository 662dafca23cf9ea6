"""Neural-net ops: activations, conv, pooling, normalization, dropout,
embedding, attention-adjacent utilities.

Reference: paddle/fluid/operators/{activation,conv,pool,batch_norm,layer_norm,
group_norm,instance_norm,dropout,lookup_table_v2,one_hot_v2,interpolate,
pixel_shuffle,unfold,softmax}_op.* and python/paddle/nn/functional/.
TPU-first: convs/matmuls go through lax.conv_general_dilated / dot_general so
XLA tiles them onto the MXU; elementwise activations fuse into neighbours.
"""
from __future__ import annotations

import functools as _functools
import math as _math

import jax
import jax.numpy as jnp

from ._registry import defop

# ------------------------------------------------------------ activations ---

@defop()
def relu(x):
    return jax.nn.relu(x)


@defop()
def relu6(x):
    return jax.nn.relu6(x)


@defop()
def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


@defop()
def prelu(x, weight):
    w = jnp.asarray(weight)
    if w.size > 1:  # per-channel on axis 1 (NCHW)
        shape = [1] * x.ndim
        shape[1] = w.size
        w = w.reshape(shape)
    return jnp.where(x >= 0, x, w * x)


@defop()
def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


@defop()
def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


@defop()
def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


@defop()
def gelu(x, approximate=False):
    return jax.nn.gelu(x, approximate=bool(approximate))


@defop()
def sigmoid(x):
    return jax.nn.sigmoid(x)


@defop()
def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


@defop()
def hardsigmoid(x, slope=1 / 6, offset=0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


@defop()
def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


@defop()
def hardtanh(x, min=-1.0, max=1.0):  # noqa: A002
    return jnp.clip(x, min, max)


@defop()
def swish(x):
    return jax.nn.silu(x)


silu = swish


@defop()
def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


@defop()
def softplus(x, beta=1.0, threshold=20.0):
    bx = beta * x
    return jnp.where(bx > threshold, x, jnp.log1p(jnp.exp(bx)) / beta)


@defop()
def softsign(x):
    return jax.nn.soft_sign(x)


@defop()
def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


@defop()
def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


@defop()
def tanhshrink(x):
    return x - jnp.tanh(x)


@defop()
def thresholded_relu(x, threshold=1.0):
    return jnp.where(x > threshold, x, 0.0)


@defop()
def stanh(x, scale_a=0.67, scale_b=1.7159):
    return scale_b * jnp.tanh(scale_a * x)


@defop()
def maxout(x, groups, axis=1):
    axis = axis % x.ndim
    c = x.shape[axis]
    shape = x.shape[:axis] + (c // groups, groups) + x.shape[axis + 1:]
    return jnp.max(jnp.reshape(x, shape), axis=axis + 1)


@defop()
def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


@defop()
def softmax(x, axis=-1):
    return jax.nn.softmax(x, axis=axis)


@defop()
def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


@defop(stochastic=True)
def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, key=None):
    g = -jnp.log(-jnp.log(jax.random.uniform(key, x.shape, x.dtype, 1e-20, 1.0)))
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.zeros_like(y)
        y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis, inplace=False) \
            if hasattr(jnp, "put_along_axis") else y_hard.at[..., :].set(
                jax.nn.one_hot(jnp.squeeze(idx, axis), y.shape[axis], dtype=y.dtype))
        y = jax.lax.stop_gradient(y_hard - y) + y
    return y


# ------------------------------------------------------------------ conv ----

def _pair(v, n):
    if isinstance(v, (list, tuple)):
        return tuple(int(e) for e in v)
    return (int(v),) * n


def _conv_padding(padding, nsp, stride=None, dilation=None):
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * nsp
    padding = list(padding)
    if len(padding) == nsp and all(isinstance(p, int) for p in padding):
        return [(p, p) for p in padding]
    if len(padding) == 2 * nsp:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(nsp)]
    if all(isinstance(p, (list, tuple)) for p in padding):
        return [tuple(p) for p in padding]
    raise ValueError(f"bad padding {padding}")


def _conv(x, weight, bias, stride, padding, dilation, groups, nsp,
          channels_last=False):
    stride = _pair(stride, nsp)
    dilation = _pair(dilation, nsp)
    pad = _conv_padding(padding, nsp)
    sp = "DHW"[3 - nsp:]
    if channels_last:
        lhs_spec = "N" + sp + "C"
        out_spec = "N" + sp + "C"
    else:
        lhs_spec = "NC" + sp
        out_spec = "NC" + sp
    rhs_spec = "OI" + sp
    dn = jax.lax.conv_dimension_numbers(x.shape, weight.shape,
                                        (lhs_spec, rhs_spec, out_spec))
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=None)
    if bias is not None:
        bshape = [1] * out.ndim
        bshape[out.ndim - 1 if channels_last else 1] = bias.shape[0]
        out = out + jnp.reshape(bias, bshape)
    return out


@defop()
def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL"):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 1,
                 channels_last=data_format == "NLC")


@defop()
def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW"):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 2,
                 channels_last=data_format == "NHWC")


@defop()
def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW"):
    return _conv(x, weight, bias, stride, padding, dilation, groups, 3,
                 channels_last=data_format == "NDHWC")


def _conv_transpose(x, weight, bias, stride, padding, output_padding, dilation,
                    groups, nsp, channels_last=False):
    stride = _pair(stride, nsp)
    dilation = _pair(dilation, nsp)
    opad = _pair(output_padding, nsp)
    sp = "DHW"[3 - nsp:]
    lhs_spec = ("N" + sp + "C") if channels_last else ("NC" + sp)
    rhs_spec = "IO" + sp  # paddle transpose-conv weight: [in, out/groups, *k]
    # transposed conv == convolution (not correlation) of the stride-dilated
    # input with the kernel → flip the spatial dims
    weight = jnp.flip(weight, axis=tuple(range(2, 2 + nsp)))
    dn = jax.lax.conv_dimension_numbers(x.shape, weight.shape,
                                        (lhs_spec, rhs_spec, lhs_spec))
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        p = _conv_padding(padding, nsp)
        # transposed conv padding: effective lo/hi = dilation*(k-1) - pad
        pad = []
        for i in range(nsp):
            eff = dilation[i] * (weight.shape[2 + i] - 1)
            pad.append((eff - p[i][0], eff - p[i][1] + opad[i]))
    if groups > 1:
        xs = jnp.split(x, groups, axis=(x.ndim - 1) if channels_last else 1)
        ws = jnp.split(weight, groups, axis=0)
        outs = [jax.lax.conv_general_dilated(
            xg, wg, window_strides=(1,) * nsp, padding=pad,
            lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn)
            for xg, wg in zip(xs, ws)]
        out = jnp.concatenate(outs, axis=(x.ndim - 1) if channels_last else 1)
    else:
        out = jax.lax.conv_general_dilated(
            x, weight, window_strides=(1,) * nsp, padding=pad,
            lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn)
    if bias is not None:
        bshape = [1] * out.ndim
        bshape[out.ndim - 1 if channels_last else 1] = bias.shape[0]
        out = out + jnp.reshape(bias, bshape)
    return out


@defop()
def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, data_format="NCL"):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, 1, data_format == "NLC")


@defop()
def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, data_format="NCHW"):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, 2, data_format == "NHWC")


@defop()
def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, data_format="NCDHW"):
    return _conv_transpose(x, weight, bias, stride, padding, output_padding,
                           dilation, groups, 3, data_format == "NDHWC")


# --------------------------------------------------------------- pooling ----

def _pool_dims(x_ndim, nsp, kernel, stride, padding, channels_last=False):
    kernel = _pair(kernel, nsp)
    stride = _pair(stride if stride is not None else kernel, nsp)
    pad = _conv_padding(padding, nsp)
    if channels_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pads = ("SAME" if pad == "SAME" else "VALID") if isinstance(pad, str) \
            else [(0, 0)] + list(pad) + [(0, 0)]
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        pads = pad if isinstance(pad, str) else [(0, 0), (0, 0)] + list(pad)
    return window, strides, pads


@defop()
def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCHW", return_mask=False):
    window, strides, pads = _pool_dims(x.ndim, 2, kernel_size, stride, padding,
                                       data_format == "NHWC")
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides, pads)
    return out


@defop()
def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    window, strides, pads = _pool_dims(x.ndim, 1, kernel_size, stride, padding)
    init = -jnp.inf
    return jax.lax.reduce_window(x, init, jax.lax.max, window, strides, pads)


@defop()
def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               data_format="NCDHW"):
    window, strides, pads = _pool_dims(x.ndim, 3, kernel_size, stride, padding,
                                       data_format == "NDHWC")
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides, pads)


def _avg_pool(x, nsp, kernel_size, stride, padding, exclusive, channels_last):
    window, strides, pads = _pool_dims(x.ndim, nsp, kernel_size, stride, padding,
                                       channels_last)
    summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides, pads)
    if exclusive and not isinstance(pads, str):
        counts = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                       window, strides, pads)
        return summed / counts
    denom = 1
    for k in window:
        denom *= k
    return summed / denom


@defop()
def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False):
    return _avg_pool(x, 1, kernel_size, stride, padding, exclusive, False)


@defop()
def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW"):
    return _avg_pool(x, 2, kernel_size, stride, padding, exclusive,
                     data_format == "NHWC")


@defop()
def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, data_format="NCDHW"):
    return _avg_pool(x, 3, kernel_size, stride, padding, exclusive,
                     data_format == "NDHWC")


def _adaptive_windows(in_size, out_size):
    # emulate adaptive pooling by splitting into near-equal regions
    import numpy as np
    starts = (np.arange(out_size) * in_size // out_size).astype(int)
    ends = ((np.arange(out_size) + 1) * in_size - 1) // out_size + 1
    return starts, ends.astype(int)


def _adaptive_pool(x, output_size, nsp, reducer, channels_last=False):
    out_size = _pair(output_size, nsp)
    sp_off = 1 if channels_last else 2
    for d in range(nsp):
        in_sz = x.shape[sp_off + d]
        o = out_size[d]
        if in_sz % o == 0:
            k = in_sz // o
            shape = x.shape[:sp_off + d] + (o, k) + x.shape[sp_off + d + 1:]
            x = reducer(jnp.reshape(x, shape), axis=sp_off + d + 1)
        else:
            starts, ends = _adaptive_windows(in_sz, o)
            slices = [reducer(jax.lax.slice_in_dim(x, int(s), int(e), axis=sp_off + d),
                              axis=sp_off + d, keepdims=True)
                      for s, e in zip(starts, ends)]
            x = jnp.concatenate(slices, axis=sp_off + d)
    return x


@defop()
def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    return _adaptive_pool(x, output_size, 2, jnp.mean, data_format == "NHWC")


@defop()
def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    return _adaptive_pool(x, output_size, 2, jnp.max, data_format == "NHWC")


@defop()
def adaptive_avg_pool1d(x, output_size):
    return _adaptive_pool(x, output_size, 1, jnp.mean)


@defop()
def adaptive_max_pool1d(x, output_size):
    return _adaptive_pool(x, output_size, 1, jnp.max)


@defop()
def adaptive_avg_pool3d(x, output_size, data_format="NCDHW"):
    return _adaptive_pool(x, output_size, 3, jnp.mean, data_format == "NDHWC")


# ------------------------------------------------------------------ norm ----

def _bn_channel_axis(data_format, ndim):
    c_axis = 1 if not data_format.endswith("C") or ndim == 2 else ndim - 1
    if data_format in ("NHWC", "NLC", "NDHWC") and ndim > 2:
        c_axis = ndim - 1
    return c_axis


@_functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _bn_train_core(x, mean, var, weight, bias, epsilon, c_axis):
    """Training-mode BN normalize+scale with a MANUAL backward.

    The auto-derived vjp of the mean/var/normalize chain emits 4-5
    separate [C]-reduces over the full feature map per BN layer (dvar,
    dmean, dgamma, dbeta, plus dx's own terms) — measured 19ms/step of
    the ResNet-50 batch-256 step (r5 profile), ~2.4x the HBM roofline
    for the bytes actually needed. The closed-form backward shares TWO
    sums for everything:
        S1 = sum(dy),  S2 = sum(dy * xhat)   over (N, spatial)
        dgamma = S2,   dbeta = S1
        dx = gamma*inv * (dy - S1/n - xhat*S2/n)
    so each map is read once for the reduces (one fused dual-output
    pass) and once for dx (elementwise, fuses into neighbors)."""
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    inv = jax.lax.rsqrt(var + epsilon).reshape(shape)
    out = (x - mean.reshape(shape)) * inv
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def _bn_core_fwd(x, mean, var, weight, bias, epsilon, c_axis):
    out = _bn_train_core(x, mean, var, weight, bias, epsilon, c_axis)
    return out, (x, mean, var, weight, bias)


def _bn_core_bwd(epsilon, c_axis, res, dy):
    x, mean, var, weight, bias = res
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    n = x.size // x.shape[c_axis]
    inv = jax.lax.rsqrt(var + epsilon).reshape(shape)
    xhat = (x - mean.reshape(shape)) * inv
    # (a Pallas dual-reduce for these sums was tried in r5: Mosaic
    # SIGABRTs on 56x56 maps whose flattened spatial isn't 128-lane
    # divisible, and only the stem map qualifies — XLA's fusion stays)
    dyf = dy.astype(jnp.float32)
    s1 = jnp.sum(dyf, axis=axes)                       # = dbeta
    s2 = jnp.sum(dyf * xhat.astype(jnp.float32), axis=axes)  # = dgamma
    g = weight.reshape(shape) if weight is not None else 1.0
    dx = (g * inv).astype(dy.dtype) * (
        dy - (s1 / n).reshape(shape).astype(dy.dtype)
        - xhat.astype(dy.dtype) * (s2 / n).reshape(shape).astype(dy.dtype))
    # dmean/dvar: the batch stats are FUNCTIONS of x in training mode —
    # their contribution is already folded into the closed-form dx, so
    # their explicit cotangents here are zero
    dmean = jnp.zeros_like(mean)
    dvar = jnp.zeros_like(var)
    dweight = None if weight is None else s2.astype(weight.dtype)
    dbias = None if bias is None else s1.astype(bias.dtype)
    return dx, dmean, dvar, dweight, dbias


_bn_train_core.defvjp(_bn_core_fwd, _bn_core_bwd)


def _bn_normalize(x, mean, var, weight, bias, epsilon, c_axis):
    # computes in the naturally-promoted dtype (low-precision x with f32
    # stats -> f32 math) and RETURNS promoted; both op-level callers cast
    # back to the input dtype themselves — that cast is the op contract
    # (reference BN returns the input dtype), do not return promoted
    # values from a new op without it
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    out = (x - mean.reshape(shape)) * jax.lax.rsqrt(
        var.reshape(shape) + epsilon)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@defop()
def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None):
    c_axis = _bn_channel_axis(data_format, x.ndim)
    reduce_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    use_batch = training and not use_global_stats
    if use_batch:
        mean = jnp.mean(x, axis=reduce_axes)
        var = jnp.var(x, axis=reduce_axes)
        n = x.size // x.shape[c_axis]
        unbiased = var * n / max(n - 1, 1)
        new_mean = momentum * running_mean + (1 - momentum) * jax.lax.stop_gradient(mean)
        new_var = momentum * running_var + (1 - momentum) * jax.lax.stop_gradient(unbiased)
        # manual-backward core: the batch stats are stop_gradiented INTO
        # the core (their x-dependence is folded into its closed-form
        # dx), and the backward shares one dual-sum pass for
        # dx/dgamma/dbeta instead of the auto-vjp's 4-5 map reduces
        out = _bn_train_core(x, jax.lax.stop_gradient(mean),
                             jax.lax.stop_gradient(var), weight, bias,
                             epsilon, c_axis)
    else:
        mean, var = running_mean, running_var
        new_mean, new_var = running_mean, running_var
        out = _bn_normalize(x, mean, var, weight, bias, epsilon, c_axis)
    # reference semantics: BN returns the INPUT dtype (normalization
    # computed in the promoted precision of the f32 running stats, then
    # cast back) — without this an AMP bf16 network silently re-promotes
    # to f32 at its first BatchNorm
    return out.astype(x.dtype), new_mean, new_var


@defop()
def sync_batch_norm(x, running_mean, running_var, weight=None, bias=None,
                    momentum=0.9, epsilon=1e-5, data_format="NCHW",
                    sync_axes=("dp",)):
    """Training-mode batch norm with CROSS-REPLICA statistics (ref:
    sync_batch_norm_op + its NCCL stats all-reduce). Moments (sum, sumsq,
    count) are computed in f32 and psummed over each axis in `sync_axes`
    that is bound in the surrounding shard_map/pmap; unbound axes (eager,
    plain pjit where GSPMD already sees the global batch) degrade to
    local = global. Running stats update with the unbiased variance, same
    as `batch_norm`. Returns (out, new_running_mean, new_running_var)."""
    c_axis = _bn_channel_axis(data_format, x.ndim)
    reduce_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    xf = x.astype(jnp.float32)
    n_local = 1
    for i in reduce_axes:
        n_local *= x.shape[i]
    s1 = jnp.sum(xf, axis=reduce_axes)
    s2 = jnp.sum(jnp.square(xf), axis=reduce_axes)
    n = jnp.asarray(float(n_local), jnp.float32)
    for a in (sync_axes or ()):
        try:
            s1, s2, n = jax.lax.psum((s1, s2, n), a)
        except NameError:
            pass  # axis not bound here
    mean = s1 / n
    var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)
    unbiased = var * n / jnp.maximum(n - 1.0, 1.0)
    new_mean = momentum * running_mean \
        + (1 - momentum) * jax.lax.stop_gradient(mean)
    new_var = momentum * running_var \
        + (1 - momentum) * jax.lax.stop_gradient(unbiased)
    out = _bn_normalize(xf, mean, var, weight, bias, epsilon, c_axis)
    return out.astype(x.dtype), new_mean, new_var


@defop()
def layer_norm(x, weight=None, bias=None, epsilon=1e-5, begin_norm_axis=None,
               normalized_ndim=None):
    """Normalize over trailing dims (paddle LayerNorm normalized_shape)."""
    if normalized_ndim is None:
        normalized_ndim = 1 if begin_norm_axis is None else x.ndim - begin_norm_axis
    axes = tuple(range(x.ndim - normalized_ndim, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@defop()
def rms_norm(x, weight=None, epsilon=1e-6):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    return out


@defop()
def instance_norm(x, weight=None, bias=None, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        shape = [1, -1] + [1] * (x.ndim - 2)
        out = out * weight.reshape(shape)
    if bias is not None:
        shape = [1, -1] + [1] * (x.ndim - 2)
        out = out + bias.reshape(shape)
    return out


@defop()
def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    n, c = x.shape[0], x.shape[1]
    g = num_groups
    xg = jnp.reshape(x, (n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = jnp.reshape((xg - mean) * jax.lax.rsqrt(var + epsilon), x.shape)
    shape = [1, c] + [1] * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@defop()
def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.square(x)
    half = size // 2
    c = x.shape[1]
    acc = jnp.zeros_like(x)
    for i in range(-half, half + 1):
        shifted = jnp.roll(sq, i, axis=1)
        mask_lo = max(0, -i)
        mask_hi = c - max(0, i)
        ch = jnp.arange(c).reshape([1, c] + [1] * (x.ndim - 2))
        valid = (ch >= mask_lo) & (ch < mask_hi)
        acc = acc + jnp.where(valid, shifted, 0.0)
    return x / jnp.power(k + alpha * acc, beta)


@defop()
def normalize(x, p=2, axis=1, epsilon=1e-12):
    norm = jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=True),
                     1.0 / p)
    return x / jnp.maximum(norm, epsilon)


# --------------------------------------------------------------- dropout ----

@defop(stochastic=True)
def dropout(x, p=0.5, training=True, mode="upscale_in_train", axis=None,
            key=None):
    if not training or p == 0.0:
        return x if mode == "upscale_in_train" or training else x * (1 - p)
    if p == 1.0:
        return jnp.zeros_like(x)
    shape = list(x.shape)
    if axis is not None:
        axes = axis if isinstance(axis, (list, tuple)) else [axis]
        shape = [s if i in axes else 1 for i, s in enumerate(shape)]
    keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), 0.0)
    return jnp.where(keep, x, 0.0)


@defop(stochastic=True)
def dropout2d(x, p=0.5, training=True, data_format="NCHW", key=None):
    if not training or p == 0.0:
        return x
    c_axis = 1 if data_format == "NCHW" else x.ndim - 1
    shape = [x.shape[0]] + [1] * (x.ndim - 1)
    shape[c_axis] = x.shape[c_axis]
    keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
    return jnp.where(keep, x / (1.0 - p), 0.0)


dropout3d = dropout2d


@defop(stochastic=True)
def alpha_dropout(x, p=0.5, training=True, key=None):
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    a = (1.0 / ((1 - p) * (1 + p * alpha_p ** 2)) ** 0.5)
    b = -a * alpha_p * p
    return a * jnp.where(keep, x, alpha_p) + b


# ---------------------------------------------------- embedding / one-hot ---

@defop()
def embedding(ids, weight, padding_idx=None, sparse=False):
    if padding_idx is not None:
        vocab = weight.shape[0]
        if not -vocab <= padding_idx < vocab:  # reference range-checks
            raise ValueError(
                f"padding_idx must be within [-{vocab}, {vocab}), "
                f"got {padding_idx}")
        if padding_idx < 0:  # reference normalizes negative indices
            padding_idx += vocab
        # padding row contributes no gradient (ref: lookup_table_v2_op padding_idx)
        frozen_row = jax.lax.stop_gradient(weight[padding_idx])
        weight = weight.at[padding_idx].set(frozen_row)
    return jnp.take(weight, jnp.asarray(ids), axis=0)


@defop(nondiff=True)
def one_hot(x, num_classes):
    return jax.nn.one_hot(jnp.asarray(x), num_classes, dtype=jnp.float32)


@defop()
def label_smooth(label, prior_dist=None, epsilon=0.1):
    k = label.shape[-1]
    if prior_dist is not None:
        return (1 - epsilon) * label + epsilon * prior_dist
    return (1 - epsilon) * label + epsilon / k


# ------------------------------------------------------- linear / matmul ----

@defop()
def linear(x, weight, bias=None):
    out = jnp.matmul(x, weight)  # paddle weight: [in_features, out_features]
    if bias is not None:
        out = out + bias
    return out


# ------------------------------------------------------- image-ish utils ----

@defop()
def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, data_format="NCHW"):
    nsp = x.ndim - 2
    if size is None:
        sf = _pair(scale_factor, nsp)
        if data_format.endswith("C") and x.ndim > 2:
            size = tuple(int(x.shape[1 + i] * sf[i]) for i in range(nsp))
        else:
            size = tuple(int(x.shape[2 + i] * sf[i]) for i in range(nsp))
    else:
        size = _pair(size, nsp)
    if data_format.endswith("C") and x.ndim > 2:
        out_shape = (x.shape[0],) + size + (x.shape[-1],)
    else:
        out_shape = x.shape[:2] + size
    method = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
              "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
    return jax.image.resize(x, out_shape, method=method)


upsample = interpolate


@defop()
def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    n, c, h, w = x.shape
    x = jnp.reshape(x, (n, c // (r * r), r, r, h, w))
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return jnp.reshape(x, (n, c // (r * r), h * r, w * r))


@defop()
def pixel_unshuffle(x, downscale_factor, data_format="NCHW"):
    r = downscale_factor
    n, c, h, w = x.shape
    x = jnp.reshape(x, (n, c, h // r, r, w // r, r))
    x = jnp.transpose(x, (0, 1, 3, 5, 2, 4))
    return jnp.reshape(x, (n, c * r * r, h // r, w // r))


@defop()
def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    k = _pair(kernel_sizes, 2)
    s = _pair(strides, 2)
    d = _pair(dilations, 2)
    p = _conv_padding(paddings, 2)
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=k, window_strides=s, padding=p, rhs_dilation=d,
        dimension_numbers=jax.lax.conv_dimension_numbers(
            x.shape, (1, x.shape[1]) + k, ("NCHW", "OIHW", "NCHW")))
    n, ckk, oh, ow = patches.shape
    return jnp.reshape(patches, (n, ckk, oh * ow))


@defop()
def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True):
    n, c, h, w = x.shape
    gx, gy = grid[..., 0], grid[..., 1]
    if align_corners:
        ix = (gx + 1) * (w - 1) / 2
        iy = (gy + 1) * (h - 1) / 2
    else:
        ix = ((gx + 1) * w - 1) / 2
        iy = ((gy + 1) * h - 1) / 2

    def sample(img, yy, xx):
        valid = (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
        yy = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xx = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        out = img[jnp.arange(n)[:, None, None, None], jnp.arange(c)[None, :, None, None],
                  yy[:, None], xx[:, None]]
        return jnp.where(valid[:, None], out, 0.0)

    if mode == "nearest":
        return sample(x, jnp.round(iy), jnp.round(ix))
    x0, y0 = jnp.floor(ix), jnp.floor(iy)
    x1, y1 = x0 + 1, y0 + 1
    wa = (x1 - ix) * (y1 - iy)
    wb = (x1 - ix) * (iy - y0)
    wc = (ix - x0) * (y1 - iy)
    wd = (ix - x0) * (iy - y0)
    va = sample(x, y0, x0)
    vb = sample(x, y1, x0)
    vc = sample(x, y0, x1)
    vd = sample(x, y1, x1)
    return (va * wa[:, None] + vb * wb[:, None] + vc * wc[:, None]
            + vd * wd[:, None])


@defop()
def affine_grid(theta, out_shape, align_corners=True):
    n, _, h, w = out_shape
    if align_corners:
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
    else:
        ys = (jnp.arange(h) * 2 + 1) / h - 1
        xs = (jnp.arange(w) * 2 + 1) / w - 1
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)  # [h, w, 3]
    return jnp.einsum("hwk,nik->nhwi", base, theta)


@defop()
def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.sqrt(jnp.sum(x1 * x1, axis=axis))
    n2 = jnp.sqrt(jnp.sum(x2 * x2, axis=axis))
    return dot / jnp.maximum(n1 * n2, eps)


@defop()
def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False):
    d = jnp.abs(x - y) + epsilon
    return jnp.power(jnp.sum(jnp.power(d, p), axis=-1, keepdims=keepdim), 1.0 / p)


@defop()
def temporal_shift(x, seg_num, shift_ratio=0.25):
    nt, c, h, w = x.shape
    n = nt // seg_num
    x = jnp.reshape(x, (n, seg_num, c, h, w))
    fold = int(c * shift_ratio)
    left = jnp.concatenate([x[:, 1:, :fold], jnp.zeros_like(x[:, :1, :fold])], axis=1)
    right = jnp.concatenate([jnp.zeros_like(x[:, :1, fold:2 * fold]),
                             x[:, :-1, fold:2 * fold]], axis=1)
    mid = x[:, :, 2 * fold:]
    out = jnp.concatenate([left, right, mid], axis=2)
    return jnp.reshape(out, (nt, c, h, w))
