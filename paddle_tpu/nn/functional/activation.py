"""Activation functionals (python/paddle/nn/functional/activation.py parity)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import apply, unwrap

__all__ = [
    "relu", "relu6", "relu_", "tanh_", "elu", "selu", "celu", "gelu", "sigmoid",
    "log_sigmoid", "tanh", "tanhshrink", "hardtanh", "hardshrink",
    "hardsigmoid", "hardswish", "leaky_relu", "prelu", "rrelu", "softmax",
    "log_softmax", "softplus", "softshrink", "softsign", "swish", "silu",
    "elu_", "softmax_",
    "mish", "maxout", "glu", "swiglu", "gumbel_softmax", "thresholded_relu",
]


def relu(x, name=None):
    return apply(jax.nn.relu, x, name="relu")


def relu_(x, name=None):
    from ...core.tensor import inplace_assign
    return inplace_assign(x, relu(x))


def relu6(x, name=None):
    return apply(jax.nn.relu6, x, name="relu6")


def elu(x, alpha=1.0, name=None):
    return apply(lambda v: jax.nn.elu(v, alpha=alpha), x, name="elu")


def selu(x, scale=1.0507009873554804934193349852946,
         alpha=1.6732632423543772848170429916717, name=None):
    return apply(lambda v: scale * jnp.where(v > 0, v, alpha * jnp.expm1(v)),
                 x, name="selu")


def celu(x, alpha=1.0, name=None):
    return apply(lambda v: jax.nn.celu(v, alpha=alpha), x, name="celu")


def gelu(x, approximate=False, name=None):
    return apply(lambda v: jax.nn.gelu(v, approximate=approximate), x, name="gelu")


def sigmoid(x, name=None):
    return apply(jax.nn.sigmoid, x, name="sigmoid")


def log_sigmoid(x, name=None):
    return apply(jax.nn.log_sigmoid, x, name="log_sigmoid")


def tanh(x, name=None):
    return apply(jnp.tanh, x, name="tanh")


def tanhshrink(x, name=None):
    return apply(lambda v: v - jnp.tanh(v), x, name="tanhshrink")


def hardtanh(x, min=-1.0, max=1.0, name=None):  # noqa: A002
    return apply(lambda v: jnp.clip(v, min, max), x, name="hardtanh")


def hardshrink(x, threshold=0.5, name=None):
    return apply(lambda v: jnp.where(jnp.abs(v) > threshold, v, 0.0), x,
                 name="hardshrink")


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    return apply(lambda v: jnp.clip(slope * v + offset, 0.0, 1.0), x,
                 name="hardsigmoid")


def hardswish(x, name=None):
    return apply(lambda v: v * jnp.clip(v + 3.0, 0.0, 6.0) / 6.0, x,
                 name="hardswish")


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply(lambda v: jnp.where(v >= 0, v, negative_slope * v), x,
                 name="leaky_relu")


def prelu(x, weight, data_format="NCHW", name=None):
    def prim(v, w):
        if w.size == 1:
            return jnp.where(v >= 0, v, w.reshape(()) * v)
        shape = [1] * v.ndim
        ch_axis = 1 if data_format[1] == "C" else v.ndim - 1
        shape[ch_axis] = w.size
        return jnp.where(v >= 0, v, w.reshape(shape) * v)
    return apply(prim, x, weight, name="prelu")


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=False, name=None):
    if training:
        from ...core.random import next_key_data
        kd = next_key_data()

        def prim(v, key_data):
            a = jax.random.uniform(jax.random.wrap_key_data(key_data),
                                   v.shape, dtype=v.dtype,
                                   minval=lower, maxval=upper)
            return jnp.where(v >= 0, v, a * v)
        return apply(prim, x, kd, name="rrelu")
    mid = (lower + upper) / 2.0
    return leaky_relu(x, mid)


def softmax(x, axis=-1, dtype=None, name=None):
    from ...core.dtypes import convert_dtype
    d = convert_dtype(dtype)
    def prim(v):
        if d is not None:
            v = v.astype(d)
        return jax.nn.softmax(v, axis=axis)
    return apply(prim, x, name="softmax")


def log_softmax(x, axis=-1, dtype=None, name=None):
    from ...core.dtypes import convert_dtype
    d = convert_dtype(dtype)
    def prim(v):
        if d is not None:
            v = v.astype(d)
        return jax.nn.log_softmax(v, axis=axis)
    return apply(prim, x, name="log_softmax")


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply(lambda v: jnp.where(beta * v > threshold, v,
                                     jnp.log1p(jnp.exp(beta * v)) / beta),
                 x, name="softplus")


def softshrink(x, threshold=0.5, name=None):
    return apply(lambda v: jnp.where(v > threshold, v - threshold,
                                     jnp.where(v < -threshold, v + threshold, 0.0)),
                 x, name="softshrink")


def softsign(x, name=None):
    return apply(jax.nn.soft_sign, x, name="softsign")


def swish(x, name=None):
    return apply(jax.nn.silu, x, name="swish")


silu = swish


def mish(x, name=None):
    return apply(lambda v: v * jnp.tanh(jax.nn.softplus(v)), x, name="mish")


def maxout(x, groups, axis=1, name=None):
    def prim(v):
        ax = axis % v.ndim
        c = v.shape[ax]
        newshape = v.shape[:ax] + (c // groups, groups) + v.shape[ax + 1:]
        return jnp.max(v.reshape(newshape), axis=ax + 1)
    return apply(prim, x, name="maxout")


def glu(x, axis=-1, name=None):
    return apply(lambda v: jax.nn.glu(v, axis=axis), x, name="glu")


def swiglu(x, y=None, name=None):
    """silu(x) * y (Shazeer 2020), the gate of a gated feed-forward; with
    one argument its last axis is split in two halves, gate first
    (paddle.incubate.nn.functional.swiglu). silu runs in float32."""
    def prim(a, *b):
        a, b = (a, b[0]) if b else jnp.split(a, 2, axis=-1)
        return (jax.nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)
                ).astype(a.dtype)

    args = [] if y is None else [y]
    return apply(prim, x, *args, name="swiglu")


def thresholded_relu(x, threshold=1.0, name=None):
    return apply(lambda v: jnp.where(v > threshold, v, 0.0), x,
                 name="thresholded_relu")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    from ...core.random import next_key_data
    kd = next_key_data()

    def prim(v, key_data):
        g = jax.random.gumbel(jax.random.wrap_key_data(key_data),
                              v.shape, dtype=v.dtype)
        y = jax.nn.softmax((v + g) / temperature, axis=axis)
        if hard:
            mx = jnp.max(y, axis=axis, keepdims=True)
            onehot = (y == mx).astype(y.dtype)
            y = jax.lax.stop_gradient(onehot - y) + y
        return y
    return apply(prim, x, kd, name="gumbel_softmax")


def tanh_(x, name=None):
    from ...core.tensor import inplace_assign
    return inplace_assign(x, tanh(x))


def elu_(x, alpha=1.0, name=None):
    from ...core.tensor import inplace_assign
    return inplace_assign(x, elu(x, alpha))


def softmax_(x, axis=-1, dtype=None, name=None):
    from ...core.tensor import inplace_assign
    return inplace_assign(x, softmax(x, axis, dtype))
