"""Normalization functionals (python/paddle/nn/functional/norm.py parity).

batch_norm handles running-stat updates by writing into the passed mean/var
tensors (state mutation — captured by to_static functionalization, mirroring
the reference's in-place moving-average updates in operators/batch_norm_op).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import apply, unwrap
from ...core.tensor import Tensor

__all__ = ["batch_norm", "layer_norm", "rms_norm", "instance_norm",
           "group_norm", "local_response_norm", "normalize", "l2_norm"]


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05,
               data_format="NCHW", use_global_stats=None, name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    if use_global_stats is None:
        use_global_stats = not training

    xv = unwrap(x)
    ch_axis = xv.ndim - 1 if channel_last else (1 if xv.ndim > 1 else 0)
    reduce_axes = tuple(i for i in range(xv.ndim) if i != ch_axis)
    bshape = [1] * xv.ndim
    bshape[ch_axis] = xv.shape[ch_axis]

    if not use_global_stats:
        # batch statistics + running stat update (functional state write)
        def prim(v, *wb):
            mean = jnp.mean(v, axis=reduce_axes)
            var = jnp.var(v, axis=reduce_axes)
            inv = jax.lax.rsqrt(var.reshape(bshape) + epsilon)
            out = (v - mean.reshape(bshape)) * inv
            i = 0
            if weight is not None:
                out = out * wb[i].reshape(bshape)
                i += 1
            if bias is not None:
                out = out + wb[i].reshape(bshape)
            return out, mean, var
        args = [a for a in (weight, bias) if a is not None]
        out, mean_t, var_t = apply(prim, x, *args, name="batch_norm")
        if running_mean is not None:
            rm = running_mean._value  # hooked read (trace capture + host pull)
            running_mean._value = (momentum * rm
                                   + (1.0 - momentum)
                                   * mean_t._value.astype(rm.dtype))
        if running_var is not None:
            n = 1
            for a in reduce_axes:
                n *= xv.shape[a]
            unbiased = var_t._value * (n / max(n - 1, 1))
            rv = running_var._value
            running_var._value = (momentum * rv
                                  + (1.0 - momentum)
                                  * unbiased.astype(rv.dtype))
        return out

    def prim_eval(v, m, s, *wb):
        inv = jax.lax.rsqrt(s.reshape(bshape) + epsilon)
        out = (v - m.reshape(bshape)) * inv
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out
    args = [a for a in (weight, bias) if a is not None]
    return apply(prim_eval, x, running_mean, running_var, *args,
                 name="batch_norm_eval")


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    ndim_norm = len(tuple(normalized_shape))

    def prim(v, *wb):
        # statistics and the affine in float32 whatever the input's type
        # (the reference kernel's LayerNormParamType; bf16 statistics lose
        # three decimal digits over a 2048-element row), the output in the
        # input's type
        axes = tuple(range(v.ndim - ndim_norm, v.ndim))
        f = jnp.promote_types(v.dtype, jnp.float32)
        vf = v.astype(f)
        mean = jnp.mean(vf, axis=axes, keepdims=True)
        var = jnp.var(vf, axis=axes, keepdims=True)
        out = (vf - mean) * jax.lax.rsqrt(var + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].astype(f)
            i += 1
        if bias is not None:
            out = out + wb[i].astype(f)
        return out.astype(v.dtype)

    args = [a for a in (weight, bias) if a is not None]
    return apply(prim, x, *args, name="layer_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-05,
                  data_format="NCHW", name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")

    def prim(v, *wb):
        nd = v.ndim
        ch_axis = nd - 1 if channel_last else 1
        axes = tuple(i for i in range(2, nd)) if not channel_last \
            else tuple(i for i in range(1, nd - 1))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) * jax.lax.rsqrt(var + eps)
        bshape = [1] * nd
        bshape[ch_axis] = v.shape[ch_axis]
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    args = [a for a in (weight, bias) if a is not None]
    return apply(prim, x, *args, name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")

    def prim(v, *wb):
        nd = v.ndim
        ch_axis = nd - 1 if channel_last else 1
        c = v.shape[ch_axis]
        g = num_groups
        if channel_last:
            newshape = v.shape[:-1] + (g, c // g)
            r = v.reshape(newshape)
            axes = tuple(range(1, nd - 1)) + (nd,)
            mean = jnp.mean(r, axis=axes, keepdims=True)
            var = jnp.var(r, axis=axes, keepdims=True)
            out = ((r - mean) * jax.lax.rsqrt(var + epsilon)).reshape(v.shape)
        else:
            newshape = (v.shape[0], g, c // g) + v.shape[2:]
            r = v.reshape(newshape)
            axes = (2,) + tuple(range(3, nd + 1))
            mean = jnp.mean(r, axis=axes, keepdims=True)
            var = jnp.var(r, axis=axes, keepdims=True)
            out = ((r - mean) * jax.lax.rsqrt(var + epsilon)).reshape(v.shape)
        bshape = [1] * nd
        bshape[ch_axis] = c
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    args = [a for a in (weight, bias) if a is not None]
    return apply(prim, x, *args, name="group_norm")


def rms_norm(x, weight=None, epsilon=1e-05, name=None):
    """x / sqrt(mean(x^2) + epsilon) over the last axis, times a learned
    gain (Zhang & Sennrich 2019). The statistics are float32 whatever the
    input's dtype; the result is cast back before the gain is applied in
    that dtype, as the public Llama-style models do."""
    def prim(v, *w):
        f = v.astype(jnp.float32)
        out = (f * jax.lax.rsqrt(jnp.mean(jnp.square(f), axis=-1, keepdims=True)
                                 + epsilon)).astype(v.dtype)
        return out * w[0] if w else out

    args = [] if weight is None else [weight]
    return apply(prim, x, *args, name="rms_norm")


def l2_norm(x, epsilon=1e-06, name=None):
    """x / sqrt(sum(x^2) + epsilon) over the last axis, the statistics in
    float32 whatever the input's dtype: the per-head norm the delta-rule
    mixers put on their queries and keys."""
    def prim(v):
        f = v.astype(jnp.float32)
        return (f * jax.lax.rsqrt(jnp.sum(jnp.square(f), axis=-1, keepdims=True)
                                  + epsilon)).astype(v.dtype)
    return apply(prim, x, name="l2_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def prim(v):
        ch_axis = 1 if data_format.startswith("NC") else v.ndim - 1
        sq = jnp.square(v)
        half = size // 2
        pads = [(0, 0)] * v.ndim
        pads[ch_axis] = (half, size - half - 1)
        padded = jnp.pad(sq, pads)
        # moving sum over channel window
        idx = [slice(None)] * v.ndim
        acc = jnp.zeros_like(v)
        for ofs in range(size):
            idx[ch_axis] = slice(ofs, ofs + v.shape[ch_axis])
            acc = acc + padded[tuple(idx)]
        denom = (k + alpha * acc / size) ** beta
        return v / denom
    return apply(prim, x, name="local_response_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def prim(v):
        if p == 2:
            n = jnp.sqrt(jnp.sum(jnp.square(v), axis=axis, keepdims=True))
        else:
            n = jnp.sum(jnp.abs(v) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return v / jnp.maximum(n, epsilon)
    return apply(prim, x, name="normalize")
