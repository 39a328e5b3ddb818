"""Convolutions (python/paddle/nn/functional/conv.py parity).

TPU-native: a single jax.lax.conv_general_dilated per op — XLA maps it onto the
MXU (the reference dispatches to cuDNN, operators/conv_op.cc). Weight layout is
the reference's OIHW; data format NCHW by default, NHWC supported (NHWC is the
TPU-friendly layout — models may pass data_format="NHWC").
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...core.dispatch import apply, unwrap

__all__ = ["conv1d", "conv2d", "conv3d", "conv1d_transpose", "conv2d_transpose",
           "conv3d_transpose", "short_conv", "short_conv_silu"]


def _norm_tuple(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    return tuple(int(x) for x in v)


def _norm_padding(padding, n, stride, dilation, kernel):
    """Returns lax padding: string 'SAME'/'VALID' or [(lo,hi)]*n."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, (int, np.integer)):
        return [(int(padding), int(padding))] * n
    padding = list(padding)
    if len(padding) == n and all(isinstance(p, (int, np.integer)) for p in padding):
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * n:
        return [(int(padding[2 * i]), int(padding[2 * i + 1])) for i in range(n)]
    # nested [[lo,hi],...] possibly including batch/channel dims
    pairs = [tuple(int(x) for x in p) for p in padding]
    if len(pairs) == n + 2:
        pairs = pairs[2:]
    return pairs


def _conv(ndim, x, weight, bias, stride, padding, dilation, groups, data_format):
    n = ndim
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    spatial = "DHW"[-n:] if n > 1 else "W"
    if channel_last:
        lhs_spec = "N" + spatial + "C"
    else:
        lhs_spec = "NC" + spatial
    rhs_spec = "OI" + spatial
    out_spec = lhs_spec
    pad = _norm_padding(padding, n, stride, dilation, None)

    def prim(xv, wv, *maybe_bias):
        out = jax.lax.conv_general_dilated(
            xv, wv,
            window_strides=stride,
            padding=pad,
            rhs_dilation=dilation,
            dimension_numbers=(lhs_spec, rhs_spec, out_spec),
            feature_group_count=groups,
            preferred_element_type=None,
        )
        if maybe_bias:
            b = maybe_bias[0]
            shape = [1] * out.ndim
            shape[out_spec.index("C")] = b.shape[0]
            out = out + b.reshape(shape)
        return out

    if bias is not None:
        return apply(prim, x, weight, bias, name=f"conv{n}d")
    return apply(prim, x, weight, name=f"conv{n}d")


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    fmt = "NLC" if data_format == "NLC" else "NCL"
    # express conv1d via the generic path with 1 spatial dim
    channel_last = fmt == "NLC"
    return _conv(1, x, weight, bias, stride, padding, dilation, groups,
                 "NLC" if channel_last else "NCW")


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv(2, x, weight, bias, stride, padding, dilation, groups, data_format)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv(3, x, weight, bias, stride, padding, dilation, groups, data_format)


def _conv_transpose(ndim, x, weight, bias, stride, padding, output_padding,
                    dilation, groups, data_format, output_size):
    n = ndim
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    spatial = "DHW"[-n:] if n > 1 else "W"
    lhs_spec = ("N" + spatial + "C") if channel_last else ("NC" + spatial)
    # reference stores transpose weights as (in, out/groups, *k) = IOHW
    rhs_spec = "IO" + spatial
    out_spec = lhs_spec

    if isinstance(padding, str):
        pad = padding.upper()
    else:
        pad = _norm_padding(padding, n, stride, dilation, None)
    opad = _norm_tuple(output_padding, n) if output_padding else (0,) * n

    def prim(xv, wv, *maybe_bias):
        if isinstance(pad, str):
            lax_pad = pad
        else:
            # conv_transpose pad semantics: effective padding on the dilated input
            k = list(wv.shape[2:])
            lax_pad = []
            for i in range(n):
                eff_k = (k[i] - 1) * dilation[i] + 1
                lo = eff_k - 1 - pad[i][0]
                hi = eff_k - 1 - pad[i][1] + opad[i]
                lax_pad.append((lo, hi))
        if groups > 1:
            # lax.conv_transpose has no feature_group_count on all versions:
            # do grouped transpose by splitting channels.
            xs = jnp.split(xv, groups, axis=lhs_spec.index("C"))
            ws = jnp.split(wv, groups, axis=0)
            outs = [
                jax.lax.conv_transpose(
                    xg, wg, strides=stride, padding=lax_pad,
                    rhs_dilation=dilation,
                    dimension_numbers=(lhs_spec, rhs_spec, out_spec),
                    transpose_kernel=False)
                for xg, wg in zip(xs, ws)
            ]
            out = jnp.concatenate(outs, axis=out_spec.index("C"))
        else:
            out = jax.lax.conv_transpose(
                xv, wv, strides=stride, padding=lax_pad,
                rhs_dilation=dilation,
                dimension_numbers=(lhs_spec, rhs_spec, out_spec),
                transpose_kernel=False)
        if maybe_bias:
            b = maybe_bias[0]
            shape = [1] * out.ndim
            shape[out_spec.index("C")] = b.shape[0]
            out = out + b.reshape(shape)
        return out

    if bias is not None:
        return apply(prim, x, weight, bias, name=f"conv{n}d_transpose")
    return apply(prim, x, weight, name=f"conv{n}d_transpose")


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    fmt = "NLC" if data_format == "NLC" else "NCW"
    return _conv_transpose(1, x, weight, bias, stride, padding, output_padding,
                           dilation, groups, fmt, output_size)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCHW", name=None):
    return _conv_transpose(2, x, weight, bias, stride, padding, output_padding,
                           dilation, groups, data_format, output_size)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    return _conv_transpose(3, x, weight, bias, stride, padding, output_padding,
                           dilation, groups, data_format, output_size)


def short_conv(bcx, weight, name=None):
    """The gated short convolution of the LFM2 models (Liquid AI 2025):
    `bcx` (batch, seq, 3 * channels) is split into B, C and x along its last
    axis; y_t = C_t * sum_j weight[:, j] * (B * x)_{t - (K-1) + j}, a causal
    depthwise convolution of kernel K with one tap per channel and step,
    zero before the sequence's start, between two multiplicative gates.
    `weight` is (channels, K) with the last tap on the current step, as a
    depthwise Conv1D holds it. K shifted multiply-adds in float32: at K = 3
    a convolution call would only hide them."""
    def prim(v, w):
        b, c, x = jnp.split(v.astype(jnp.float32), 3, axis=-1)
        return (c * _causal_taps(b * x, w)).astype(v.dtype)

    return apply(prim, bcx, weight, name="short_conv")


def _causal_taps(u, w):
    """sum_j w[:, j] * u_{t - (K-1) + j} over u (batch, seq, channels) in
    float32, zero before the sequence's start: K shifted multiply-adds."""
    taps = w.astype(jnp.float32)
    k = taps.shape[1]
    seq = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + seq] * taps[:, j] for j in range(k))


def short_conv_silu(x, weight, norm_head_dim=None, epsilon=1e-06, name=None):
    """silu of a causal depthwise convolution over `x` (batch, seq,
    channels): y_t = silu(sum_j weight[:, j] * x_{t - (K-1) + j}), zero
    before the sequence's start, as the linear-attention mixers put it after
    their q, k and v projections (Kimi Delta Attention: K = 4). `weight` is
    (channels, K), the last tap on the current step; the same shifted
    multiply-adds as `short_conv`, in float32. With `norm_head_dim` the
    result, rounded to x's dtype, is then divided by its L2 norm over every
    head of that many channels, sqrt(sum(y^2) + epsilon) in float32, as
    `l2_norm` over the heads would: the mixers' queries and keys.

    On a TPU a stream of float32 or bfloat16 in whole lane chunks is one pass
    forward and one backward (ops/pallas/short_conv.py, which keeps the input
    alone for its backward); everything else runs the jnp rule below, which
    is the oracle of the other (`short_conv.kernel_total`,
    `short_conv.xla_total` count the traced calls by path)."""
    from ...ops import attention
    from ...ops.pallas import short_conv as kernels
    from ...ops.pallas.flash_attention import _interpret
    from ...profiler import metrics
    xv = unwrap(x)
    kernel = kernels.takes(xv.shape, xv.dtype, unwrap(weight).shape[1], norm_head_dim,
                           attention._platform(), attention._on_mesh(xv))
    metrics.get_registry().inc_counter(
        "short_conv.kernel_total" if kernel else "short_conv.xla_total")
    if kernel:
        interp = _interpret(xv)

        def prim(v, w):
            return kernels.short_conv_silu(v, w, norm_head_dim, epsilon, interp)
    else:
        def prim(v, w):
            return _silu_taps(v, w, norm_head_dim, epsilon)
    return apply(prim, x, weight, name="short_conv")


def _silu_taps(v, w, norm_head_dim=None, epsilon=1e-06):
    """The jnp rule of `short_conv_silu`."""
    y = jax.nn.silu(_causal_taps(v.astype(jnp.float32), w)).astype(v.dtype)
    if norm_head_dim is None:
        return y
    f = y.astype(jnp.float32).reshape(y.shape[:-1] + (-1, norm_head_dim))
    return (f * jax.lax.rsqrt(jnp.sum(jnp.square(f), axis=-1, keepdims=True)
                              + epsilon)).astype(v.dtype).reshape(y.shape)
