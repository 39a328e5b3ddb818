"""Fused (ReLU ->) Conv2D -> BatchNorm — forward and hand-written backward.

Reference analog: operators/fused/conv_fusion_op.cc (conv+act) and
operators/fused/fused_bn_add_activation_op.cu (BN+act with a saved-reserve-
space backward). TPU-native design: the convolutions themselves stay on
XLA's MXU conv emitter (already at the HBM roofline — docs/performance.md);
the fusion attacks the *memory plan* of the backward pass instead.

Per-op autodiff of [relu ->] conv -> batch_norm saves TWO full activation
tensors per layer across the forward->backward boundary: the activated conv
input (the conv's wgrad residual) and the pre-BN conv output `z` (BN's vjp
reads it to re-form x_hat). This op keeps ONE: its own *pre-activation*
output y = gamma * x_hat + beta. The backward then reconstructs everything
else elementwise:

    x_hat  = (y - beta) / gamma                        (exact, everywhere)
    conv-in = relu(saved input)                        (fused into wgrad read)
    d(input) = conv_dgrad(dz) * (saved input > 0)      (fused epilogue)

and dx/dW come from jax.vjp of relu+conv itself — XLA's tuned dgrad/wgrad
kernels with these elementwise expressions fused into their reads. The
activation handoff between consecutive fused layers is the pre-activation
tensor, so a chain of N conv+BN+ReLU layers stores N activation tensors
instead of 2N (ResNet-50 @ b128 bf16: ~2.4 GB fewer backward residuals).

Why the activation is fused on the INPUT side, not the output: the BN
backward's batch-coupling term needs x_hat at every position, but behind an
output ReLU x_hat is unrecoverable where the mask is zero — only the
pre-activation output supports exact recovery.

Batch statistics are computed in float32 regardless of input dtype (bf16
statistics lose ~3 decimal digits on 100k-element reductions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.dispatch import apply

__all__ = ["fused_conv_bn"]


def _conv_fn(stride, pad, dilation, groups, dn, act_input):
    def conv(xv, wv):
        if act_input:
            xv = jnp.maximum(xv, jnp.asarray(0, xv.dtype))
        return jax.lax.conv_general_dilated(
            xv, wv, window_strides=stride, padding=pad,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups)
    return conv


# Channels with |gamma| at/below this threshold treat x_hat as zero in the
# backward: x_hat = (y - beta)/gamma is noise-dominated once |gamma| falls
# under the rounding error of the saved y, and dividing by a clamped tiny
# value would produce enormous (finite) garbage gradients instead. The
# trade-off is explicit: such channels get dgamma = 0 and dz = 0, so a BN
# gamma EXACTLY zero-initialized (zero_init_residual recipes) would stay
# zero under the custom backward. fused_conv_bn guards against that
# silently biting (ADVICE r4 finding 3): when gamma is concrete (eager
# mode) and ANY channel sits in the degenerate band, it routes through the
# plain-autodiff path — same forward math, jax-derived backward, correct
# dgamma. Under jit tracing gamma is abstract and the guard cannot fire;
# zero-init-gamma recipes compiled with to_static should pass
# fused_conv_bn=False / PADDLE_TPU_FUSED_CONV_BN=0. In-tree models
# initialize gamma = 1.
_GAMMA_TOL = 1e-6


def _gamma_degenerate(bn_weight):
    """Some channel inside the |gamma| <= _GAMMA_TOL band where the custom
    backward freezes it (shared guard: ops/_param_guard.py)."""
    from ._param_guard import degenerate_below_tol
    return degenerate_below_tol(bn_weight, _GAMMA_TOL)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _fused_conv_bn_diff(x, w, gamma, beta, stride, pad, dilation, groups,
                        dn, eps, act_input):
    """Returns (y_pre_activation, batch_mean, batch_var). mean/var are
    emitted for the running-statistics update only: their cotangents are
    IGNORED by the custom backward (they are buffers, never differentiated
    through)."""
    y, mean, var, _ = _fused_fwd_impl(x, w, gamma, beta, stride, pad,
                                      dilation, groups, dn, eps, act_input)
    return y, mean, var


def _fused_fwd_impl(x, w, gamma, beta, stride, pad, dilation, groups, dn,
                    eps, act_input):
    ch_axis = dn[0].index("C")
    z = _conv_fn(stride, pad, dilation, groups, dn, act_input)(x, w)
    red = tuple(i for i in range(z.ndim) if i != ch_axis)
    zf = z.astype(jnp.float32)
    # same association as nn.functional.batch_norm (two-pass var,
    # (z-mean)*inv then affine) so the fused forward matches the unfused
    # composition bit-for-bit — divergence between the two paths is then
    # confined to backward reassociation
    mean = jnp.mean(zf, axis=red)
    var = jnp.var(zf, axis=red)
    inv = jax.lax.rsqrt(var + eps)
    bshape = [1] * z.ndim
    bshape[ch_axis] = z.shape[ch_axis]
    y = (zf - mean.reshape(bshape)) * inv.reshape(bshape)
    y = y * gamma.astype(jnp.float32).reshape(bshape)
    y = y + beta.astype(jnp.float32).reshape(bshape)
    return y.astype(z.dtype), mean, var, inv


def _fused_fwd(x, w, gamma, beta, stride, pad, dilation, groups, dn, eps,
               act_input):
    y, mean, var, inv = _fused_fwd_impl(x, w, gamma, beta, stride, pad,
                                        dilation, groups, dn, eps, act_input)
    # residuals: x and w (the conv's vjp needs them), the pre-activation
    # output y, and per-channel scalars — the conv output z and the
    # activated conv input are deliberately absent
    return (y, mean, var), (x, w, gamma, beta, inv, y)


def _fused_bwd(stride, pad, dilation, groups, dn, eps, act_input, res, cts):
    dy = cts[0]  # mean/var cotangents ignored (buffer outputs, see above)
    x, w, gamma, beta, inv, y = res
    ch_axis = dn[0].index("C")
    red = tuple(i for i in range(y.ndim) if i != ch_axis)
    bshape = [1] * y.ndim
    bshape[ch_axis] = y.shape[ch_axis]

    gf = gamma.astype(jnp.float32)
    live = jnp.abs(gf) > _GAMMA_TOL  # see _GAMMA_TOL note
    gdiv = jnp.where(live, gf, 1.0)
    bf = beta.astype(jnp.float32)
    g = dy.astype(jnp.float32)
    xhat = jnp.where(live.reshape(bshape),
                     (y.astype(jnp.float32) - bf.reshape(bshape))
                     / gdiv.reshape(bshape), 0.0)

    m = 1
    for a in red:
        m *= y.shape[a]
    dbeta = jnp.sum(g, axis=red)
    dgamma = jnp.sum(g * xhat, axis=red)
    # dz = gamma*inv * (g - mean(g) - xhat * mean(g*xhat)): the batch-norm
    # backward with both reductions already in hand
    coef = (gf * inv).reshape(bshape)
    dz = coef * (g - (dbeta / m).reshape(bshape)
                 - xhat * (dgamma / m).reshape(bshape))
    dz = dz.astype(x.dtype)

    conv = _conv_fn(stride, pad, dilation, groups, dn, act_input)
    _, conv_vjp = jax.vjp(conv, x, w)  # dead fwd conv is DCE'd by XLA
    dx, dw = conv_vjp(dz)
    return dx, dw, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype)


_fused_conv_bn_diff.defvjp(_fused_fwd, _fused_bwd)


def _specs(data_format):
    lhs = "NHWC" if data_format == "NHWC" else "NCHW"
    return (lhs, "OIHW", lhs)


def fused_conv_bn(x, weight, bn_weight, bn_bias, running_mean=None,
                  running_var=None, *, training=True, momentum=0.9,
                  epsilon=1e-5, stride=1, padding=0, dilation=1, groups=1,
                  data_format="NCHW", act_input=False):
    """[relu ->] conv2d -> batch_norm as ONE differentiable op whose backward
    saves a single activation tensor (see module docstring). Returns the
    PRE-activation BN output — apply the output nonlinearity outside (or
    fuse it into the next layer's `act_input=True`).

    Updates running stats like nn.functional.batch_norm when training. Eval
    mode folds BN (running stats) into a post-conv scale/shift epilogue (the
    inference fast path — the reference conv_fusion_op's main use).
    """
    from ..nn.functional.conv import _norm_padding, _norm_tuple

    stride_t = _norm_tuple(stride, 2)
    dil_t = _norm_tuple(dilation, 2)
    pad_raw = _norm_padding(padding, 2, stride_t, dil_t, None)
    pad_n = pad_raw if isinstance(pad_raw, str) else tuple(
        tuple(p) for p in pad_raw)
    dn = _specs(data_format)
    ch_axis = dn[0].index("C")

    if not training:
        def prim_eval(xv, wv, gv, bv, mv, vv):
            z = _conv_fn(stride_t, pad_n, dil_t, groups, dn, act_input)(xv, wv)
            bshape = [1] * z.ndim
            bshape[ch_axis] = z.shape[ch_axis]
            invv = jax.lax.rsqrt(vv.astype(jnp.float32) + epsilon)
            scale = (gv.astype(jnp.float32) * invv).reshape(bshape)
            shift = (bv.astype(jnp.float32)
                     - gv.astype(jnp.float32) * invv
                     * mv.astype(jnp.float32)).reshape(bshape)
            out = z.astype(jnp.float32) * scale + shift
            return out.astype(z.dtype)

        return apply(prim_eval, x, weight, bn_weight, bn_bias,
                     running_mean, running_var, name="fused_conv_bn_eval")

    def prim_plain(xv, wv, gv, bv):
        y, mean, var, _ = _fused_fwd_impl(xv, wv, gv, bv, stride_t,
                                          pad_n, dil_t, groups, dn,
                                          epsilon, act_input)
        return y, mean, var

    def prim_fused(xv, wv, gv, bv):
        return _fused_conv_bn_diff(xv, wv, gv, bv, stride_t, pad_n,
                                   dil_t, groups, dn, epsilon, act_input)

    # zero/near-zero gamma channels: plain autodiff through the same
    # forward math (saves the conv output z as a residual, but keeps
    # dgamma exact where the custom backward would freeze it)
    prim = prim_plain if _gamma_degenerate(bn_weight) else prim_fused
    out, mean_t, var_t = apply(prim, x, weight, bn_weight, bn_bias,
                               name="fused_conv_bn")

    if running_mean is not None:
        rm = running_mean._value
        running_mean._value = (momentum * rm + (1.0 - momentum)
                               * mean_t._value.astype(rm.dtype))
    if running_var is not None:
        n = 1
        for i, s in enumerate(out.shape):
            if i != ch_axis:
                n *= int(s)
        unbiased = var_t._value * (n / max(n - 1, 1))
        rv = running_var._value
        running_var._value = (momentum * rv + (1.0 - momentum)
                              * unbiased.astype(rv.dtype))
    return out
