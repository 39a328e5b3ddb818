"""Fused transformer feed-forward — forward and hand-written backward.

Reference analog: operators/fused/fused_feedforward_op.cc (linear1 -> act ->
dropout -> linear2 fused with its own grad kernels). TPU-native design: the
two matmuls stay on the MXU via jnp.dot; the fusion changes the *residual
plan*. Per-op autodiff of fc2(act(fc1(x))) saves x, the pre-activation h,
AND the activated a = act(h) — a is the widest tensor in the block
(4*hidden). This op's custom_vjp saves only (x, h) and recomputes a = act(h)
elementwise inside the backward, where XLA fuses it into the dW2/da matmul
reads. Per GPT-medium layer at b8/s1024 that removes a 64 MB residual; x24
layers ~1.6 GB of HBM working set.

Activation derivative is exact (tanh-approximated GeLU's own derivative for
approximate=True, erf-based otherwise), matching what autodiff of the
unfused path produces.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.dispatch import apply

__all__ = ["fused_ffn"]

_SQRT_2_OVER_PI = math.sqrt(2.0 / math.pi)


def _act_fns(activation):
    if activation == "gelu":
        def f(h):
            return jax.nn.gelu(h, approximate=False)

        def df(h):
            # d/dh [h * Phi(h)] = Phi(h) + h * phi(h)
            phi = jnp.exp(-0.5 * h * h) / math.sqrt(2.0 * math.pi)
            Phi = 0.5 * (1.0 + jax.lax.erf(h / math.sqrt(2.0)))
            return Phi + h * phi
        return f, df
    if activation == "gelu_tanh":
        def f(h):
            return jax.nn.gelu(h, approximate=True)

        def df(h):
            u = _SQRT_2_OVER_PI * (h + 0.044715 * h ** 3)
            t = jnp.tanh(u)
            du = _SQRT_2_OVER_PI * (1.0 + 3 * 0.044715 * h * h)
            return 0.5 * (1.0 + t) + 0.5 * h * (1.0 - t * t) * du
        return f, df
    if activation == "relu":
        def f(h):
            return jnp.maximum(h, jnp.asarray(0, h.dtype))

        def df(h):
            return (h > 0).astype(h.dtype)
        return f, df
    raise ValueError(f"unsupported activation {activation!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused_ffn_diff(x, w1, b1, w2, b2, activation):
    f, _ = _act_fns(activation)
    h = jnp.dot(x, w1) + b1
    return jnp.dot(f(h), w2) + b2


def _ffn_fwd(x, w1, b1, w2, b2, activation):
    f, _ = _act_fns(activation)
    h = jnp.dot(x, w1) + b1
    y = jnp.dot(f(h), w2) + b2
    # residuals: x, h, and the weights — the activated a = f(h) (the widest
    # tensor of the block) is deliberately absent
    return y, (x, w1, w2, h)


def _ffn_bwd(activation, res, dy):
    x, w1, w2, h = res
    f, df = _act_fns(activation)
    a = f(h)  # recomputed; fuses into the reads below
    red = tuple(range(dy.ndim - 1))
    db2 = jnp.sum(dy, axis=red)
    # contract all leading axes: dW = a^T dy over flattened tokens
    d_model_in = x.shape[-1]
    d_ff = h.shape[-1]
    a2 = a.reshape(-1, d_ff)
    dy2 = dy.reshape(-1, dy.shape[-1])
    dw2 = jnp.dot(a2.T, dy2)
    da = jnp.dot(dy, w2.T)
    dh = (da * df(h)).astype(h.dtype)
    db1 = jnp.sum(dh, axis=red)
    x2 = x.reshape(-1, d_model_in)
    dh2 = dh.reshape(-1, d_ff)
    dw1 = jnp.dot(x2.T, dh2)
    dx = jnp.dot(dh, w1.T)
    return dx, dw1.astype(w1.dtype), db1, dw2.astype(w2.dtype), db2


_fused_ffn_diff.defvjp(_ffn_fwd, _ffn_bwd)


def fused_ffn(x, w1, b1, w2, b2, activation="gelu"):
    """y = act(x @ w1 + b1) @ w2 + b2 as ONE differentiable op whose backward
    recomputes the activation instead of saving it (module docstring).

    x: (..., d_model); w1: (d_model, d_ff); w2: (d_ff, d_model);
    activation: gelu | gelu_tanh | relu.
    """
    def prim(xv, w1v, b1v, w2v, b2v):
        return _fused_ffn_diff(xv, w1v, b1v, w2v, b2v, activation)

    return apply(prim, x, w1, b1, w2, b2, name="fused_ffn")
