"""Fused residual-add -> LayerNorm — forward and hand-written backward.

Reference analog: operators/fused/fused_bias_dropout_residual_layer_norm_op.cu
and the fused_dropout_helper.h residual+LN epilogues of
operators/fused/fused_attention_op.cu. TPU-native design: XLA already fuses
the elementwise add into the norm reductions in the FORWARD; what it cannot
do is change the autodiff *memory plan* — per-op autodiff saves the summed
residual stream z = x + y across the fwd->bwd boundary for the LN backward.
This op never saves z:

    x_hat = (out - bias) / weight          (exact where |weight| > tol)
    dz    = rstd * (dx_hat - mean(dx_hat) - x_hat * mean(dx_hat * x_hat))

so its residuals are the LN OUTPUT (which the following matmul saves anyway
as ITS wgrad operand — no extra tensor crosses the boundary) plus the
per-row rstd scalars. In a pre-LN decoder the z_i chain is the residual
stream itself: every per-layer (b, s, h) z tensor disappears from the
backward plan (GPT-medium b4 s1024: ~8 MB x 2 x 24 layers).

Statistics are computed in float32 regardless of input dtype, and x_hat
reconstruction mirrors ops/fused_conv_bn.py: under the custom backward,
channels with |weight| <= tol contribute x_hat = 0 and would freeze. LN
weights initialize at 1.0 and stay O(1) in practice, but fused_residual_ln
guards the degenerate case the same way fused_conv_bn does: when the
weight is concretely inspectable (eager mode) and ANY channel sits in the
tol band, it routes through plain autodiff of the identical forward math
(z is then saved, dw stays exact). Under jit tracing the weight is
abstract and the custom path runs — compile zero-LN-scale recipes with
this in mind (both branches return identical shapes, so a recompute
discovery/trace disagreement cannot change program structure).

No model in the tree calls this op: in the GPT cell the plain composition
(`x + y`, then `nn.LayerNorm`) measured 0.45% more tokens a second and no
more memory than this backward (PERF.md, PR 30), so GPT and the
transformer layers take that. The op stays for callers of its own;
ROADMAP D4 has its deletion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.dispatch import apply

__all__ = ["fused_residual_ln"]

_W_TOL = 1e-6


def _stats(zf, eps):
    mean = jnp.mean(zf, axis=-1, keepdims=True)
    var = jnp.var(zf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return (zf - mean) * rstd, rstd


def _fwd_impl(x, y, w, b, eps, return_residual, stream_dtype):
    """The ONE forward (shared by the custom-vjp primal, its fwd rule, and
    the degenerate-weight fallback — the fused_conv_bn _fused_fwd_impl
    pattern, so the fallback's 'identical forward math' guarantee cannot
    drift). Returns (outputs, rstd)."""
    z = x + y
    xhat, rstd = _stats(z.astype(jnp.float32), eps)
    out = (xhat * w.astype(jnp.float32)
           + b.astype(jnp.float32)).astype(z.dtype)
    if return_residual:
        return (z.astype(stream_dtype or z.dtype), out), rstd
    return out, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_residual_ln_diff(x, y, w, b, eps, return_residual, stream_dtype):
    """stream_dtype: dtype of the returned residual stream z. Under AMP the
    op is black-listed (promoted to f32) like layer_norm — but only the
    NORM should promote; the carried residual stream must stay in the
    pre-promotion dtype, else every per-layer (b, s, h) stream tensor
    doubles its bytes on an HBM-bound lane (the unfused composition's
    residual add ran un-promoted)."""
    outs, _ = _fwd_impl(x, y, w, b, eps, return_residual, stream_dtype)
    return outs


def _fwd(x, y, w, b, eps, return_residual, stream_dtype):
    outs, rstd = _fwd_impl(x, y, w, b, eps, return_residual, stream_dtype)
    out = outs[1] if return_residual else outs
    return outs, (w, b, out, rstd)


def _bwd(eps, return_residual, stream_dtype, res, cts):
    w, b, out, rstd = res
    if return_residual:
        dz_in, dout = cts
    else:
        dz_in, dout = None, cts
    wf = w.astype(jnp.float32)
    live = jnp.abs(wf) > _W_TOL
    wdiv = jnp.where(live, wf, 1.0)
    xhat = jnp.where(live, (out.astype(jnp.float32)
                            - b.astype(jnp.float32)) / wdiv, 0.0)
    g = dout.astype(jnp.float32)
    dxhat = g * wf
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dz = rstd * (dxhat - m1 - xhat * m2)
    if dz_in is not None:
        dz = dz + dz_in.astype(jnp.float32)
    red = tuple(range(out.ndim - 1))
    dw = jnp.sum(g * xhat, axis=red).astype(w.dtype)
    db = jnp.sum(g, axis=red).astype(b.dtype)
    dz = dz.astype(out.dtype)
    return dz, dz, dw, db


_fused_residual_ln_diff.defvjp(_fwd, _bwd)


def _weight_degenerate(w):
    """Some channel inside the |w| <= tol band where the backward's x_hat
    reconstruction freezes it (shared guard: ops/_param_guard.py)."""
    from ._param_guard import degenerate_below_tol
    return degenerate_below_tol(w, _W_TOL)


def fused_residual_ln(x, y, weight, bias, epsilon=1e-5,
                      return_residual=False):
    """layer_norm(x + y) with the no-saved-z backward (module docstring).

    return_residual=True additionally returns z = x + y (the pre-LN
    decoder's carried residual stream): `z, out = fused_residual_ln(...)`.
    """
    from ..core.dispatch import unwrap

    # pre-promotion stream dtype, captured BEFORE the AMP seam casts the
    # op's inputs to f32 (see _fused_residual_ln_diff docstring)
    stream_dtype = getattr(unwrap(x), "dtype", None)

    def prim_plain(xv, yv, wv, bv):
        outs, _ = _fwd_impl(xv, yv, wv, bv, epsilon, return_residual,
                            stream_dtype)
        return outs

    def prim_fused(xv, yv, wv, bv):
        return _fused_residual_ln_diff(xv, yv, wv, bv, epsilon,
                                       return_residual, stream_dtype)

    # zero/near-zero LN weight channels: plain autodiff through the
    # IDENTICAL forward (saves z, keeps dw exact where the custom
    # backward's x_hat reconstruction would freeze it)
    prim = prim_plain if _weight_degenerate(weight) else prim_fused
    return apply(prim, x, y, weight, bias, name="fused_residual_ln")
