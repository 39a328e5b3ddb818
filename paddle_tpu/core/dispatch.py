"""Op dispatch: the seam between the paddle-style eager API and JAX/XLA.

Reference parity: paddle/fluid/imperative/tracer.cc TraceOp +
prepared_operator.cc kernel selection. TPU-native redesign: there is no kernel
registry keyed by (backend, dtype, layout) — XLA is the single backend; an "op"
is a pure function over jax.Arrays. `apply` runs it eagerly, and when autograd
is on it records a GradNode holding the `jax.vjp` closure (forward runs once;
residuals live in the closure). Under `to_static` tracing the same path runs on
tracers, so the whole tape lowers into one XLA computation.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from ..profiler import metrics as _metrics
from ..profiler.compile_events import OP_TIMER as _OP_TIMER
from . import autograd
from .autograd import GradNode
from .tensor import Tensor

__all__ = ["apply", "unwrap", "wrap"]


def unwrap(x):
    return x._value if isinstance(x, Tensor) else x


def _is_diff_value(v):
    return hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.inexact)


_DEBUG = {"check_nan_inf": False}

# Every op the tape dispatches, eager or under a trace: `dispatch.ops_total`
# in the metrics registry, which reads it when asked. A compiled step adds
# none, so what a window adds is what ran outside its programs. A plain
# integer: the add is all the hot path pays (threads may lose one).
OPS_DISPATCHED = [0]
_metrics.get_registry().register_counter_fn("dispatch.ops_total",
                                            lambda: OPS_DISPATCHED[0])
_NO_SCOPE = contextlib.nullcontext()

# Static-graph builder (paddle_tpu/static/graph.py). When set, apply() records
# ops into the current Program instead of executing (framework.py append_op
# parity); Tensor.backward and Optimizer.minimize also consult it.
_STATIC_BUILDER = [None]


def set_static_builder(builder):
    _STATIC_BUILDER[0] = builder


def get_static_builder():
    return _STATIC_BUILDER[0]


def set_debug(check_nan_inf):
    """Wire FLAGS_check_nan_inf (nan_inf_utils_detail.cc parity: scan outputs
    after every op)."""
    _DEBUG["check_nan_inf"] = bool(check_nan_inf)


def _check_finite(out, name):
    import jax.core as jax_core
    vals = out if isinstance(out, (tuple, list)) else (out,)
    for v in vals:
        if isinstance(v, jax_core.Tracer):
            continue
        if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.inexact):
            if not bool(jnp.all(jnp.isfinite(v))):
                raise FloatingPointError(
                    f"Operator '{name}' output contains NaN/Inf "
                    f"(FLAGS_check_nan_inf is enabled)")


def apply(prim, *args, name=None, **kwargs):
    """Run `prim(*raw_args, **kwargs)` with autograd recording.

    - args may mix Tensors and python values; kwargs are static.
    - prim must be a jax-traceable pure function returning an array or a
      tuple/list of arrays.
    - differentiable inputs = Tensor args with inexact dtype and
      stop_gradient=False (while grad mode enabled).
    """
    if _STATIC_BUILDER[0] is not None:
        return _STATIC_BUILDER[0].record(prim, args, kwargs, name)
    if _OP_TIMER[0] is not None:   # a to_static discovery pass is open
        return _OP_TIMER[0](name or getattr(prim, "__name__", "op"),
                            _apply_impl, prim, args, kwargs, name)
    return _apply_impl(prim, args, kwargs, name)


_AMP_MODULE = None


def _amp_module():
    """The amp.auto_cast MODULE (the package re-exports a same-named
    function, so a plain `from ..amp import auto_cast` grabs the function);
    imported lazily to avoid a core<->amp import cycle."""
    global _AMP_MODULE
    if _AMP_MODULE is None:
        import importlib
        _AMP_MODULE = importlib.import_module("paddle_tpu.amp.auto_cast")
    return _AMP_MODULE


def _amp_cast_prim(prim, target):
    """Fold AMP input casts INSIDE the differentiated function so jax.vjp
    routes cotangents back through the cast — grads for f32 params arrive in
    f32 even when the op computed in bf16 (imperative/amp_auto_cast.cc
    CastToFP16/NeedCast parity)."""
    import numpy as np

    target = np.dtype(target)

    def run(*vals, **kw):
        cast = [v.astype(target)
                if _is_diff_value(v) and v.dtype != target else v
                for v in vals]
        return prim(*cast, **kw)

    run.__name__ = getattr(prim, "__name__", "op")
    return run


def _apply_impl(prim, args, kwargs, name):
    OPS_DISPATCHED[0] += 1
    # the op's name on every instruction it stages (tracer.cc:150 RecordEvent
    # parity, kept in the HLO): a device trace then says which Paddle op a
    # fusion came from. Entered inside the differentiated function, so that
    # the backward keeps it too: `jvp(linear)`, `transpose(jvp(linear))`; a
    # scope round jax.vjp would name the forward only
    scope = _NO_SCOPE if name is None else jax.named_scope(name)
    # AMP O1/O2: white-list ops compute in the low dtype, black-list ops are
    # promoted to f32 (softmax/norm/loss numerics) — consulted per-op at this
    # single dispatch seam, the tracer.cc AmpOperators analog
    _amp = _amp_module()
    if _amp.is_enabled() and name is not None:
        if _amp.should_cast_to_low(name):
            prim = _amp_cast_prim(prim, _amp.amp_dtype())
        elif _amp.should_cast_to_high(name):
            from .dtypes import float32
            prim = _amp_cast_prim(prim, float32)
    raw = [unwrap(a) for a in args]
    record = autograd.is_grad_enabled()
    diff_idx = []
    if record:
        for i, a in enumerate(args):
            if (
                isinstance(a, Tensor)
                and not a.stop_gradient
                and _is_diff_value(raw[i])
            ):
                diff_idx.append(i)

    if not diff_idx:
        with scope:
            out = prim(*raw, **kwargs)
        if _DEBUG["check_nan_inf"]:
            _check_finite(out, name or getattr(prim, "__name__", "op"))
        return _wrap_outputs(out, stop_gradient=True)

    def closed(*diff_vals):
        vals = list(raw)
        for i, dv in zip(diff_idx, diff_vals):
            vals[i] = dv
        with scope:
            r = prim(*vals, **kwargs)
        # normalize list->tuple so the vjp cotangent structure is always tuple
        return tuple(r) if isinstance(r, list) else r

    out, vjp_fn = jax.vjp(closed, *[raw[i] for i in diff_idx])
    if _DEBUG["check_nan_inf"]:
        _check_finite(out, name or getattr(prim, "__name__", "op"))
    multi = isinstance(out, (tuple, list))
    outs = list(out) if multi else [out]
    # integer/bool outputs terminate gradient flow (comparisons, argmax...):
    # no node to record
    if not any(_is_diff_value(o) for o in outs):
        return _wrap_outputs(out, stop_gradient=True)
    # None outputs (jax treats None as an empty pytree subtree — e.g. a
    # block under recompute that returns (stream, None)) pass through: no
    # meta, no Tensor, None cotangent slot
    out_meta = [None if o is None else (o.shape, o.dtype) for o in outs]
    node = GradNode(
        vjp_fn=vjp_fn,
        inputs=[args[i] for i in diff_idx],
        out_meta=out_meta,
        multi_output=multi,
        name=name or getattr(prim, "__name__", "op"),
    )
    tensors = []
    for slot, o in enumerate(outs):
        if o is None:
            tensors.append(None)
            continue
        t = Tensor(o, stop_gradient=False)
        t._grad_node = node
        t._out_index = slot
        tensors.append(t)
    if multi:
        return tuple(tensors)
    return tensors[0]


def _wrap_outputs(out, stop_gradient):
    if isinstance(out, (tuple, list)):
        return tuple(None if o is None
                     else Tensor(o, stop_gradient=stop_gradient)
                     for o in out)
    if out is None:
        return None
    return Tensor(out, stop_gradient=stop_gradient)


def wrap(value, stop_gradient=True):
    return Tensor(value, stop_gradient=stop_gradient)
