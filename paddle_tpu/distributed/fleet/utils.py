"""fleet.utils (fleet/utils/recompute.py:182 parity).

TPU-native: recompute = rematerialization of the layer function — the
forward keeps the region's inputs only and the backward re-executes it,
trading FLOPs for HBM exactly like the reference's PyLayer-based rerun. The
state the region reads without differentiating it (the RNG key, running
statistics, a frozen parameter) enters as inputs too, so the backward's rerun
sees the forward's values: the same dropout mask, whatever drew random
numbers in between (preserve_rng_state parity). Written as a
`jax.custom_vjp` whose backward differentiates the region again behind an
optimization barrier (what `jax.checkpoint` does to keep XLA from merging the
two forwards) rather than as `jax.checkpoint` itself, which puts
`checkpoint/` in front of every instruction's `op_name`: the device trace
then attributes a rematerialised block to the ops inside it, forward,
recomputation and backward alike (docs/observability.md).

Closure parameters (layer weights referenced inside `function`) are
discovered with an abstract trace (jax.eval_shape + read hooks — no FLOPs)
and passed to the checkpointed region as explicit differentiable inputs, so
their gradients flow exactly as in the plain forward; the same trace finds
the state it only reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import apply, unwrap
from ...core.tensor import Tensor, _TraceHooks

__all__ = ["recompute"]


# write-seam: discovery snapshot/restore of _val around the probe trace
def recompute(function, *args, **kwargs):
    kwargs.pop("preserve_rng_state", True)
    tensor_args = [a for a in args if isinstance(a, Tensor)]
    other = [(i, a) for i, a in enumerate(args) if not isinstance(a, Tensor)]
    seen = {id(t) for t in tensor_args}

    def rebuild(vals):
        rebuilt = []
        vi = 0
        oi = 0
        for i in range(len(args)):
            if oi < len(other) and other[oi][0] == i:
                rebuilt.append(other[oi][1])
                oi += 1
            else:
                t = Tensor(vals[vi], stop_gradient=False)
                vi += 1
                # rebuilt arg tensors are per-call wrappers, not closure
                # state — never admit them into closure_reads (they hold
                # trace-local tracers)
                seen.add(id(t))
                rebuilt.append(t)
        return rebuilt

    # -- discovery: which closure tensors does `function` read? -------------
    # closure_reads are differentiated; state_reads (the generator's key,
    # buffers, frozen parameters) only ride along, so that the backward's
    # rerun reads what the forward read
    closure_reads, state_reads = [], []

    def on_read(t):
        if id(t) in seen or t._trace_transparent:
            return
        seen.add(id(t))
        if not t.stop_gradient and jnp.issubdtype(t._val.dtype, jnp.inexact):
            closure_reads.append(t)
        elif isinstance(t._val, jax.Array):
            state_reads.append(t)

    # abstract-trace writes (RNG splits, BN stats) must not leak tracers
    # into real state: snapshot old values and restore after discovery
    written = {}

    def on_write(t, new_value=None):
        if id(t) not in written:
            written[id(t)] = (t, t._val)

    from ...core import autograd as _autograd
    prev = (_TraceHooks.on_read, _TraceHooks.on_write, _TraceHooks.on_create)
    _TraceHooks.on_read = on_read
    _TraceHooks.on_write = on_write
    # a tensor the body makes is no state of the closure
    _TraceHooks.on_create = lambda t: seen.add(id(t))
    try:
        with _autograd.no_grad():
            jax.eval_shape(
                lambda *vals: jax.tree_util.tree_map(
                    unwrap, function(*rebuild(vals), **kwargs)),
                *[jax.ShapeDtypeStruct(t._val.shape, t._val.dtype)
                  for t in tensor_args])
    finally:
        (_TraceHooks.on_read, _TraceHooks.on_write,
         _TraceHooks.on_create) = prev
        for t, old in written.values():
            t._val = old

    n_args = len(tensor_args)
    bound = closure_reads + state_reads

    # traced-fn: checkpointed region body; write-seam: tracer rebind + restore
    def pure(*vals):
        saved = [(t, t._val) for t in bound]
        # writes during the traced run (BN running stats, RNG keys) would
        # store tracers into real state — snapshot and restore them, same as
        # the discovery pass. State updates inside a recompute block are
        # therefore dropped (functional purity; the checkpointed region may
        # re-execute in backward, so double-updates would be wrong anyway).
        written = {}
        prev_write = _TraceHooks.on_write

        def on_write(t, new_value=None):
            if id(t) not in written:
                written[id(t)] = (t, t._val)
            if prev_write is not None:
                prev_write(t, new_value)

        _TraceHooks.on_write = on_write
        try:
            for t, v in zip(bound, vals[n_args:]):
                t._val = v
            # no_grad: inner per-op GradNodes are useless here (the outer
            # apply() differentiates the whole checkpointed region), and an
            # inner eager jax.vjp would UNWRAP custom_vjp ops (e.g. Pallas
            # flash attention) into raw pallas_calls that jax.checkpoint's
            # linearization cannot jvp — with the custom_vjp primitive kept
            # intact, remat uses its rule as designed
            with _autograd.no_grad():
                out = function(*rebuild(vals[:n_args]), **kwargs)
            # tuple-returning blocks unwrap leaf-wise; apply() handles
            # pytree outputs, a None leaf among them
            return jax.tree_util.tree_map(unwrap, out)
        finally:
            _TraceHooks.on_write = prev_write
            for t, old in written.values():
                t._val = old
            for t, v in saved:
                t._val = v

    return apply(_rematerialised(pure, n_args + len(closure_reads)),
                 *tensor_args, *bound)


def _rematerialised(pure, n_diff):
    """`pure` with a backward that runs it again: nothing but its inputs
    crosses from the forward pass to the backward pass. The first `n_diff`
    inputs are differentiated; the rest are the state the region reads."""
    @jax.custom_vjp
    def region(*vals):
        return pure(*vals)

    def fwd(*vals):
        return pure(*vals), vals

    def bwd(vals, g):
        # the barrier ties the second forward to the cotangent's arrival, so
        # that XLA cannot serve it from the first one's intermediates
        vals, g = jax.lax.optimization_barrier((vals, g))
        state = vals[n_diff:]
        grads = jax.vjp(lambda *diff: pure(*diff, *state), *vals[:n_diff])[1](g)
        return (*grads, *(None for _ in state))

    region.defvjp(fwd, bwd)
    return region
