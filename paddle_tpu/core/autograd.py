"""Tape-based autograd engine.

Reference parity: paddle/fluid/imperative/basic_engine.cc (BasicEngine::Execute,
queue-driven topological traversal with dependency counting) and
gradient_accumulator.cc. TPU-native redesign: instead of per-op grad kernels,
each forward op records a `jax.vjp` closure (the VJP holds XLA residuals); the
backward pass is the same dep-counted queue walk, but every VJP call is itself a
traceable JAX computation, so the whole backward fuses into one XLA program
under `to_static`/jit.
"""
from __future__ import annotations

import contextlib
from collections import defaultdict, deque

import jax.numpy as jnp

from ..profiler.compile_events import OP_TIMER as _OP_TIMER

__all__ = [
    "GradNode",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "set_grad_enabled",
    "backward",
    "grad_for_tensors",
]

_grad_enabled = [True]


def is_grad_enabled() -> bool:
    return _grad_enabled[0]


def set_grad_enabled(mode: bool):
    _grad_enabled[0] = bool(mode)


class _GradGuard(contextlib.ContextDecorator):
    def __init__(self, mode):
        self._mode = mode
        self._prev = None

    def __enter__(self):
        self._prev = _grad_enabled[0]
        _grad_enabled[0] = self._mode
        return self

    def __exit__(self, *exc):
        _grad_enabled[0] = self._prev
        return False


def no_grad():
    """paddle.no_grad parity — usable as context manager or decorator."""
    return _GradGuard(False)


def enable_grad():
    return _GradGuard(True)


class GradNode:
    """One recorded op on the tape.

    vjp_fn: callable(cotangents_matching_forward_output) -> tuple of input grads
    inputs: the differentiable input Tensors, in vjp order
    out_meta: list of (shape, dtype) per output slot (for zero cotangents)
    multi_output: whether forward returned a tuple (vjp cotangent structure)
    """

    __slots__ = ("vjp_fn", "inputs", "out_meta", "multi_output", "name",
                 "input_versions")

    def __init__(self, vjp_fn, inputs, out_meta, multi_output, name):
        self.vjp_fn = vjp_fn
        self.inputs = inputs
        self.out_meta = out_meta
        self.multi_output = multi_output
        # snapshot of each input's in-place version (tensor_version check
        # parity: backward must fail loudly if an input was later mutated
        # in place, instead of silently differentiating the wrong graph)
        self.input_versions = [getattr(t, "_version", 0) for t in inputs]
        self.name = name

    def release(self):
        self.vjp_fn = None
        self.inputs = ()


def _reachable_nodes(root_nodes):
    seen = set()
    order = []
    stack = list(root_nodes)
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        order.append(node)
        for t in node.inputs:
            nxt = t._grad_node
            if nxt is not None and id(nxt) not in seen:
                stack.append(nxt)
    return order


# Incremented on every LEAF-ACCUMULATING engine run (Tensor.backward) — not
# paddle.grad, whose gradient computation is part of a forward (WGAN-GP
# pattern). to_static discovery reads it to learn whether a traced function
# performs its own optimizer-style backward (train-step pattern), in which
# case outer gradient flow through the compiled program is skipped.
backward_run_counter = [0]

# Fired after a leaf-accumulating backward completes (the seam the reference
# uses for Reducer::FinalizeBackward — flush incomplete DP buckets, reconcile
# late grad contributions). Callbacks take no args; DataParallel's Reducer
# registers here so the standard backward/step/clear_grad loop stays in sync
# without an explicit apply_collective_grads() call.
post_backward_callbacks = []


def backward(tensors, grad_tensors=None, retain_graph=False,
             accumulate_leaves=True):
    """Run reverse accumulation from `tensors`, writing into leaf `.grad`.

    Mirrors BasicEngine: PrepareDeps (consumer counting) then queue-driven
    execution; gradient accumulation is plain `+` on jax arrays.
    accumulate_leaves=False (paddle.grad path) touches only tensors with a
    _grad_capture hook, leaving other leaves' .grad untouched.
    """
    from .tensor import Tensor  # local import to avoid cycle

    if accumulate_leaves:
        backward_run_counter[0] += 1

    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)

    # Seed cotangents keyed by (node id, output slot); leaves seed .grad directly.
    pending = defaultdict(dict)  # id(node) -> {slot: cotangent array}
    node_by_id = {}
    roots = []
    for t, g in zip(tensors, grad_tensors):
        if g is None:
            seed = jnp.ones(t.shape, dtype=t._value.dtype)
        else:
            seed = g._value if isinstance(g, Tensor) else jnp.asarray(g)
        node = t._grad_node
        if node is None:
            if not t.stop_gradient and (accumulate_leaves
                                        or t._grad_capture is not None):
                t._accumulate_grad(seed)
            continue
        if node.vjp_fn is None:
            raise RuntimeError(
                "trying to backward through a released graph; pass "
                "retain_graph=True to backward() to keep it"
            )
        node_by_id[id(node)] = node
        slot = t._out_index
        cur = pending[id(node)].get(slot)
        pending[id(node)][slot] = seed if cur is None else cur + seed
        roots.append(node)

    nodes = _reachable_nodes(roots)
    for n in nodes:
        node_by_id[id(n)] = n
    # consumer edge count: how many reachable consumers feed cotangents into node
    deps = defaultdict(int)
    for n in nodes:
        for t in n.inputs:
            if t._grad_node is not None:
                deps[id(t._grad_node)] += 1

    ready = deque(n for n in nodes if deps[id(n)] == 0)
    executed = set()
    while ready:
        node = ready.popleft()
        if id(node) in executed:
            continue
        executed.add(id(node))
        slots = pending.pop(id(node), {})
        cots = []
        for i, meta in enumerate(node.out_meta):
            if meta is None:
                # None output slot (empty pytree leaf): its cotangent
                # is None to match the forward's output structure
                cots.append(None)
                continue
            shape, dtype = meta
            c = slots.get(i)
            cots.append(c if c is not None else jnp.zeros(shape, dtype=dtype))
        cot = tuple(cots) if node.multi_output else cots[0]
        for t, ver in zip(node.inputs, node.input_versions):
            if getattr(t, "_version", 0) != ver:
                raise RuntimeError(
                    f"tensor used by operator '{node.name}' was modified by "
                    f"an in-place operation before backward ran (version "
                    f"{getattr(t, '_version', 0)} != {ver}); clone() the "
                    f"tensor before the in-place op")
        if _OP_TIMER[0] is None:
            in_grads = node.vjp_fn(cot)
        else:   # a to_static discovery pass is open
            in_grads = _OP_TIMER[0](f"grad({node.name})", node.vjp_fn, cot)
        for t, g in zip(node.inputs, in_grads):
            nxt = t._grad_node
            if nxt is not None:
                # decrement regardless of g: a None grad must not stall the
                # producer subgraph (its cotangent just stays zero)
                if g is not None:
                    cur = pending[id(nxt)].get(t._out_index)
                    pending[id(nxt)][t._out_index] = (
                        g if cur is None else cur + g)
                deps[id(nxt)] -= 1
                if deps[id(nxt)] == 0:
                    ready.append(nxt)
            if g is None:
                continue
            if t._grad_capture is not None:
                from .selected_rows import SelectedRows
                if isinstance(g, SelectedRows):
                    g = g.to_dense()  # capture (paddle.grad) is dense-typed
                t._grad_capture(g)
            elif nxt is None and not t.stop_gradient and accumulate_leaves:
                t._accumulate_grad(g)
        if not retain_graph:
            node.release()

    if accumulate_leaves:
        for cb in list(post_backward_callbacks):
            cb()


def grad_for_tensors(outputs, inputs, grad_outputs=None, retain_graph=False,
                     allow_unused=False):
    """Functional gradient (paddle.grad parity, autograd/backward_mode.py).

    Returns grads for `inputs` without mutating their .grad.
    """
    from .tensor import Tensor

    outputs = list(outputs)
    inputs = list(inputs)
    # Redirect gradient flow at `inputs` into a side table via per-tensor
    # capture hooks; backward() calls the hook instead of touching .grad.
    capture = {}

    def make_hook(t):
        def hook(g):
            cur = capture.get(id(t))
            capture[id(t)] = g if cur is None else cur + g
        return hook

    hooks = []
    for t in inputs:
        hooks.append((t, t._grad_capture))
        t._grad_capture = make_hook(t)
    try:
        backward(outputs, grad_outputs, retain_graph=retain_graph,
                 accumulate_leaves=False)
    finally:
        for t, prev in hooks:
            t._grad_capture = prev
    results = []
    for t in inputs:
        g = capture.get(id(t))
        if g is None and not allow_unused:
            g = jnp.zeros(t.shape, dtype=t._value.dtype)
        results.append(Tensor(g, stop_gradient=True) if g is not None else None)
    return results
