"""Eager Tensor.

Reference parity: paddle/fluid/imperative/layer.h (VarBase = value + grad +
hooks) and python/paddle/fluid/dygraph/varbase_patch_methods.py. TPU-native
redesign: the value is a jax.Array (PJRT buffer on TPU); eager ops run through
JAX's eager dispatch; the tape is attached here (`_grad_node`); mutation of
`_value` is hooked so the `to_static` functionalizer can treat any Tensor
(parameters, optimizer moments, RNG keys) as traced state.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import autograd
from .dtypes import convert_dtype, get_default_dtype, narrow_host_array

__all__ = ["Tensor", "Parameter", "to_tensor"]


class _TraceHooks:
    """Module-level hooks installed by the jit/to_static functionalizer."""

    on_read = None    # fn(tensor) — called when ._value is read
    on_write = None   # fn(tensor, new_value) — called BEFORE ._value assign
    on_create = None  # fn(tensor) — called from Tensor.__init__


class Tensor:
    # True on static-graph Variables: they are always written inside a traced
    # region before being read, so to_static discovery must NOT treat them as
    # captured state (their placeholder value is not a valid jit input)
    _trace_transparent = False

    __slots__ = (
        "_val",
        "grad",
        "stop_gradient",
        "_grad_node",
        "_out_index",
        "_grad_capture",
        "name",
        "persistable",
        "trainable",
        "_hooks",
        "dist_attr",   # auto_parallel annotation (DistAttr), set lazily
        "_version",    # in-place mutation counter (tensor_version parity)
        "_degen_cache",  # fused-op degenerate-weight check memo
                         # (ops/fused_conv_bn.py, ops/fused_residual_ln.py)
        "_donate_unsafe",  # the donation taint: True from any write of _val
                           # from outside a compiled launch until a launch
                           # writes its own output back. The value may then
                           # be a numpy buffer that PJRT's CPU client imported
                           # without taking ownership (donating that corrupts
                           # memory), or be held by something else as well (a
                           # tensor built from this one, the caller's array:
                           # donating deletes it under them). What the gate
                           # does with it: jit/to_static.py::_donation_gate.
        "__weakref__",
    )

    def __init__(self, value, dtype=None, place=None, stop_gradient=True,
                 name=None):
        tainted = False
        if isinstance(value, Tensor):
            tainted = True   # two holders of one array from here on
            value = value._val
        dtype = convert_dtype(dtype)
        if not isinstance(value, jax.Array):
            tainted = True
            arr = np.asarray(value)
            if dtype is None and arr.dtype == np.float64:
                dtype = get_default_dtype()
            # x64 policy: 64-bit int host data destined for integer storage
            # narrows to 32-bit with a range check instead of jax's
            # truncate-and-warn (dtypes.py); an explicit float dtype request
            # keeps the plain cast (the int32 range is irrelevant there)
            if dtype is None or dtype.kind in "iu":
                arr = narrow_host_array(arr)
            value = jnp.asarray(arr, dtype=dtype)
        elif dtype is not None and value.dtype != dtype:
            value = value.astype(dtype)
        if place is not None:
            value = jax.device_put(value, place.jax_device)
        self._val = value
        self.grad = None
        self.stop_gradient = stop_gradient
        self._grad_node = None
        self._out_index = 0
        self._grad_capture = None
        self.name = name
        self.persistable = False
        self.trainable = True
        self._hooks = None
        self._version = 0
        self._donate_unsafe = tainted
        if _TraceHooks.on_create is not None:
            _TraceHooks.on_create(self)

    # -- value access (hooked for trace capture) --------------------------------
    @property
    def _value(self):
        if _TraceHooks.on_read is not None:
            _TraceHooks.on_read(self)
        return self._val

    @_value.setter
    def _value(self, v):   # write-seam: THE taint source — fires on_write, sets _donate_unsafe
        # hook fires BEFORE the write so tracers can snapshot the old value;
        # the new value is passed so the static builder can record the
        # assignment as a replayable node
        if _TraceHooks.on_write is not None:
            _TraceHooks.on_write(self, v)
        self._val = v
        # conservative donation taint: an assigned array may be host-imported
        # (set_state_dict restore, checkpoint load, setitem) or still be held
        # by whoever assigned it. The compiled fast path clears this when it
        # writes back its own XLA-owned outputs (to_static.py _run); until
        # then its gate donates the value only where it can see that neither
        # holds, and a copy otherwise.
        self._donate_unsafe = True

    @property
    def value(self):
        return self._value

    # -- metadata ---------------------------------------------------------------
    @property
    def shape(self):
        return list(self._val.shape)

    @property
    def dtype(self):
        return np.dtype(self._val.dtype)

    @property
    def ndim(self):
        return self._val.ndim

    @property
    def size(self):
        return int(np.prod(self._val.shape)) if self._val.shape else 1

    @property
    def place(self):
        from .device import CPUPlace, TPUPlace
        try:
            dev = list(self._val.devices())[0]
        except Exception:
            return CPUPlace(0)
        if dev.platform == "cpu":
            return CPUPlace(dev.id)
        return TPUPlace(dev.id)

    @property
    def is_leaf(self):
        return self._grad_node is None

    # -- conversion -------------------------------------------------------------
    def numpy(self):
        return np.asarray(self._value)

    def item(self):
        return self._value.item()

    def tolist(self):
        return np.asarray(self._value).tolist()

    def astype(self, dtype):
        from ..tensor.manipulation import cast
        return cast(self, dtype)

    def cast(self, dtype):
        return self.astype(dtype)

    def detach(self):
        t = Tensor(self._val, stop_gradient=True)
        return t

    def clone(self):
        from .dispatch import apply
        return apply(lambda x: x + 0, self, name="clone")

    def cpu(self):
        from .device import CPUPlace
        return Tensor(jax.device_put(self._val, CPUPlace(0).jax_device),
                      stop_gradient=self.stop_gradient)

    def tpu(self, device_id=0):
        from .device import TPUPlace
        return Tensor(jax.device_put(self._val, TPUPlace(device_id).jax_device),
                      stop_gradient=self.stop_gradient)

    cuda = tpu  # reference-API shim

    def pin_memory(self):
        return self

    # -- autograd ---------------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        from .dispatch import get_static_builder
        b = get_static_builder()
        if b is not None:  # static-graph build: schedule, don't run
            b.record_backward(self, retain_graph=retain_graph)
            return
        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self, set_to_zero=False):
        from .selected_rows import SelectedRows
        if set_to_zero and self.grad is not None \
                and not isinstance(self.grad, SelectedRows):
            # zero in place (hooked write): keeps the grad object stable so
            # compiled programs can treat it as mutated state
            self.grad._value = jnp.zeros_like(self.grad._val)
        else:
            self.grad = None

    def _accumulate_grad(self, g):
        from .selected_rows import SelectedRows
        observed = self._grad_capture is not None or self._hooks
        if observed:
            # capture/hooks (paddle.grad, DataParallel) are dense-typed:
            # densify the incoming grad AND any stale sparse .grad, then
            # fall through to the normal path so they always fire
            if isinstance(g, SelectedRows):
                g = g.to_dense()
            if isinstance(self.grad, SelectedRows):
                self.grad = Tensor(self.grad.to_dense(), stop_gradient=True)
        elif isinstance(g, SelectedRows):
            # sparse (embedding) gradient — gradient_accumulator.cc
            # SelectedRows branch parity
            if self.grad is None:
                self.grad = g
            elif isinstance(self.grad, SelectedRows):
                self.grad = self.grad.add(g)
            else:
                self.grad._value = self.grad._value + g.to_dense()
            return
        elif isinstance(self.grad, SelectedRows):
            self.grad = Tensor(self.grad.to_dense() + g, stop_gradient=True)
            return
        if self._grad_capture is not None:
            self._grad_capture(g)
            return
        if self._hooks:
            for hook in self._hooks:
                out = hook(Tensor(g, stop_gradient=True))
                if out is not None:
                    g = out._val if isinstance(out, Tensor) else jnp.asarray(out)
        if self.grad is None:
            # create NEUTRAL (zeros) and land the first gradient via the
            # hooked write below: the tensor's creation value must mean
            # "no gradient yet" so trace/discovery rollback (to_static
            # batch-1 throwaway) restores an empty accumulator, not the
            # first gradient it happened to see
            self.grad = Tensor(jnp.zeros_like(g), stop_gradient=True)
        # accumulate IN PLACE on the existing grad tensor (hooked write):
        # gradient-merge/no-clear flows keep `.grad` alive across compiled
        # programs, so the object must stay stable for state capture
        self.grad._value = self.grad._value + g

    def register_hook(self, hook):
        """Gradient hook on a leaf (imperative/hooks.h parity)."""
        if self._hooks is None:
            self._hooks = []
        self._hooks.append(hook)
        idx = len(self._hooks) - 1

        class _Removable:
            def remove(_self):
                self._hooks[idx] = lambda g: None
        return _Removable()

    # -- in-place (optimizer/runtime use; not differentiated through) -----------
    def set_value(self, value):   # write-seam: routes through _value, invalidates _degen_cache
        if isinstance(value, Tensor):
            value = value._val
        value = jnp.asarray(value, dtype=self._val.dtype)
        if tuple(value.shape) != tuple(self._val.shape):
            from ..framework.errors import InvalidArgumentError
            raise InvalidArgumentError(
                f"set_value shape mismatch: {value.shape} vs {self._val.shape}")
        self._value = value
        # explicit re-initialization may move the value into/out of the
        # fused-op degenerate band (ops/_param_guard.py sticky cache)
        self._degen_cache = None

    def copy_(self, other, blocking=True):
        self.set_value(other)
        return self

    def _replace_value(self, v):   # write-seam: routes through _value, invalidates _degen_cache
        """Internal raw replacement (functional state update)."""
        self._value = v
        # the replacement may move the value into/out of the fused-op
        # degenerate band (ops/_param_guard.py sticky cache)
        self._degen_cache = None

    def scale_(self, factor):   # write-seam: in-place op, invalidates _degen_cache
        self._value = self._val * factor
        self._degen_cache = None  # may scale into the degenerate band
        return self

    def zero_(self):   # write-seam: in-place op, invalidates _degen_cache
        self._value = jnp.zeros_like(self._val)
        self._degen_cache = None  # zero-init recipes (ops/_param_guard.py)
        return self

    def fill_(self, v):   # write-seam: in-place op, invalidates _degen_cache
        self._value = jnp.full_like(self._val, v)
        self._degen_cache = None
        return self

    # -- python protocol --------------------------------------------------------
    def __len__(self):
        if not self._val.shape:
            raise TypeError("len() of a 0-d tensor")
        return self._val.shape[0]

    def __repr__(self):
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
            f"stop_gradient={self.stop_gradient},\n{np.asarray(self._val)!r})"
        )

    def __bool__(self):
        return bool(self._value)

    def __int__(self):
        return int(self._value)

    def __float__(self):
        return float(self._value)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __format__(self, spec):
        if self._val.ndim == 0:
            return format(self.item(), spec)
        return repr(self)

    # Arithmetic dunders are patched in paddle_tpu/tensor/__init__.py (the
    # reference monkey-patches VarBase the same way:
    # python/paddle/fluid/dygraph/math_op_patch.py).

    # jax interop: allow jnp.asarray(tensor)
    def __jax_array__(self):
        return self._value

    def __array__(self, dtype=None):
        a = np.asarray(self._val)
        return a.astype(dtype) if dtype is not None else a


class Parameter(Tensor):
    """Trainable leaf (python/paddle/fluid/framework.py Parameter parity)."""

    __slots__ = ("optimize_attr", "regularizer", "need_clip", "is_distributed",
                 "sharding_spec")

    def __init__(self, value, dtype=None, name=None, trainable=True):
        super().__init__(value, dtype=dtype, stop_gradient=not trainable,
                         name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False
        self.sharding_spec = None

    def __repr__(self):
        return "Parameter: " + super().__repr__()


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor parity (python/paddle/tensor/creation.py)."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)


# write-seam: in-place rebind routes through _value and invalidates
# _degen_cache after the tape surgery
def inplace_assign(x, out):
    """Shared implementation of paddle's `op_(x)` in-place family: rebind
    x's buffer to `out`'s AND transplant out's tape node so autograd flows
    through the in-place op (imperative inplace-version semantics). In-place
    on a leaf that requires grad is an error, as in the reference.

    Tape surgery: `out`'s GradNode holds x ITSELF as an input edge; after the
    rebind that edge must point at x's PRE-assign history, so the old
    (value, node, slot) triple moves to a snapshot tensor and the node's
    input list is rewired to it.
    """
    from . import autograd as _ag
    if (_ag.is_grad_enabled() and not x.stop_gradient
            and x._grad_node is None and x._val is not out._val):
        raise RuntimeError(
            "a leaf Tensor that requires grad is being used in an in-place "
            "operation; detach it or disable gradients first")
    node = out._grad_node
    if node is not None and getattr(node, "inputs", None):
        snap = Tensor(x._val, stop_gradient=x.stop_gradient)
        snap._grad_node = x._grad_node
        snap._out_index = x._out_index
        node.inputs = [snap if t is x else t for t in node.inputs]
        if hasattr(node, "input_versions"):
            node.input_versions = [getattr(t, "_version", 0)
                                   for t in node.inputs]
    # bump the version: any EARLIER op that captured x as a tape input will
    # refuse to backprop through the mutated value (tensor_version check)
    x._version += 1
    x._value = out._val
    x._degen_cache = None  # in-place op may enter the degenerate band
    x._grad_node = node
    x._out_index = getattr(out, "_out_index", None)
    x.stop_gradient = out.stop_gradient
    return x
