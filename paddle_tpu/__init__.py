"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas.

Architecture (vs the reference at /root/reference, see SURVEY.md):
  - eager Tensor + tape autograd over jax.vjp (≈ imperative/ dygraph engine)
  - `jit.to_static` functionalizes state and lowers whole train steps to
    cached XLA computations (≈ ProgramDesc + executors, but compiled)
  - distribution = jax.sharding Mesh + collectives (≈ fleet + NCCL rings)
"""
from __future__ import annotations

import time as _time

_import_start = _time.perf_counter()   # `runtime.import`, closed at the last line

__version__ = "0.1.0"

import os as _os

# Persistent XLA compilation cache. One rule places it everywhere (tests,
# bench, chip_smoke): JAX_COMPILATION_CACHE_DIR where the environment sets it
# (jax reads that variable itself, so no path is set in code), otherwise
# <checkout>/.jax_cache — a fixed path, because the path is part of the key.
# Eager dispatch compiles one executable per (op, shape), so cold start is
# dominated by those compiles; whole-program to_static compiles are cached too.
import jax as _jax

_env_cache_dir = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
_cache_dir = _env_cache_dir or _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")
try:
    _os.makedirs(_cache_dir, exist_ok=True)
    if not _env_cache_dir:
        _jax.config.update("jax_compilation_cache_dir", _cache_dir)
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
except OSError as _e:
    if _env_cache_dir:
        raise RuntimeError(
            f"JAX_COMPILATION_CACHE_DIR={_env_cache_dir!r} cannot be used as "
            f"the compilation cache: {_e}") from _e
    import warnings as _warnings
    _warnings.warn(f"paddle_tpu: no persistent compilation cache — "
                   f"{_cache_dir!r} cannot be created: {_e}")

# every compile request of the process from here on, by set-up phase: the
# constructors' compiles come before any span
from .profiler import compile_events as _compile_events
_compile_events.listen()

from .core import autograd as _autograd_mod  # noqa: F401
from .core.autograd import enable_grad, no_grad, set_grad_enabled  # noqa: F401
from .core.device import (  # noqa: F401
    CPUPlace, CUDAPlace, Place, TPUPlace, get_device, set_device,
    is_compiled_with_cuda, is_compiled_with_tpu,
)
from .core.dtypes import (  # noqa: F401
    bfloat16, complex64, complex128, float16, float32, float64,
    get_default_dtype, int8, int16, int32, int64, set_default_dtype, uint8,
)
from .core.dtypes import bool_ as bool  # noqa: F401,A001
from .core.random import get_state as get_cuda_rng_state  # noqa: F401
from .core.random import seed  # noqa: F401
from .core.selected_rows import SelectedRows  # noqa: F401
from .core.tensor import Parameter, Tensor, to_tensor  # noqa: F401

# functional tensor API (also patches Tensor methods)
from .tensor import *  # noqa: F401,F403
from .tensor import math as _tensor_math  # noqa: F401

from . import nn  # noqa: F401,E402
from . import optimizer  # noqa: F401,E402
from . import autograd  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import static  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from . import fft  # noqa: F401,E402
from . import signal  # noqa: F401,E402
from . import text  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
# paddle.DataParallel is a top-level name in the reference
# (fluid/dygraph/parallel.py re-export)
from .distributed.parallel import DataParallel  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import serving  # noqa: F401,E402
from . import cost_model  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import callbacks  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import slim  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from .utils import flops  # noqa: F401,E402
from .framework import io_utils as _io_utils  # noqa: F401,E402
from .framework.io_utils import load, save  # noqa: F401,E402


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """paddle.grad parity (python/paddle/autograd/backward_mode.py)."""
    from .core.autograd import grad_for_tensors
    outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    gouts = grad_outputs if grad_outputs is None or isinstance(grad_outputs, (list, tuple)) else [grad_outputs]
    # NB: builtin bool is shadowed at module level by the dtype export
    retain = (True if retain_graph else False) if retain_graph is not None \
        else create_graph
    return grad_for_tensors(outs, ins, gouts, retain_graph=retain,
                            allow_unused=allow_unused)


def disable_static(place=None):
    """Return to dygraph (the default mode)."""
    from . import static as static_mod
    static_mod._disable()
    return None


def enable_static():
    from . import static as static_mod
    static_mod._enable()


def in_dynamic_mode():
    from . import static as static_mod
    return not static_mod._static_mode[0]


def is_grad_enabled():
    from .core.autograd import is_grad_enabled as _ig
    return _ig()


def set_printoptions(**kwargs):
    import numpy as np
    np.set_printoptions(**{k: v for k, v in kwargs.items()
                           if k in ("precision", "threshold", "edgeitems", "linewidth")})


def get_flags(flags=None):
    from .framework.flags import get_flags as _gf
    return _gf(flags)


def set_flags(flags):
    from .framework.flags import set_flags as _sf
    return _sf(flags)


def Model(network, inputs=None, labels=None):
    """paddle.Model parity (hapi/model.py:906)."""
    from .hapi.model import Model as _Model
    return _Model(network, inputs, labels)


def summary(net, input_size=None, dtypes=None, input=None):  # noqa: A002
    from .hapi.model_summary import summary as _summary
    return _summary(net, input_size, dtypes=dtypes, input=input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.dynamic_flops import flops as _flops
    return _flops(net, input_size, custom_ops=custom_ops, print_detail=print_detail)


# -- remaining top-level reference names (python/paddle/__init__.py __all__) --
from .framework.param_attr import ParamAttr  # noqa: E402,F401
from .nn.functional.activation import tanh_  # noqa: E402,F401
import numpy as _np  # noqa: E402
dtype = _np.dtype  # paddle.dtype: the type of dtype objects (VarType parity)
from .core.device import CPUPlace as CUDAPinnedPlace  # noqa: E402,F401
from .core.device import TPUPlace as NPUPlace  # noqa: E402,F401


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    """paddle.create_parameter parity (fluid/layers/tensor.py)."""
    from .core.dtypes import convert_dtype
    from .framework.param_attr import ParamAttr
    from .nn import initializer as I
    attr = ParamAttr._to_attr(attr)
    if attr is False:
        return None
    init = attr.initializer or default_initializer
    if init is None:
        init = I.Constant(0.0) if is_bias else I.XavierNormal()
    # parameters materialize eagerly even under enable_static(): they are
    # startup-program state, not main-program ops (fluid runs initializers
    # in the startup program)
    from .core import dispatch as _dispatch
    b = _dispatch.get_static_builder()
    _dispatch.set_static_builder(None)
    try:
        value = init(list(shape), convert_dtype(dtype))
    finally:
        _dispatch.set_static_builder(b)
    prm = Parameter(value, name=name or attr.name, trainable=attr.trainable)
    return prm


def tolist(x):
    return x.tolist()


def batch(reader, batch_size, drop_last=False):
    """Deprecated fluid-style batch reader decorator (fluid/io.py batch)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def set_cuda_rng_state(state):
    """Reference set_cuda_rng_state — maps onto the single RNG state."""
    from .core import random as _random
    _random.set_state(state)


def disable_signal_handler():
    """Reference disables its C++ fatal-signal dumper; no native signal
    handlers are installed here, so this is a documented no-op."""
    return None


def check_shape(shape):
    """Static shape validity check (framework utils parity)."""
    if isinstance(shape, Tensor):
        return
    for d in list(shape):
        if not isinstance(d, int) and not hasattr(d, "shape"):
            raise TypeError(f"invalid dim {d!r} in shape {shape!r}")


_compile_events.record_import(_import_start, _time.perf_counter())
