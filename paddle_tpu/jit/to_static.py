"""`to_static`: whole-program capture → cached XLA computation.

Reference parity: python/paddle/fluid/dygraph/dygraph_to_static/
(StaticFunction/ConcreteProgram/PartialProgramLayer — jit.py:161,
program_translator.py:234,590; partial_program.py:116). The reference
AST-rewrites python into a ProgramDesc and runs it as one fused `run_program`
op. TPU-native redesign: no AST surgery — the eager tape IS jax-traceable, so
we functionalize instead:

  phase A (discovery, first call per input signature): run the function
    eagerly with read/write hooks on Tensor._value installed — every Tensor
    read is a capture (parameters, optimizer moments, RNG key, lr, BN stats),
    every captured Tensor written is mutated state.
  phase B (compile): build pure_fn(mut_vals, ro_vals, arg_vals) ->
    (out_vals, new_state), jit it (donating mutated-state buffers when no
    gradient is recorded), cache by input signature.
  steady state: one compiled XLA executable per signature; python only
    shuttles buffers — the reference's per-op interpreter loop is gone (the
    TPU throughput seam named in SURVEY.md §2.8).

Gradient flows through a compiled forward like the reference's run_program
grad: the jitted function is recorded on the tape as a single op whose VJP is
jax's vjp of the whole program (also compiled).

Python control flow is evaluated at trace time (same static-unrolling
semantics as the reference's to_static for non-tensor conditions).
"""
from __future__ import annotations

import functools
import sys
import threading

import jax
import jax.numpy as jnp

from ..core import autograd
from ..core import dispatch as _dispatch
from ..core.autograd import GradNode
from ..core.dtypes import is_inexact
from ..core.tensor import Tensor, _TraceHooks
from ..profiler import metrics as _metrics
from ..profiler.compile_events import compile_span, setup_span, timed_ops

__all__ = ["to_static", "not_to_static", "TracedLayer", "InputSpec"]


class InputSpec:
    """paddle.static.InputSpec parity."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name


def _sig_of(value):
    if isinstance(value, Tensor):
        return ("T", tuple(value._val.shape), str(value._val.dtype))
    if isinstance(value, (list, tuple)):
        return (type(value).__name__, tuple(_sig_of(v) for v in value))
    if isinstance(value, dict):
        return ("dict", tuple(sorted((k, _sig_of(v)) for k, v in value.items())))
    return ("py", value if isinstance(value, (int, float, str, bool, type(None)))
            else str(type(value)))


def _sig_of_step(value):
    """Per-step signature of a run_steps argument: Tensor signatures drop
    the leading steps axis. Derived symbolically — actually slicing would
    dispatch device ops and pull data host-side on EVERY call just to
    compute a cache key."""
    if isinstance(value, Tensor):
        return ("T", tuple(value._val.shape[1:]), str(value._val.dtype))
    if isinstance(value, (list, tuple)):
        return (type(value).__name__, tuple(_sig_of_step(v) for v in value))
    if isinstance(value, dict):
        return ("dict", tuple(sorted(
            (k, _sig_of_step(v)) for k, v in value.items())))
    return ("py", value if isinstance(
        value, (int, float, str, bool, type(None)))
        else str(type(value)))


def _flatten_tensors(obj, out):
    if isinstance(obj, Tensor):
        out.append(obj)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            _flatten_tensors(v, out)
    elif isinstance(obj, dict):
        for k in sorted(obj):
            _flatten_tensors(obj[k], out)
    return out


_LEAF = object()


def _build_tree(obj):
    if isinstance(obj, Tensor):
        return (_LEAF, obj.stop_gradient)
    if isinstance(obj, (list, tuple)):
        return (type(obj), [_build_tree(v) for v in obj])
    if isinstance(obj, dict):
        return (dict, [(k, _build_tree(obj[k])) for k in sorted(obj)])
    return ("const", obj)


def _unflatten(tree, leaves):
    tag = tree[0]
    if tag is _LEAF:
        t = leaves.pop(0)
        return t
    if tag == "const":
        return tree[1]
    if tag is dict:
        return {k: _unflatten(sub, leaves) for k, sub in tree[1]}
    return tag(_unflatten(sub, leaves) for sub in tree[1])


class _DiscoveryCtx:
    """Installed during phase A: records reads (captures) and writes (state)."""

    def __init__(self, explicit_ids):
        self.explicit = set(explicit_ids)
        self.created_ids = set()
        self.captured = []
        self.captured_ids = set()
        self.mutated_ids = set()
        self.mutated = []

    def on_create(self, t):
        # tensors born inside the traced region are intermediates, not state
        self.created_ids.add(id(t))

    def on_read(self, t):
        if t._trace_transparent:
            return
        i = id(t)
        if i in self.explicit or i in self.created_ids or i in self.captured_ids:
            return
        self.captured_ids.add(i)
        self.captured.append(t)

    def on_write(self, t, new_value=None):
        if t._trace_transparent:
            return
        i = id(t)
        if i in self.explicit or i in self.created_ids or i in self.mutated_ids:
            return
        self.mutated_ids.add(i)
        self.mutated.append(t)
        # write-only state (e.g. BN running stats updated via ._val reads)
        # still needs an input slot + write-back: register as captured too
        if i not in self.captured_ids:
            self.captured_ids.add(i)
            self.captured.append(t)


# The dispatch decisions, counted in the metrics registry where each is taken
# (`to_static.<name>_total`); every `to_static.call` span carries the running
# values, so a trace gives counts per step as differences between two spans.
_RUNNING = ("launches", "undonated_launches", "grad_path_launches",
            "diverted_calls")


def _count(name, n=1):
    _metrics.get_registry().inc_counter(f"to_static.{name}_total", n)


def _running_counts():
    reg = _metrics.get_registry()
    counts = {name: int(reg.counter_value(f"to_static.{name}_total"))
              for name in _RUNNING}
    counts["dispatch_ops"] = _dispatch.OPS_DISPATCHED[0]
    return counts


class _Program:
    __slots__ = ("captured", "mutated", "ro", "jitted", "jitted_donate",
                 "out_tree", "n_outs", "stage", "internal_backward",
                 "pure_fn", "scanned", "scanned_donate", "scanned_ready",
                 "ran")

    def __init__(self):
        self.captured = []
        self.mutated = []
        self.ro = []
        self.jitted = None
        self.jitted_donate = None
        self.out_tree = None
        self.n_outs = 0
        self.stage = 0
        # the traced fn ran its own backward (train-step pattern): outputs
        # are post-update losses — outer grad flow would re-trace the whole
        # program per call for a gradient nobody consumes, so skip it
        self.internal_backward = False
        self.pure_fn = None
        # lax.scan-over-steps executables (run_steps), built lazily;
        # scanned_ready flips after the first traced execution completes
        self.scanned = None
        self.scanned_donate = None
        self.scanned_ready = False
        # which of "plain", "donating", "grad" have been launched: the first
        # launch of each traces and compiles (to_static.compile)
        self.ran = set()


# Discovery/trace phases mutate global state (_TraceHooks, and shared model
# variables temporarily hold tracers while jax traces the pure function), so
# compiles from concurrent threads (framework/trainer.py hogwild workers)
# must serialize — AND must not overlap compiled-path runs, which read the
# same shared variables. Reader/compiler coordination: compiled fast-path
# calls register as readers; a compile waits for in-flight readers to drain
# and readers arriving while a compile is pending divert into the compile
# lock. _compile_lock is an RLock so nested to_static calls inside a trace
# re-enter on the same thread.
_compile_lock = threading.RLock()
_state_lock = threading.Lock()
_state_cv = threading.Condition(_state_lock)
_readers = [0]
_compiling = [0]
_tl = threading.local()  # per-thread reader count (nested-call re-entrancy)


def _enter_fast_path():
    """Register as a compiled-path reader; False if a compile is pending
    (caller must take the slow path)."""
    with _state_lock:
        if _compiling[0]:
            return False
        _readers[0] += 1
        _tl.readers = getattr(_tl, "readers", 0) + 1
        return True


def _exit_fast_path():
    with _state_cv:
        _readers[0] -= 1
        _tl.readers = getattr(_tl, "readers", 0) - 1
        # notify unconditionally: a _compile_guard waiter excludes its own
        # registrations, so it may become runnable before the count hits 0
        _state_cv.notify_all()


class _compile_guard:
    """Hold the compile lock and wait out in-flight compiled runs.

    A thread may reach here while itself registered as a fast-path reader
    (a compiled program whose re-trace runs a nested, not-yet-compiled
    to_static function) — waiting for its OWN reader registration to drain
    would self-deadlock, so the wait only covers OTHER threads' readers.
    """

    def __enter__(self):
        _compile_lock.acquire()
        with _state_cv:
            _compiling[0] += 1
            own = getattr(_tl, "readers", 0)
            while _readers[0] - own > 0:
                _state_cv.wait()
        return self

    def __exit__(self, *exc):
        with _state_lock:
            _compiling[0] -= 1
        _compile_lock.release()
        return False

# Donating state buffers (FLAGS_donate_state_buffers) is unsafe when several
# threads drive the SAME compiled program over shared state: each launch
# donates the buffer every other in-flight launch still holds as input.
# Hogwild trainers pause donation for their threaded phase.
_donation_paused = [0]


class pause_donation:
    """Context manager: compiled programs run their non-donating executables
    while active (framework/trainer.py multi-worker phase)."""

    def __enter__(self):
        _donation_paused[0] += 1
        return self

    def __exit__(self, *exc):
        _donation_paused[0] -= 1
        return False


# The donation gate. A state tensor is tainted (`_donate_unsafe`) from any
# write of its value until a compiled launch writes its own output back
# (core/tensor.py). The taint stands for two hazards, and a tainted value is
# donated as it stands only where the gate can see that neither applies:
#   - the value may be backed by host memory: PJRT's CPU client imports a
#     numpy buffer without taking ownership, and donating that corrupts
#     memory. On any other platform the value was copied to device memory
#     when it became a jax.Array, so only a value on the CPU is held to this;
#   - something else may hold the same jax.Array (a tensor built from this
#     one, `a.set_value(b)`, a value the caller kept): donating deletes it
#     under the other holder, on every platform. The reference count says
#     whether there is one. (Another jax.Array over the same buffer, as
#     `jax.device_put` to the array's own device returns, is not seen.)
# Any other tainted value is copied on its device and the copy is donated, so
# no platform needs a second, non-donating program for the purpose: that one
# is traced and compiled only for `pause_donation`, the gradient path and
# FLAGS_donate_state_buffers off. What the eager discovery pass wrote is
# operations' results that nothing else holds: on a TPU the first compiled
# launch donates them as they are, and the state is held once.

def _platform_of(value):
    """Platform of the devices that hold `value`: "cpu", "tpu", ..."""
    return next(iter(value.devices())).platform


class _Slot:
    __slots__ = ("held",)


# what sys.getrefcount reads where a tensor's slot alone holds the value (the
# slot and the call's own argument), taken as _donatable takes it
_probe = _Slot()
_probe.held = object()
_SOLE_HOLDER = sys.getrefcount(_probe.held)
del _probe


def _donatable(t):
    """The value of state tensor `t` as a donating program may consume it."""
    if (not getattr(t, "_donate_unsafe", True)
            or isinstance(t._val, jax.core.Tracer)):   # a step inside a trace
        return t._val
    if (_platform_of(t._val) != "cpu"
            and sys.getrefcount(t._val) <= _SOLE_HOLDER):
        return t._val
    _count("rehomed_leaves")
    # a copy where the value is, placed and committed as the value is, and
    # no program compiled for it
    return jax.device_put(t._val, may_alias=False)


def _donation_gate(state, has_donating_twin):
    """(donate, operands) for one launch over the state tensors `state`:
    whether to run the donating executable, and the values to give it. The
    one gate of `_run` and of `run_steps`' scan."""
    if not has_donating_twin or _donation_paused[0]:
        return False, tuple(t._val for t in state)
    if any(getattr(t, "_donate_unsafe", True) for t in state):
        return True, tuple(_donatable(t) for t in state)
    return True, tuple(t._val for t in state)


def _discovery_passes():
    """1 (default): one eager pass + traced set-extension fixpoint.
    2 (PADDLE_TPU_TWO_PASS_DISCOVERY=1): legacy two eager passes."""
    import os
    return 2 if os.environ.get("PADDLE_TPU_TWO_PASS_DISCOVERY") == "1" else 1


class StaticFunction:
    """Callable wrapper (program_translator.py:234 StaticFunction parity)."""

    def __init__(self, fn, input_spec=None, build_strategy=None):
        functools.update_wrapper(self, fn)
        # AST control-flow conversion (dygraph_to_static transformer parity):
        # if/while/and/or/not become runtime dispatchers so Tensor-dependent
        # control flow survives XLA tracing. Falls back to `fn` untouched.
        from .ast_transform import apply_ast_transforms
        self._fn = apply_ast_transforms(fn)
        self._name = getattr(fn, "__qualname__", type(fn).__name__)  # in spans
        self._input_spec = input_spec
        self._programs = {}
        self._enabled = True  # per-function; see also _default_enabled

    # global to_static switch (ProgramTranslator.enable parity)
    _default_enabled = True

    def __get__(self, instance, owner):
        if instance is None:
            return self
        # one bound wrapper (and program cache) PER INSTANCE — programs capture
        # the instance's parameter tensors, so sharing across instances would
        # run one model's compiled program with another model's weights.
        cache_name = f"__static_fn_{id(self)}"
        bound = instance.__dict__.get(cache_name)
        if bound is None:
            bound = StaticFunction.__new__(StaticFunction)
            bound.__dict__ = self.__dict__.copy()
            bound._fn = self._fn.__get__(instance, owner)
            bound._programs = {}
            instance.__dict__[cache_name] = bound
        return bound

    @property
    def programs(self):
        return self._programs

    # -- multi-step execution (steps_per_execution) -----------------------------
    def run_steps(self, *args, **kwargs):
        """Run K steps of this program in ONE device dispatch.

        Every Tensor argument must carry a leading axis of the same length K
        (the step index); python-scalar arguments are held fixed across steps.
        The program's mutated state (parameters, optimizer moments, BN stats,
        RNG keys) is threaded step-to-step through `lax.scan`, so the result
        is bit-identical to calling the function K times — minus K-1 host
        round-trips. The first invocation runs the discovery pass(es)
        eagerly (one by default; see _discovery_passes) and scans the rest.
        Returns the function's outputs stacked on a leading K axis (outputs
        are non-differentiable; split train/eval phases into separate
        to_static functions if you need outer gradients).

        TPU rationale: host→device dispatch latency dominates small/medium
        step times (SURVEY.md §2.8 names the per-op interpreter loop as the
        reference's throughput seam; its answer is the C++ executor loop +
        CUDA graphs — run_program_op.cc. Keras' steps_per_execution is the
        same idea on TPU). One scan dispatch amortizes the latency K×.
        """
        leaves = _flatten_tensors((args, kwargs), [])
        if not leaves:
            raise ValueError("run_steps needs at least one Tensor argument "
                             "with a leading steps axis")
        ks = {t._val.shape[0] if t._val.ndim else None for t in leaves}
        if len(ks) != 1 or None in ks:
            raise ValueError(
                f"run_steps: all Tensor args must share the same leading "
                f"steps-axis length; got lengths {sorted(map(str, ks))}")
        k = ks.pop()
        if k == 0:
            raise ValueError("run_steps: leading steps axis is empty (K=0)")

        def step_slice(i):
            vals = iter([Tensor(t._val[i], stop_gradient=True)
                         for t in leaves])
            def sub(obj):
                if isinstance(obj, Tensor):
                    return next(vals)
                if isinstance(obj, (list, tuple)):
                    return type(obj)(sub(v) for v in obj)
                if isinstance(obj, dict):
                    return {kk: sub(obj[kk]) for kk in sorted(obj)}
                return obj
            a2 = sub(args)
            kw2 = sub(kwargs)
            return a2, kw2

        key = (_sig_of_step(args), _sig_of_step(kwargs),
               autograd.is_grad_enabled())

        # fast path (default): discover the program on a THROWAWAY batch-1
        # eager pass with full state rollback, so every one of the K steps
        # runs inside the compiled scan. Disable with
        # PADDLE_TPU_FAST_DISCOVERY=0 to restore eager full-shape warmup.
        import os as _os
        prog0 = self._programs.get(key)
        if (prog0 is None or prog0.stage < _discovery_passes()) and \
                _os.environ.get("PADDLE_TPU_FAST_DISCOVERY", "1") != "0":
            with _compile_guard():
                prog0 = self._programs.get(key)
                if prog0 is None or prog0.stage < _discovery_passes():
                    self._discover_throwaway(key, step_slice)

        # warm eagerly until the per-step program is discovered (two eager
        # passes); warmup calls ARE real steps (state advances), their
        # outputs are stitched onto the front of the scanned outputs. The
        # single-step executable is deliberately NOT built/compiled — only
        # the scanned program ever runs on the device.
        eager_outs = []
        i = 0
        while i < k:
            prog = self._programs.get(key)
            if prog is not None and prog.stage >= _discovery_passes():
                break
            ai, kwi = step_slice(i)
            eager_outs.append(self(*ai, **kwi))
            i += 1
        if i == k:
            stacked = [jnp.stack([t._val for t in per_leaf])
                       for per_leaf in zip(*(
                           _flatten_tensors(o, []) for o in eager_outs))]
            outs = [Tensor(v, stop_gradient=True) for v in stacked]
            return _unflatten(self._programs[key].out_tree, outs)

        prog = self._programs[key]
        if prog.pure_fn is None or prog.scanned is None:
            with _compile_guard():
                if prog.pure_fn is None:
                    ai, kwi = step_slice(i)
                    self._build(prog, ai, kwi)
                if prog.scanned is None:
                    self._build_scan(prog)

        # steady state (i == 0): pass buffers through untouched — a [0:]
        # slice would dispatch a device op and copy the whole stack per call
        rest_vals = (tuple(t._val for t in leaves) if i == 0
                     else tuple(t._val[i:] for t in leaves))

        def _exec_scan():   # write-seam: scan write-back of XLA-owned outputs clears taint
            ro_vals = tuple(t._val for t in prog.ro)
            rest = rest_vals
            donate, mut_vals = _donation_gate(
                prog.mutated, prog.scanned_donate is not prog.scanned)
            exec_fn = prog.scanned_donate if donate else prog.scanned
            outs, new_state = exec_fn(mut_vals, ro_vals, rest)
            for t, v in zip(prog.mutated, new_state):
                t._val = v
                t._donate_unsafe = False
            return outs

        # the FIRST execution traces pure_fn (temporarily rebinding shared
        # model tensors to tracers) — it must hold the compile guard so no
        # concurrent fast-path run observes tracer-bound state
        if prog.scanned_ready and _enter_fast_path():
            try:
                outs = _exec_scan()
            finally:
                _exit_fast_path()
        else:
            with _compile_guard():
                outs = _exec_scan()
                prog.scanned_ready = True

        if eager_outs:
            eager_leaves = [[t._val for t in _flatten_tensors(o, [])]
                            for o in eager_outs]

            def _cat(j, v):
                head = jnp.stack([el[j] for el in eager_leaves])
                sh = getattr(v, "sharding", None)
                if sh is not None:
                    head = jax.device_put(head, list(sh.device_set)[0])
                return jnp.concatenate([head, v], axis=0)

            outs = [_cat(j, v) for j, v in enumerate(outs)]
        leaves_out = [Tensor(v, stop_gradient=True) for v in outs]
        return _unflatten(prog.out_tree, leaves_out)

    def _discover_throwaway(self, key, step_slice):   # write-seam: snapshot/rollback restore of _val
        """Discovery without advancing state: one eager pass on a batch-1
        sub-slice of the step-0 inputs, snapshotting the pre-write value of
        every tensor written (lazily-created optimizer moments roll back to
        their creation value), then restoring everything. On success the
        program is registered stage-complete, so run_steps scans ALL K steps
        on-device with no full-shape eager step — at TPU batch sizes the
        eager host pass otherwise dominates warm-up (minutes for a
        batch-128 ResNet step; the reference pays the analogous cost as the
        first full run_program invocation, partial_program.py:116).

        Returns True on success; on any failure state is restored and the
        caller falls back to the eager warm-up path.
        """
        ai, kwi = step_slice(0)

        def shrink(t):
            v = t._val
            if getattr(v, "ndim", 0) >= 1 and v.shape[0] > 1:
                v = v[:1]
            return Tensor(v, stop_gradient=t.stop_gradient)

        leaves1 = iter([shrink(t)
                        for t in _flatten_tensors((ai, kwi), [])])

        def sub(obj):
            if isinstance(obj, Tensor):
                return next(leaves1)
            if isinstance(obj, (list, tuple)):
                return type(obj)(sub(v) for v in obj)
            if isinstance(obj, dict):
                return {kk: sub(obj[kk]) for kk in sorted(obj)}
            return obj

        a1 = sub(ai)
        kw1 = sub(kwi)
        arg_tensors = _flatten_tensors((a1, kw1), [])
        ctx = _DiscoveryCtx([id(t) for t in arg_tensors])
        snaps = []
        snap_ids = set()
        grad_snaps = []
        grad_ids = set()

        def _note_grad(t):
            # SelectedRows gradients rebind `.grad` without a hooked _value
            # write, so value-rollback alone would leave the throwaway's
            # batch-1 sparse grad attached; remember the pre-pass attribute
            i = id(t)
            if i not in grad_ids:
                grad_ids.add(i)
                grad_snaps.append((t, t.grad))

        def on_read(t):
            _note_grad(t)
            ctx.on_read(t)

        def on_write(t, new_value=None):
            i = id(t)
            _note_grad(t)
            if i not in snap_ids:
                snap_ids.add(i)
                snaps.append((t, t._val))
            ctx.on_write(t, new_value)

        prev = (_TraceHooks.on_read, _TraceHooks.on_write,
                _TraceHooks.on_create)
        _TraceHooks.on_read = on_read
        _TraceHooks.on_write = on_write
        _TraceHooks.on_create = ctx.on_create
        bwd_before = autograd.backward_run_counter[0]
        out = None
        ok = False
        try:
            out = self._fn(*a1, **kw1)
            ok = True
        except Exception:
            pass
        finally:
            (_TraceHooks.on_read, _TraceHooks.on_write,
             _TraceHooks.on_create) = prev
            for t, v in snaps:
                t._val = v
            from ..core.selected_rows import SelectedRows
            for t, g_old in grad_snaps:
                # dense grads roll back via the hooked-write snapshot (and
                # stay attached as zeroed state); sparse ones must have the
                # ATTRIBUTE restored
                if isinstance(t.grad, SelectedRows) and t.grad is not g_old:
                    t.grad = g_old
        if not ok:
            return False
        prog = self._programs.get(key) or _Program()
        prog.stage = _discovery_passes()
        prog.internal_backward = (autograd.backward_run_counter[0]
                                  > bwd_before)
        prog.captured = ctx.captured
        mutated_ids = ctx.mutated_ids & ctx.captured_ids
        prog.mutated = [t for t in ctx.captured if id(t) in mutated_ids]
        prog.ro = [t for t in ctx.captured if id(t) not in mutated_ids]
        prog.out_tree = _build_tree(out)
        prog.n_outs = len(_flatten_tensors(out, []))
        self._cache_program(key, prog)
        return True

    def _cache_program(self, key, prog):
        """Insert under the FLAGS_max_cached_programs bound: a
        signature-churning caller (e.g. varying python scalars) retraces
        forever but must not grow the cache without bound. FIFO eviction
        — an evicted signature simply re-traces on its next call."""
        self._programs[key] = prog
        from ..framework.flags import get_flag
        cap = int(get_flag("FLAGS_max_cached_programs", 64) or 0)
        if cap > 0:
            while len(self._programs) > cap:
                oldest = next(iter(self._programs))
                if oldest == key:
                    break  # never evict the program just inserted
                del self._programs[oldest]

    def _build_scan(self, prog):
        pure_fn = prog.pure_fn
        n_outs = prog.n_outs

        def scan_fn(mut_vals, ro_vals, stacked_arg_vals):   # traced-fn: jitted K-step scan body
            def body(carry, xs):
                flat = pure_fn(carry, ro_vals, xs)
                return tuple(flat[n_outs:]), tuple(flat[:n_outs])
            new_state, outs = jax.lax.scan(body, tuple(mut_vals),
                                           stacked_arg_vals)
            return outs, new_state

        prog.scanned = jax.jit(scan_fn)
        from ..framework.flags import get_flag
        if get_flag("FLAGS_donate_state_buffers", True):
            prog.scanned_donate = jax.jit(scan_fn, donate_argnums=(0,))
        else:
            prog.scanned_donate = prog.scanned

    def __call__(self, *args, **kwargs):
        if not (self._enabled and StaticFunction._default_enabled):
            return self._fn(*args, **kwargs)
        # one span a call, from the signature to the returned outputs, with
        # the running counters: on the fast path it and its one child,
        # to_static.launch, are all the annotations a step opens
        with jax.profiler.TraceAnnotation("to_static.call", fn=self._name,
                                          **_running_counts()):
            return self._call(args, kwargs)

    def _call(self, args, kwargs):
        key = (_sig_of(args), _sig_of(kwargs), autograd.is_grad_enabled())
        prog = self._programs.get(key)
        if (prog is not None and prog.stage >= _discovery_passes()
                and prog.jitted is not None):
            if _enter_fast_path():
                try:
                    return self._run(prog, args, kwargs)
                finally:
                    _exit_fast_path()
            _count("diverted_calls")
        with _compile_guard():
            prog = self._programs.get(key)
            # ONE eager discovery call warms lazily-created state (optimizer
            # accumulators, RNG splits) and records a first capture/mutation
            # guess; _build then closes the sets with a ZERO-FLOP traced
            # fixpoint (jax.eval_shape probes catch state the eager pass
            # classified as created-inside). PADDLE_TPU_TWO_PASS_DISCOVERY=1
            # restores the old two-eager-pass scheme.
            if prog is None or prog.stage < _discovery_passes():
                return self._discover(key, args, kwargs)
            if prog.jitted is None:
                self._build(prog, args, kwargs)
            return self._run(prog, args, kwargs)

    # -- phase A ---------------------------------------------------------------
    def _discover(self, key, args, kwargs):
        arg_tensors = _flatten_tensors((args, kwargs), [])
        ctx = _DiscoveryCtx([id(t) for t in arg_tensors])
        prev = (_TraceHooks.on_read, _TraceHooks.on_write,
                _TraceHooks.on_create)
        _TraceHooks.on_read = ctx.on_read
        _TraceHooks.on_write = ctx.on_write
        _TraceHooks.on_create = ctx.on_create
        bwd_before = autograd.backward_run_counter[0]
        ops_before = _dispatch.OPS_DISPATCHED[0]
        try:
            with setup_span("to_static.discover", fn=self._name) as span, \
                    timed_ops(span):
                out = self._fn(*args, **kwargs)
                ops = _dispatch.OPS_DISPATCHED[0] - ops_before
                _count("discover_ops", ops)
                span["attrs"]["ops"] = ops
        finally:
            (_TraceHooks.on_read, _TraceHooks.on_write,
             _TraceHooks.on_create) = prev
        prog = self._programs.get(key) or _Program()
        prog.stage += 1
        prog.internal_backward = autograd.backward_run_counter[0] > bwd_before
        prog.captured = ctx.captured
        mutated_ids = ctx.mutated_ids & ctx.captured_ids
        prog.mutated = [t for t in ctx.captured if id(t) in mutated_ids]
        prog.ro = [t for t in ctx.captured if id(t) not in mutated_ids]
        prog.out_tree = _build_tree(out)
        prog.n_outs = len(_flatten_tensors(out, []))
        self._cache_program(key, prog)
        return out

    # -- phase B ---------------------------------------------------------------
    def _make_pure_fn(self, prog, args, kwargs, probe=None):
        """Build pure_fn over prog's CURRENT capture sets.

        probe: optional dict with "reads"/"writes"/"promote" sets — when
        given, the traced run records stray reads (tensors touched but not
        inputs), stray writes, and writes to read-only inputs, so the
        discovery fixpoint can extend the sets (zero FLOPs: only used under
        jax.eval_shape).
        """
        fn = self._fn
        mutated, ro = list(prog.mutated), list(prog.ro)
        arg_tensors = _flatten_tensors((args, kwargs), [])
        # the trace sees only tracers: tell it which mesh the program's
        # inputs are spread over (None: one device)
        from ..distributed.mesh import mesh_of, trace_mesh
        mesh = mesh_of(t._val for t in mutated + ro + arg_tensors)

        # traced-fn: THE jitted program body; write-seam: tracer rebind + restore of _val
        def pure_fn(mut_vals, ro_vals, arg_vals):
            all_t = mutated + ro + arg_tensors
            all_ids = {id(t) for t in all_t}
            ro_ids = {id(t) for t in ro}
            saved = [t._val for t in all_t]
            created = set()
            # safety net: the trace may write tensors the discovery pass did
            # not see (rare dynamic state); snapshot-before-write and restore,
            # so no tracer ever leaks out of the trace.
            stray = {}

            def track_create(t):
                created.add(id(t))

            def track_read(t):
                if t._trace_transparent:
                    return
                i = id(t)
                if i not in all_ids and i not in created:
                    probe["reads"][i] = t

            def track_write(t, new_value=None):
                if t._trace_transparent:
                    return  # static-graph Variables are never jit state
                i = id(t)
                if i not in all_ids and i not in created and i not in stray:
                    stray[i] = (t, t._val)
                    if probe is not None:
                        probe["writes"][i] = t
                elif probe is not None and i in ro_ids:
                    probe["promote"][i] = t

            prev_hooks = (_TraceHooks.on_read, _TraceHooks.on_write,
                          _TraceHooks.on_create)
            _TraceHooks.on_read = track_read if probe is not None else None
            _TraceHooks.on_write = track_write
            _TraceHooks.on_create = track_create if probe is not None else None
            try:
                for t, v in zip(mutated, mut_vals):
                    t._val = v
                for t, v in zip(ro, ro_vals):
                    t._val = v
                for t, v in zip(arg_tensors, arg_vals):
                    t._val = v
                with trace_mesh(mesh):
                    out = fn(*args, **kwargs)
                out_vals = tuple(t._val for t in _flatten_tensors(out, []))
                new_state = tuple(t._val for t in mutated)
                return out_vals + new_state
            finally:
                (_TraceHooks.on_read, _TraceHooks.on_write,
                 _TraceHooks.on_create) = prev_hooks
                for t, v in zip(all_t, saved):
                    t._val = v
                for t, v in stray.values():
                    t._val = v

        return pure_fn

    def _build(self, prog, args, kwargs):
        arg_tensors = _flatten_tensors((args, kwargs), [])

        def aval(t):
            return jax.ShapeDtypeStruct(tuple(t._val.shape), t._val.dtype)

        if _discovery_passes() < 2:
            # traced set-extension fixpoint: the single eager pass classified
            # lazily-created state (optimizer moments, grad accumulators
            # surviving across steps) as created-inside; abstract probes
            # (no FLOPs, no compile) surface them as stray reads/writes
            for _ in range(5):
                probe = {"reads": {}, "writes": {}, "promote": {}}
                probe_fn = self._make_pure_fn(prog, args, kwargs, probe=probe)
                with compile_span("to_static.probe", probe_fn.__name__,
                                  fn=self._name):
                    jax.eval_shape(probe_fn,
                                   tuple(aval(t) for t in prog.mutated),
                                   tuple(aval(t) for t in prog.ro),
                                   tuple(aval(t) for t in arg_tensors))
                if not (probe["reads"] or probe["writes"]
                        or probe["promote"]):
                    break
                written = set(probe["writes"]) | set(probe["promote"])
                prog.mutated = prog.mutated + [
                    t for i, t in {**probe["writes"],
                                   **probe["promote"]}.items()]
                prog.ro = ([t for t in prog.ro if id(t) not in written]
                           + [t for i, t in probe["reads"].items()
                              if i not in written])
            else:
                raise RuntimeError(
                    "to_static discovery did not converge: the traced "
                    "probes kept finding new state; set "
                    "PADDLE_TPU_TWO_PASS_DISCOVERY=1 to fall back to "
                    "eager discovery")

        pure_fn = self._make_pure_fn(prog, args, kwargs)
        prog.pure_fn = pure_fn
        prog.jitted = jax.jit(pure_fn)
        from ..framework.flags import get_flag
        if get_flag("FLAGS_donate_state_buffers", True):
            prog.jitted_donate = jax.jit(pure_fn, donate_argnums=(0,))
        else:
            prog.jitted_donate = prog.jitted

    def _launch(self, prog, which, launch, *operands):
        """One launch of a program's executable `which` ("plain",
        "donating", or "grad": jax.vjp of the plain one) under its span;
        the first launch of each traces and compiles, and says so."""
        _count("launches")
        if which in prog.ran:
            with jax.profiler.TraceAnnotation("to_static.launch"):
                return launch(*operands)
        with compile_span("to_static.compile", prog.pure_fn.__name__,
                          fn=self._name, program=which):
            with jax.profiler.TraceAnnotation("to_static.launch"):
                out = launch(*operands)
        prog.ran.add(which)
        return out

    def _run(self, prog, args, kwargs):   # write-seam: compiled write-back of XLA-owned outputs clears taint
        arg_tensors = _flatten_tensors((args, kwargs), [])
        ro_vals = tuple(t._val for t in prog.ro)
        arg_vals = tuple(t._val for t in arg_tensors)
        n_outs = prog.n_outs

        # does gradient need to flow through this program?
        diff_tensors = []
        if autograd.is_grad_enabled() and not prog.internal_backward:
            for t in list(prog.mutated) + list(prog.ro) + arg_tensors:
                if (not t.stop_gradient and is_inexact(t._val.dtype)
                        and t._grad_node is None):
                    diff_tensors.append(t)

        if not diff_tensors:
            has_twin = prog.jitted_donate is not prog.jitted
            donate, mut_vals = _donation_gate(prog.mutated, has_twin)
            if has_twin and not donate:
                _count("undonated_launches")
            flat = self._launch(prog, "donating" if donate else "plain",
                                prog.jitted_donate if donate else prog.jitted,
                                mut_vals, ro_vals, arg_vals)
            out_vals, new_state = flat[:n_outs], flat[n_outs:]
            for t, v in zip(prog.mutated, new_state):
                t._val = v
                t._donate_unsafe = False
            leaves = [Tensor(v, stop_gradient=True) for v in out_vals]
            if prog.internal_backward and autograd.is_grad_enabled():
                # the fast path skips outer grad flow; if the caller later
                # tries to differentiate these outputs, fail loudly instead
                # of silently yielding zero gradients (GAN-style programs
                # that both update internally AND return differentiable
                # outputs should split the function in two)
                def _raise(*a, **k):
                    raise RuntimeError(
                        "cannot differentiate through the output of a "
                        "to_static function that runs its own backward(): "
                        "outer gradient flow is disabled for compiled "
                        "train-step programs. Split the function so the "
                        "internally-optimized part and the externally-"
                        "differentiated part are separate to_static "
                        "functions.")
                node = GradNode(vjp_fn=_raise, inputs=[],
                                out_meta=[(v.shape, v.dtype)
                                          for v in out_vals],
                                multi_output=True,
                                name="to_static_internal_backward")
                for slot, t in enumerate(leaves):
                    t.stop_gradient = False
                    t._grad_node = node
                    t._out_index = slot
            return _unflatten(prog.out_tree, leaves)

        # grad path: record the whole program as ONE tape op (run_program-grad
        # parity). Donation is off (residuals alias inputs).
        mut_vals = tuple(t._val for t in prog.mutated)
        all_tensors = list(prog.mutated) + list(prog.ro) + arg_tensors
        all_vals = list(mut_vals) + list(ro_vals) + list(arg_vals)
        diff_idx = [i for i, t in enumerate(all_tensors)
                    if not t.stop_gradient and is_inexact(t._val.dtype)
                    and t._grad_node is None]
        n_mut = len(prog.mutated)
        n_ro = len(prog.ro)

        def closed(*diff_vals):
            vals = list(all_vals)
            for i, dv in zip(diff_idx, diff_vals):
                vals[i] = dv
            return prog.jitted(tuple(vals[:n_mut]),
                               tuple(vals[n_mut:n_mut + n_ro]),
                               tuple(vals[n_mut + n_ro:]))

        _count("grad_path_launches")
        flat, vjp_fn = self._launch(prog, "grad", jax.vjp, closed,
                                    *[all_vals[i] for i in diff_idx])
        out_vals, new_state = flat[:n_outs], flat[n_outs:]
        for t, v in zip(prog.mutated, new_state):
            t._val = v
            t._donate_unsafe = False  # vjp outputs are XLA-owned
        node = GradNode(
            vjp_fn=vjp_fn,
            inputs=[all_tensors[i] for i in diff_idx],
            out_meta=[(v.shape, v.dtype) for v in flat],
            multi_output=True,
            name="to_static_program",
        )
        leaves = []
        for slot, v in enumerate(out_vals):
            t = Tensor(v, stop_gradient=False)
            t._grad_node = node
            t._out_index = slot
            leaves.append(t)
        return _unflatten(prog.out_tree, leaves)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """paddle.jit.to_static parity (fluid/dygraph/jit.py:161 declarative)."""

    def decorate(fn):
        from ..nn import Layer
        if isinstance(fn, Layer):
            layer = fn
            layer.forward = StaticFunction(type(layer).forward.__get__(layer),
                                           input_spec)
            return layer
        return StaticFunction(fn, input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class TracedLayer:
    """fluid.dygraph.TracedLayer shim over StaticFunction."""

    def __init__(self, layer):
        self._layer = layer
        self._static = StaticFunction(layer.forward)

    @staticmethod
    def trace(layer, inputs):
        tl = TracedLayer(layer)
        out = tl._static(*inputs)
        return out, tl

    def __call__(self, *args, **kwargs):
        return self._static(*args, **kwargs)


def functionalized_call(layer):
    """Return a jax-traceable fn over plain arrays: params/buffers are closed
    over as constants, inputs arrive as arrays. Used by export paths
    (inference.save_predictor_model, onnx.export) — the TPU analog of tracing
    a Layer into a self-contained ProgramDesc (fluid/dygraph/jit.py save)."""
    from ..core import autograd as _ag
    from ..core.tensor import Tensor as _T

    def fn(*array_args):
        with _ag.no_grad():
            out = layer(*[_T(a) for a in array_args])
        if isinstance(out, _T):
            return out._val
        leaves = _flatten_tensors(out, [])
        return [t._val for t in leaves]

    return fn
