"""Whole-step compilation: one donated, sharding-annotated program per step.

``CompiledTrainStep`` wraps a python train step (forward + backward +
optimizer update) in a :class:`~paddle_tpu.jit.to_static.StaticFunction` and
makes the compile lifecycle *observable*:

- every call that still has trace/build work ahead of it runs under the
  ``step/compile`` StepTimer phase, so recompiles land in their own column
  of the step breakdown instead of ``unattributed``;
- ``compiled_step.compiles_total`` increments exactly once per signature
  when its XLA executable is built, and ``compiled_step.cache_hits_total``
  on every steady-state fast-path call — the bench/parity lanes assert
  "one steady-state trace per signature" directly off these counters;
- a retrace-storm guard counts DISTINCT signatures per step function and,
  past ``FLAGS_compiled_step_max_retraces``, warns once through the flight
  recorder (op ``compiled_step.retrace_storm``) and ``warnings`` —
  mirroring the serving compile-cache bound that caught the same pathology
  on the inference side.

The flag seam: ``FLAGS_compiled_step`` (default ON) routes
``hapi.Model.train_batch``/``fit`` and the bench LM lanes through this
wrapper; setting it to 0 opts back into the eager path, which stays the
debug/parity oracle (bit-exact f32 — see tests/test_compiled_step.py).

``CompiledStageProgram`` is the same lifecycle for lanes GSPMD can't place
as one program: pipeline 1F1B stage programs and the shard_map ring-attention
step compile ONE raw-jax program per input signature and share the
compile/cache-hit counters (and the trace sanitizer's retrace accounting)
with the whole-step wrapper. Sharding comes in through the inputs:
parameters placed by ``distributed.spec_layout.shard_params`` and batches by
``shard_batch`` carry ``NamedSharding``s, and jit propagates them through
the whole fused program (GSPMD), folding the hand-wired MULTICHIP dp/ZeRO
collectives into the compiled step.
"""
from __future__ import annotations

import threading
import warnings

from ..core import autograd
from ..profiler import metrics as _metrics
from ..profiler import steptimer as _steptimer
from .to_static import StaticFunction, _discovery_passes, _sig_of, \
    _sig_of_step

__all__ = ["CompiledTrainStep", "CompiledStageProgram",
           "compiled_step_enabled", "compile_stats", "reset_compile_stats"]

_stats_lock = threading.Lock()
_STATS = {"compiles": 0, "cache_hits": 0, "retrace_warnings": 0}


def compiled_step_enabled():
    """The FLAGS_compiled_step seam (default ON since the compiled lane
    passed its eager-parity gates; eager stays the debug/parity oracle)."""
    from ..framework.flags import get_flag
    return bool(get_flag("FLAGS_compiled_step", True))


def compile_stats():
    """Process-wide counters (mirrored into the metrics registry): compiles,
    cache hits, retrace-storm warnings. Bench/tests read this instead of
    scraping the registry snapshot."""
    with _stats_lock:
        return dict(_STATS)


def reset_compile_stats():
    with _stats_lock:
        for k in _STATS:
            _STATS[k] = 0


def _note_compile(n=1):
    with _stats_lock:
        _STATS["compiles"] += n
    _metrics.get_registry().inc_counter("compiled_step.compiles_total", n)


def _note_cache_hit(n=1):
    with _stats_lock:
        _STATS["cache_hits"] += n
    _metrics.get_registry().inc_counter("compiled_step.cache_hits_total", n)


class CompiledTrainStep:
    """Callable wrapper: StaticFunction + compile attribution + retrace guard.

    Drop-in for the inline ``StaticFunction(_step)`` the hapi Model builds:
    supports ``__call__`` (one step) and ``run_steps`` (K fused steps via
    lax.scan). `label` names this step in flight-recorder warnings.
    """

    def __init__(self, fn, label="train_step"):
        self._static = fn if isinstance(fn, StaticFunction) \
            else StaticFunction(fn)
        self._label = label
        self._seen_sigs = set()
        self._storm_warned = False

    @property
    def static_function(self):
        return self._static

    # -- retrace-storm guard ---------------------------------------------------
    def _guard_retrace(self, key):
        """Count distinct (signature, shapes) keys; past the flag bound this
        step fn is retracing per batch (ragged shapes, python objects in the
        signature) — warn loudly once instead of silently recompiling."""
        if key in self._seen_sigs:
            return
        self._seen_sigs.add(key)
        from ..framework.flags import get_flag
        bound = int(get_flag("FLAGS_compiled_step_max_retraces", 8))
        if bound <= 0 or len(self._seen_sigs) <= bound or self._storm_warned:
            return
        self._storm_warned = True
        with _stats_lock:
            _STATS["retrace_warnings"] += 1
        try:
            from ..resilience.recorder import get_recorder
            rec = get_recorder()
            entry = rec.start(
                "compiled_step.retrace_storm", group=self._label,
                seq=len(self._seen_sigs),
                shapes=[str(key[0])[:200]])
            rec.finish(entry, status="warn")
        except Exception:
            pass  # observability must not turn a retrace into a crash
        warnings.warn(
            f"compiled_step[{self._label}]: {len(self._seen_sigs)} distinct "
            f"input signatures traced (> FLAGS_compiled_step_max_retraces="
            f"{bound}). Every new shape compiles a fresh XLA program — pad "
            "or bucket inputs to a fixed set of shapes "
            "(docs/compiled_step.md has the runbook).",
            RuntimeWarning, stacklevel=3)

    # -- single step -----------------------------------------------------------
    def __call__(self, *args, **kwargs):   # hot-path: the per-step dispatch chokepoint
        st = self._static
        if not (st._enabled and StaticFunction._default_enabled):
            return st(*args, **kwargs)  # eager oracle: no counters, no phase
        key = (_sig_of(args), _sig_of(kwargs), autograd.is_grad_enabled())
        prog = st._programs.get(key)
        if prog is not None and prog.stage >= _discovery_passes() \
                and prog.jitted is not None:
            _note_cache_hit()
            return st(*args, **kwargs)
        self._guard_retrace(key)
        built_before = prog is not None and prog.jitted is not None
        timer = _steptimer.get_steptimer()
        with timer.phase("step/compile"):
            out = st(*args, **kwargs)
        prog = st._programs.get(key)
        if prog is not None and prog.jitted is not None and not built_before:
            _note_compile()
        return out

    # -- K fused steps (lax.scan) ----------------------------------------------
    def run_steps(self, *args, **kwargs):   # hot-path: the K-step scan dispatch chokepoint
        st = self._static
        if not (st._enabled and StaticFunction._default_enabled):
            return st.run_steps(*args, **kwargs)
        key = (_sig_of_step(args), _sig_of_step(kwargs),
               autograd.is_grad_enabled())
        prog = st._programs.get(key)
        if prog is not None and prog.scanned_ready:
            _note_cache_hit()
            return st.run_steps(*args, **kwargs)
        self._guard_retrace(key)
        ready_before = prog is not None and prog.scanned_ready
        timer = _steptimer.get_steptimer()
        with timer.phase("step/compile"):
            out = st.run_steps(*args, **kwargs)
        prog = st._programs.get(key)
        if prog is not None and prog.scanned_ready and not ready_before:
            _note_compile()
        return out


def _stage_sig(args):
    """Signature of raw-jax stage-program operands: nested lists/tuples of
    arrays (or scalars). Symbolic — shapes/dtypes only, no device sync."""
    out = []
    for a in args:
        if isinstance(a, (list, tuple)):
            out.append(_stage_sig(a))
        elif hasattr(a, "shape") and hasattr(a, "dtype"):
            out.append((tuple(a.shape), str(a.dtype)))
        else:
            out.append(("py", a if isinstance(
                a, (int, float, str, bool, type(None))) else str(type(a))))
    return tuple(out)


class CompiledStageProgram:
    """One donated, signature-keyed jitted program for a lane stage.

    The pipeline 1F1B engine and the ring-attention step operate on raw jax
    arrays below the Tensor/StaticFunction layer, but they need the same
    compile lifecycle as :class:`CompiledTrainStep`: steady state must be
    all cache hits, every build runs under the ``step/compile`` phase and
    bumps ``compiled_step.compiles_total``, and the trace sanitizer patches
    :meth:`_note_stage_compile` to hard-fail steady-state retraces. `label`
    names the stage in stats/flight-recorder output. `donate_argnums` is
    forwarded to ``jax.jit`` (stage programs donate operands whose last use
    is this call — e.g. the stashed activation consumed by the recompute
    backward)."""

    def __init__(self, fn, label="stage", donate_argnums=(),
                 static_argnums=()):
        import jax
        self._jit = jax.jit(fn, donate_argnums=donate_argnums,
                            static_argnums=static_argnums)
        self._label = label
        self._seen = set()

    def _note_stage_compile(self, key):
        """Called exactly once per new input signature, before the build.
        The trace sanitizer monkeypatches this to attribute/raise."""
        _note_compile()

    def __call__(self, *args):   # hot-path: per-unit lane dispatch chokepoint
        key = _stage_sig(args)
        if key in self._seen:
            _note_cache_hit()
            return self._jit(*args)
        self._seen.add(key)
        self._note_stage_compile((key, self._label))
        with _steptimer.get_steptimer().phase("step/compile"):
            return self._jit(*args)
