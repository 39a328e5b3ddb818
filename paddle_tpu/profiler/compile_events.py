"""jax's own compile durations, credited to `to_static`.

`to_static` opens :func:`compile_span` round the work that makes jax trace,
lower or compile one of its programs (`to_static.probe`, `to_static.compile`).
The span is a `jax.profiler.TraceAnnotation`, so it lands in the profiler's
trace beside the device operations, and while it is open on a thread one
`jax.monitoring` listener adds what jax reports for the watched function to
the always-on registry:

- ``to_static.trace_sec``            python tracing to a jaxpr
- ``to_static.lower_sec``            jaxpr to an MLIR module
- ``to_static.backend_compile_sec``  XLA, or the load from the persistent cache
- ``to_static.compiles_total``       backend compile requests

jax reports every nested trace too (an inner `jit`, a `jnp` function, an
eager op run while tracing), each inside its parent's duration: only the
watched function's own events count, so the seconds stay under wall time and
a `jax.jit` compiled outside a span adds nothing.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.monitoring

from . import metrics as _metrics

__all__ = ["compile_span"]

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"

_watch = threading.local()   # .span: the open span's names and trace seconds
_listening = []              # the listener, once registered
_listen_lock = threading.Lock()


def _on_duration(event, seconds, fun_name=None, **_):
    span = getattr(_watch, "span", None)
    if span is None or fun_name not in span["names"]:
        return
    reg = _metrics.get_registry()
    if event == _TRACE:
        # a nested program of the same name (a to_static function called
        # inside another's trace) reports first, and inside the outermost's
        # duration, which comes last: keep the last
        span["trace_sec"] = seconds
    elif event == _LOWER:
        reg.inc_counter("to_static.lower_sec", seconds)
    elif event == _BACKEND:
        reg.inc_counter("to_static.backend_compile_sec", seconds)
        reg.inc_counter("to_static.compiles_total")


def _listen():
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening.append(_on_duration)


@contextlib.contextmanager
def compile_span(name, watch, **attrs):
    """A `TraceAnnotation` named `name` during which jax's compile durations
    of the function `watch` (the `__name__` handed to `jax.jit`) count. A
    span opened inside another on the same thread only annotates: its
    seconds are already inside the outer one's."""
    if not _listening:
        _listen()
    span = None
    if getattr(_watch, "span", None) is None:
        span = _watch.span = {"names": (watch, f"jit({watch})"),
                              "trace_sec": 0.0}
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield
    finally:
        if span is not None:
            _watch.span = None
            if span["trace_sec"]:
                _metrics.get_registry().inc_counter("to_static.trace_sec",
                                                    span["trace_sec"])
