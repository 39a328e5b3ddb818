"""Set-up from inside the program: seconds by phase, and every compile
request by phase, always on.

A *set-up span* (:func:`setup_span`) is opened round work a process pays
before its steady state: `to_static.discover`, `to_static.probe`,
`to_static.compile` (jit/to_static.py). It is a
`jax.profiler.TraceAnnotation`, so a profiler trace that covers set-up shows
it on the device's clock, and, always, two `perf_counter` reads that add its
wall seconds to the registry counter ``<name>_sec`` and keep one record on
an in-memory timeline (:func:`setup_timeline`; `TIMELINE_BOUND`
records, then ``runtime.setup_records_dropped_total``). The open spans of a
thread are a stack: a record names its parent, and a phase's self time is
its own less its children's. `runtime.import` is a record without an
annotation (:func:`record_import`, from `paddle_tpu/__init__.py`).

The `jax.monitoring` listeners (:func:`listen`, at the package's import)
count every compile request of the process under the label ``phase``: the
innermost open set-up span of the thread (`discover`, `probe`,
`compile`), else `eager` where the package's own code asked (layer
constructors, initialisers, `set_state_dict`, the optimizer's first state),
else `user` (a `jax.jit` of the caller's own, with no frame of the package
under it):

- ``compile.requests_total{phase}``, ``compile.backend_sec{phase}``
  backend compile requests, a load from the persistent cache being one
- ``compile.cache_hits_total{phase}``, ``compile.cache_misses_total{phase}``
- ``compile.cache_load_sec{phase}``  reading a hit from the cache

A miss also leaves the name and seconds of the backend compile that follows
it on the thread on the open span's record, or on the process-wide `eager`
or `user` record (the `NAMES_BOUND` longest a record).

:func:`compile_span` is the set-up span that also watches one jax function
(`to_static`'s own programs) and credits jax's durations for it to

- ``to_static.trace_sec``            python tracing to a jaxpr
- ``to_static.lower_sec``            jaxpr to an MLIR module
- ``to_static.backend_compile_sec``  XLA, or the load from the persistent cache
- ``to_static.compiles_total``       backend compile requests

jax reports every nested trace too (an inner `jit`, a `jnp` function, an
eager op run while tracing), each inside its parent's duration: only the
watched function's own events count there, so those seconds stay under wall
time.

While a discovery span is open, and only then, `OP_TIMER` holds a timer that
`core/dispatch.apply` and the eager backward's tape loop hand each op to
(:func:`timed_ops`); the span's record keeps the `NAMES_BOUND` longest.
"""
from __future__ import annotations

import contextlib
import os
import sys
import threading
import time

import jax
import jax.monitoring

from . import metrics as _metrics

__all__ = ["setup_span", "compile_span", "setup_timeline", "timed_ops",
           "listen", "record_import", "OP_TIMER"]

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_ANSWERS = {_HIT: "compile.cache_hits_total",
                  _MISS: "compile.cache_misses_total"}

TIMELINE_BOUND = 4096   # records kept; later ones are counted, not kept
NAMES_BOUND = 16        # missed programs, and discovery's ops, a record

_PHASES = {"to_static.discover": "discover", "to_static.probe": "probe",
           "to_static.compile": "compile"}
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) + os.sep

# core/dispatch.apply and core/autograd.backward check this slot an op; it
# is empty except while a discovery span is open (timed_ops)
OP_TIMER = [None]


def _record(name, start, parent=None, attrs=None):
    return {"name": name, "start": start, "end": None, "parent": parent,
            "attrs": attrs or {}, "requests": 0, "misses": 0,
            "missed_programs": []}


# compile requests with no span open land on these two, which never close
_OUTSIDE = {phase: _record(phase, time.perf_counter())
            for phase in ("eager", "user")}
_timeline = list(_OUTSIDE.values())   # guarded-by: _lock
_lock = threading.Lock()
_thread = threading.local()   # .stack, .watch, .missed
_listening = []


def _stack():
    stack = getattr(_thread, "stack", None)
    if stack is None:
        stack = _thread.stack = []
    return stack


def _keep(record):
    with _lock:
        if len(_timeline) < TIMELINE_BOUND:
            _timeline.append(record)
            return
    _metrics.get_registry().inc_counter("runtime.setup_records_dropped_total")


def setup_timeline():
    """The set-up records of this process in the order they were opened, as
    plain dicts: `name`, `start` and `end` (`time.perf_counter` seconds; no
    `end` yet on an open span, never on the two process-wide records `eager`
    and `user`), `parent` (an index into this list, or None), `attrs`,
    `requests` and `misses` (compile requests and cache misses while the
    record was the thread's innermost), `missed_programs` ([name, seconds]
    of the compiles behind those misses) and, on a discovery span,
    `slowest_ops` ([op, calls, seconds, requests, misses], self time)."""
    with _lock:
        records = list(_timeline)
    index = {id(r): i for i, r in enumerate(records)}
    return [dict(r, parent=index.get(id(r["parent"])), attrs=dict(r["attrs"]),
                 missed_programs=list(r["missed_programs"])) for r in records]


def record_import(start, end):
    """The package's import, from its first line to its last."""
    record = _record("runtime.import", start)
    record["end"] = end
    _keep(record)
    _metrics.get_registry().inc_counter("runtime.import_sec", end - start)


@contextlib.contextmanager
def setup_span(name, **attrs):
    """A set-up span: yields its record, whose `attrs` may grow while it is
    open (the annotation gets what was added when it closes)."""
    stack = _stack()
    record = _record(name, 0.0, stack[-1] if stack else None, dict(attrs))
    _keep(record)
    stack.append(record)
    record["start"] = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **attrs) as annotation:
            try:
                yield record
            finally:
                late = {k: v for k, v in record["attrs"].items()
                        if k not in attrs}
                if late:
                    annotation.set_metadata(**late)
    finally:
        record["end"] = time.perf_counter()
        stack.pop()
        _metrics.get_registry().inc_counter(
            name + "_sec", record["end"] - record["start"])


@contextlib.contextmanager
def compile_span(name, watch, **attrs):
    """The set-up span during which jax's compile durations of the function
    `watch` (the `__name__` handed to `jax.jit`) count for `to_static`. One
    opened inside another on the same thread is a set-up span and no more:
    its function's seconds are already inside the outer one's."""
    watching = None
    if getattr(_thread, "watch", None) is None:
        watching = _thread.watch = {"names": (watch, f"jit({watch})"),
                                    "trace_sec": 0.0}
    try:
        with setup_span(name, **attrs) as record:
            yield record
    finally:
        if watching is not None:
            _thread.watch = None
            if watching["trace_sec"]:
                _metrics.get_registry().inc_counter("to_static.trace_sec",
                                                    watching["trace_sec"])


# ---------------------------------------------------------------------------
# jax's compile events

def _innermost():
    """(record, phase) a compile event of this thread counts under. For a
    listener to call: two frames up is jax's, and what called jax above it."""
    stack = _stack()
    if stack:
        name = stack[-1]["name"]
        return stack[-1], _PHASES.get(name, name)
    frame = sys._getframe(2)
    while frame is not None:
        if frame.f_code.co_filename.startswith(_PACKAGE):
            return _OUTSIDE["eager"], "eager"
        frame = frame.f_back
    return _OUTSIDE["user"], "user"


def _on_event(event, **_):
    counter = _CACHE_ANSWERS.get(event)
    if counter is None:
        return
    record, phase = _innermost()
    _metrics.get_registry().inc_counter(counter, labels={"phase": phase})
    if event == _MISS:
        record["misses"] += 1
        _thread.missed = True    # jax reports the compile behind it next


def _on_duration(event, seconds, fun_name=None, **_):
    reg = _metrics.get_registry()
    if event == _BACKEND:
        record, phase = _innermost()
        reg.inc_counter("compile.requests_total", labels={"phase": phase})
        reg.inc_counter("compile.backend_sec", seconds,
                        labels={"phase": phase})
        record["requests"] += 1
        if getattr(_thread, "missed", False):
            _thread.missed = False
            missed = record["missed_programs"]
            if len(missed) < NAMES_BOUND:
                missed.append([fun_name, seconds])
            else:   # keep the longest
                least = min(missed, key=lambda m: m[1])
                if seconds > least[1]:
                    least[:] = [fun_name, seconds]
    elif event == _LOAD:
        reg.inc_counter("compile.cache_load_sec", seconds,
                        labels={"phase": _innermost()[1]})
    watch = getattr(_thread, "watch", None)
    if watch is None or fun_name not in watch["names"]:
        return
    if event == _TRACE:
        # a nested program of the same name (a to_static function called
        # inside another's trace) reports first, and inside the outermost's
        # duration, which comes last: keep the last
        watch["trace_sec"] = seconds
    elif event == _LOWER:
        reg.inc_counter("to_static.lower_sec", seconds)
    elif event == _BACKEND:
        reg.inc_counter("to_static.backend_compile_sec", seconds)
        reg.inc_counter("to_static.compiles_total")


def listen():
    """Register the listeners, once a process."""
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening.append(True)


# ---------------------------------------------------------------------------
# the discovery pass by op

class _OpTimes:
    """Host seconds, compile requests and cache misses of each op handed to
    it, less those of the ops it ran inside it, summed by the op's name."""

    def __init__(self, record):
        self.record = record
        self.thread = threading.get_ident()
        self.by_op = {}              # name: [calls, seconds, requests, misses]
        self.inside = [[0.0, 0, 0]]  # what the open ops' children took

    def __call__(self, name, fn, *args):
        if threading.get_ident() != self.thread:
            return fn(*args)
        record = self.record
        requests, misses = record["requests"], record["misses"]
        self.inside.append([0.0, 0, 0])
        start = time.perf_counter()
        try:
            return fn(*args)
        finally:
            whole = (time.perf_counter() - start,
                     record["requests"] - requests, record["misses"] - misses)
            inner = self.inside.pop()
            row = self.by_op.setdefault(name, [0, 0.0, 0, 0])
            row[0] += 1
            for i, value in enumerate(whole):
                row[i + 1] += value - inner[i]
                self.inside[-1][i] += value


@contextlib.contextmanager
def timed_ops(record):
    """While open, every op that `core/dispatch.apply` dispatches and every
    node of the eager backward (`grad(<op>)`) is timed on the host, which is
    what an asynchronous dispatch costs the discovery pass; at the end
    `record["slowest_ops"]` holds the `NAMES_BOUND` longest by summed self
    seconds: [op, calls, seconds, compile requests, misses]."""
    timer, before = _OpTimes(record), OP_TIMER[0]
    OP_TIMER[0] = timer
    try:
        yield
    finally:
        OP_TIMER[0] = before
        rows = sorted(timer.by_op.items(), key=lambda kv: -kv[1][1])
        record["slowest_ops"] = [[name, *row] for name, row in rows[:NAMES_BOUND]]
