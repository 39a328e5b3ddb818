"""Profiler (reference: platform/profiler.h RecordEvent/EnableProfiler +
python/paddle/utils/profiler, paddle.profiler v2 API).

TPU-native: host spans recorded by a lightweight in-process recorder (chrome
trace JSON export, ≈ profiler.proto timeline); device timeline comes from
jax.profiler (XPlane/TensorBoard trace) — start_trace/stop_trace wrap it.
RecordEvent and the step-phase timer also emit jax.profiler.TraceAnnotation,
always, so host spans sit beside device activity in whichever XPlane trace is
being taken; the compiled step's own spans (`to_static.*`) live in
jit/to_static.py and :mod:`paddle_tpu.profiler.compile_events`.

Always-on metrics (queue depth, integrity cost, step-phase times) live in
the companion registry (:mod:`paddle_tpu.profiler.metrics`): record_counter
feeds it unconditionally and only ALSO lands on the chrome "C" track while
tracing is enabled. Step-phase attribution is in
:mod:`paddle_tpu.profiler.steptimer`.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import jax

from . import metrics as _metrics
from .compile_events import setup_timeline

__all__ = [
    "setup_timeline",
    "Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
    "start_profiler", "stop_profiler", "reset_profiler", "profiler",
    "export_chrome_tracing", "export_rank_trace", "summary",
    "record_counter", "counter_samples",
]


class _HostEventRecorder:
    def __init__(self):
        self._events = []    # (name, start_us, dur_us, tid, cat)
        self._counters = []  # (name, ts_us, value) chrome "C" events
        self._instants = []  # (name, ts_us, args) chrome "i" events
        self._lock = threading.Lock()
        self.enabled = False

    def record(self, name, start_us, dur_us, tid, cat=None):
        if not self.enabled:
            return
        with self._lock:
            self._events.append((name, start_us, dur_us, tid, cat or "host"))

    def record_counter(self, name, value, ts_us=None):
        if not self.enabled:
            return
        if ts_us is None:
            ts_us = time.perf_counter_ns() / 1000.0
        with self._lock:
            self._counters.append((name, ts_us, value))

    def record_instant(self, name, ts_us=None, args=None):
        if not self.enabled:
            return
        if ts_us is None:
            ts_us = time.perf_counter_ns() / 1000.0
        with self._lock:
            self._instants.append((name, ts_us, args))

    def clear(self):
        with self._lock:
            self._events.clear()
            self._counters.clear()
            self._instants.clear()

    def chrome_trace(self):
        evs = [{
            "name": name, "ph": "X", "ts": start, "dur": dur,
            "pid": os.getpid(), "tid": tid, "cat": cat,
        } for name, start, dur, tid, cat in self._events]
        evs.extend({
            "name": name, "ph": "C", "ts": ts, "pid": os.getpid(),
            "args": {"value": value}, "cat": "counter",
        } for name, ts, value in self._counters)
        evs.extend({
            "name": name, "ph": "i", "ts": ts, "pid": os.getpid(),
            "s": "p", "args": args or {}, "cat": "instant",
        } for name, ts, args in self._instants)
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def aggregate(self, event_type=None):
        agg = {}
        for name, _start, dur, _tid, cat in self._events:
            if event_type is not None and cat != event_type:
                continue
            tot, cnt, mx = agg.get(name, (0.0, 0, 0.0))
            agg[name] = (tot + dur, cnt + 1, max(mx, dur))
        return agg

    def categories(self):
        """{span name: cat} (last writer wins) for summary() display."""
        with self._lock:
            return {name: cat for name, _s, _d, _t, cat in self._events}


_recorder = _HostEventRecorder()


class RecordEvent:
    """platform/profiler.h:216 RecordEvent parity (RAII span). Usable as a
    context manager or decorator. Every span is a jax TraceAnnotation too,
    whether or not this module's recorder is on: a trace that
    `jax.profiler.start_trace` (TensorBoard, benchmarks/run.py) takes holds
    it on the host line, on the device operations' clock."""

    def __init__(self, name, event_type=None):
        self.name = name
        self.event_type = event_type  # chrome `cat`; filterable in summary()
        self._start = None
        self._jax_ann = None

    def begin(self):
        self._start = time.perf_counter_ns()
        self._jax_ann = jax.profiler.TraceAnnotation(self.name)
        self._jax_ann.__enter__()

    def end(self):
        if self._start is None:
            return
        dur_us = (time.perf_counter_ns() - self._start) / 1000.0
        _recorder.record(self.name, self._start / 1000.0, dur_us,
                         threading.get_ident(), self.event_type)
        self._jax_ann.__exit__(None, None, None)
        self._jax_ann = None
        self._start = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*a, **k):
            with RecordEvent(self.name, self.event_type):
                return fn(*a, **k)
        return wrapped


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    TPU = "tpu"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class Profiler:
    """paddle.profiler.Profiler (v2 API) parity.

    ``scheduler=(skip, warmup, active, repeat)`` windows the HOST recorder
    the way paddle.profiler.make_scheduler does: each cycle records nothing
    for `skip` steps, records-then-discards for `warmup` steps, and keeps
    `active` steps of spans (``on_trace_ready`` fires at the end of each
    active window). `repeat` bounds the number of cycles; 0 = unbounded.
    Driven by :meth:`step`, which also stamps a chrome instant event per
    boundary and feeds samples/sec through the metrics registry.
    """

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self._tmpdir = None
        self._device_trace = not timer_only
        self.scheduler = tuple(scheduler) if scheduler is not None else None
        if self.scheduler is not None:
            skip, warmup, active, repeat = self.scheduler
            if active < 1:
                raise ValueError("scheduler needs active >= 1")
            if skip < 0 or warmup < 0 or repeat < 0:
                raise ValueError("scheduler window values must be >= 0")
        self._step_num = 0
        self._last_step_us = None
        self._sched_phase = None  # "closed" | "warmup" | "active"

    def _schedule_phase(self, step_num):
        skip, warmup, active, repeat = self.scheduler
        cycle = skip + warmup + active
        if repeat and step_num >= repeat * cycle:
            return "closed"
        pos = step_num % cycle
        if pos < skip:
            return "closed"
        if pos < skip + warmup:
            return "warmup"
        return "active"

    def _apply_schedule(self):
        phase = self._schedule_phase(self._step_num)
        prev, self._sched_phase = self._sched_phase, phase
        if phase == prev:
            return
        if prev == "active":
            # active window just ended: hand the recorded spans over
            # BEFORE the next state clears them
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)
        if phase == "closed":
            _recorder.enabled = False
        elif phase == "warmup":
            _recorder.enabled = True
            _recorder.clear()
        else:  # active: drop warmup spans, record for real
            _recorder.enabled = True
            _recorder.clear()

    def start(self):
        _recorder.enabled = True
        _recorder.clear()
        _metrics.get_registry().clear_samples()
        if self._device_trace:
            import tempfile
            self._tmpdir = tempfile.mkdtemp(prefix="paddle_tpu_prof_")
            try:
                jax.profiler.start_trace(self._tmpdir)
            except Exception:
                self._tmpdir = None
        if self.scheduler is not None:
            self._step_num = 0
            self._sched_phase = None
            self._apply_schedule()

    def stop(self):
        _recorder.enabled = False
        if self._tmpdir is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        if self.on_trace_ready is not None and self._sched_phase != "closed":
            # with a scheduler, a closed window already fired its callback
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        """Mark a step boundary: chrome instant event, samples/sec gauge,
        and (when a scheduler is set) the window transition for the step
        that begins now."""
        now_us = time.perf_counter_ns() / 1000.0
        _recorder.record_instant("profiler.step", now_us,
                                 {"step": self._step_num})
        if num_samples is not None and self._last_step_us is not None:
            dt_s = (now_us - self._last_step_us) / 1e6
            if dt_s > 0:
                _metrics.get_registry().set_gauge(
                    "profiler.samples_per_sec", num_samples / dt_s)
        self._last_step_us = now_us
        self._step_num += 1
        if self.scheduler is not None:
            self._apply_schedule()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def export(self, path, format="json"):  # noqa: A002
        export_chrome_tracing(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        return summary()

    @property
    def xplane_dir(self):
        """Directory with the jax/XLA device trace (TensorBoard-loadable)."""
        return self._tmpdir


def record_counter(name, value, ts_us=None):
    """Record a counter sample. ALWAYS lands in the metrics registry
    (:mod:`paddle_tpu.profiler.metrics` — production gauges must not vanish
    when nobody is tracing); while profiling is enabled it is additionally
    emitted as a chrome-trace counter event ("ph": "C") onto the host
    timeline. The serving subsystem exports its queue-depth / shed /
    occupancy gauges through this."""
    _metrics.get_registry().record_sample(name, value, ts_us)
    _recorder.record_counter(name, value, ts_us)


def counter_samples(name=None):
    """Snapshot of recorded counter samples as ``(name, ts_us, value)``
    tuples, optionally filtered by name. Lets tests and CI gates assert on
    gauges (integrity check cost, straggler ratios, serving queue depth)
    without exporting and parsing a chrome trace. Backed by the always-on
    registry's bounded sample ring, so it works with profiling disabled;
    ``start_profiler``/``reset_profiler`` clear it (session semantics)."""
    return _metrics.get_registry().counter_samples(name)


def _trace_metadata():
    """Rank / elastic-generation / wall-clock anchor stamped into every
    exported trace so tools/trace_merge.py can place per-rank perf_counter
    timelines on one wall clock and group them by generation."""
    meta = {"anchor": {"wall_s": time.time(),
                       "ts_us": time.perf_counter_ns() / 1000.0}}
    try:
        from ..resilience.recorder import _process_rank
        meta["rank"] = _process_rank()
    except Exception:
        meta["rank"] = 0
    try:
        from ..resilience.recovery import current_generation
        meta["generation"] = current_generation()
    except Exception:
        meta["generation"] = 0
    return meta


def export_chrome_tracing(path, dir_name=None):
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    trace = _recorder.chrome_trace()
    trace.update(_trace_metadata())
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


def export_rank_trace(directory=None):
    """Export this rank's chrome trace as ``trace_rank<N>.json`` into the
    artifacts dir (PADDLE_TPU_ARTIFACTS_DIR), next to the flight-recorder
    dumps — the layout tools/trace_merge.py consumes."""
    if directory is None:
        from ..resilience.recorder import artifacts_dir
        directory = artifacts_dir()
    from ..resilience.recorder import _process_rank
    return export_chrome_tracing(
        os.path.join(directory, f"trace_rank{_process_rank()}.json"))


def summary(sorted_by="total", event_type=None):
    """Aggregate host spans; `event_type` filters to one chrome `cat`
    (e.g. "step_phase" shows only steptimer attribution spans)."""
    agg = _recorder.aggregate(event_type=event_type)
    cats = _recorder.categories()
    rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
    header = (f"{'Event':<48}{'Cat':<12}{'Calls':>8}{'Total(us)':>14}"
              f"{'Avg(us)':>12}{'Max(us)':>12}")
    lines = [header, "-" * len(header)]
    for name, (tot, cnt, mx) in rows:
        lines.append(f"{name:<48}{cats.get(name) or 'host':<12}{cnt:>8}"
                     f"{tot:>14.1f}{tot / cnt:>12.1f}{mx:>12.1f}")
    out = "\n".join(lines)
    print(out)
    return agg


# -- classic API (fluid/profiler.py parity) -----------------------------------
_classic = {"profiler": None}


def start_profiler(state="All", tracer_option="Default"):
    _recorder.enabled = True
    _recorder.clear()
    # session semantics: counter_samples() reports samples from this start
    # (aggregated registry metrics persist — only the ring is cleared)
    _metrics.get_registry().clear_samples()


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    _recorder.enabled = False
    summary()


def reset_profiler():
    _recorder.clear()
    _metrics.get_registry().clear_samples()


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option="Default"):
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)
