"""Step-phase attribution: where did this training step's time go?

A :class:`StepTimer` splits each step into named phases —

- ``step/input_wait``   — blocked on the data loader;
- ``step/h2d``          — host→device transfer / Tensor staging;
- ``step/compile``      — trace + XLA build of a compiled train step
  (jit/compiled_step.py); a steady state that keeps paying this phase is a
  retrace storm (docs/compiled_step.md);
- ``step/compute``      — dispatch + execution of the compiled step;
- ``step/collective_wait`` — eager collective tail (the watch_section wrap
  points in distributed/collective.py);
- ``step/optimizer``    — optimizer work outside the compiled step;
- ``step/ckpt_io``      — the BLOCKING portion of checkpoint save/restore
  only: under ``FLAGS_async_checkpoint`` that is the device→host snapshot
  (serialize/sha256/commit run on the background committer and show up in
  the ``ckpt.commit_ms`` metric, not here);
- ``step/integrity``    — SDC consensus checks (resilience/integrity.py).

Phases nest: a child's wall time is subtracted from its parent's SELF time
(per-thread phase stack), so the per-phase totals sum to attributed wall
time instead of double-counting (e.g. a collective_wait inside compute).

Because JAX dispatch is asynchronous, the host-side compute phase measures
dispatch, not execution. Every ``FLAGS_steptimer_sync_interval`` steps the
timer calls ``jax.block_until_ready`` on the step output (:meth:`sync`), so
sampled steps carry TRUE device-inclusive step time (``device_wait_ms``)
while the steady state keeps pipelining — that sampling is what keeps
instrumentation overhead <1% (self-measured in ``overhead_ms`` and asserted
in tests/test_observability.py, same contract as ``integrity.check_ms``).

Everything lands in the always-on metrics registry
(``steptimer.<phase>_ms`` histograms); as a ``jax.profiler.TraceAnnotation``
of the phase's name (the step as a ``StepTraceAnnotation``), so any XPlane
trace holds the phases on the host line beside ``to_static.*`` and on the
device operations' clock; and — while this package's profiler is tracing —
as chrome spans with ``cat="step_phase"`` so ``tools/trace_merge.py`` can
name the slowest rank per phase. See docs/observability.md.
"""
from __future__ import annotations

import collections
import threading
import time
from contextlib import contextmanager

import jax

from . import metrics as _metrics

__all__ = ["PHASES", "StepTimer", "get_steptimer", "reset_steptimer",
           "phase"]

PHASES = (
    "step/input_wait",
    "step/h2d",
    "step/compile",
    "step/compute",
    "step/collective_wait",
    "step/optimizer",
    "step/ckpt_io",
    "step/integrity",
)

_STEP_HISTORY = 4096
_EXPORT_CHECK_EVERY = 32  # steps between exporter-interval checks


def _short(name):
    return name.split("/", 1)[1] if "/" in name else name


def _percentile(values, q):
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sequence."""
    if not values:
        return 0.0
    vs = sorted(values)
    idx = min(len(vs) - 1, max(0, int(round(q / 100.0 * (len(vs) - 1)))))
    return float(vs[idx])


class StepTimer:
    """Per-process step/phase attribution accumulator.

    The clock is injectable (fake-clock acceptance tests reconstruct known
    phase durations exactly); ``sync_interval``/``enabled`` default from
    FLAGS. Thread model: phases stack per thread; one step context is
    active per thread, and the aggregate state is lock-guarded. The
    overhead accumulator is intentionally unlocked (monotonic float adds —
    a lost microsecond of self-time is not worth a lock on the hot path).
    """

    def __init__(self, clock=None, sync_interval=None, enabled=None,
                 registry=None):
        from ..framework.flags import get_flag
        self._clock = clock or time.perf_counter
        self._registry = registry if registry is not None \
            else _metrics.get_registry()
        self.enabled = bool(get_flag("FLAGS_steptimer", True)) \
            if enabled is None else bool(enabled)
        self.sync_interval = int(
            get_flag("FLAGS_steptimer_sync_interval", 16) or 0) \
            if sync_interval is None else int(sync_interval)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._global_phase_s = {}           # phases seen outside any step
        self._steps = collections.deque(maxlen=_STEP_HISTORY)
        self._step_count = 0
        self._overhead_s = 0.0
        self._export_countdown = _EXPORT_CHECK_EVERY

    # -- phase spans -----------------------------------------------------------
    @contextmanager
    def phase(self, name):
        """Attribute the enclosed work to `name` (nesting-aware: the
        enclosing phase is credited only its self time)."""
        if not self.enabled:
            yield
            return
        t_in = self._clock()
        tls = self._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        frame = [name, 0.0, 0.0]  # [name, start, child wall time]
        stack.append(frame)
        # the same span on the jax profiler's host line, on the device
        # operations' clock, whoever started the trace
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        frame[1] = self._clock()
        self._overhead_s += frame[1] - t_in
        try:
            yield
        finally:
            t1 = self._clock()
            ann.__exit__(None, None, None)
            dur = t1 - frame[1]
            stack.pop()
            self_s = max(0.0, dur - frame[2])
            if stack:
                stack[-1][2] += dur
            step = getattr(tls, "step", None)
            if step is not None:
                ph = step["phase_s"]
                ph[name] = ph.get(name, 0.0) + self_s
            else:
                # outside a step (serving batches, standalone loaders):
                # accumulate globally and feed the histogram directly
                with self._lock:
                    self._global_phase_s[name] = \
                        self._global_phase_s.get(name, 0.0) + self_s
                self._registry.observe(
                    f"steptimer.{_short(name)}_ms", self_s * 1e3)
            _chrome_span(name, frame[1], dur, "step_phase")
            self._overhead_s += self._clock() - t1

    def current_phase(self):
        """The innermost phase name open on THIS thread, or None. Cheap
        enough for per-event checks (the trace sanitizer keys its
        in-phase host-sync detection on it)."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1][0] if stack else None

    # -- step boundaries -------------------------------------------------------
    @contextmanager
    def step(self, n_steps=1):
        """One step boundary (or a scan group of `n_steps` fused steps —
        phase and wall times are normalized per step for the histograms).
        Nested step contexts pass through (the outer one owns the times).
        """
        if not self.enabled or getattr(self._tls, "step", None) is not None:
            yield self
            return
        t_in = self._clock()
        n = max(1, int(n_steps))
        sync_this = (self.sync_interval > 0
                     and self._step_count % self.sync_interval == 0)
        step = self._tls.step = {"phase_s": {}, "n": n, "sync": sync_this,
                                 "device_wait_s": 0.0, "t0": 0.0}
        ann = jax.profiler.StepTraceAnnotation("step",
                                               step_num=self._step_count)
        ann.__enter__()
        step["t0"] = self._clock()
        self._overhead_s += step["t0"] - t_in
        try:
            yield self
        finally:
            t1 = self._clock()
            ann.__exit__(None, None, None)
            self._tls.step = None
            wall = t1 - step["t0"]
            rec = {"n": n, "wall_s": wall, "phase_s": step["phase_s"],
                   "synced": sync_this,
                   "device_wait_s": step["device_wait_s"]}
            with self._lock:
                self._steps.append(rec)
                self._step_count += n
            items = [("steptimer.step_ms", wall / n * 1e3)]
            items.extend((f"steptimer.{_short(k)}_ms", v / n * 1e3)
                         for k, v in step["phase_s"].items())
            if sync_this and step["device_wait_s"]:
                items.append(("steptimer.device_wait_ms",
                              step["device_wait_s"] / n * 1e3))
            self._registry.observe_many(items)
            _chrome_span("step", step["t0"], wall, "step")
            self._overhead_s += self._clock() - t1
            # export cadence is seconds — checking the wall clock (and the
            # interval flag behind it) once every N steps is plenty, and
            # keeps the per-step cost to one integer decrement
            self._export_countdown -= 1
            if self._export_countdown <= 0:
                self._export_countdown = _EXPORT_CHECK_EVERY
                _metrics.get_exporter().maybe_export()

    def sync(self, value):
        """On sampled steps, block until `value` is device-ready so the
        enclosing phase (and the step wall time) include true device time;
        off-sample steps return immediately and keep pipelining."""
        step = getattr(self._tls, "step", None)
        if step is None or not step["sync"] or value is None:
            return value
        t0 = self._clock()
        try:
            jax.block_until_ready(
                value._val if hasattr(value, "_val") else value)
        except Exception:
            return value
        step["device_wait_s"] += self._clock() - t0
        return value

    # -- reading ---------------------------------------------------------------
    def breakdown(self):
        """Aggregate attribution over the recorded window: phase totals and
        fractions, per-step wall percentiles (synced steps preferred — they
        carry true device time), and self-measured overhead."""
        with self._lock:
            recs = list(self._steps)
            phase_s = dict(self._global_phase_s)
            steps = self._step_count
            overhead = self._overhead_s
        wall = 0.0
        device = 0.0
        per_step_ms = []
        synced_ms = []
        for r in recs:
            wall += r["wall_s"]
            device += r["device_wait_s"]
            for k, v in r["phase_s"].items():
                phase_s[k] = phase_s.get(k, 0.0) + v
            ms = r["wall_s"] / r["n"] * 1e3
            per_step_ms.append(ms)
            if r["synced"]:
                synced_ms.append(ms)
        attributed = sum(phase_s.values())
        total = wall if wall > 0 else attributed
        basis = synced_ms or per_step_ms
        return {
            "steps": steps,
            "phase_ms": {_short(k): v * 1e3
                         for k, v in sorted(phase_s.items())},
            "phase_fraction": {
                _short(k): (v / total if total else 0.0)
                for k, v in sorted(phase_s.items())},
            "wall_ms": wall * 1e3,
            "attributed_ms": attributed * 1e3,
            "unattributed_ms": max(0.0, (wall - attributed) * 1e3)
            if wall else 0.0,
            "step_ms_p50": _percentile(basis, 50),
            "step_ms_p99": _percentile(basis, 99),
            "device_wait_ms": device * 1e3,
            "synced_steps": len(synced_ms),
            "overhead_ms": overhead * 1e3,
        }

    @property
    def overhead_ms(self):
        return self._overhead_s * 1e3

    def reset(self):
        with self._lock:
            self._global_phase_s.clear()
            self._steps.clear()
            self._step_count = 0
            self._overhead_s = 0.0


_rec_ref = None


def _chrome_span(name, start_s, dur_s, cat):
    """Host-recorder span in the timer's clock domain (perf_counter by
    default, matching RecordEvent's timestamps). The recorder lookup is
    cached and the enabled check happens here, before the call — this is
    on every phase exit, so while not tracing it must cost two attribute
    loads, not an import."""
    global _rec_ref
    rec = _rec_ref
    if rec is None:
        from . import _recorder
        rec = _rec_ref = _recorder
    if not rec.enabled:
        return
    rec.record(name, start_s * 1e6, dur_s * 1e6,
               threading.get_ident(), cat)


_timer = None
_timer_lock = threading.Lock()


def get_steptimer():
    global _timer
    if _timer is None:
        with _timer_lock:
            if _timer is None:
                _timer = StepTimer()
    return _timer


def reset_steptimer():
    """Drop the process timer (tests / bench lanes re-read FLAGS)."""
    global _timer
    with _timer_lock:
        _timer = None


@contextmanager
def phase(name):
    """Module-level convenience: ``with steptimer.phase("step/h2d"): ...``"""
    with get_steptimer().phase(name):
        yield
