"""Block-size autotuning for the kernel tier.

``Autotuner`` is a per-(op, signature) candidate search (ISSUE 5 tentpole).
Candidates are timed on device with ``jax.block_until_ready`` (warmup
excluded) and the winner is memoised in-process and persisted to an on-disk
cache (``PADDLE_TPU_AUTOTUNE_CACHE``, default ``<checkout>/.autotune_cache``;
atomic tmp+``os.replace`` writes like ``FileStore.put``) so steady-state runs
pay zero search cost.  Cache keys carry a kernel-source hash so editing a
kernel invalidates its stale tuned configs.  On CPU/interpret (tier-1 tests)
the search never runs: callers get a deterministic fallback and the disk
cache is left untouched.

Its one user is the Pallas kernels' tile search (ops/pallas/flash_attention.py).
Which kernel an op runs is not searched: that is a rule of shapes and
platform, stated where the op is (docs/kernels.md, "Which kernel runs").

Searches are reached from inside traces too (to_static, recompute): the
caller's ``make_args`` builds concrete probe arrays and the search runs on a
fresh thread, so tuning happens once per signature for staged programs as
well.
"""
from __future__ import annotations

import concurrent.futures
import functools
import hashlib
import inspect
import json
import os
import time

import jax
import jax.numpy as jnp

from ..profiler import metrics as _metrics
from ..profiler.compile_events import on_thread, setup_span

# ---------------------------------------------------------------------------
# counters: `autotune.<name>_total` in the metrics registry

_COUNTED = (
    "searches",            # timed candidate searches actually performed
    "candidate_failures",  # candidates that raised while being timed
    "mem_hits",            # in-process memo hits
    "disk_hits",           # persistent-cache hits (zero-search steady state)
    "fallbacks",           # unsearchable placements served the fallback table
    "cache_errors",        # corrupt/torn cache files ignored and rebuilt
)
_since = {}   # the registry's totals at the last reset_counters()


def _count(name):
    _metrics.get_registry().inc_counter(f"autotune.{name}_total")


def _totals():
    reg = _metrics.get_registry()
    return {name: int(reg.counter_value(f"autotune.{name}_total"))
            for name in _COUNTED}


def counters():
    """The registry's `autotune.*_total` since the last `reset_counters()`
    (a registry emptied since then counts from zero again)."""
    out = {}
    for name, total in _totals().items():
        since = _since.get(name, 0)
        out[name] = total - since if total >= since else total
    return out


def reset_counters():
    _since.update(_totals())


# ---------------------------------------------------------------------------
# signature helpers

def shape_bucket(shape):
    """Round each dim up to a power of two so nearby shapes share one tuned
    config (and one search) instead of fragmenting the cache per-shape."""
    return tuple(1 if d <= 1 else 1 << (int(d) - 1).bit_length() for d in shape)


_DTYPE_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16",
                "float64": "f64"}


def short_dtype(dtype):
    name = str(jnp.dtype(dtype))
    return _DTYPE_SHORT.get(name, name)


def device_platform(*vals):
    """'tpu' | 'cpu' | ... — where the computation will execute: the concrete
    operands' placement when known, else the default backend. Tracers carry
    no placement, so staged traces resolve to the backend they stage for."""
    for v in vals:
        if isinstance(v, jax.core.Tracer) or not isinstance(v, jax.Array):
            continue
        plats = {d.platform for d in v.devices()}
        if plats:
            return "tpu" if "tpu" in plats else sorted(plats)[0]
    return jax.default_backend()


def source_version(module_name):
    """Short hash of a kernel module's source text; autotune keys carry it so
    a kernel edit invalidates every tuned config it produced."""
    try:
        import importlib
        mod = importlib.import_module(module_name)
        src = inspect.getsource(mod)
    except Exception:
        return "unknown"
    return hashlib.sha1(src.encode()).hexdigest()[:12]


source_version = functools.lru_cache(maxsize=None)(source_version)


# ---------------------------------------------------------------------------
# persistent cache (FileStore-style atomic writes; torn files are misses)

def default_cache_dir():
    # inside the checkout (git-ignored, next to .jax_cache/): nothing the
    # program uses comes from outside the tree
    return os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".autotune_cache")


class AutotuneCache:
    """One JSON file per key under the cache dir. Readers tolerate missing,
    torn, or corrupt files (treated as a miss and rebuilt); writers go
    through tmp + os.replace so a concurrent reader never sees a partial
    record and concurrent writers last-write-win a whole record."""

    def __init__(self, path=None):
        self.path = path or default_cache_dir()

    def _file(self, key):
        digest = hashlib.sha1(key.encode()).hexdigest()[:24]
        return os.path.join(self.path, digest + ".json")

    def get(self, key):
        try:
            with open(self._file(key)) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            return None
        if not isinstance(rec, dict) or rec.get("key") != key:
            _count("cache_errors")
            return None
        return rec.get("value")

    def put(self, key, value):
        try:
            os.makedirs(self.path, exist_ok=True)
            path = self._file(key)
            tmp = "%s.tmp.%d" % (path, os.getpid())
            with open(tmp, "w") as f:
                json.dump({"key": key, "value": value}, f)
            os.replace(tmp, path)
        except OSError:
            pass  # the cache is an optimisation; never fail the op for it


def _jsonable(v):
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    return v


def _tuplify(v):
    if isinstance(v, list):
        return tuple(_tuplify(x) for x in v)
    return v


# ---------------------------------------------------------------------------
# measurement

def measure(fn, args, warmup=1, reps=3):
    """Best-of-`reps` wall time of fn(*args), with `warmup` untimed calls
    first so compilation and first-touch costs never pollute the timing."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# the tuner

def _outside_any_trace(fn):
    """Run fn() where no jax trace is active. Searches are reached from
    inside traces too (a to_static step's trace asks for the kernel's
    blocks), and there every call on concrete arrays would only be
    staged — the "timing" would be the time to stage it. Trace state is per
    thread, so a fresh thread executes for real."""
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        return pool.submit(fn).result()


class AutotuneError(RuntimeError):
    """A searched candidate that had to run (or every candidate) failed."""


class Autotuner:
    """Candidate search with a three-level lookup: in-process memo ->
    persistent disk cache -> timed search (device only). `measure_fn`,
    `searchable`, and `cache_dir` are injectable for hermetic tests."""

    def __init__(self, cache_dir=None, measure_fn=None, searchable=None,
                 warmup=1, reps=3):
        self._cache = AutotuneCache(cache_dir)
        self._measure = measure_fn or (
            lambda fn, args: measure(fn, args, warmup, reps))
        self._searchable_override = searchable
        self._mem = {}
        self.first_failure = None   # text of the first candidate exception
        self.last_times = {}        # key -> {repr(candidate): seconds}

    def decisions(self):
        """{key: winner} of every (op, signature) answered in this process —
        searched, read from disk, or served from the fallback."""
        return dict(self._mem)

    def searchable(self):
        if self._searchable_override is not None:
            return bool(self._searchable_override())
        from ..framework.flags import get_flag
        if not get_flag("FLAGS_autotune", True):
            return False
        return device_platform() == "tpu"

    def get(self, op, signature, *, candidates, build, make_args, fallback,
            version="", required=()):
        """Return the winning candidate for (op, signature).

        candidates: iterable of JSON-able candidate configs.
        build(cand): callable to time (given the args from make_args()).
        make_args(): concrete probe arguments (called only when searching).
        fallback: deterministic answer for unsearchable placements only.
        required: candidates that must build and run — one that fails
            raises instead of losing the search by default.

        A candidate that raises while being timed is counted
        (`candidate_failures`, first message in `first_failure`) and loses;
        if every candidate fails the search raises: on a searchable
        placement a kernel the compiler refuses is an error, never a silent
        switch to another path.
        """
        key = "%s|%s|v=%s" % (op, signature, version)
        if key in self._mem:
            _count("mem_hits")
            return self._mem[key]
        got = self._cache.get(key)
        if got is not None:
            _count("disk_hits")
            got = _tuplify(got)
            self._mem[key] = got
            return got
        if not self.searchable():
            # deterministic fallback; memoised in-process only, so a later
            # run on a real device still gets to search
            _count("fallbacks")
            self._mem[key] = fallback
            return fallback
        times, errors = {}, {}

        def search(span):
            with on_thread(span):   # the candidates' compiles count under it
                args = make_args()
                for cand in candidates:
                    try:
                        times[cand] = self._measure(build(cand), args)
                    except Exception as e:  # counted; raised below when it matters
                        _count("candidate_failures")
                        errors[cand] = e
                        if self.first_failure is None:
                            self.first_failure = "%s|%s %r: %s: %s" % (
                                op, signature, cand, type(e).__name__, e)

        with setup_span("autotune.search", op=op, signature=signature) as span:
            _outside_any_trace(functools.partial(search, span))
            span["attrs"].update(candidates=len(times) + len(errors),
                                 failed=len(errors))
        _count("searches")
        for cand in required:
            if cand in errors:
                raise AutotuneError(
                    "%s|%s: required candidate %r failed to build or run"
                    % (op, signature, cand)) from errors[cand]
        if not times:
            raise AutotuneError(
                "%s|%s: every candidate failed to build or run (%d tried)"
                % (op, signature, len(errors))) from next(iter(errors.values()))
        best = min(times, key=times.get)
        self.last_times[key] = {repr(c): t for c, t in times.items()}
        self._cache.put(key, _jsonable(best))
        self._mem[key] = best
        return best


_TUNER = [None]


def get_tuner():
    if _TUNER[0] is None:
        _TUNER[0] = Autotuner()
    return _TUNER[0]


def set_tuner(tuner):
    """Swap the process tuner (tests); returns the previous one."""
    old = _TUNER[0]
    _TUNER[0] = tuner
    return old
