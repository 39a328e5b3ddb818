"""Block-size autotuning and a measured fusion policy for the kernel tier.

Two services for the Pallas/fused-op layer (ISSUE 5 tentpole):

* ``Autotuner`` — a per-(op, signature) candidate search.  Candidates are
  timed on device with ``jax.block_until_ready`` (warmup excluded) and the
  winner is memoised in-process and persisted to an on-disk cache
  (``PADDLE_TPU_AUTOTUNE_CACHE``, default ``<checkout>/.autotune_cache``;
  atomic tmp+``os.replace`` writes like ``FileStore.put``) so steady-state runs pay zero search cost.  Cache keys
  carry a kernel-source hash so editing a kernel invalidates its stale tuned
  configs.  On CPU/interpret (tier-1 tests) the search never runs: callers
  get a deterministic fallback and the disk cache is left untouched.

* A *measured fusion policy* — each fused op registers its fused and unfused
  candidates through :func:`choose_fused`; under ``FLAGS_fusion_policy=auto``
  the dispatcher runs whichever side measured faster for the live
  (shape-bucket, dtype, direction, placement) signature.  A fused path that
  loses (e.g. fused_ffn bf16 fwd, 0.551x in OPBENCH r5) automatically falls
  back to the unfused XLA composition.  Off-device the decision comes from
  ``_POLICY_FALLBACK``, seeded with the checked-in OPBENCH.json losers, so
  CPU behaviour is deterministic and matches what auto would pick on TPU.

Searches are driven from op entry points *before* ``dispatch.apply`` wraps
everything in ``jax.vjp`` tracing: when the incoming values are tracers
(to_static / recompute) the probe synthesises concrete arrays of the same
shape/dtype, so tuning still happens exactly once per signature even for
fully staged programs.
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
import numpy as np

# ---------------------------------------------------------------------------
# counters (test/observability seam; profiler counter events ride on top)

_COUNTERS = {
    "searches": 0,       # timed candidate searches actually performed
    "candidate_failures": 0,  # candidates that raised while being timed
    "mem_hits": 0,       # in-process memo hits
    "disk_hits": 0,      # persistent-cache hits (zero-search steady state)
    "fallbacks": 0,      # unsearchable placements served the fallback table
    "cache_errors": 0,   # corrupt/torn cache files ignored and rebuilt
    "policy_fused": 0,   # fusion-policy decisions that kept the fused path
    "policy_unfused": 0,  # fusion-policy decisions that fell back to unfused
}


def counters():
    return dict(_COUNTERS)


def reset_counters():
    for k in _COUNTERS:
        _COUNTERS[k] = 0


def _record(name, value):
    """Mirror a decision onto the profiler timeline as a counter event."""
    try:
        from .. import profiler
        profiler.record_counter(name, value)
    except Exception:
        pass


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
            _COUNTERS["cache_errors"] += 1
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


def _synth_args(raw_args):
    """Concrete stand-ins for a probe run: tracers (to_static / recompute /
    vjp staging) are replaced by fixed-seed host-generated arrays of the same
    shape/dtype; already-concrete operands pass through untouched."""
    rng = np.random.default_rng(0)
    out = []
    for a in raw_args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is None or dtype is None:
            out.append(a)
            continue
        if not isinstance(a, jax.core.Tracer):
            out.append(jnp.asarray(a))
            continue
        if jnp.issubdtype(dtype, jnp.inexact):
            host = rng.standard_normal(shape, dtype=np.float32)
            out.append(jnp.asarray(host).astype(dtype))
        else:
            out.append(jnp.zeros(shape, dtype))
    return out


# ---------------------------------------------------------------------------
# the tuner

def _outside_any_trace(fn):
    """Run fn() where no jax trace is active. Searches are reached from
    inside traces too (the fused probe is a jit whose trace asks for the
    kernel's blocks), and there every call on concrete arrays would only be
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
            _COUNTERS["mem_hits"] += 1
            return self._mem[key]
        got = self._cache.get(key)
        if got is not None:
            _COUNTERS["disk_hits"] += 1
            got = _tuplify(got)
            self._mem[key] = got
            return got
        if not self.searchable():
            # deterministic fallback; memoised in-process only, so a later
            # run on a real device still gets to search
            _COUNTERS["fallbacks"] += 1
            self._mem[key] = fallback
            return fallback
        times, errors = {}, {}

        def search():
            args = make_args()
            for cand in candidates:
                try:
                    times[cand] = self._measure(build(cand), args)
                except Exception as e:  # counted; raised below when it matters
                    _COUNTERS["candidate_failures"] += 1
                    errors[cand] = e
                    if self.first_failure is None:
                        self.first_failure = "%s|%s %r: %s: %s" % (
                            op, signature, cand, type(e).__name__, e)

        _outside_any_trace(search)
        _COUNTERS["searches"] += 1
        _record("autotune.search/%s" % op, 1)
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


# ---------------------------------------------------------------------------
# measured fusion policy

# Deterministic decisions for unsearchable placements (CPU / interpret /
# tier-1), seeded from the checked-in OPBENCH.json (TPU v5 lite, r5): every
# (op, dtype, direction) whose fused path measured *slower* than the unfused
# XLA composition routes unfused; everything else stays fused.
_POLICY_FALLBACK = {
    ("fused_ffn", "bf16", "fwd"): "unfused",           # 0.551x
    ("fused_ffn", "f32", "fwd_bwd"): "unfused",        # 0.939x
    ("fused_conv_bn", "bf16", "fwd"): "unfused",       # 0.995x
    ("fused_conv_bn", "bf16", "fwd_bwd"): "unfused",   # 0.995x
    ("fused_conv_bn", "f32", "fwd_bwd"): "unfused",    # 1.000x wash, strictly slower
    ("fused_residual_ln", "bf16", "fwd_bwd"): "unfused",  # 0.975x
}

# Ambient direction hint: recompute() differentiates its region even though
# the traced body runs under no_grad(), so grad-mode inspection alone would
# misclassify it as inference. fleet.utils.recompute sets this to "fwd_bwd"
# around the traced call.
_FORCE_DIRECTION = [None]


def fusion_policy():
    from ..framework.flags import get_flag
    pol = str(get_flag("FLAGS_fusion_policy", "auto") or "auto").lower()
    if pol not in ("auto", "always", "never"):
        raise ValueError(
            "FLAGS_fusion_policy must be auto|always|never, got %r" % pol)
    return pol


def auto_winner(fused_ms, unfused_ms):
    """Strict measured winner: fused dispatches only when it is not slower."""
    return "fused" if fused_ms <= unfused_ms else "unfused"


def policy_table_choice(op, dtype_short, direction):
    return _POLICY_FALLBACK.get((op, dtype_short, direction), "fused")


def current_direction():
    if _FORCE_DIRECTION[0] is not None:
        return _FORCE_DIRECTION[0]
    from ..core import autograd
    return "fwd_bwd" if autograd.is_grad_enabled() else "fwd"


def _grad_probe(fn, raw_args):
    """Jitted fwd+bwd probe: grad of a scalar reduction of fn's outputs with
    respect to every inexact operand — what the op costs inside a train
    step, which is the regime the policy is choosing for."""
    argnums = tuple(
        i for i, a in enumerate(raw_args)
        if getattr(a, "dtype", None) is not None
        and jnp.issubdtype(a.dtype, jnp.inexact))

    def loss(*args):
        outs = fn(*args)
        return sum(jnp.sum(o.astype(jnp.float32))
                   for o in jax.tree_util.tree_leaves(outs))

    if not argnums:
        return jax.jit(fn)
    return jax.jit(jax.grad(loss, argnums=argnums))


def choose_fused(op, fused_prim, unfused_prim, raw_args, *, module=None):
    """Pick the fused or unfused primitive for this call.

    raw_args are the unwrapped (jax-level) operands — possibly tracers.
    Returns (prim, choice) where choice is "fused" | "unfused". The decision
    is recorded as a fusion_policy/<op> profiler counter (1 = fused).
    """
    pol = fusion_policy()
    if pol == "always":
        choice = "fused"
    elif pol == "never":
        choice = "unfused"
    else:
        choice = _auto_choice(op, fused_prim, unfused_prim, raw_args, module)
    _COUNTERS["policy_fused" if choice == "fused" else "policy_unfused"] += 1
    _record("fusion_policy/%s" % op, 1.0 if choice == "fused" else 0.0)
    return (fused_prim if choice == "fused" else unfused_prim), choice


def _auto_choice(op, fused_prim, unfused_prim, raw_args, module):
    lead = raw_args[0]
    dt = short_dtype(lead.dtype)
    direction = current_direction()
    fallback = policy_table_choice(op, dt, direction)
    tuner = get_tuner()
    if not tuner.searchable():
        # skip signature/string assembly on the hot eager path off-device
        _COUNTERS["fallbacks"] += 1
        return fallback
    bucket = "x".join(str(d) for d in shape_bucket(lead.shape))
    sig = "%s|%s|%s|%s" % (bucket, dt, direction,
                           device_platform(*raw_args))
    version = source_version(module) if module else ""

    def build(cand):
        fn = fused_prim if cand == "fused" else unfused_prim
        if direction == "fwd_bwd":
            return _grad_probe(fn, raw_args)
        return jax.jit(fn)

    def make_args():
        return _synth_args(raw_args)

    # the fused side must build and run where a search is possible: losing a
    # measurement is fine, failing is not an answer of "unfused"
    return tuner.get("fusion.%s" % op, sig, candidates=("fused", "unfused"),
                     build=build, make_args=make_args, fallback=fallback,
                     version=version, required=("fused",))
