"""What every cell shares: the manifest, weights and numbers from a seed, the
reference's three steps, the comparison that decides `correct`, and the
compile counter. Imports nothing of paddle_tpu.
"""
import importlib
import importlib.util
import json
import os
import statistics

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
CHECK_STEPS = 3   # the reference follows the run's first three steps


def read_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(workload):
    """The cell named `workload`: its manifest entry, configuration, job
    (the traffic file), limits, and the family and entry modules they name."""
    bench = manifest()
    # parked.json: cells and configurations whose files are kept for a later
    # PR and that BENCHMARK.json does not list, so no check runs them and no
    # metric is reported in them; an entry of BENCHMARK.json shadows them
    parked = read_json("parked.json")
    cells = {w["name"]: w for w in parked["workloads"] + bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(w['name'] for w in bench['workloads'])}")
    cell = cells[workload]
    configs = {c["name"]: c for c in parked["configs"] + bench["configs"]}
    cfg_entry = configs[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    job = read_json("jobs", cell["traffic"] + ".json")
    return {
        "bench": bench, "cell": cell, "cfg": cfg, "job": job,
        "limits": read_json("limits", workload + ".json"),
        "family": importlib.import_module(f"benchmarks.families.{cfg['family']}"),
        "entry": importlib.import_module(f"benchmarks.entries.{job['entry']}"),
    }


def load_reader(kind, name):
    """The `read(measured)` of benchmarks/<kind>/<name>.py; a metric's name
    may hold dots, so its reader is loaded by path."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metric_cells(metric, bench):
    """The cells a metric is reported in: its `workloads`, else every cell."""
    return metric.get("workloads") or [w["name"] for w in bench["workloads"]]


def longest_intervals(intervals, k=3):
    """[[index, seconds]] of the `k` longest step intervals of a window, for
    the `train` line: a run that reads far off (one window in thirty lost
    4.9 s with its p95 unmoved: PERF.md, the fix round) then says whether
    one stall or many made it so, and where in the window."""
    order = sorted(range(len(intervals)), key=intervals.__getitem__)[-k:]
    return [[i, intervals[i]] for i in reversed(order)]


# ---------------------------------------------------------------------------
# numbers from the seed

def seed_key(seed):
    """A jax PRNG key from any whole number (seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.wrap_key_data(
        np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32))


def init_params(shapes, seed, dtype):
    """Every leaf of `shapes` ({name: (shape, std | 'ones' | 'zeros')}) on
    the device, in `dtype`, from one jitted call."""
    import jax
    import jax.numpy as jnp
    names = sorted(shapes)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, init = shapes[name]
            if init == "ones":
                leaf = jnp.ones(shape, jnp.float32)
            elif init == "zeros":
                leaf = jnp.zeros(shape, jnp.float32)
            else:
                leaf = init * jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
            out[name] = leaf.astype(dtype)
        return out

    return make(seed_key(seed))


def leaf_norms(leaves):
    """{name: l2 norm in float32} of a dict of arrays, in one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in tree.items()}

    return {k: float(v) for k, v in norms(leaves).items()}


def vector_leaves(leaves, scale=1.0):
    """The one-dimensional leaves (biases, LayerNorm gains) on the host in
    float32: small enough to keep, so that the program's and the
    reference's can be compared entry by entry."""
    return {k: scale * np.asarray(v, np.float32)
            for k, v in leaves.items() if v.ndim == 1}


def diff_norms(after, before):
    """{name: ||after - before||} in float32; `before` may be bf16."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in a}

    return {k: float(v) for k, v in norms(after, before).items()}


# ---------------------------------------------------------------------------
# the reference's three steps

def spread_over(devices):
    """A placement for the reference's float32 leaves: whole on one device;
    over several, every matrix split along its first axis that divides. The
    arithmetic is jax.numpy's either way; only where the numbers live
    changes, so that on a four-chip cell the reference needs a chip's share
    of the room and not all of it on one."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    if len(devices) == 1:
        return lambda leaf: leaf
    mesh = Mesh(np.array(devices), ("reference",))

    def place(leaf):
        spec = [None] * leaf.ndim
        if leaf.ndim >= 2:
            for axis, size in enumerate(leaf.shape):
                if size % len(devices) == 0:
                    spec[axis] = "reference"
                    break
        return jax.device_put(leaf, NamedSharding(mesh, PartitionSpec(*spec)))

    return place


def reference_numbers(ref, cfg, make_params, batches, rows_per_block,
                      devices, mm=None):
    """Follow `batches` (the run's first steps, numpy (inputs, labels)) with
    the plain reference in float32 at 'highest' matmul precision: each
    step's loss, the first gradient's norm per leaf, and the norm of each
    leaf's change after the last step. Gradients are accumulated over blocks
    of `rows_per_block` rows so that the float32 backward fits the chip.
    `make_params()` gives the seeded weights in the dtype they are served
    in; they are upcast, so both sides start from the same values, and made
    again at the end rather than kept, to leave the memory to the backward.
    `devices` are the cell's chips (see `spread_over`)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import adamw

    opt = cfg["optimizer"]
    kwargs = {} if mm is None else {"mm": mm}

    @jax.jit
    def block_grad(p, x, y):
        return jax.value_and_grad(
            lambda p: ref.loss_fn(p, x, y, cfg, **kwargs))(p)

    @jax.jit
    def accumulate(acc, g, w):
        return jax.tree_util.tree_map(lambda a, b: a + w * b, acc, g)

    out = {"losses": []}
    with jax.default_matmul_precision("highest"):
        place = spread_over(devices)
        p = {k: place(v.astype(jnp.float32)) for k, v in make_params().items()}
        state = adamw.init(p)
        for step, (x, y) in enumerate(batches):
            rows = x.shape[0]
            if rows % rows_per_block:
                raise ValueError(f"{rows} rows do not split into blocks of "
                                 f"{rows_per_block}")
            grads, loss, w = None, 0.0, rows_per_block / rows
            for r in range(0, rows, rows_per_block):
                l, g = block_grad(p, jnp.asarray(x[r:r + rows_per_block]),
                                  jnp.asarray(y[r:r + rows_per_block]))
                grads = g if w == 1.0 else accumulate(
                    grads or jax.tree_util.tree_map(jnp.zeros_like, p), g, w)
                loss += w * float(l)
            out["losses"].append(loss)
            if step == 0:
                out["grad_norms"] = leaf_norms(grads)
                out["grad_vectors"] = vector_leaves(grads)
            p, state = adamw.update(
                p, grads, state, lr=opt["learning_rate"], beta1=opt["beta1"],
                beta2=opt["beta2"], eps=opt["epsilon"],
                weight_decay=opt["weight_decay"])
        out["update_norms"] = diff_norms(p, make_params())
    return out


# ---------------------------------------------------------------------------
# the comparison

def worst_leaf_gap(prog, ref):
    """(gap, leaf): the widest |program norm - reference norm| over leaves,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    floor = statistics.median(ref.values())
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], floor) for k in ref}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def compare(prog, ref, limits):
    """Each number compared, beside its limit: a list of dicts with `name`,
    `value`, `limit`, `ok` (and the leaf a worst-leaf gap was read at).
    `prog` and `ref` hold `losses`, `grad_norms`, `grad_vectors`,
    `update_norms`; `prog`, where a program made it, also how many of its
    compared steps ran another program than the window's."""
    loss_gaps = [abs(a - b) / abs(b)
                 for a, b in zip(prog["losses"], ref["losses"])]
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    # a leaf whose gradient is zero by the mathematics (the attention keys'
    # bias: softmax is invariant to it) gets a gradient of rounding noise,
    # which Adam's first steps turn into full-size updates in whatever
    # precision made the noise: its update says nothing about a fault
    floor = 1e-4 * statistics.median(ref["grad_norms"].values())
    moved = [k for k, g in ref["grad_norms"].items() if g > floor]
    upd_gap, upd_leaf = worst_leaf_gap({k: prog["update_norms"][k] for k in moved},
                                       {k: ref["update_norms"][k] for k in moved})
    # the first gradient of every bias and LayerNorm gain, entry by entry:
    # the norm of the difference over the reference's norm. A norm gap sees
    # rounding only in second order; this sees it in first
    keys = sorted(ref["grad_vectors"])
    diff = np.concatenate([prog["grad_vectors"][k] - ref["grad_vectors"][k]
                           for k in keys])
    whole = np.concatenate([ref["grad_vectors"][k] for k in keys])
    rows = [
        # the loss at the seeded weights, and the losses after one and two
        # updates: Adam's first updates move every weight by the learning
        # rate whatever the gradient's size, so the later losses swing
        {"name": "first_loss_gap", "value": loss_gaps[0],
         "program": prog["losses"], "reference": ref["losses"]},
        {"name": "later_loss_gap", "value": max(loss_gaps[1:])},
        {"name": "grad_norm_gap", "value": grad_gap, "leaf": grad_leaf},
        {"name": "grad_vector_error",
         "value": float(np.linalg.norm(diff) / np.linalg.norm(whole))},
        {"name": "update_norm_gap", "value": upd_gap, "leaf": upd_leaf},
        # compared steps that compiled or did not consume their state: they
        # ran another program than the donating one the window drives
        {"name": "steps_off_the_window_program",
         "value": prog.get("steps_off_the_window_program", 0), "limit": 0},
    ]
    for row in rows:
        if "limit" not in row:
            row["limit"] = limits[row["name"]]
        row["ok"] = bool(np.isfinite(row["value"])
                         and row["value"] <= row["limit"])
    return rows


# ---------------------------------------------------------------------------
# memory

def memory_reading(devices, key):
    """The largest `memory_stats()[key]` over `devices`; 0 where the backend
    keeps no such statistic (the CPU)."""
    return max((d.memory_stats() or {}).get(key, 0) for d in devices)


def held(devices):
    """What is held now: `bytes_in_use` on the fullest of `devices` by its
    allocator or, where the backend keeps no statistic (the CPU), the bytes
    of the arrays alive; and how many arrays are alive (`live_arrays`)."""
    import jax
    live = jax.live_arrays()
    stats = [d.memory_stats() for d in devices]
    in_use = max(s["bytes_in_use"] for s in stats) if all(stats) \
        else sum(a.nbytes for a in live)
    return {"bytes_in_use": in_use, "live_arrays": len(live)}


# ---------------------------------------------------------------------------
# counters

class CompileEvents:
    """What jax.monitoring reports of compilation: every request to compile
    (a hit in the persistent cache is one too), and the cache's hits and
    misses. `requests` read at the window's two ends gives the compiles
    inside it."""

    def __init__(self):
        import jax.monitoring
        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
