#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted as `setup_s`): imports, the seeded weights, the program's
eager discovery pass and its two compiles, the three compared steps (compiled,
from the seed again), a few settling steps. Then the cell's entry trains for
`--seconds`. The program first, the yardstick after: when the entry returns
the peak is read, which is the program's because nothing else has run; the
metrics are read; the program's state is given back (a run that cannot give
it back stops there); and only then the plain reference follows the three
compared steps, for `correct`. The last line of standard output is the
result: `correct`, `attempted`, `failed` (steps), `metrics`, `device`, and
with `--trace 1` a `breakdown`. With `--trace 0` the metrics are the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics. Every earlier
line is one JSON object of a phase.

Without a TPU, with fewer chips than the cell asks for, or on a device kind
that benchmarks/peaks.json does not list, the run exits non-zero before any
work and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# what the run may still hold on a chip when the reference starts, over what
# was held when it began (nothing, in a process of its own): the Kimi Linear
# cell's reference needs 12.7 GB of a chip's 15.75 GiB
HELD_BEFORE_REFERENCE = 64 * 2 ** 20


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def fix_caches(workload):
    """Both compile caches at fixed paths inside the checkout, whatever the
    environment says: the path is part of the cache's key, and two checkouts
    must share nothing. Before jax or paddle_tpu is imported.

    Each cell keeps its own directory, with no size cap: a machine that caps
    jax's cache (the chip machines do, at 192 MiB, and one cell's 400
    programs take 90 to 190 MiB) evicts one cell's programs while another
    runs, and every later run of the evicted cell compiles again (PERF.md,
    Findings PR 24)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache", workload)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["PADDLE_TPU_AUTOTUNE_CACHE"] = os.path.join(ROOT, ".autotune_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def find_device(chips, need_tpu=True):
    """jax's devices as the result line reports them; exits where the cell
    cannot be measured."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if need_tpu and device["platform"] != "tpu":
        sys.exit(f"benchmarks/run.py: no TPU, jax found {device}; nothing was run")
    if len(devs) < chips:
        sys.exit(f"benchmarks/run.py: the cell needs {chips} chips, jax found {device}")
    from benchmarks import harness
    peaks = harness.read_json("peaks.json")
    if need_tpu and device["kind"] not in peaks:
        sys.exit(f"benchmarks/run.py: no peaks known for device kind "
                 f"{device['kind']!r}; add it to benchmarks/peaks.json with its source")
    return device, peaks.get(device["kind"])


def run_cell(cell, seed, seconds, trace, need_tpu=True):
    """One run of a loaded cell (harness.load_cell); returns the result
    object. `need_tpu=False` is the rehearsal's way in (tests/benchmark):
    the same run, at a size the test sets, on whatever jax finds, from which
    only counts may be read."""
    from benchmarks import harness, program
    workload = cell["cell"]["name"]
    chips = cell["cell"]["chips"]
    device, peak = find_device(chips, need_tpu)
    events = harness.CompileEvents()
    import jax
    emit("device", **device, chips_used=chips, seed=seed, jax=jax.__version__,
         workload=workload)
    devices = jax.local_devices()[:chips]
    at_start = harness.held(devices)      # nothing, in a process of the run's own

    cfg, job, family = cell["cfg"], cell["job"], cell["family"]
    stream = family.Stream(cfg, job, seed)
    check_batches = [stream.next() for _ in range(harness.CHECK_STEPS)]
    shapes = family.reference.param_shapes(cfg)

    def make_weights():
        return harness.init_params(shapes, seed, cfg["weights_dtype"])

    ctx = {
        "cell": cell, "cfg": cfg, "job": job, "family": family, "seed": seed,
        "seconds": seconds, "trace": bool(trace), "stream": stream,
        "make_weights": make_weights, "rounds": [(make_weights, check_batches)],
        "events": events, "emit": emit, "chips": chips, "t_process": T_PROCESS,
        "trace_dir": os.path.join(ROOT, ".bench_trace", workload),
    }
    run = cell["entry"].run(ctx)   # see entries/to_static_loop.py for the keys

    # the program's peak: nothing but the program has run on these chips
    memory_peak = harness.memory_reading(devices, "peak_bytes_in_use")
    measured = {
        "device": device, "peak": peak, "chips": chips, "run": run,
        "tokens_per_step": family.tokens_per_step(job),
        "memory_peak_bytes": memory_peak,
        "flops_per_token": family.flops_per_token(cfg, job),
        "cell": cell, "trace_dir": ctx["trace_dir"],
    }
    # read while the program lives (its registry pulls the device counters
    # from the layers) and before the reference's compiles are counted
    bench = cell["bench"]
    wanted = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = {}
    for metric in wanted:
        # no peaks, no chip: a rehearsal, whose times and rates mean nothing
        if peak is None or workload not in harness.metric_cells(metric, bench):
            continue
        value = harness.load_reader(
            "layer_metrics" if trace else "end_metrics", metric["name"])(measured)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    # the chips given back before the yardstick runs: it may need more room
    # than the program left, and whatever it needs is then no part of the peak
    after_window = harness.held(devices)
    program.release()
    before_reference = harness.held(devices)
    if before_reference["bytes_in_use"] - at_start["bytes_in_use"] > HELD_BEFORE_REFERENCE:
        sys.exit(f"benchmarks/run.py: {before_reference['bytes_in_use']} bytes in "
                 f"{before_reference['live_arrays']} arrays are still held on a chip "
                 f"after the program was released ({after_window['bytes_in_use']} "
                 f"before, {at_start['bytes_in_use']} when the run began), over "
                 f"{HELD_BEFORE_REFERENCE} more than it began with: the reference "
                 f"would run beside the program's state; no result")

    t_ref = time.perf_counter()
    reference = harness.reference_numbers(
        family.reference, cfg, make_weights, check_batches,
        job["reference_rows_per_block"], devices)
    reference_s = time.perf_counter() - t_ref
    peak_after_reference = harness.memory_reading(devices, "peak_bytes_in_use")
    emit("reference", seconds=reference_s, losses=reference["losses"],
         peak_bytes_in_use=peak_after_reference)

    checks = harness.compare(run["program"], reference, cell["limits"])
    losses = run["losses"]
    failed = sum(1 for x in losses if x != x or abs(x) == float("inf"))
    tail = losses[-32:]
    checks += [
        {"name": "steps_not_finite", "value": failed, "limit": 0},
        {"name": "loss_last32_over_first", "limit": cell["limits"]["loss_last32_over_first"],
         "value": (sum(tail) / len(tail)) / run["program"]["losses"][0]},
        {"name": "compiles_in_window", "value": run["compiles_in_window"],
         "limit": 0},
    ]
    for row in checks:
        row.setdefault("ok", row["value"] <= row["limit"])
        emit("check", **row)
    correct = all(row["ok"] for row in checks)

    emit("memory", peak_bytes_in_use=memory_peak,
         peak_after_reference=peak_after_reference,
         bytes_limit=harness.memory_reading(devices, "bytes_limit"),
         bytes_in_use_after_window=after_window["bytes_in_use"],
         bytes_in_use_before_reference=before_reference["bytes_in_use"],
         live_arrays_before_reference=before_reference["live_arrays"])
    emit("compile_cache", dir=jax.config.jax_compilation_cache_dir,
         max_size=jax.config.jax_compilation_cache_max_size,
         requests=events.requests, hits=events.hits, misses=events.misses)

    device_out = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": len(losses), "failed": failed,
              "metrics": metrics, "device": device_out}
    reduced = run.get("trace")
    if reduced:
        device_out["busy_s"] = reduced["busy_s"]
        device_out["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    result["checks"] = checks     # each number compared beside its limit, last
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)
    fix_caches(ns.workload)
    from benchmarks import harness
    result = run_cell(harness.load_cell(ns.workload), ns.seed, ns.seconds,
                      ns.trace)
    print(json.dumps(result), flush=True)
    for row in result["checks"]:
        print(f"{row['name']} {row['value']} limit {row['limit']}", file=sys.stderr)


if __name__ == "__main__":
    main()
