#!/usr/bin/env python3
"""The two readings a limit of `correct` is set from, in one process.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 --program-seeds 11,12,...

The control: the plain reference put in the program's place and computed one
precision below the configuration's, compared with the float32 reference
exactly as a run compares the program. It has to come out as not correct.
The program: the cell's entry as a run drives it, with one round of compared
steps per seed (the state put back to each seed in turn) and a window of
half a second. Prints one JSON line per seed with each number compared
beside its limit. Run on the chip at the cell's own size when a limit is
set; the test suite runs it at a small size (tests/benchmark/test_rehearsal.py).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the nearest precision below the one a configuration states
LOWER = {"bfloat16": "float8_e4m3fn"}


def lower_precision_mm(dtype):
    """Matrix multiplication with both operands rounded to `dtype`, float32
    accumulation. float8 operands are scaled per tensor to the format's
    range, as an fp8 training recipe does; a two-byte type is rounded by
    XLA's ReducePrecision (served_precision.rounded_to: the TPU compiler
    takes a float32 -> bfloat16 -> float32 pair of converts out, and the
    control would be the reference itself). Gradients pass the rounding
    straight through."""
    import jax
    import jax.numpy as jnp
    from benchmarks import served_precision
    target = jnp.dtype(dtype)

    def scaled(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(target).max)
        q = (x / scale).astype(target).astype(jnp.float32) * scale
        return x + jax.lax.stop_gradient(q - x)

    rounded = scaled if target.itemsize == 1 else served_precision.rounded_to(target)
    return lambda a, b: jnp.matmul(rounded(a), rounded(b))


def seeded(cell, seed):
    """(make_weights, the first batches) of a seed, as run.py draws them."""
    from benchmarks import harness
    cfg, job, family = cell["cfg"], cell["job"], cell["family"]
    stream = family.Stream(cfg, job, seed)
    batches = [stream.next() for _ in range(harness.CHECK_STEPS)]
    shapes = family.reference.param_shapes(cfg)
    return (lambda: harness.init_params(shapes, seed, cfg["weights_dtype"])), batches


def reference_numbers(cell, seed, mm=None):
    import jax
    from benchmarks import harness
    job = cell["job"]
    return harness.reference_numbers(
        cell["family"].reference, cell["cfg"], *seeded(cell, seed),
        job["reference_rows_per_block"], jax.local_devices()[:job["chips"]], mm=mm)


def control_checks(cell, seed):
    """The comparison's rows for one seed, the control in the program's place."""
    from benchmarks import harness
    control = reference_numbers(
        cell, seed, mm=lower_precision_mm(LOWER[cell["cfg"]["weights_dtype"]]))
    return harness.compare(control, reference_numbers(cell, seed), cell["limits"])


def program_checks(cell, seeds, need_tpu=True):
    """The comparison's rows for each seed, the program read as a run reads
    it: every reference first, then one entry run of as many rounds."""
    import time
    from benchmarks import harness, run
    run.find_device(cell["cell"]["chips"], need_tpu)
    references = [reference_numbers(cell, seed) for seed in seeds]
    rounds = [seeded(cell, seed) for seed in seeds]
    ctx = {
        "cell": cell, "cfg": cell["cfg"], "job": cell["job"], "family": cell["family"],
        "seed": seeds[-1], "seconds": 0.5, "trace": False,
        "stream": cell["family"].Stream(cell["cfg"], cell["job"], seeds[-1]),
        "make_weights": rounds[-1][0], "rounds": rounds,
        "events": harness.CompileEvents(), "emit": run.emit,
        "chips": cell["cell"]["chips"], "t_process": time.perf_counter(),
    }
    out = cell["entry"].run(ctx)
    return [harness.compare(prog, ref, cell["limits"])
            for prog, ref in zip(out["rounds"], references)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="", help="the control's, comma-separated")
    ap.add_argument("--program-seeds", default="", help="the program's")
    ns = ap.parse_args(argv)
    from benchmarks import harness, run
    run.fix_caches(ns.workload)
    cell = harness.load_cell(ns.workload)

    def report(side, seed, rows):
        print(json.dumps({"seed": seed, "side": side,
                          "correct": all(r["ok"] for r in rows), "checks": rows}),
              flush=True)

    lower = LOWER[cell["cfg"]["weights_dtype"]]
    for seed in (int(s) for s in ns.seeds.split(",") if s):
        report(lower, seed, control_checks(cell, seed))
    seeds = [int(s) for s in ns.program_seeds.split(",") if s]
    if seeds:
        for seed, rows in zip(seeds, program_checks(cell, seeds)):
            report("program", seed, rows)


if __name__ == "__main__":
    main()
