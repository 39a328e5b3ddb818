#!/usr/bin/env python3
"""A second witness for a cell's loss limits: the plain reference computed
in the precision the configuration serves, compared with the float32
reference exactly as a run compares the program.

    python3 benchmarks/served_precision.py --workload <name> --seeds 1,2

The loss sees every weight rounded to `weights_dtype` while AdamW moves the
float32 values (the program's masters), and both operands of every matrix
product are rounded to it, float32 accumulation. Where the program's later
losses stand off the float32 reference's by more than the control's do
(benchmarks/control.py: float32 weights, float8 operands), this says whether
rounding the weights is the whole of it: the witness should land where the
program does. It decides no `correct`. One JSON line per seed, each number
compared beside its limit. Run on the chip at the cell's own size; the test
suite runs it at a small size (tests/benchmark/test_rehearsal_lfm2.py).

The rounding is XLA's ReducePrecision. A float32 -> bfloat16 -> float32 pair
of converts is excess precision to the TPU compiler, which takes it out: a
witness written with `astype` matched the float32 reference to the last bit
on the chip (PERF.md, PR 27).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rounded_to(dtype):
    """x -> x at `dtype`'s precision, still float32; the gradient passes
    straight through the rounding."""
    import jax
    import jax.numpy as jnp
    info = jnp.finfo(dtype)

    def rounded(x):
        q = jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)
        return x + jax.lax.stop_gradient(q - x)

    return rounded


class ServedWeights:
    """`reference` with its loss taken at the weights as they are served:
    rounded to `dtype`, the gradient going to the float32 values, as the
    program's goes from its bf16 weights to its masters."""

    def __init__(self, reference, dtype):
        self.reference, self.rounded = reference, rounded_to(dtype)

    def loss_fn(self, p, x, y, cfg, **kwargs):
        served = {k: self.rounded(v) for k, v in p.items()}
        return self.reference.loss_fn(served, x, y, cfg, **kwargs)


def checks(cell, seed):
    """The comparison's rows for one seed, the witness in the program's place."""
    import jax
    import jax.numpy as jnp
    from benchmarks import control, harness
    cfg, job = cell["cfg"], cell["job"]
    dtype = cfg["weights_dtype"]
    rounded = rounded_to(dtype)
    witness = harness.reference_numbers(
        ServedWeights(cell["family"].reference, dtype), cfg,
        *control.seeded(cell, seed), job["reference_rows_per_block"],
        jax.local_devices()[:job["chips"]],
        mm=lambda a, b: jnp.matmul(rounded(a), rounded(b)))
    return harness.compare(witness, control.reference_numbers(cell, seed),
                           cell["limits"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ns = ap.parse_args(argv)
    from benchmarks import harness, run
    run.fix_caches(ns.workload)
    cell = harness.load_cell(ns.workload)
    for seed in (int(s) for s in ns.seeds.split(",") if s):
        rows = checks(cell, seed)
        print(json.dumps({"seed": seed, "side": "served_" + cell["cfg"]["weights_dtype"],
                          "correct": all(r["ok"] for r in rows), "checks": rows}),
              flush=True)


if __name__ == "__main__":
    main()
