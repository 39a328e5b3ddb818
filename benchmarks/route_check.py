#!/usr/bin/env python3
"""How far the program's routing is from the float32 reference's, at the
seeded weights, on the cell's first batch, at the cell's own size.

    python3 benchmarks/route_check.py --workload <cell> --seeds 1,2

Per seed and expert layer: the (token, expert) pairs the reference routes to
the held experts and the rows the program's device counter counted (no pair
is dropped, so they differ only by flips); the share of tokens whose set of
picked experts differs (a token whose fourth and fifth scores tie within the
rounding of the bf16 activations picks another expert); and per held expert
the gap between the program's and the reference's gradient norm of its first
projection. Run on the chip when the cell's limits are read (PERF.md section
6); the test suite runs it at a small size. One JSON line per seed.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check(cell, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from benchmarks import harness
    from paddle_tpu.incubate import moe
    cfg, job, family = cell["cfg"], cell["job"], cell["family"]
    ref = family.reference
    x, y = family.Stream(cfg, job, seed).next()
    shapes = ref.param_shapes(cfg)
    seeded = harness.init_params(shapes, seed, cfg["weights_dtype"])
    expert_leaves = sorted(k for k in shapes if k.endswith("e_w1"))

    # the reference: its picks, and its gradient one block of rows at a time
    picks = []
    with jax.default_matmul_precision("highest"):
        p = {k: v.astype(jnp.float32) for k, v in seeded.items()}
        ref._forward(p, jnp.asarray(x), cfg, jnp.matmul,
                     lambda pre, a: picks.append(np.asarray(
                         ref.route(p, pre, a, cfg, jnp.matmul)[0]).reshape(-1, cfg["num_experts_per_tok"])))
        grad = jax.jit(jax.grad(lambda p, a, b: ref.loss_fn(p, a, b, cfg)))
        rows = job["reference_rows_per_block"]
        want = {k: 0.0 for k in expert_leaves}
        for r in range(0, x.shape[0], rows):
            g = grad(p, jnp.asarray(x[r:r + rows]), jnp.asarray(y[r:r + rows]))
            for k in expert_leaves:
                want[k] = want[k] + g[k] * (rows / x.shape[0])
            del g
        want = {k: np.asarray(jnp.sqrt(jnp.sum(jnp.square(v), axis=(1, 2))))
                for k, v in want.items()}
        del p

    # the program, eagerly: a forward pass in eval mode with its plans watched
    # (no block is rematerialised there, so each plan runs once, on concrete
    # values, and nothing is kept for a backward pass), then the training
    # step's forward and backward for the gradients
    model = family.build_model(cfg)
    if cfg["weights_dtype"] == "bfloat16":
        model.bfloat16()
    names = family.program_names(cfg)
    model.set_state_dict({names[k]: paddle.Tensor(v) for k, v in seeded.items()})
    seen, plan = [], moe._route_plan
    moe._route_plan = lambda *a, **kw: seen.append(plan(*a, **kw)) or seen[-1]
    try:
        model.eval()
        with paddle.no_grad():
            model(paddle.to_tensor(x))
    finally:
        moe._route_plan = plan
    seen = [[np.asarray(v) for v in out] for out in seen]
    model.train()
    family.loss_of(model, paddle.to_tensor(x), paddle.to_tensor(y)).backward()
    held = np.asarray(cfg["held_experts"])
    layers, state = [], model.state_dict()
    for leaf, theirs, mine in zip(expert_leaves, picks, seen):
        got = np.asarray(mine[0])
        g = state[names[leaf]].grad._val.astype(jnp.float32)
        norms = np.asarray(jnp.sqrt(jnp.sum(jnp.square(g), axis=(1, 2))))
        layers.append({
            "reference_rows_here": int(np.isin(theirs, held).sum()),
            "program_rows_here": int(np.asarray(mine[6]).sum()),
            "tokens_with_another_pick": float(np.mean(
                (np.sort(theirs, axis=1) != np.sort(got, axis=1)).any(axis=1))),
            "expert_grad_norm_gap": [float(v) for v in
                                     np.abs(norms - want[leaf]) / want[leaf]],
        })
    return {"seed": seed, "tokens": int(x.size), "layers": layers}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ns = ap.parse_args(argv)
    from benchmarks import harness, run
    run.fix_caches(ns.workload)
    cell = harness.load_cell(ns.workload)
    run.find_device(cell["cell"]["chips"])
    for seed in (int(s) for s in ns.seeds.split(",")):
        print(json.dumps(check(cell, seed)), flush=True)


if __name__ == "__main__":
    main()
