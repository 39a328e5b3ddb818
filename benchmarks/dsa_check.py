#!/usr/bin/env python3
"""How far the program's choices are from the float32 reference's, at the
seeded weights, on the cell's first batch, at the cell's own size: a cell
whose attention reads the keys a learned index picks.

    python3 benchmarks/dsa_check.py --workload <cell> --seeds 1,2

Per seed: the language-model loss and the index loss, each apart, program
beside reference (a run compares their sum); per layer, the share of the
reference's (query, key) picks that the program picked too and how many each
side picked (a key whose index score stands within bf16's rounding of its
row's threshold falls on the other side: flips at the edge of a set, never a
set of another size but by ties), and the share of tokens whose set of picked
experts differs (as benchmarks/route_check.py reads it for a sigmoid router),
and the expert layer's rows here with the busiest held slot's.
Run on the chip when the cell's limits are read (PERF.md section 6); the test
suite runs it at a small size. One JSON line per seed.
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
    from paddle_tpu.ops import sparse_index
    from paddle_tpu.ops.pallas import flash_attention
    cfg, job, family = cell["cfg"], cell["job"], cell["family"]
    ref = family.reference
    x, y = family.Stream(cfg, job, seed).next()
    seeded = harness.init_params(ref.param_shapes(cfg), seed, cfg["weights_dtype"])

    # the reference: its sets, its picks and its two losses
    noted = {}
    with jax.default_matmul_precision("highest"):
        p = {k: v.astype(jnp.float32) for k, v in seeded.items()}
        ref.forward(p, jnp.asarray(x), cfg, note=lambda name, v: noted.update(
            {name: np.asarray(v)}))
        want = [float(v) for v in jax.jit(
            lambda p, a, b: ref.loss_parts(p, a, b, cfg))(p, jnp.asarray(x), jnp.asarray(y))]
        del p

    # the program, eagerly, in eval mode (no block is rematerialised there,
    # so each index and each plan runs once, on concrete values)
    model = family.build_model(cfg)
    if cfg["weights_dtype"] == "bfloat16":
        model.bfloat16()
    names = family.program_names(cfg)
    model.set_state_dict({names[k]: paddle.Tensor(v) for k, v in seeded.items()})
    sets, plans = [], []
    index, plan = sparse_index.index_key_set, moe._route_plan
    sparse_index.index_key_set = lambda *a, **kw: sets.append(index(*a, **kw)) or sets[-1]
    moe._route_plan = lambda *a, **kw: plans.append(plan(*a, **kw)) or plans[-1]
    try:
        model.eval()
        with paddle.no_grad():
            _, lm, index_loss = model(paddle.to_tensor(x), labels=paddle.to_tensor(y))
    finally:
        sparse_index.index_key_set, moe._route_plan = index, plan
    got = [float(lm.item()), float(index_loss.item())]
    layers = []
    for i, ((mine, _), routed) in enumerate(zip(sets, plans)):
        theirs = noted[f"l{i}.key_set"]
        # where the index ran as kernels the set is in their layout
        mine = np.asarray(flash_attention.set_square(mine)) != 0
        picks = noted[f"l{i}.picks"].reshape(-1, cfg["num_experts_per_tok"])
        layers.append({
            "reference_pairs": int(theirs.sum()), "program_pairs": int(mine.sum()),
            "pairs_in_common_share": float((theirs & mine).sum() / theirs.sum()),
            "tokens_with_another_pick": float(np.mean(
                (np.sort(picks, axis=1) != np.sort(np.asarray(routed[0]), axis=1)).any(axis=1))),
            # the expert layer's rows here, and the busiest held slot's
            "rows_here": int(np.asarray(routed[6]).sum()),
            "rows_busiest_slot": int(np.asarray(routed[6]).max()),
        })
    return {"seed": seed, "tokens": int(x.size),
            "lm_loss": {"program": got[0], "reference": want[0],
                        "gap": abs(got[0] - want[0]) / want[0]},
            "index_loss": {"program": got[1], "reference": want[1],
                           "gap": abs(got[1] - want[1]) / want[1]},
            "layers": layers}


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
