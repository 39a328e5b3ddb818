#!/usr/bin/env python3
"""SHA-256 of a cell's lowered train step, to show that a change leaves a
cell's program as it was. Run by hand, here on the CPU, one process at a
time (each loads the TPU's compiler):

    python3 tools/lowered_step_sha.py <checkout> <workload> [masked.txt]

The step is built as `benchmarks/rehearse_aot.py` builds it (the eager
discovery pass at the real size on the CPU: minutes for an expert cell), the
program is then made to choose as on the chip (`ops.attention._platform`,
`flash_attention._interpret` patched after the discovery pass: the flash pair
where `takes_flash` says so, the expert layer's row moves staged too), and
the donating program, the one a window drives, is lowered for a described
`v5e:2x2`. The digest is of the StableHLO text with every kernel's payload
(`backend_config`: a Mosaic kernel's text carries its checkout's path and
line numbers) replaced by `<kernel>`. PERF.md section 6 keeps the digests:
cell 1's 96c69531...6d01 (no custom call), the LFM2 cell's 3c85c0c2...3df9.
"""
import hashlib
import json
import os
import re
import sys


def main(argv):
    tree, workload = os.path.abspath(argv[1]), argv[2]
    keep = os.path.abspath(argv[3]) if len(argv) > 3 else None
    os.chdir(tree)
    sys.path.insert(0, tree)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmarks import harness, program
    from benchmarks.entries import to_static_loop
    from paddle_tpu.jit.to_static import _flatten_tensors

    cell = harness.load_cell(workload)
    cfg, job, family, entry = cell["cfg"], cell["job"], cell["family"], cell["entry"]
    shapes = family.reference.param_shapes(cfg)
    ctx = {"cfg": cfg, "job": job, "family": family, "seed": 0, "chips": job["chips"],
           "make_weights": lambda: harness.init_params(shapes, 0, cfg["weights_dtype"])}
    paddle, model, opt, *wrapped = getattr(entry, "build", program.build)(ctx)
    step = to_static_loop.make_step(paddle, family, *(wrapped or (model, opt)))
    x, y = (paddle.to_tensor(a) for a in family.Stream(cfg, job, 0).next())
    step(x, y)                                    # the eager discovery pass
    # from here on the program chooses as on the chip: attention by
    # `takes_flash`'s rule of shapes, kernels staged and not interpreted
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas import flash_attention
    attention._platform = lambda *a, **k: "tpu"
    flash_attention._interpret = lambda x=None: False
    (prog,) = step.programs.values()
    step._build(prog, (x, y), {})                 # traces; compiles nothing

    one_chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def described(t):
        return jax.ShapeDtypeStruct(t._val.shape, t._val.dtype, sharding=one_chip)
    args = (tuple(described(t) for t in prog.mutated), tuple(described(t) for t in prog.ro),
            tuple(described(t) for t in _flatten_tensors(((x, y), {}), [])))
    text = jax.jit(prog.pure_fn, donate_argnums=(0,)).lower(*args).as_text()
    masked = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', 'backend_config = "<kernel>"', text)
    if keep:
        with open(keep, "w") as f:
            f.write(masked)
    print(json.dumps({"checkout": tree, "workload": workload,
                      "custom_calls": text.count("tpu_custom_call"), "characters": len(masked),
                      "sha256": hashlib.sha256(masked.encode()).hexdigest()}))


if __name__ == "__main__":
    main(sys.argv)
