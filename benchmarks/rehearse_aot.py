#!/usr/bin/env python3
"""Compile a cell's train step at its real size for a described `v5e:2x2`,
with no chip, and print the compiler's memory analysis: how a cell's depth is
found before any chip time is spent. Run by hand, here on the CPU:

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_aot.py --workload <name> [--layers N] [--traffic <job>]

`--traffic` puts another file of benchmarks/jobs/ in the cell's place: a
four-chip job that has no cell yet, over a configuration that has one.

The program is built as the cell's entry builds it (on virtual CPU devices,
four for a four-chip cell), `to_static`'s eager discovery pass runs once at
the real size on the CPU (minutes), and the traced step is then lowered for
the described chips with every input's sharding carried over. What it prints
is one program's bytes per device, first as the un-donated program that the
second call runs (state held twice) and then as its donating twin; it is not
a chip run and gives no time.
"""
import argparse
import contextlib
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, help="try another depth than the configuration's")
    ap.add_argument("--traffic", help="try another job than the cell's")
    ns = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding
    from benchmarks import harness, program
    from benchmarks.entries import to_static_loop
    from paddle_tpu.distributed.mesh import build_mesh, get_mesh, trace_mesh
    from paddle_tpu.jit.to_static import _flatten_tensors

    cell = harness.load_cell(ns.workload)
    cfg, job, family, entry = cell["cfg"], cell["job"], cell["family"], cell["entry"]
    if ns.traffic:
        job = harness.read_json("jobs", ns.traffic + ".json")
        entry = importlib.import_module(f"benchmarks.entries.{job['entry']}")
    if ns.layers:
        cfg["num_layers"] = ns.layers
    shapes = family.reference.param_shapes(cfg)
    ctx = {"cfg": cfg, "job": job, "family": family, "seed": 0,
           "chips": job["chips"],
           "make_weights": lambda: harness.init_params(shapes, 0, cfg["weights_dtype"])}
    build = getattr(entry, "build", program.build)
    place = getattr(entry, "place", lambda paddle, a: paddle.to_tensor(a))
    paddle, model, opt, *wrapped = build(ctx)
    step = to_static_loop.make_step(paddle, family, *(wrapped or (model, opt)))
    x, y = family.Stream(cfg, job, 0).next()
    x, y = place(paddle, x), place(paddle, y)
    step(x, y)                                    # the eager discovery pass
    (prog,) = step.programs.values()
    step._build(prog, (x, y), {})                 # traces; compiles nothing

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    cpu_mesh = get_mesh() if job["chips"] > 1 else None
    if cpu_mesh is not None:
        tpu_mesh = Mesh(np.array(topo.devices[:job["chips"]]).reshape(
            cpu_mesh.devices.shape), cpu_mesh.axis_names)
        build_mesh(job["mesh"], list(tpu_mesh.devices.flat))

    def described(t):
        v = t._val
        if cpu_mesh is None:
            sharding = SingleDeviceSharding(topo.devices[0])
        else:
            spec = getattr(v.sharding, "spec", PartitionSpec())
            sharding = NamedSharding(tpu_mesh, spec)
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)

    args = (tuple(described(t) for t in prog.mutated),
            tuple(described(t) for t in prog.ro),
            tuple(described(t) for t in _flatten_tensors(((x, y), {}), [])))
    out = {"workload": ns.workload, "num_layers": cfg["num_layers"],
           "parameters": sum(int(np.prod(s)) for s, _ in shapes.values())}
    for name, donate in (("first_compiled_call", ()), ("donating_twin", (0,))):
        with (trace_mesh(tpu_mesh) if cpu_mesh is not None
              else contextlib.nullcontext()):
            compiled = jax.jit(prog.pure_fn, donate_argnums=donate).lower(*args).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        out[name] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_gib_per_device": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2 ** 30,
            "collectives": to_static_loop.count_collectives(text),
            "tpu_custom_calls": text.count("tpu_custom_call"),
        }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
