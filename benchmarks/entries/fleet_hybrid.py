"""The to_static loop of entries/to_static_loop.py over fleet hybrid
parallelism: data parallel x tensor parallel on one host's chips.

The job's `mesh` gives the degrees ({"data": 2, "model": 2}). The model is
built with `tensor_parallel=True` (column- and row-parallel projections, a
vocabulary-parallel embedding), wrapped by `fleet.distributed_model` and
`fleet.distributed_optimizer`, and each batch is sharded over `data`. The
seeded weights are made whole on the first chip and sharded by
`distributed_model`, so the plain reference, which runs once all of this
is gone, starts from the same values.
"""
from benchmarks import program
from benchmarks.entries import to_static_loop


def build(ctx):
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.base import DistributedStrategy
    mesh = ctx["job"]["mesh"]
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": mesh["data"], "mp_degree": mesh["model"],
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)   # the mesh, over jax.devices()
    paddle, model, opt = program.build(ctx, tensor_parallel=True)
    # the wrappers shard and drive the same tensors: `model` and `opt` stay
    # the handles the comparison reads the state through
    return paddle, model, opt, fleet.distributed_model(model), \
        fleet.distributed_optimizer(opt)


def place(paddle, array):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.mesh import get_mesh
    spec = P("data", *([None] * (array.ndim - 1)))
    return paddle.to_tensor(jax.device_put(jnp.asarray(array),
                                           NamedSharding(get_mesh(), spec)))


def run(ctx):
    return to_static_loop.run(ctx, build=build, place=place)
