"""The program under test, as the entries build and read it: model and
optimizer from a configuration, the seeded weights put in their place (and
put back, once the step is compiled), and the two readings of its state
that `correct` compares, its registry of counters, and what gives the chips
back when a run is over. Beside the entries and the readers of the program's
own timeline (program_trace.py, setup_trace.py), the benchmark module that
imports paddle_tpu."""
from benchmarks import harness


def build(ctx, tensor_parallel=False):
    """(paddle, model, optimizer): the family's model holding the seeded
    weights in the configuration's dtype, and AdamW as the configuration
    states it."""
    import paddle_tpu as paddle
    cfg, family = ctx["cfg"], ctx["family"]
    paddle.seed(ctx["seed"] % (2 ** 31))
    model = family.build_model(cfg, tensor_parallel=tensor_parallel)
    if cfg["weights_dtype"] == "bfloat16":
        model.bfloat16()
    names = family.program_names(cfg)
    missing, unexpected = model.set_state_dict(
        {names[k]: paddle.Tensor(v) for k, v in ctx["make_weights"]().items()})
    if missing or unexpected:
        raise RuntimeError(f"seeded weights do not cover the program's state: "
                           f"missing {missing}, unexpected {unexpected}")
    o = cfg["optimizer"]
    opt = paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        multi_precision=o["master_weights"] == "float32"
        and cfg["weights_dtype"] != "float32",
        parameters=model.parameters())
    return paddle, model, opt


def _state_leaves(ctx, model, opt, accumulator):
    """{reference leaf: the optimizer's `accumulator` of that parameter, or
    the parameter itself where the optimizer keeps none}."""
    names = ctx["family"].program_names(ctx["cfg"])
    tensors = model.state_dict()
    index = {id(p): i for i, p in enumerate(model.parameters())}
    state = opt.state_dict()
    out = {}
    for leaf, key in names.items():
        p = tensors[key]
        acc = state.get(f"param_{index[id(p)]}__{accumulator}", p)
        out[leaf] = acc._val
    return out


def reset(ctx, model, opt, make_weights):
    """Put the step's state back to the seed, in the tensors it already
    holds: the seeded weights, float32 masters cast from them, every other
    accumulator at its initial value. `to_static` runs a step's first call
    eagerly, so a compiled program can be driven from the seed only after
    its state has been set back; the step, its programs and the tensors
    they read stay the ones the window gets.

    One leaf at a time, each new value placed as the old one was, so that
    the peak stays the program's. The values come from jitted calls, so the
    donating program may take them: the taint that a host-assigned value
    carries is cleared, and all three compared steps go through the donating
    program that the window drives."""
    import jax
    import jax.numpy as jnp

    def put(tensor, value):
        old, value = tensor._val, value.astype(tensor._val.dtype)
        # committed to its devices only where the old value was spread over
        # several: another placement would be another program
        tensor._val = jax.device_put(value, old.sharding) \
            if len(old.sharding.device_set) > 1 else value
        tensor._donate_unsafe = False

    names = ctx["family"].program_names(ctx["cfg"])
    tensors = model.state_dict()
    seeded = make_weights()
    leaf_of = {id(tensors[key]): leaf for leaf, key in names.items()}
    for leaf, key in names.items():
        put(tensors[key], seeded[leaf])
    for name, by_param in opt._accumulators.items():
        for pid, acc in by_param.items():
            if name == "master_weight":
                put(acc, seeded[leaf_of[pid]].astype(jnp.float32))
            else:
                put(acc, jnp.full(acc._val.shape, opt._acc_inits[name],
                                  acc._val.dtype))
    if opt._aux:
        raise RuntimeError(f"optimizer state {sorted(opt._aux)} has no seeded value")


def ran_donating(held, compiles):
    """Whether the step just run consumed `held`, a parameter's value from
    before it, and asked for no compile: the donating program, already
    compiled, that the window drives."""
    return compiles == 0 and held.is_deleted()


def first_gradient(ctx, model, opt):
    """(per-leaf norm, one-dimensional leaves) of the first gradient as the
    optimizer got it, from its state after one step: AdamW's first moment
    starts at 0, so after step 1 it is (1 - beta1) times that gradient."""
    scale = 1.0 / (1.0 - ctx["cfg"]["optimizer"]["beta1"])
    moments = _state_leaves(ctx, model, opt, "moment1")
    norms = harness.leaf_norms(moments)
    return ({k: scale * v for k, v in norms.items()},
            harness.vector_leaves(moments, scale))


def update_norms(ctx, model, opt, make_weights):
    """Per-leaf norm of the parameters' change since the seeded weights,
    read from the float32 masters where the optimizer keeps them."""
    return harness.diff_norms(
        _state_leaves(ctx, model, opt, "master_weight"), make_weights())


def registry():
    """{"counters": ..., "gauges": ...} of the program's registry, or None
    where the program keeps none: reading it is what fetches the device
    counters, from the layers that are alive."""
    try:
        from paddle_tpu.profiler import metrics
        return metrics.get_registry().snapshot()
    except Exception:
        return None


def release():
    """Give the chips back once the entry has returned and let go of its
    model, optimizer and step. What still holds them is the program's cache
    of converted functions (jit/ast_transform.py keeps every function
    `to_static` has rewritten, and the rewritten step's namespace holds the
    closure it was made in: model and optimizer, so every parameter, master
    and moment); then jax's own caches, whose executables keep their
    constants and their code on the device. run.py measures what is left and
    refuses to go on beside it, so a tree that keeps its state elsewhere
    stops with a message and is not read wrongly."""
    import gc
    import jax
    from paddle_tpu.jit import ast_transform
    getattr(ast_transform, "_CACHE", {}).clear()
    gc.collect()
    jax.clear_caches()
