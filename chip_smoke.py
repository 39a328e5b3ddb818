#!/usr/bin/env python
"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # one four-chip host: the dp2 x mp2 path only

One chip: drives the trainer — a `@paddle.jit.to_static` train step (forward,
backward, AdamW with fp32 masters) of a GPT at the widths of GPT-3 1.3B
(hidden 2048, 16 heads of 128, FFN 8192, vocab 50304), sequence 1024, bf16,
depth cut to what one v5e's 16 GB holds — through the public API, after
checking the flash-attention kernels against the XLA reference and running
one tiny `paddle.enable_static()` Program through `Executor`.

Four chips (`--chips 4`): the fleet dp2 x mp2 tensor-parallel GPT at the same
widths against the serial, unsharded model of the same weights on one chip,
loss by loss; no other phase.

Every phase prints one JSON line. Nothing is caught: a phase that fails ends
the process with a traceback and a non-zero exit code. Without a TPU the
script exits non-zero before any work. The last line is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

SEQ = 1024
ONE_CHIP_LAYERS = 6     # of 24: bf16 weights + fp32 masters/moments, batch 2
ONE_CHIP_BATCH = 2
ONE_CHIP_STEPS = 12     # 1 eager discovery pass + 11 compiled calls
FOUR_CHIP_LAYERS = 4    # of 24: the fp32 serial reference must fit ONE chip
FOUR_CHIP_BATCH = 4
FOUR_CHIP_STEPS = 3
PARITY_RTOL = 5e-4      # the tolerance of __graft_entry__'s hybrid leg


def check(ok, what):
    """A failed check ends the run (asserts would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def permutation_stream(seed, batch, seq, sub=512):
    """bench.py's learnable stream: x[t+1] = perm[x[t]] over a 512-token
    sub-vocabulary, so next-token CE has structure to learn while the
    softmax and embedding keep the full vocabulary."""
    rng = np.random.RandomState(seed)
    perm = rng.permutation(sub)
    while True:
        ids = np.empty((batch, seq + 1), np.int64)
        ids[:, 0] = rng.randint(0, sub, batch)
        for t in range(seq):
            ids[:, t + 1] = perm[ids[:, t]]
        yield ids[:, :-1].astype("int32"), ids[:, 1:].astype("int32")


def make_step(paddle, model, opt):
    @paddle.jit.to_static
    def train_step(xx, yy):
        loss = model(xx, labels=yy)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss.astype("float32")
    return train_step


def step_program(step, x, y):
    """(compiled HLO text, memory analysis) of the step's one program."""
    from paddle_tpu.jit.to_static import _flatten_tensors
    (prog,) = step.programs.values()
    compiled = prog.jitted_donate.lower(
        tuple(t._val for t in prog.mutated), tuple(t._val for t in prog.ro),
        tuple(t._val for t in _flatten_tensors(((x, y), {}), []))).compile()
    return compiled.as_text(), compiled.memory_analysis()


class CacheEvents:
    """Persistent-compilation-cache hits and misses, as jax reports them."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------------------
# one chip

def phase_flash_parity(seed):
    """Flash forward+backward at (bh 32, s 1024, d 128) bf16 causal — the
    attention of the model below — against ops.attention._xla_attention."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import _flash_attention_diff, _xla_attention

    b, h, d = 2, 16, 128
    scale = d ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, w = (jax.random.normal(kk, (b, SEQ, h, d), jnp.float32)
                  .astype(jnp.bfloat16) for kk in keys)

    def run(attn):
        def loss(q, k, v):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out,) + grads

    flash = run(lambda q, k, v: _flash_attention_diff(q, k, v, True, scale,
                                                      False))
    ref = run(lambda q, k, v: _xla_attention(q, k, v, None, scale, True, 0.0,
                                             None))
    errs = {}
    for name, a, r in zip(("out", "dq", "dk", "dv"), flash, ref):
        a = np.asarray(a.astype(jnp.float32))
        r = np.asarray(r.astype(jnp.float32))
        check(a.shape == r.shape == (b, SEQ, h, d) and np.isfinite(a).all(),
              f"flash {name}: wrong shape or non-finite values")
        # bf16 has 8 bits of mantissa: both sides round their results (and
        # the reference its S x S probabilities) to it
        errs[name] = float(np.abs(a - r).max() / np.abs(r).max())
        check(errs[name] < 2e-2, f"flash {name} is {errs[name]} off the reference")
    emit("flash_parity", shape=[b, SEQ, h, d], dtype="bfloat16", causal=True,
         reference="ops.attention._xla_attention", tolerance=2e-2,
         max_err_over_max_ref=errs)


def phase_static_executor(paddle):
    """The other entry point: a tiny Program through Executor on the chip."""
    t0 = time.perf_counter()
    xv = np.random.RandomState(0).randn(4, 8).astype("float32")
    paddle.enable_static()
    try:
        main, startup = paddle.static.Program(), paddle.static.Program()
        with paddle.static.program_guard(main, startup):
            x = paddle.static.data("x", [None, 8], "float32")
            y = paddle.static.nn.fc(x, 4)
            z = y * 2.0 + 1.0
        exe = paddle.static.Executor()
        exe.run(startup)
        y_out, z_out = exe.run(main, feed={"x": xv}, fetch_list=[y, z])
    finally:
        paddle.disable_static()
    check(z_out.shape == (4, 4) and np.isfinite(z_out).all(),
          "static Program fetched a wrong shape or non-finite values")
    np.testing.assert_allclose(z_out, y_out * 2.0 + 1.0, rtol=1e-5, atol=1e-5)
    emit("static_executor", fetch_shape=list(z_out.shape),
         seconds=round(time.perf_counter() - t0, 3))


def phase_train(paddle, seed, cache_events):
    import jax
    from paddle_tpu.ops.pallas import flash_attention
    from paddle_tpu.profiler import metrics
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(seed)
    attention_before = metrics.get_registry().snapshot()["counters"]
    cfg = GPTConfig.gpt3_1p3b(dropout=0.0, max_position_embeddings=SEQ)
    cfg.num_layers = ONE_CHIP_LAYERS
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, multi_precision=True,
                                 parameters=model.parameters())
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    emit("model", config="GPTConfig.gpt3_1p3b", hidden=cfg.hidden_size,
         heads=cfg.num_heads, head_dim=cfg.hidden_size // cfg.num_heads,
         ffn=cfg.intermediate_size, vocab=cfg.vocab_size,
         layers=cfg.num_layers, layers_published=24, seq=SEQ,
         batch=ONE_CHIP_BATCH, params=n_params, weights="bfloat16",
         optimizer="AdamW, fp32 master weights and moments")

    step = make_step(paddle, model, opt)
    stream = permutation_stream(seed, ONE_CHIP_BATCH, SEQ)
    losses, seconds = [], []
    for _ in range(ONE_CHIP_STEPS):
        x_np, y_np = next(stream)
        x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
        t0 = time.perf_counter()
        loss = step(x, y)
        jax.block_until_ready(loss._val)
        seconds.append(time.perf_counter() - t0)
        losses.append(float(loss.item()))
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    # call 1 is the eager discovery pass (dygraph on the chip); call 2 traces
    # and compiles the step's one program, the buffer-donating one (on a TPU
    # it consumes what the eager pass assigned); from call 3 on the compiled
    # step is steady
    emit("train", steps=len(losses), losses=[round(v, 4) for v in losses],
         first_loss=losses[0], last_loss=losses[-1],
         eager_discovery_seconds=seconds[0],
         compile_seconds=seconds[1] - min(seconds[2:]),
         first_compiled_call_seconds=seconds[1],
         step_seconds=float(np.median(seconds[2:])),
         step_seconds_min=min(seconds[2:]), step_seconds_max=max(seconds[2:]))

    # which attention the compiled step holds: ops/attention.takes_flash
    # chooses from the shapes (XLA's attention under FLASH_MIN_SEQ_K keys),
    # the counters say which path the traces took, and the compiled text
    # must agree with them
    text, mem = step_program(step, x, y)
    kernels = text.count("tpu_custom_call")
    counters = metrics.get_registry().snapshot()["counters"]
    took = {name: counters.get("attention.%s_total" % name, 0)
            - attention_before.get("attention.%s_total" % name, 0)
            for name in ("flash", "xla")}
    check((took["flash"] > 0) != (took["xla"] > 0),
          f"the step's attention took both paths or none: {took}")
    path = "flash" if took["flash"] else "xla"
    emit("attention_path", path=path, calls_traced=took,
         tpu_custom_calls_in_compiled_step=kernels,
         flash_kernels_if_flash=2 * cfg.num_layers,
         flash_tiles=flash_attention.tiles(SEQ, SEQ))
    check(kernels == (2 * cfg.num_layers if path == "flash" else 0),
          f"attention took the {path} path but the compiled step holds {kernels} kernels")
    stats = jax.devices()[0].memory_stats()
    emit("memory", peak_bytes_in_use=stats["peak_bytes_in_use"],
         bytes_limit=stats.get("bytes_limit"),
         step_program_temp_bytes=mem.temp_size_in_bytes,
         step_program_argument_bytes=mem.argument_size_in_bytes)
    emit("compile_cache", dir=jax.config.jax_compilation_cache_dir,
         hits=cache_events.hits, misses=cache_events.misses)


# ---------------------------------------------------------------------------
# four chips

def phase_hybrid(paddle, seed):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet.base import DistributedStrategy
    from paddle_tpu.distributed.fleet.meta_parallel import ColumnParallelLinear
    from paddle_tpu.distributed.mesh import build_mesh, get_mesh
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    devices = jax.devices()[:4]
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 1}
    build_mesh({"data": 2, "model": 2}, devices)
    mesh = get_mesh()
    fleet.init(is_collective=True, strategy=strategy)

    def build(tensor_parallel):
        paddle.seed(seed)
        cfg = GPTConfig.gpt3_1p3b(dropout=0.0, max_position_embeddings=SEQ,
                                  tensor_parallel=tensor_parallel)
        cfg.num_layers = FOUR_CHIP_LAYERS
        return cfg, GPTForCausalLM(cfg)

    cfg, model = build(True)
    dist_model = fleet._fleet.distributed_model(model)
    opt = fleet._fleet.distributed_optimizer(paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters()))
    # serial reference: the same weights, no fleet wrappers, unsharded, on
    # the first chip
    _, serial = build(False)
    serial.set_state_dict({
        k: paddle.to_tensor(jnp.asarray(np.asarray(v._val)))
        for k, v in model.state_dict().items()})
    opt_sr = paddle.optimizer.AdamW(learning_rate=1e-4,
                                    parameters=serial.parameters())
    emit("model", config="GPTConfig.gpt3_1p3b(tensor_parallel=True)",
         mesh={"data": 2, "model": 2}, hidden=cfg.hidden_size,
         heads=cfg.num_heads, ffn=cfg.intermediate_size, vocab=cfg.vocab_size,
         layers=cfg.num_layers, layers_published=24, seq=SEQ,
         batch=FOUR_CHIP_BATCH, weights="float32", optimizer="AdamW",
         compared_with="serial unsharded model of the same weights on "
                       "one chip")

    # the work is really spread: a column-parallel weight lives on all four
    # chips, each holding half of it (2-way 'model' split, 2-way 'data'
    # replication)
    col = next(l for l in model.sublayers()
               if isinstance(l, ColumnParallelLinear)).weight._val
    check(len(col.sharding.device_set) == 4,
          f"column-parallel weight is not on 4 devices: {col.sharding}")
    shard_shapes = {tuple(s.data.shape) for s in col.addressable_shards}
    check(shard_shapes == {(col.shape[0], col.shape[1] // 2)},
          f"column-parallel shards are {shard_shapes}")

    step_hy = make_step(paddle, dist_model, opt)
    step_sr = make_step(paddle, serial, opt_sr)
    stream = permutation_stream(seed, FOUR_CHIP_BATCH, SEQ)
    data_sharding = NamedSharding(mesh, P("data", None))
    hybrid, single, seconds = [], [], []
    for _ in range(FOUR_CHIP_STEPS):
        x_np, y_np = next(stream)
        x = paddle.to_tensor(jax.device_put(jnp.asarray(x_np), data_sharding))
        y = paddle.to_tensor(jax.device_put(jnp.asarray(y_np), data_sharding))
        t0 = time.perf_counter()
        hybrid.append(float(step_hy(x, y).item()))
        seconds.append(time.perf_counter() - t0)
        single.append(float(step_sr(paddle.to_tensor(x_np),
                                    paddle.to_tensor(y_np)).item()))
    deltas = [abs(a - b) / abs(b) for a, b in zip(hybrid, single)]
    check(all(np.isfinite(hybrid)) and max(deltas) < PARITY_RTOL,
          f"dp2 x mp2 diverged from serial: {hybrid} vs {single}")
    emit("hybrid_parity", steps=FOUR_CHIP_STEPS, hybrid_losses=hybrid,
         serial_losses=single, max_relative_delta=max(deltas),
         tolerance=PARITY_RTOL, hybrid_call_seconds=seconds)

    text, mem = step_program(step_hy, x, y)
    collectives = {name: text.count(f" {name}(") + text.count(f" {name}-start(")
                   for name in ("all-reduce", "all-gather", "reduce-scatter",
                                "collective-permute", "all-to-all")}
    check(sum(collectives.values()) > 0, "no collective in the dp x mp step")
    emit("collectives", in_compiled_step=collectives,
         tpu_custom_calls_in_compiled_step=text.count("tpu_custom_call"),
         step_program_temp_bytes_per_device=mem.temp_size_in_bytes,
         step_program_argument_bytes_per_device=mem.argument_size_in_bytes)

    # each chip holds its share: the hybrid model's state is split evenly, so
    # no chip is empty and chips 1-3 (which hold nothing else) agree
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    param_bytes = sum(int(np.prod(p.shape)) * 4 for p in model.parameters())
    check(min(in_use) > param_bytes // 4,
          f"a chip holds less than its share: {in_use} of {param_bytes}")
    check(max(in_use[1:]) < 1.25 * min(in_use[1:]),
          f"chips 1-3 hold unequal shares: {in_use}")
    emit("placement", column_parallel_weight_devices=4,
         column_parallel_shard_shape=list(shard_shapes.pop()),
         bytes_in_use_per_device=in_use,
         peak_bytes_in_use_per_device=[
             d.memory_stats()["peak_bytes_in_use"] for d in devices],
         hybrid_param_bytes_total=param_bytes)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the dp2 x mp2 path and its serial "
                         "reference, and no other phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the random weights and the data stream")
    ns = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU — jax found {device}; nothing was run")
    if len(devs) < ns.chips:
        sys.exit(f"chip_smoke: --chips {ns.chips} needs {ns.chips} chips, "
                 f"jax found {device}")
    cache_events = CacheEvents()
    import paddle_tpu as paddle
    emit("device", **device, chips_used=ns.chips, seed=ns.seed,
         jax=jax.__version__)

    if ns.chips == 4:
        phase_hybrid(paddle, ns.seed)
    else:
        phase_flash_parity(ns.seed)
        phase_static_executor(paddle)
        phase_train(paddle, ns.seed, cache_events)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
