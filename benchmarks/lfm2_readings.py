"""What the readers of the `lfm2_moe` family's own per-layer metrics share:
the expert layers' device counters as the program's registry gives them
(paddle_tpu/incubate/moe.py), and the roofline shares of the kernels such a
cell runs, from their device time by program scope, the rows the counters
say were present, the widths and sizes of the cell that ran and
benchmarks/peaks.json. Every function returns None, and does not raise,
where the program has no such counter or the run no scoped trace (a parent
of the PR that added them)."""
import os

from benchmarks import harness, kernel_costs, program_trace


def cell_of_the_run():
    """The cell whose traced run a reader is scoring: a reader is handed no
    name, and a run writes its trace under `.bench_trace/<cell>/`, where
    `program_trace.of` found the one it read (the newest, refused unless
    its steps and window are the run's). Any cell of the family is then
    scored with its own batch, sequence and held experts."""
    rel = os.path.relpath(program_trace.newest_trace(),
                          os.path.join(harness.ROOT, ".bench_trace"))
    return harness.load_cell(rel.split(os.sep)[0])


def registry():
    """{"counters": ..., "gauges": ...} of the program's registry: reading
    it is what fetches the device counters."""
    try:
        from paddle_tpu.profiler import metrics
        return metrics.get_registry().snapshot()
    except Exception:
        return None


def routing(m):
    """{"rows_per_step", "rows_per_layer_step", "load_max_over_mean"}, once
    per run (kept in `m`): `moe.rows_here_total` over the steps run since
    the model was built (`moe.layer_calls_total` over `moe.live_layers_count`)."""
    if "moe_routing" not in m:
        m["moe_routing"] = None
        snap = registry() or {}
        counters, gauges = snap.get("counters", {}), snap.get("gauges", {})
        rows, calls = (counters.get("moe.rows_here_total"),
                       counters.get("moe.layer_calls_total"))
        layers = gauges.get("moe.live_layers_count")
        if rows is not None and calls and layers:
            m["moe_routing"] = {
                "rows_per_step": rows * layers / calls,
                "rows_per_layer_step": rows / calls,
                "load_max_over_mean": gauges.get("moe.load_max_over_mean_ratio")}
    return m["moe_routing"]


def gmm_roofline_pct(m):
    """The grouped products' share of their roofline over one step: the
    roofline seconds of every expert layer's products at the mean rows
    present (kernel_costs.expert_layer_seconds) over `moe_experts`' device
    time."""
    spent, routed = program_trace.scope_ms(m, ("moe_experts",)), routing(m)
    if not spent or routed is None:
        return None
    cfg = cell_of_the_run()["cfg"]
    layers = cfg["num_layers"] - cfg["num_dense_layers"]
    least = layers * kernel_costs.expert_layer_seconds(
        routed["rows_per_layer_step"], cfg["hidden_size"],
        cfg["moe_intermediate_size"], len(cfg["held_experts"]),
        2 if cfg["recompute"] else 1, m["peak"])
    return 100.0 * least * 1e3 / spent


def flash_roofline_pct(m):
    """The flash kernels' share of their roofline over one step, from
    `flash_attention`'s device time (the layout changes and the reduction
    over a key/value group that XLA runs round the kernels are in it)."""
    spent = program_trace.scope_ms(m, ("flash_attention",))
    if not spent:
        return None
    cell = cell_of_the_run()
    cfg, job = cell["cfg"], cell["job"]
    layers = sum(op == "full_attention" for op, _ in cell["family"].layer_kinds(cfg))
    heads = cfg["num_attention_heads"]
    least = layers * kernel_costs.causal_attention_seconds(
        job["batch"], heads, cfg["num_key_value_heads"], job["seq"],
        cfg["hidden_size"] // heads, 2 if cfg["recompute"] else 1, m["peak"])
    return 100.0 * least * 1e3 / spent
