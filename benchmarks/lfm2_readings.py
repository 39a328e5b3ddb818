"""What the readers of the expert cells' per-layer metrics share (the
`lfm2_moe` family's first, since PR 46 every family that runs
`DroplessMoELayer`): the expert layers' device counters as the program's
registry gives them (paddle_tpu/incubate/moe.py), and the roofline shares of
the kernels such a cell runs, from their device time by program scope, the
(token, expert) pairs the counters say were computed here, the widths and
sizes of the cell that ran (`m["cell"]`), the passes its trace holds and
benchmarks/peaks.json. Every function returns None, and does not raise,
where the program has no such counter or the run no scoped trace (a parent
of the PR that added them)."""
from benchmarks import kernel_costs, program, program_trace


def routing(m):
    """{"rows_per_step", "rows_per_layer_step", "load_max_over_mean",
    "layers"}, once per run (kept in `m`): `moe.rows_here_total` over the
    steps run since the model was built (`moe.layer_calls_total` over
    `moe.live_layers_count`, the expert layers alive). The rows are pairs
    computed here, a stand-in's among them (`absent_experts`), never the
    sorted buffer's worst-case rows."""
    if "moe_routing" not in m:
        m["moe_routing"] = None
        snap = program.registry() or {}
        counters, gauges = snap.get("counters", {}), snap.get("gauges", {})
        rows, calls = (counters.get("moe.rows_here_total"),
                       counters.get("moe.layer_calls_total"))
        layers = gauges.get("moe.live_layers_count")
        if rows is not None and calls and layers:
            m["moe_routing"] = {
                "rows_per_step": rows * layers / calls,
                "rows_per_layer_step": rows / calls, "layers": layers,
                "load_max_over_mean": gauges.get("moe.load_max_over_mean_ratio")}
    return m["moe_routing"]


def gmm_roofline_pct(m):
    """The grouped products' share of their roofline over one step: the
    roofline seconds of every expert layer's products at the mean pairs
    computed (kernel_costs.expert_layer_seconds), at the cell's own hidden
    size, expert width and held experts, over `moe_experts`' device time. A
    layer's three projections are a kernel each in every forward pass and two
    in the backward, so its custom calls less six, three to a pass, are the
    forward passes the trace holds."""
    spent, routed = program_trace.scope_ms(m, ("moe_experts",)), routing(m)
    if not spent or routed is None:
        return None
    cfg = m["cell"]["cfg"]
    passes = kernel_costs.forward_passes(
        program_trace.kernels_a_layer(m, ("moe_experts",), routed["layers"]),
        backward_kernels=6, kernels_a_pass=3, otherwise=2 if cfg["recompute"] else 1)
    least = routed["layers"] * kernel_costs.expert_layer_seconds(
        routed["rows_per_layer_step"], cfg["hidden_size"],
        cfg["moe_intermediate_size"], len(cfg["held_experts"]), passes, m["peak"])
    return 100.0 * least * 1e3 / spent


def flash_roofline_pct(m):
    """The flash kernels' share of their roofline over one step, from
    `flash_attention`'s device time (the layout changes and the reduction
    over a key/value group that XLA runs round the kernels are in it); a
    layer runs one backward kernel, so its custom calls less one are the
    forward passes the trace holds."""
    spent = program_trace.scope_ms(m, ("flash_attention",))
    if not spent:
        return None
    cell = m["cell"]
    cfg, job = cell["cfg"], cell["job"]
    layers = sum(op == "full_attention" for op, _ in cell["family"].layer_kinds(cfg))
    heads = cfg["num_attention_heads"]
    passes = kernel_costs.forward_passes(
        program_trace.kernels_a_layer(m, ("flash_attention",), layers),
        backward_kernels=1, otherwise=2 if cfg["recompute"] else 1)
    least = layers * kernel_costs.causal_attention_seconds(
        job["batch"], heads, cfg["num_key_value_heads"], job["seq"],
        cfg["hidden_size"] // heads, passes, m["peak"])
    return 100.0 * least * 1e3 / spent
