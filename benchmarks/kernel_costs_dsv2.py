"""What the DeepSeek-V2 configuration's readers share: the flash pair at
latent attention's head sizes against its roofline, with the count of
operations and bytes that the Kimi Linear cell's reader uses
(kernel_costs_kimi.latent_attention_seconds, imported and not copied), over
every layer of this model and with the forward passes that the traced step
program runs; and the router's gauge. Every function returns None, and does
not raise, where the run has no scoped trace or the program no such gauge (a
parent of the PR that added them)."""
from benchmarks import lfm2_readings, program_trace
from benchmarks.kernel_costs_kimi import latent_attention_seconds

FLASH_SCOPE = "flash_attention"


def kernels_a_step(trace, scope=FLASH_SCOPE):
    """Executions a step of the custom calls (the Pallas kernels: an event
    is named by its instruction's text, `%name = type custom-call(operands)`)
    that the window's program stages under `scope`, on the first device of a
    loaded trace (program_trace.load); None where the trace names no program
    or holds no step."""
    for device in trace["devices"].values():
        runs = {}
        for name, start, dur in device["modules"]:
            runs.setdefault(name, []).append((start, start + dur))
        if not runs:
            return None
        program = max(runs, key=lambda n: sum(e - s for s, e in runs[n]))
        scopes = trace["programs"].get(program, {})
        lo = min(s for s, _ in runs[program])
        hi = max(e for _, e in runs[program])
        calls = sum(1 for name, start, dur in device["ops"]
                    if lo <= start < hi and " custom-call(" in name
                    and scopes.get(program_trace.instruction_of(name), (None,))[0] == scope)
        return calls / len(runs[program])
    return None


def flash_forward_passes(cfg, kernels_a_layer=None):
    """Forward passes of the flash pair a layer in one training step: what
    the traced program ran where the trace says (a layer runs one backward
    kernel, so its kernels less one), else what the configuration's
    `recompute` means for this model: whole blocks are rematerialised, the
    flash forward among what is rerun."""
    if kernels_a_layer in (2.0, 3.0):
        return int(kernels_a_layer) - 1
    return 2 if cfg["recompute"] else 1


def flash_seconds(cfg, job, forward_passes, peak):
    """Roofline seconds of every layer's flash pair in one training step."""
    return cfg["num_layers"] * latent_attention_seconds(
        job["batch"], cfg["num_attention_heads"], job["seq"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        forward_passes, peak)


def flash_roofline_pct(m):
    """`mla_rope_flash_roofline_pct` of the traced run behind `m` (what a
    reader is handed)."""
    spent = program_trace.scope_ms(m, (FLASH_SCOPE,))
    if not spent:
        return None
    cell = lfm2_readings.cell_of_the_run()
    cfg, job = cell["cfg"], cell["job"]
    kernels = kernels_a_step(program_trace.load(program_trace.newest_trace()))
    passes = flash_forward_passes(
        cfg, None if kernels is None else kernels / cfg["num_layers"])
    return 100.0 * flash_seconds(cfg, job, passes, m["peak"]) * 1e3 / spent


def router_max_over_mean(m):
    """The gauge `moe.router_max_over_mean_ratio`; None where the program
    has none, or no expert layer that keeps it has run."""
    gauges = (lfm2_readings.registry() or {}).get("gauges", {})
    return gauges.get("moe.router_max_over_mean_ratio") or None
