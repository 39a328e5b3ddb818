"""What the DeepSeek-V2 configuration's readers share: the flash pair at
latent attention's head sizes against its roofline, with the count of
operations and bytes that the Kimi Linear cell's reader uses
(kernel_costs_kimi.latent_attention_seconds, imported and not copied), over
every layer of this model and with the forward passes that the traced step
program runs; and the router's gauge. Every function returns None, and does
not raise, where the run has no scoped trace or the program no such gauge (a
parent of the PR that added them)."""
from benchmarks import kernel_costs, program, program_trace
from benchmarks.kernel_costs_kimi import latent_attention_seconds

FLASH_SCOPE = "flash_attention"


def flash_seconds(cfg, job, forward_passes, peak):
    """Roofline seconds of every layer's flash pair in one training step."""
    return cfg["num_layers"] * latent_attention_seconds(
        job["batch"], cfg["num_attention_heads"], job["seq"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        forward_passes, peak)


def flash_roofline_pct(m):
    """`mla_rope_flash_roofline_pct` of the traced run behind `m` (what a
    reader is handed): a layer runs one backward kernel, so its custom calls
    under the scope less one are the forward passes the trace holds."""
    spent = program_trace.scope_ms(m, (FLASH_SCOPE,))
    if not spent:
        return None
    cfg, job = m["cell"]["cfg"], m["cell"]["job"]
    passes = kernel_costs.forward_passes(
        program_trace.kernels_a_layer(m, (FLASH_SCOPE,), cfg["num_layers"]),
        backward_kernels=1, otherwise=2 if cfg["recompute"] else 1)
    return 100.0 * flash_seconds(cfg, job, passes, m["peak"]) * 1e3 / spent


def router_max_over_mean(m):
    """The gauge `moe.router_max_over_mean_ratio`; None where the program
    has none, or no expert layer that keeps it has run."""
    gauges = (program.registry() or {}).get("gauges", {})
    return gauges.get("moe.router_max_over_mean_ratio") or None
