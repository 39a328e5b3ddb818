"""The flash pair at latent attention's head sizes (query/key 192, value 128)
against its roofline: FlashAttention-2's product count at the two sizes,
halved by the causal mask (benchmarks/kernel_costs_kimi.py), over the
`flash_attention` scope's device time, the copies XLA makes round the
kernels included. None where the trace has no such scope."""
from benchmarks import kernel_costs_kimi


def read(m):
    return kernel_costs_kimi.read_share(m, "mla_flash_roofline_pct")
