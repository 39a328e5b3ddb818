"""The flash pair under rotary latent attention (query/key heads of 192,
value heads of 128, every layer of the model) against its roofline:
FlashAttention-2's product count at the two sizes, halved by the causal mask
(benchmarks/kernel_costs_kimi.py's, through benchmarks/kernel_costs_dsv2.py),
the forward counted as often as the traced step program ran it, over the
`flash_attention` scope's device time, the copies XLA makes round the kernels
included. None where the trace has no such scope."""
from benchmarks import kernel_costs_dsv2


def read(m):
    return kernel_costs_dsv2.flash_roofline_pct(m)
