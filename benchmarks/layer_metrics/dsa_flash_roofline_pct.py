"""Attention over the index's sets against its roofline: FlashAttention-2's
product count at the pairs of the sets, sum over t of min(t + 1, topk), never
the dense triangle, the forward as often as the traced program runs it
(benchmarks/kernel_costs_keye.py), over the
`flash_attention` scope's device time, the copies XLA makes round the kernels
and the set's tiling included. A pair that multiplies every causal tile and
masks reads low. None where the trace has no such scope."""
from benchmarks import kernel_costs_keye


def read(m):
    return kernel_costs_keye.read_share(m, "dsa_flash_roofline_pct")
