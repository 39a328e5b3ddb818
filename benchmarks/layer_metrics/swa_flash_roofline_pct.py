"""All the layers' attention against its roofline in a cell that mixes window
and full layers: the window layers' products at the band's pairs and the full
layers' at the triangle's, over the `flash_attention` scope's device time,
the copies XLA makes round the kernels included
(benchmarks/kernel_costs_laguna.py). None where the trace has no such scope."""
from benchmarks import kernel_costs_laguna


def read(m):
    return kernel_costs_laguna.flash_roofline_pct(m)
