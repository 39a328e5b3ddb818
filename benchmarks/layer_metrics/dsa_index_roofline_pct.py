"""The sparse-attention index and its loss against their roofline: the
projections, the index's product at every causal pair and the loss's second
pass over the main scores at the pairs of the sets, as many forward passes
as the traced program runs (its sets and loss kernels a layer a step) and a
backward of twice the projections and the index's product
(benchmarks/kernel_costs_keye.py), over the `dsa_index` and
`dsa_index_loss` scopes' device time, whatever implements them. None where
the trace has no such scope."""
from benchmarks import kernel_costs_keye


def read(m):
    return kernel_costs_keye.read_share(m, "dsa_index_roofline_pct")
