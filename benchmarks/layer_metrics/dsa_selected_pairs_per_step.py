"""(query, key) pairs the sparse-attention index selected, per step, summed
over the layers: the device counter `dsa.selected_pairs_total` over the steps
run since the model was built (benchmarks/kernel_costs_keye.py). Exactly
topk keys a row from position topk on gives layers x sum over t of
min(t + 1, topk); ties at a row's threshold add to it."""
from benchmarks import kernel_costs_keye


def read(m):
    return kernel_costs_keye.selected_pairs_per_step(m)
