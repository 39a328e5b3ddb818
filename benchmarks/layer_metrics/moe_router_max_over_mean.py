"""The most-picked published expert's picks over the mean of all published
experts' picks, averaged over the steps run, in the worst expert layer (the
program's gauge `moe.router_max_over_mean_ratio`, from counters it keeps on
the device): 1 is a router in balance, the number of published experts over
the experts a token picks a router that has collapsed. Where held experts
stand in for the absent ones the rows here do not move with the router, and
`moe_load_max_over_mean` sees only the held slots; this sees the router."""
from benchmarks import kernel_costs_dsv2


def read(m):
    return kernel_costs_dsv2.router_max_over_mean(m)
