"""The busiest held expert's rows over the mean of the held experts' rows,
averaged over the steps run, in the worst expert layer (the program's gauge
`moe.load_max_over_mean_ratio`, from counters it keeps on the device): 1 is a
perfect balance, the number of held experts one expert taking everything.
A grouped product's time follows the rows, so imbalance costs nothing on
one chip; across chips the busiest rank sets the pace."""
from benchmarks import lfm2_readings


def read(m):
    routed = lfm2_readings.routing(m)
    return None if routed is None else routed["load_max_over_mean"]
