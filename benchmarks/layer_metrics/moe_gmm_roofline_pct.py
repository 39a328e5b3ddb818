"""The expert layers' grouped products (Pallas gmm and tgmm, forward,
rematerialised forward and backward) against their roofline: the least time
the chip could take for the rows the counters say were present, over the
kernels' device time by scope (benchmarks/lfm2_readings.py,
benchmarks/kernel_costs.py). Padding rows and re-read operands are not
counted, so the share is understated, never overstated."""
from benchmarks import lfm2_readings


def read(m):
    return lfm2_readings.gmm_roofline_pct(m)
