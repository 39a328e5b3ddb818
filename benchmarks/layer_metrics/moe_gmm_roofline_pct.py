"""The expert layers' grouped products (Pallas gmm and tgmm, forward,
rematerialised forward and backward) against their roofline: the least time
the chip could take for the (token, expert) pairs the counters say were
computed here (a stand-in's among them, never the buffer's worst-case rows),
at the cell's own hidden size, expert width and held experts and with the
forward passes the trace holds, over the kernels' device time by scope
(benchmarks/lfm2_readings.py, benchmarks/kernel_costs.py). Padding rows and
re-read operands are not counted, so the share is understated, never
overstated."""
from benchmarks import lfm2_readings


def read(m):
    return lfm2_readings.gmm_roofline_pct(m)
