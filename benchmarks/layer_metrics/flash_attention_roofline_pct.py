"""The flash attention kernels at grouped-query shapes against their
roofline: 2 products forward (each pass the traced program runs) and 5
backward, halved by the causal mask, at the chip's bf16 peak, over
`flash_attention`'s device time by scope (benchmarks/lfm2_readings.py). The
kernels' products go through the MXU in bf16 passes; heads of 64 fill half
of its 128 x 128 array (a contraction or an output of 64 in every product),
so about 45-50 is the most these shapes allow (PERF.md, PR 31)."""
from benchmarks import lfm2_readings


def read(m):
    return lfm2_readings.flash_roofline_pct(m)
