"""The flash attention kernels at grouped-query shapes against their
roofline: 2 products forward (each pass) and 5 backward, halved by the causal
mask, at the chip's bf16 peak, over `flash_attention`'s device time by scope
(benchmarks/lfm2_readings.py). The kernels multiply in float32, so the share
is bounded well under 100."""
from benchmarks import lfm2_readings


def read(m):
    return lfm2_readings.flash_roofline_pct(m)
