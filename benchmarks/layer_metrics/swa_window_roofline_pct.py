"""The banded flash pair against its roofline: the window layers' two
attention products at the pairs of the band, sum over t of min(t + 1, window),
never the triangle, FlashAttention-2's count of the backward, the forward as
often as the trace holds it, operands once a pass, over the device time of the
kernels named `flash_window_*` (benchmarks/kernel_costs_laguna.py). Tiles the
grid visits outside the band lower the share. None where the trace holds no
such kernel."""
from benchmarks import kernel_costs_laguna


def read(m):
    return kernel_costs_laguna.window_roofline_pct(m)
