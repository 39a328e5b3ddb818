"""Per step, the device time of the kernels named `flash_window_*` (the
flash pair over its banded grid, forward and backward, in the window layers:
paddle_tpu/ops/pallas/flash_attention.py), summed over the traced window's
device operations (benchmarks/kernel_costs_laguna.py). None where the trace
holds no such kernel."""
from benchmarks import kernel_costs_laguna


def read(m):
    return kernel_costs_laguna.window_ms(m)
