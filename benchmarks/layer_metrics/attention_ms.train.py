"""Per step, the device time of the operations whose scope is
`flash_attention` or `sdpa`, forward and backward
(benchmarks/program_trace.py, `scope_ms`)."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("flash_attention", "sdpa"))
