"""Per step, the device time of the operations whose scope is `short_conv`
(benchmarks/program_trace.py, `scope_ms`): the two gates and the three
shifted multiply-adds of the gated short convolution, forward and backward,
where XLA keeps them as operations of their own; what it fused into the
projections beside them is in `linear`."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("short_conv",))
