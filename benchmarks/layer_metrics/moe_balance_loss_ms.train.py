"""Per step, the device time of the operations whose scope is
`moe_balance_loss` (the expert layers' sequence-wise balance loss: the picks
of each published expert a sequence, the mean scores, their product, its
gradient into the router's scores, and the two device counters it moves),
forward, rematerialised forward and backward (benchmarks/program_trace.py,
`scope_ms`). None where the program stages no such scope."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("moe_balance_loss",)) or None
