"""Per step, the device time of the operations whose scope is `moe_route`
or `moe_combine` (benchmarks/program_trace.py, `scope_ms`): the router's
scores, the top-k, the two sorts of the plan, the gather of the tokens' rows
into the sorted buffer, the weighted gather back, and their backward. The row
moves are Pallas kernels that follow the rows present (PR 29; PR 38 for a
hidden size of 2304); the sorts and the router are XLA's."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("moe_route", "moe_combine"))
