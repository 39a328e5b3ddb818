"""Per step, the device time of the operations whose scope is `dsa_index`
(the sparse-attention indexer's projections, its scores at every causal pair,
the row thresholds and the sets) or `dsa_index_loss` (the index's loss and
its gradient, with their second pass over the main scores), forward,
rematerialised forward and backward (benchmarks/program_trace.py,
`scope_ms`). None where the program stages no such scope."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("dsa_index", "dsa_index_loss")) or None
