"""Per step, the device time of the operations whose scope is `optimizer`
(benchmarks/program_trace.py, `scope_ms`: a fusion takes its root's scope,
the same rule as `attention_ms.train` and `norm_ms.train`, and the scopes add
up to busy time). On the TPU XLA makes the AdamW update of most weights the
epilogue of the matmul that computes the gradient, so this is the updates
that run as operations of their own (the embedding's, the small leaves');
`optimizer_carrier_ms.train` is the time of every operation that carries an
update."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("optimizer",))
