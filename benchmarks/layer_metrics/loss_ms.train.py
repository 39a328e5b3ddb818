"""Per step, the device time of the operations whose scope is
`cross_entropy`, forward and backward (benchmarks/program_trace.py,
`scope_ms`): the loss's own passes over the logits, the row reductions and
whatever the compiler writes out between them. Work that XLA fused into a
matmul beside it (the row maximum in the logits fusion's epilogue, the
gradient `softmax - onehot` as the prologue of the head's two backward
matmuls) is in that matmul's time, under `linear`."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("cross_entropy",))
