"""Per step, the device time of the operations whose scope is `layer_norm`
or `fused_residual_ln`, forward and backward (benchmarks/program_trace.py,
`scope_ms`): what the LayerNorms cost as operations of their own. None of
the tree's models calls `fused_residual_ln` since PR 30 (they call
`nn.LayerNorm` on the sum), and RMS norms are under `rms_norm`, which no
metric reads: 0 in a cell without a LayerNorm. A norm that XLA
fused into the matmul beside it is in that matmul's time (`held_ms` on the
`program_trace` line says how much time holds some norm work)."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("layer_norm", "fused_residual_ln"))
