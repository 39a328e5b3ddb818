"""Per step, the device time of the operations that hold a computing
instruction of the scope `optimizer`, whatever their root
(benchmarks/program_trace.py, `held_ms`): carrier time. On the TPU most of
it is weight-gradient matmuls with the AdamW update as epilogue, so it counts
matmul time too, adds up with no other scope's number, and can fall while
the step gets slower (an update moved out of the epilogue into a kernel of
its own): a change to the update is judged on the step, not on this."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("optimizer",), key="held_ms")
