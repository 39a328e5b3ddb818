"""Per step, the device time of the operations whose scope is `attn_gate`
(the gate on the attention output: its projection from the block's normed
input, the sigmoid, the multiply a head), forward, rematerialised forward and
backward (benchmarks/program_trace.py, `scope_ms`). None where the program
stages no such scope."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("attn_gate",)) or None
