"""Per step, the device time of the operations whose scope is `kda`
(benchmarks/program_trace.py, `scope_ms`): Kimi Delta Attention's chunked
op, every stage of it, Pallas or XLA, in the forward, the rematerialised
forward and the backward. None where the program stages no such scope."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("kda",)) or None
