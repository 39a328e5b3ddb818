"""Per step, the device time of the operations whose scope is `mla_rope`
(latent attention's decoupled rotary part: the slices of the queries' last 64
entries and of the shared key part, their rotation under YaRN's frequencies
in float32, the query put together again), forward, rematerialised forward
and backward (benchmarks/program_trace.py, `scope_ms`). None where the program
stages no such scope."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("mla_rope",)) or None
