"""Share of the device's busy time in operations that carry no program
scope: the compiler's own copies and what the program stages outside any
named op (benchmarks/program_trace.py)."""
from benchmarks import program_trace


def read(m):
    reduced = program_trace.of(m)
    return None if reduced is None else reduced["unscoped_pct"]
