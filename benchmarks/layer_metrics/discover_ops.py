"""Ops dispatched inside `to_static.discover` spans (the eager discovery
passes), from the program's registry: set-up is outside the traced window."""
from benchmarks import program_trace


def read(m):
    return program_trace.counter("to_static.discover_ops_total")
