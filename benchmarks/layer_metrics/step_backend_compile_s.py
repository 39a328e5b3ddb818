"""Seconds in XLA's backend compile of the step's programs, or in loading
them from the persistent cache (`to_static.backend_compile_sec`)."""
from benchmarks import program_trace


def read(m):
    return program_trace.counter("to_static.backend_compile_sec")
