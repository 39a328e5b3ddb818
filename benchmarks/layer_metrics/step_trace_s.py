"""Seconds jax spent tracing the step to a jaxpr and lowering it to MLIR,
probes included (`to_static.trace_sec` + `to_static.lower_sec`)."""
from benchmarks import program_trace


def read(m):
    trace = program_trace.counter("to_static.trace_sec")
    lower = program_trace.counter("to_static.lower_sec")
    return None if trace is None or lower is None else trace + lower
