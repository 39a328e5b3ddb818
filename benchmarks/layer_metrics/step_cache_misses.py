"""Persistent-cache misses inside `to_static.compile` spans
(`compile.cache_misses_total{phase="compile"}`): the step's own programs
compiled, not loaded. 0 in a warm run; the `setup_trace` line's
`missed_programs` names them."""
from benchmarks import setup_trace


def read(m):
    return setup_trace.metric(m, "step_cache_misses")
