"""Persistent-cache misses of the process since the package was imported, all
phases (`compile.cache_misses_total`): programs compiled, not loaded. 0 in a
warm run."""
from benchmarks import setup_trace


def read(m):
    return setup_trace.metric(m, "setup_cache_misses")
