"""Seconds the program's package took to import, its first line to its last
(`runtime.import_sec`): jax's own import too where nothing had imported jax
before it."""
from benchmarks import setup_trace


def read(m):
    return setup_trace.metric(m, "import_s")
