"""Median duration of the `to_static.launch` spans of the traced window:
the one call of the compiled executable inside `StaticFunction._run`, from
the flattened state to the returned buffers."""
from benchmarks import program_trace


def read(m):
    reduced = program_trace.of(m)
    return None if reduced is None else reduced["launch_ms"]
