"""Seconds inside `to_static.discover` spans (`to_static.discover_sec`): the
eager discovery pass as the program times it, the inside twin of
`eager_pass_s`."""
from benchmarks import setup_trace


def read(m):
    return setup_trace.metric(m, "discover_s")
