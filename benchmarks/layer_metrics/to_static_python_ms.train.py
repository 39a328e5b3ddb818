"""Median self time of the `to_static.call` spans of the traced window:
each call's duration less its `to_static.launch`, which is the signature,
the program lookup, the flattening of the state, the donation gate and the
write-back."""
from benchmarks import program_trace


def read(m):
    reduced = program_trace.of(m)
    return None if reduced is None else reduced["python_ms"]
