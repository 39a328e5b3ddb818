"""Ops the tape dispatched one by one per step of the traced window:
`dispatch.ops_total` as the window's first and last `to_static.call` spans
carry it, over the calls between them. A compiled step dispatches none."""
from benchmarks import program_trace


def read(m):
    reduced = program_trace.of(m)
    return None if reduced is None else reduced["per_call"].get("dispatch_ops")
