"""Seconds inside `to_static.probe` and `to_static.compile` spans
(`to_static.probe_sec` + `to_static.compile_sec`): the `eval_shape` rounds and
the first launch of each of the step's programs, trace, lowering, backend
compile or cache load and the launch itself; the inside twin of `compile_s`,
which subtracts two steady steps."""
from benchmarks import setup_trace


def read(m):
    return setup_trace.metric(m, "step_build_s")
