"""The run's `setup_s` less `import_s` + `discover_s` + `step_build_s` +
`eager_compile_load_s`: jax's import and client start, the benchmark's own
seeded weights, `program.reset`, the compared and settling steps, and the
program's Python outside any phase (the `setup_trace` line's `top_level` has
where the phases lie)."""
from benchmarks import setup_trace


def read(m):
    return setup_trace.metric(m, "setup_unattributed_s")
