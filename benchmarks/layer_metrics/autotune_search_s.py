"""Seconds inside `autotune.search` spans (`autotune.search_sec`): the Pallas
kernels' tile searches, each on a thread of its own under the discovery pass
or a step's trace, so inside `discover_s` or `step_build_s` and in no sum. 0
where the disk cache answered every signature."""
from benchmarks import setup_trace


def read(m):
    return setup_trace.metric(m, "autotune_search_s")
