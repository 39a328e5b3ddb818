"""Seconds of backend compile requests that the package's own code made
outside any set-up span (`compile.backend_sec{phase="eager"}`): compiling, or
loading from the persistent cache, the small programs of layer constructors,
initialisers, `set_state_dict` and the optimizer's first state."""
from benchmarks import setup_trace


def read(m):
    return setup_trace.metric(m, "eager_compile_load_s")
