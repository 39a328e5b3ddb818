"""Backend compile requests of the process since the package was imported,
all phases (`compile.requests_total`), a load from the persistent cache being
one. Read after the traced steps, so the one `lower().compile()` of
`entries/to_static_loop.compiled_text` is among them (phase `user`), and the
plain reference's where the family module imported the package before it."""
from benchmarks import setup_trace


def read(m):
    return setup_trace.metric(m, "setup_compile_requests")
