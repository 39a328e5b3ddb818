"""`peak_bytes_in_use` on the fullest chip when the entry has returned and
before the plain reference runs: the program's, whatever the reference
needs afterwards (benchmarks/run.py)."""


def read(m):
    return m["memory_peak_bytes"] / 2 ** 30
