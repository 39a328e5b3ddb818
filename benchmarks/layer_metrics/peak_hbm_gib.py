"""`peak_bytes_in_use` after the window, on the fullest chip."""


def read(m):
    return m["memory_peak_bytes"] / 2 ** 30
