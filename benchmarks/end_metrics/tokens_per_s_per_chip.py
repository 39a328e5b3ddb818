"""Tokens trained in the window, over the window's seconds and the chips."""


def read(m):
    run = m["run"]
    return run["steps"] * m["tokens_per_step"] / run["window_s"] / m["chips"]
