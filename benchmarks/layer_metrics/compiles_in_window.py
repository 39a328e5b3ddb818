"""Requests to compile inside the measured window; anything but 0 makes
the run not correct."""


def read(m):
    return m["run"]["compiles_in_window"]
