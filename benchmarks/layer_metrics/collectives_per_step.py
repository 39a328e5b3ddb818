"""Collective operations in the compiled step's text."""


def read(m):
    return m["run"]["collectives"]
