"""Process start to the window's start, less the plain reference's time."""


def read(m):
    return m["run"]["setup_s"]
