"""to_static's eager discovery pass: the step's first call."""


def read(m):
    return m["run"]["eager_pass_s"]
