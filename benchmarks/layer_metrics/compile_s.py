"""The step's second and third calls (the plain program, then its
state-donating twin), less two steady steps."""


def read(m):
    return m["run"]["compile_s"]
