"""Per step, the host's time inside the step call before any wait. Where
Model.fit owns the loop: StepTimer's step/h2d + step/compute, of which the
second waits for the device on every 16th step."""


def read(m):
    return 1e3 * m["run"]["host_dispatch_s"]
