"""Per step, the training loop's wait for its next batch (StepTimer's
`step/input_wait`). Entries whose loop is the benchmark's own have none."""


def read(m):
    wait = m["run"]["input_wait_s"]
    return None if wait is None else 1e3 * wait
