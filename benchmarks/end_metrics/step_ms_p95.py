"""95th percentile of the interval between completions of successive steps."""
import numpy as np


def read(m):
    return 1e3 * float(np.percentile(m["run"]["step_intervals_s"], 95))
