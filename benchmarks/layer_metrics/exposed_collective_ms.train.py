"""Per step, the time in all-reduce, all-gather, reduce-scatter,
collective-permute and all-to-all operations during which no other
operation runs on that device; the worst device."""


def read(m):
    trace = m["run"]["trace"]
    if not trace or not trace["steps"]:
        return None
    return 1e3 * trace["exposed_collective_s"] / trace["steps"]
