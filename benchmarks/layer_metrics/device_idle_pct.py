"""1 - union of device-op intervals / traced window, on the worst device."""


def read(m):
    trace = m["run"]["trace"]
    return None if not trace else trace["idle_pct_worst"]
