"""Required FLOPs per step over the device's busy time per step, against
the peak: what the kernels reach while the device runs."""


def read(m):
    trace = m["run"]["trace"]
    if not trace or not trace["steps"]:
        return None
    busy_per_step = trace["busy_s"] / trace["steps"]
    flops = m["flops_per_token"] * m["tokens_per_step"] / m["chips"]
    return 100.0 * flops / busy_per_step / m["peak"]["bf16_flops_per_s"]
