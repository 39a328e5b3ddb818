"""Required FLOPs per second per chip over the device kind's bf16 peak."""
from benchmarks.harness import load_reader


def read(m):
    rate = load_reader("end_metrics", "tokens_per_s_per_chip")(m)
    return 100.0 * rate * m["flops_per_token"] / m["peak"]["bf16_flops_per_s"]
