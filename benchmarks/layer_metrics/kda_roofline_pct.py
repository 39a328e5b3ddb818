"""The chunked Kimi Delta Attention op against its roofline: the chunk form's
products at the op's chunk size, each once a pass, the triangular solve at
2 C^3 / 3, the operands once a pass, two forward passes under
rematerialisation and the backward (benchmarks/kernel_costs_kimi.py), over
the `kda` scope's device time, whatever stage is a kernel. None where the
trace has no such scope."""
from benchmarks import kernel_costs_kimi


def read(m):
    return kernel_costs_kimi.read_share(m, "kda_roofline_pct")
