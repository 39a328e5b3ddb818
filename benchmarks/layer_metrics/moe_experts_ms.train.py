"""Per step, the device time of the operations whose scope is `moe_experts`
(benchmarks/program_trace.py, `scope_ms`): the expert layers' grouped
products, forward, rematerialised forward and backward (the gmm and tgmm
kernels of paddle_tpu/ops/pallas/grouped_matmul.py). The SwiGLU between them
is under `swiglu`, the routing under `moe_route_ms.train`."""
from benchmarks import program_trace


def read(m):
    return program_trace.scope_ms(m, ("moe_experts",))
