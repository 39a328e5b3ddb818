"""The attention path the traced window took: the share of `attention_ms.train`
under scope `flash_attention` (100: the Pallas kernel, 0: XLA's softmax
attention, `sdpa`). The fusion policy decides per checkout and shape
(paddle_tpu/ops/attention.py); a run whose neighbour took the other path
differs by that and not by the change under test."""
from benchmarks import program_trace


def read(m):
    flash = program_trace.scope_ms(m, ("flash_attention",))
    xla = program_trace.scope_ms(m, ("sdpa",))
    if flash is None or not flash + xla:
        return None
    return 100.0 * flash / (flash + xla)
