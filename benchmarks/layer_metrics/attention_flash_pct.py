"""The attention path the traced window took: the share of `attention_ms.train`
under scope `flash_attention` (100: the Pallas kernel, 0: XLA's softmax
attention, `sdpa`). Which path a call takes is a rule of shapes, mask,
dropout and platform (paddle_tpu/ops/attention.py::takes_flash, PR 30), the
same in every checkout: a cell reads 100 or 0, and a change of the rule
shows here before it shows in `attention_ms.train`."""
from benchmarks import program_trace


def read(m):
    flash = program_trace.scope_ms(m, ("flash_attention",))
    xla = program_trace.scope_ms(m, ("sdpa",))
    if flash is None or not flash + xla:
        return None
    return 100.0 * flash / (flash + xla)
