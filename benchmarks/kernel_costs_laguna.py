"""Operations and bytes of what the Laguna configuration adds, from shapes,
and the least time the chip could take for them
(kernel_costs.roofline_seconds). Required work, whatever implements it: a
window layer's two attention products at the pairs of the band, sum over t
of min(t + 1, window), never the causal triangle; a full layer's at the
triangle. An implementation that multiplies tiles outside the band and
masks therefore reads a low share, and no share can pass 100. As
kernel_costs.py: FlashAttention-2's count of the backward, a forward pass as
often as the traced program runs it, operands across HBM once a pass. Every
reader returns None, and does not raise, where the run has no trace or the
trace no such kernel or scope.
"""
from benchmarks import kernel_costs, program_trace
# the band's pairs are a set's of `window` keys a query, sum over t of
# min(t + 1, window), and a pair over them costs what the pair over the sets
# costs: the Keye cell's count, imported and not copied
from benchmarks.kernel_costs_keye import causal_pairs  # noqa: F401  (tests, PERF.md)
from benchmarks.kernel_costs_keye import set_attention_seconds, set_pairs as band_pairs

WINDOW_KERNELS = "flash_window_"      # the banded pair's instructions start so
FLASH_SCOPE = "flash_attention"


def layers(cfg):
    """[(kind of attention, query heads)] of the layers held."""
    first = cfg["first_layer"]
    return [(cfg["layer_types"][i], cfg["num_attention_heads_per_layer"][i])
            for i in range(first, first + cfg["num_layers"])]


def keys_a_query(cfg, kind, seq):
    """The most keys a query of a layer of `kind` reads in a row of `seq`: its
    window, or the whole row (a full layer is a window of the row's length)."""
    return min(cfg["sliding_window"], seq) if kind == "sliding_attention" else seq


def layer_pairs(cfg, kind, seq):
    return band_pairs(seq, keys_a_query(cfg, kind, seq))


def attention_train_flops(cfg, seq):
    """Required FLOPs of one row of `seq` tokens in every layer's attention
    in training: scores and values, 2 x head_dim a pair a head each, forward
    and twice that backward (benchmarks/flops.py's convention). For a count a
    token: divide by seq."""
    return sum(3.0 * 2 * 2.0 * heads * cfg["head_dim"] * layer_pairs(cfg, kind, seq)
               for kind, heads in layers(cfg))


def flash_seconds(cfg, job, forward_passes, peak, kinds=("full_attention",
                                                         "sliding_attention")):
    """Roofline seconds of the flash pairs of the layers of `kinds` in one
    training step: two products forward, five backward (FlashAttention-2's
    count), each 2 x head_dim a kept pair a head, a full layer's pairs the
    window of a whole row's; q, k, v, out and their gradients cross HBM once
    a pass."""
    return sum(set_attention_seconds(
        job["batch"], job["seq"], heads, cfg["num_key_value_heads"], cfg["head_dim"],
        keys_a_query(cfg, kind, job["seq"]), forward_passes, peak)
        for kind, heads in layers(cfg) if kind in kinds)


def window_kernels(m):
    """(device ms a step, instructions) of the kernels named `flash_window_*`
    in the traced run behind `m`; None where the run has no device trace or
    the trace no such kernel."""
    reduced = m["run"].get("trace")
    if not reduced or not reduced.get("steps"):
        return None
    mine = [seconds for name, seconds in reduced.get("device_ops", ())
            if name.startswith(WINDOW_KERNELS)]
    if not mine:
        return None
    return 1e3 * sum(mine) / reduced["steps"], len(mine)


def window_ms(m):
    found = window_kernels(m)
    return found and found[0]


def window_roofline_pct(m):
    """`swa_window_roofline_pct`: the window layers' roofline seconds over
    their kernels' device time. A layer runs one backward kernel, so its
    instructions less one are the forward passes the trace holds."""
    found = window_kernels(m)
    if found is None:
        return None
    spent, instructions = found
    cfg, job = m["cell"]["cfg"], m["cell"]["job"]
    held = sum(kind == "sliding_attention" for kind, _ in layers(cfg))
    passes = kernel_costs.forward_passes(instructions / held, backward_kernels=1)
    least = flash_seconds(cfg, job, passes, m["peak"], kinds=("sliding_attention",))
    return 100.0 * least * 1e3 / spent


def flash_roofline_pct(m):
    """`swa_flash_roofline_pct`: all the layers' required attention work over
    the `flash_attention` scope's device time, the copies XLA makes round
    the kernels included."""
    spent = program_trace.scope_ms(m, (FLASH_SCOPE,))
    if not spent:
        return None
    cfg, job = m["cell"]["cfg"], m["cell"]["job"]
    passes = kernel_costs.forward_passes(
        program_trace.kernels_a_layer(m, (FLASH_SCOPE,), cfg["num_layers"]),
        backward_kernels=1)
    return 100.0 * flash_seconds(cfg, job, passes, m["peak"]) * 1e3 / spent
