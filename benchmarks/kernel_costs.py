"""Operations and bytes of the kernels this repo writes, from shapes and
from the rows a run's counters say were present, and the least time the chip
could take for them (the larger of operations over peak FLOP/s and bytes
over peak bytes/s). benchmarks/flops.py counts what a training step
requires; this counts what a kernel is asked to do each time it runs, so a
rematerialised forward counts again, as often as the traced program runs it
(`forward_passes`). Kept conservative: padding rows that a kernel computes
are not counted, and every operand is counted once however often a kernel
re-reads it, so a share of the roofline can only be understated.
"""


def roofline_seconds(flops, nbytes, peak):
    """(seconds, which bound) for one call of `flops` and `nbytes`."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def forward_passes(kernels_a_layer, backward_kernels, kernels_a_pass=1, otherwise=1):
    """Forward passes of a kernel scope in one training step, as the traced
    program ran them: a layer's custom calls a step (program_trace.
    kernels_a_layer) less its `backward_kernels`, `kernels_a_pass` to a
    forward pass, where that comes to one pass or two; `otherwise` where the
    trace does not say (for a model whose blocks are rematerialised whole, 2
    with `recompute` and 1 without)."""
    if kernels_a_layer is not None:
        passes = (kernels_a_layer - backward_kernels) / kernels_a_pass
        if passes in (1.0, 2.0):
            return int(passes)
    return otherwise


def grouped_product(rows, k, n, groups, itemsize=2):
    """(flops, bytes) of out[rows, n] = x[rows, k] @ w[group of the row],
    `groups` weight matrices of (k, n) each crossing HBM once. The product
    against the transposed weights (k and n exchanged) and the weight
    gradient dw[g] = x_g^T @ dy_g multiply as much and move the same three
    arrays, so all three kernels of a projection cost this."""
    return 2.0 * rows * k * n, itemsize * (rows * k + groups * k * n + rows * n)


def expert_layer_seconds(rows, hidden, width, groups, forward_passes, peak):
    """Roofline seconds of the grouped products of one SwiGLU expert layer
    in one training step with `rows` (token, expert) pairs present: three
    projections, each a product in every forward pass (two when the block is
    rematerialised), and a product against the transposed weights and a
    weight gradient in the backward pass."""
    return sum((forward_passes + 2) * roofline_seconds(
        *grouped_product(rows, k, n, groups), peak)[0]
        for k, n in ((hidden, width), (hidden, width), (width, hidden)))


def causal_attention_seconds(batch, heads, kv_heads, seq, head_dim,
                             forward_passes, peak, itemsize=2):
    """Roofline seconds of causal flash attention in one training step: two
    products forward (scores, values) and five backward (scores again, dV,
    dP, dK, dQ: FlashAttention-2's count), each 2*seq*seq*head_dim a head and
    half of that under the causal mask; q, k, v, out and their gradients
    cross HBM once a pass."""
    product = 2.0 * batch * heads * seq * seq * head_dim / 2.0
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    forward = roofline_seconds(2 * product, 2 * q + 2 * kv, peak)[0]
    backward = roofline_seconds(5 * product, 4 * q + 4 * kv, peak)[0]
    return forward_passes * forward + backward
