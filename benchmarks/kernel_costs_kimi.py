"""Operations and bytes of the kernels the Kimi Linear configuration adds,
from shapes, and the least time the chip could take for them
(kernel_costs.roofline_seconds). As kernel_costs.py: what a kernel is asked to
do each time it runs, so a rematerialised forward counts again; kept
conservative (a triangular product counts its triangle, operands cross HBM
once a pass), so a share of the roofline can only be understated.
"""
from benchmarks import kernel_costs, program_trace

KDA_CHUNK = 64      # paddle_tpu/ops/kda.py CHUNK, SUB
KDA_SUB = 16


def kda_pass_flops(tokens_heads, d_k, d_v, chunk=KDA_CHUNK, sub=KDA_SUB):
    """FLOPs of one forward pass of chunked Kimi Delta Attention over
    `tokens_heads` (token, head) pairs, every product of the chunk form once:
      the key-key and query-key products, a block row of `sub` rows against
        the keys up to its own block: 2 x chunk x chunk (n + 1) / 2n x d_k x 2,
        n = chunk / sub;
      (I + A)^-1 by forward substitution: 2 x chunk^3 / 3 (multiply-adds of
        the vector unit; counted as operations all the same);
      U = T V and W = T (K Gamma), T lower triangular: chunk (chunk + 1) / 2
        rows x columns, x (d_v + d_k) x 2;
      W S_0, q S_0 and K_end^T Delta against the state: 3 x chunk x d_k x d_v x 2;
      the query-key triangle times Delta: chunk (chunk + 1) / 2 x d_v x 2."""
    n = chunk // sub
    tri = chunk * (chunk + 1) / 2
    per_chunk = (2 * 2.0 * chunk * chunk * (n + 1) / (2 * n) * d_k
                 + 2.0 * chunk ** 3 / 3
                 + 2.0 * tri * (d_v + d_k)
                 + 3 * 2.0 * chunk * d_k * d_v
                 + 2.0 * tri * d_v)
    return per_chunk * tokens_heads / chunk


def kda_intra_flops(tokens_heads, d_k, d_v, chunk=KDA_CHUNK, sub=KDA_SUB):
    """The part of `kda_pass_flops` inside the chunks (stage 1), which the
    backward forms again from the inputs: everything but the three products
    against the state and the triangle times Delta."""
    tri = chunk * (chunk + 1) / 2
    rest = (3 * 2.0 * chunk * d_k * d_v + 2.0 * tri * d_v) * tokens_heads / chunk
    return kda_pass_flops(tokens_heads, d_k, d_v, chunk, sub) - rest


def kda_layer_seconds(batch, seq, heads, d_k, d_v, forward_passes, peak,
                      itemsize=2):
    """Roofline seconds of one KDA layer's op in one training step: each
    forward pass reads q, k, v (`itemsize`), g (float32) and beta and writes
    o; the backward forms stage 1 again, multiplies twice for every product
    of a forward pass (one product a gradient), reads the inputs, the output's
    cotangent and the chunk-start states and writes five gradients."""
    pairs = batch * seq * heads
    forward = kda_pass_flops(pairs, d_k, d_v)
    operands = pairs * ((2 * d_k + d_v) * itemsize + 4 * d_k + itemsize)
    states = pairs / KDA_CHUNK * d_k * d_v * 4
    fwd = kernel_costs.roofline_seconds(
        forward, operands + pairs * d_v * itemsize + states, peak)[0]
    bwd = kernel_costs.roofline_seconds(
        kda_intra_flops(pairs, d_k, d_v) + 2 * forward,
        2 * operands + pairs * d_v * itemsize + states, peak)[0]
    return forward_passes * fwd + bwd


def latent_attention_seconds(batch, heads, seq, d_qk, d_v, forward_passes, peak,
                             itemsize=2):
    """Roofline seconds of causal flash attention with query/key heads of
    `d_qk` and value heads of `d_v` in one training step, FlashAttention-2's
    count: forward the scores (d_qk) and the values (d_v); backward the scores
    again, dK and dQ (d_qk each), dV and dP (d_v each); every product
    2 x seq x seq x width a head, half of it under the causal mask; q, k, v,
    out and their gradients cross HBM once a pass."""
    unit = 2.0 * batch * heads * seq * seq / 2.0
    qk = batch * seq * heads * d_qk * itemsize
    vo = batch * seq * heads * d_v * itemsize
    forward = kernel_costs.roofline_seconds(
        unit * (d_qk + d_v), 2 * qk + 2 * vo, peak)[0]
    backward = kernel_costs.roofline_seconds(
        unit * (3 * d_qk + 2 * d_v), 4 * qk + 4 * vo, peak)[0]
    return forward_passes * forward + backward


def cell_shares(cell, scope_ms, peak):
    """{metric: percent} of a traced run of a `kimi_linear` cell from its
    device milliseconds a step by scope: the KDA op and the flash pair
    against their rooflines. A scope the trace lacks gives no entry."""
    cfg, job = cell["cfg"], cell["job"]
    kinds = [op for op, _ in cell["family"].layer_kinds(cfg)]
    passes = 2 if cfg["recompute"] else 1
    lin = cfg["linear_attn_config"]
    least = {
        "kda_roofline_pct": ("kda", kinds.count("kda") * kda_layer_seconds(
            job["batch"], job["seq"], lin["num_heads"], lin["head_dim"],
            lin["head_dim"], passes, peak)),
        "mla_flash_roofline_pct": (
            "flash_attention",
            kinds.count("full_attention") * latent_attention_seconds(
                job["batch"], cfg["num_attention_heads"], job["seq"],
                cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], passes, peak)),
    }
    return {name: 100.0 * seconds * 1e3 / scope_ms[scope]
            for name, (scope, seconds) in least.items() if scope_ms.get(scope)}


def read_share(m, metric):
    """For a reader: `metric` of `cell_shares` for the traced run behind `m`
    (what a reader is handed); None for an untraced run or a trace without
    the metric's scope (a parent of the PR that added it)."""
    reduced = program_trace.of(m)
    if reduced is None or not reduced["scope_ms"]:
        return None
    return cell_shares(m["cell"], reduced["scope_ms"], m["peak"]).get(metric)
