"""Operations and bytes of what the Keye-VL-2.0 configuration adds, from
shapes, and the least time the chip could take for them
(kernel_costs.roofline_seconds). Required work, whatever implements it: the
main attention's products at the pairs of the sets, sum over t of
min(t + 1, topk), never the dense causal triangle; the index's products at
every causal pair (a row's threshold needs them all); the index loss's
second pass over the main scores once a pass. An implementation that
multiplies the whole triangle and masks therefore reads a low share, and no
share can pass 100. As kernel_costs.py: a forward pass counts as often as
the traced program runs it; operands cross HBM once a pass.
"""
from benchmarks import kernel_costs, program, program_trace


def causal_pairs(seq):
    """(query, key) pairs with the key at or before the query."""
    return seq * (seq + 1) / 2.0


def set_pairs(seq, topk):
    """Pairs of the sets: sum over t of min(t + 1, topk); ties, which add
    keys, are not counted."""
    if seq <= topk:
        return causal_pairs(seq)
    return causal_pairs(topk) + (seq - topk) * float(topk)


def main_forward_flops(seq, heads, d, topk):
    """Scores and values over the sets: two products of 2 x d a pair a head."""
    return 2 * 2.0 * heads * d * set_pairs(seq, topk)


def index_forward_flops(seq, index_heads, index_d):
    """The index's one product, 2 x d a causal pair a head (the relu, the
    weighted sum and the threshold are not matrix products)."""
    return 2.0 * index_heads * index_d * causal_pairs(seq)


def second_pass_flops(seq, heads, d, topk):
    """The index loss forms the main scores again over the sets, once."""
    return 2.0 * heads * d * set_pairs(seq, topk)


def layer_train_flops(seq, heads, d, index_heads, index_d, topk):
    """Required FLOPs of one row of `seq` tokens in one layer's attention in
    training: the main and the index products forward and twice that
    backward, the loss's second pass once. For benchmarks/flops.py's count
    a token: divide by seq."""
    forward = (main_forward_flops(seq, heads, d, topk)
               + index_forward_flops(seq, index_heads, index_d))
    return 3.0 * forward + second_pass_flops(seq, heads, d, topk)


def set_attention_seconds(batch, seq, heads, kv_heads, d, topk, forward_passes,
                          peak, itemsize=2):
    """Roofline seconds of the flash pair over the sets in one training step:
    two products forward, five backward (FlashAttention-2's count), each
    2 x d a pair of the sets a head; q, k, v, out and their gradients cross
    HBM once a pass (the set's own bytes are not counted)."""
    product = batch * 2.0 * heads * d * set_pairs(seq, topk)
    q = batch * seq * heads * d * itemsize
    kv = batch * seq * kv_heads * d * itemsize
    forward = kernel_costs.roofline_seconds(2 * product, 2 * q + 2 * kv, peak)[0]
    backward = kernel_costs.roofline_seconds(5 * product, 4 * q + 4 * kv, peak)[0]
    return forward_passes * forward + backward


def index_seconds(batch, seq, hidden, heads, d, index_heads, index_d, topk,
                  forward_passes, peak, itemsize=2):
    """Roofline seconds of the index and its loss in one training step
    (scopes `dsa_index` and `dsa_index_loss`): a forward pass makes the three
    projections and the scores at every causal pair and, for the loss, the
    main scores over the sets once more; the backward is twice the
    projections and the scores. Operands: the block's input, the index
    queries, keys and weights, the main queries and keys, the set (a byte a
    pair of the square) written once and read twice a pass."""
    width = index_heads * index_d + index_d + index_heads
    proj = batch * 2.0 * seq * hidden * width
    scores = batch * index_forward_flops(seq, index_heads, index_d)
    second = batch * second_pass_flops(seq, heads, d, topk)
    acts = batch * seq * (hidden + width + 2 * heads * d) * itemsize
    pairs = 3.0 * batch * seq * seq
    forward = kernel_costs.roofline_seconds(proj + scores + second,
                                            acts + pairs, peak)[0]
    backward = kernel_costs.roofline_seconds(2 * (proj + scores), 2 * acts, peak)[0]
    return forward_passes * forward + backward


def cell_shares(cell, scope_ms, peak, flash_passes=1, index_passes=1):
    """{metric: percent} of a traced run of a `keye_vl2` cell from its device
    milliseconds a step by scope (program_trace.reduce's `scope_ms`) and the
    forward passes the traced program ran. A scope the trace lacks gives no
    entry. One pass where the trace does not say: the sparse-attention core
    stands outside the rematerialised regions (PR 43)."""
    cfg, job = cell["cfg"], cell["job"]
    sa, layers = cfg["sa_config"], cfg["num_layers"]
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    spent_index = (scope_ms.get("dsa_index") or 0.0) + (scope_ms.get("dsa_index_loss") or 0.0)
    least = {
        "dsa_flash_roofline_pct": (
            scope_ms.get("flash_attention"),
            layers * set_attention_seconds(
                job["batch"], job["seq"], heads, cfg["num_key_value_heads"], d,
                sa["topk"], flash_passes, peak)),
        "dsa_index_roofline_pct": (
            spent_index,
            layers * index_seconds(
                job["batch"], job["seq"], cfg["hidden_size"], heads, d,
                sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
                index_passes, peak)),
    }
    return {name: 100.0 * seconds * 1e3 / spent
            for name, (spent, seconds) in least.items() if spent}


def read_share(m, metric):
    """For a reader: `metric` of `cell_shares` for the traced run behind `m`;
    None for an untraced run or a trace without the metric's scope (a parent
    of the PR that added it). The passes are the trace's: a layer's flash pair
    is one backward kernel and a forward kernel a pass; the index is a sets
    kernel and a loss kernel a pass, its backward inside them."""
    reduced = program_trace.of(m)
    if reduced is None or not reduced["scope_ms"]:
        return None
    layers = m["cell"]["cfg"]["num_layers"]
    return cell_shares(
        m["cell"], reduced["scope_ms"], m["peak"],
        kernel_costs.forward_passes(
            program_trace.kernels_a_layer(m, ("flash_attention",), layers), 1),
        kernel_costs.forward_passes(
            program_trace.kernels_a_layer(m, ("dsa_index", "dsa_index_loss"), layers),
            0, kernels_a_pass=2)).get(metric)


def selected_pairs_per_step(m):
    """(query, key) pairs the index selected a step, summed over the layers:
    the device counter `dsa.selected_pairs_total` over the steps run since
    the model was built (`dsa.calls_total` over the layers). None where the
    program has no such counter."""
    counters = (program.registry() or {}).get("counters", {})
    pairs, calls = counters.get("dsa.selected_pairs_total"), counters.get("dsa.calls_total")
    if pairs is None or not calls:
        return None
    layers = m["cell"]["cfg"]["num_layers"]
    return pairs * layers / calls
