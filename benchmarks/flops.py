"""Operations and bytes a training step requires, from shapes alone.

Required work only: what the forward and backward passes of the published
architecture need, whatever the program does to get there. Recomputation is
not counted, an embedding gather is not a matrix multiplication, and a
causal attention needs half the score and value products of a full one.
"""


def matmul_weights(shapes):
    """Sum of in x out over the weight matrices a token is multiplied by."""
    return sum(a * b for a, b in shapes)


def train_flops_per_token(per_token_shapes, per_sequence_shapes, seq, layers,
                          hidden, causal):
    """Forward plus backward FLOPs per trained token.

    A weight matrix costs 2 FLOPs per entry forward and 4 backward (input
    and weight gradients). Attention's two products (scores, values) cost
    4*s*h forward per token and layer, so 12*L*s*h in training, halved
    when causal. `per_sequence_shapes` are matrices applied to one position
    of a sequence (a pooler, a classifier): their cost is spread over `seq`.
    """
    dense = 6.0 * (matmul_weights(per_token_shapes)
                   + matmul_weights(per_sequence_shapes) / seq)
    attention = 12.0 * layers * seq * hidden
    return dense + (attention / 2.0 if causal else attention)


def optimizer_bytes_per_step(n_params, weight_bytes, master_weights):
    """HBM traffic of one AdamW update: read gradient and both float32
    moments, write both moments; read and write the float32 master (or the
    weight itself without masters), write the served weight."""
    if master_weights:
        per_param = weight_bytes + 3 * 4 + 3 * 4 + weight_bytes
    else:
        per_param = weight_bytes + 2 * 4 + weight_bytes + 2 * 4 + weight_bytes
    return n_params * per_param
