"""Keye-VL-2.0's language model (Kwai-Keye, `KeyeVL2`) in plain jax.numpy.

Float32 throughout, written from the architecture's public description (the
model's config.json; for the sparse-attention indexer DeepSeek-V3.2-Exp's
report, section "DeepSeek Sparse Attention", and its released inference code
as recalled): token embedding; 48 identical blocks h = x + Attn(N1(x)),
y = h + Experts(N2(h)), N an RMS norm with a learned gain; a last RMS norm
and a head of its own. No bias. No kernels, no cache; nothing is imported
from paddle_tpu. With a = N1(x), per row of the batch, t a query position
and s <= t a key position:

 1. q = rope(rmsnorm_head(a Wq)) (32 heads of 128), k = rope(rmsnorm_head(a
    Wk)), v = a Wv (4 heads of 128); query head h reads key/value head h // 8;
 2. the indexer, on a_sg = a with its gradient stopped: qI = rope(a_sg WqI)
    (16 heads of 64), kI = rope(layernorm(a_sg WkI)) (one head of 64),
    w = a_sg Ww (16) times 16^-1/2 64^-1/2;
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]);
 3. S_t = every s <= t while t < topk, else the s <= t with I[t, s] >= tau_t,
    tau_t the topk-th largest of row t (`lax.top_k`); ties at tau_t all kept;
    no gradient through the choice;
 4. o[h, t] = sum_{s in S_t} softmax_{S_t}(q[h, t] . k[h // 8, s] / sqrt(128))
    v[h // 8, s]; then Wo and the residual;
 5. the index loss of the layer: p[t, s] = the mean over the 32 heads of
    those probabilities, gradient stopped;
    L_I = mean_t sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(I[t, .])[s]);
 6. experts: sc = softmax(a' Wg) over the 128 published experts, the 8
    largest, wt = sc / their sum, y = sum_e wt_e W2_e(silu(W1_e a') * W3_e a')
    over the picked experts held here.

The loss is the mean next-token cross-entropy plus the sum of the layers'
L_I (the report's sparse training stage, coefficient 1): the indexer's leaves
(`index_*`) take gradient from L_I alone, since a_sg and the choice cut every
other path, and every other leaf from the cross-entropy alone, since p is
stopped.

Rotary positions are multimodal (`mrope_section` 16, 24, 24 of the 64
frequency pairs read the temporal, height and width stream): here every id is
text and the three streams are equal, 0, 1, 2, ...; `positions` may say
otherwise. The vision tower is left out.

Attention is computed a block of QUERY_ROWS query rows at a time under
`jax.checkpoint`, everything in steps 2-5 being local to a query row, so that
the float32 scores of 8192 positions fit beside the state: that changes no
arithmetic.

The chip's share (benchmarks/configs/keye-vl2-30b-a3b.json): the router
scores all `num_experts_published` experts and picks `num_experts_per_tok`,
the weights are normalised over all picked, and the sum runs over the picked
experts that are in `held_experts`; the vocabulary is the held slice. With
`absent_experts` "drop" what the absent experts would add is left out; with
"stand_in" (the configuration's) an absent expert e is computed by the held
leaves' slot e mod the number held, so all 8 picks of a token are computed
here, as in the program: the rows a rank of the deployment is sent, with the
weights this rank has (no share of the published layer: experts 16 apart
share weights).

Departures, all under `assumed` in the configuration: the indexer reads the
block's normed input; rotary positions turn all 64 dimensions of qI and kI,
their 32 pairs split 8, 12, 12 over the streams; a LayerNorm (gain and bias)
on kI; the weights' scale; per-head RMS norms on q and k; `expert_bias` is the
program's leaf of 128 zeros a layer that takes no gradient (the publication
has no such leaf; zero adds nothing).

`mm` is the matrix multiplication of every projection, the router, the
index's and the attention's products and the experts: `jnp.matmul` for the
reference, a rounding wrapper for the lower-precision control
(benchmarks/control.py).
"""
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
QUERY_ROWS = 512     # query rows of one checkpointed block of attention


def param_shapes(cfg):
    """{leaf: (shape, init)}; init is a std for a normal draw, or the
    constant a gain (1) or a bias (0) starts at."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    sa = cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    fe = cfg["moe_intermediate_size"]
    held, routed = len(cfg["held_experts"]), cfg["num_experts_published"]
    shapes = {"wte": ((v, h), INIT_STD), "norm_g": ((h,), "ones"),
              "head_w": ((h, v), INIT_STD)}
    for i in range(cfg["num_layers"]):
        p = f"l{i}."
        shapes[p + "op_norm_g"] = ((h,), "ones")
        shapes[p + "ff_norm_g"] = ((h,), "ones")
        shapes[p + "q_w"] = ((h, heads * d), INIT_STD)
        shapes[p + "k_w"] = ((h, kv * d), INIT_STD)
        shapes[p + "v_w"] = ((h, kv * d), INIT_STD)
        shapes[p + "o_w"] = ((heads * d, h), INIT_STD)
        shapes[p + "q_norm_g"] = ((d,), "ones")
        shapes[p + "k_norm_g"] = ((d,), "ones")
        shapes[p + "index_q_w"] = ((h, ih * idim), INIT_STD)
        shapes[p + "index_k_w"] = ((h, idim), INIT_STD)
        shapes[p + "index_w_w"] = ((h, ih), INIT_STD)
        shapes[p + "index_k_norm_g"] = ((idim,), "ones")
        shapes[p + "index_k_norm_b"] = ((idim,), "zeros")
        shapes[p + "gate_w"] = ((h, routed), INIT_STD)
        shapes[p + "expert_bias"] = ((routed,), "zeros")
        shapes[p + "e_w1"] = ((held, h, fe), INIT_STD)
        shapes[p + "e_w3"] = ((held, h, fe), INIT_STD)
        shapes[p + "e_w2"] = ((held, fe, h), INIT_STD)
    return shapes


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def silu(x):
    return x * jax.nn.sigmoid(x)


def index_sections(cfg):
    """The index head's frequency pairs over the three streams, in the main
    head's proportions: (8, 12, 12) of 32 for (16, 24, 24) of 64."""
    pairs = cfg["sa_config"]["indexer_head_dim"] // 2
    main = cfg["rope_scaling"]["mrope_section"]
    parts = [n * pairs // sum(main) for n in main]
    parts[-1] += pairs - sum(parts)
    return parts


def rotate(x, positions, sections, theta):
    """Rotary positions over the whole head, rotate-half convention, pair i
    turned by its stream's position: x (b, s, heads, d), positions (3, b, s),
    sections the pairs a stream."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    stream = jnp.asarray([j for j, n in enumerate(sections) for _ in range(n)])
    t = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., stream]  # (b, s, d/2)
    angle = jnp.concatenate([t * inv] * 2, axis=-1)[:, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def index_scores(qi, ki, w, mm):
    """I (b, r, s): qi (b, r, heads, d), ki (b, s, d), w (b, r, heads)."""
    dots = mm(qi.transpose(0, 2, 1, 3), ki.transpose(0, 2, 1)[:, None])
    return jnp.sum(w.transpose(0, 2, 1)[..., None] * jax.nn.relu(dots), axis=1)


def key_sets(scores, first, topk):
    """(b, r, s) bool: the set of each of the r queries from position
    `first` on, from their index scores over every key."""
    r, s = scores.shape[1:]
    causal = (first + jnp.arange(r))[:, None] >= jnp.arange(s)[None, :]
    row = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
    # a row with fewer than topk causal keys has tau = -inf: all of them
    tau = jax.lax.top_k(row, min(topk, s))[0][..., -1:]
    return causal & (row >= tau)


def attention_rows(q, k, v, qi, ki, w, first, cfg, mm, all_causal_keys=False):
    """Steps 2-5 for a block of query rows: q (b, r, heads, d), qi (b, r,
    index heads, index d), w (b, r, index heads), the queries from position
    `first` on, against every key k, v (b, s, kv heads, d), ki (b, s, index d).
    Returns (context (b, r, heads * d), the rows' sum of KL terms, the set).
    `all_causal_keys` is the faulty program of the tests: a set that is the
    whole causal row."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    group, d = heads // kv, cfg["head_dim"]
    scores = index_scores(qi, ki, w, mm)
    in_set = key_sets(scores, first, cfg["sa_config"]["topk"])
    if all_causal_keys:
        in_set = (first + jnp.arange(q.shape[1]))[:, None] >= jnp.arange(k.shape[1])[None, :]
        in_set = jnp.broadcast_to(in_set, scores.shape)
    ctx, p = [], 0.0
    for g in range(kv):
        dots = mm(q[:, :, g * group:(g + 1) * group].transpose(0, 2, 1, 3),
                  k[:, :, g].transpose(0, 2, 1)[:, None]) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(in_set[:, None], dots, -jnp.inf), axis=-1)
        ctx.append(mm(probs, v[:, :, g][:, None]).transpose(0, 2, 1, 3))
        p = p + jnp.sum(jax.lax.stop_gradient(probs), axis=1) / heads
    log_index = jax.nn.log_softmax(jnp.where(in_set, scores, -jnp.inf), axis=-1)
    live = in_set & (p > 0)
    kl = jnp.sum(jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                      - jnp.where(live, log_index, 0.0)), 0.0))
    b, r = q.shape[:2]
    return jnp.concatenate(ctx, axis=2).reshape(b, r, heads * d), kl, in_set


def attention_inputs(p, pre, a, positions, cfg, mm):
    """(q, k, v, qI, kI, w) of a layer from its normed input a (b, s, h)."""
    b, s, _ = a.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    sa, eps, theta = cfg["sa_config"], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    main = cfg["rope_scaling"]["mrope_section"]
    q = mm(a, p[pre + "q_w"]).reshape(b, s, heads, d)
    k = mm(a, p[pre + "k_w"]).reshape(b, s, kv, d)
    v = mm(a, p[pre + "v_w"]).reshape(b, s, kv, d)
    q = rotate(rms_norm(q, p[pre + "q_norm_g"], eps), positions, main, theta)
    k = rotate(rms_norm(k, p[pre + "k_norm_g"], eps), positions, main, theta)
    a_sg = jax.lax.stop_gradient(a)
    qi = rotate(mm(a_sg, p[pre + "index_q_w"]).reshape(b, s, ih, idim),
                positions, index_sections(cfg), theta)
    ki = layer_norm(mm(a_sg, p[pre + "index_k_w"]), p[pre + "index_k_norm_g"],
                    p[pre + "index_k_norm_b"], eps)
    ki = rotate(ki[:, :, None, :], positions, index_sections(cfg), theta)[:, :, 0]
    w = mm(a_sg, p[pre + "index_w_w"]) * (ih ** -0.5 * idim ** -0.5)
    return q, k, v, qi, ki, w


def attention(p, pre, a, positions, cfg, mm, note=None, **fault):
    """(out (b, s, h), L_I of the layer): the query rows in blocks of
    QUERY_ROWS, each rematerialised."""
    b, s, _ = a.shape
    q, k, v, qi, ki, w = attention_inputs(p, pre, a, positions, cfg, mm)
    rows = min(QUERY_ROWS, s)
    if s % rows:
        raise ValueError(f"{s} positions do not split into blocks of {rows}")

    def block(args):
        first, q_r, qi_r, w_r = args
        ctx, kl, in_set = attention_rows(q_r, k, v, qi_r, ki, w_r, first, cfg, mm,
                                         **fault)
        return (ctx, kl, in_set) if note is not None else (ctx, kl)

    def by_block(x):
        return jnp.moveaxis(x.reshape(b, s // rows, rows, *x.shape[2:]), 1, 0)
    out = jax.lax.map(block if note is not None else jax.checkpoint(block),
                      (jnp.arange(0, s, rows), by_block(q), by_block(qi), by_block(w)))
    if note is not None:
        note(pre + "key_set", jnp.moveaxis(out[2], 0, 1).reshape(b, s, s))
    ctx = jnp.moveaxis(out[0], 0, 1).reshape(b, s, -1)
    return mm(ctx, p[pre + "o_w"]), jnp.sum(out[1]) / (b * s)


def route(p, pre, x, cfg, mm):
    """(idx (.., k) the experts picked, w (.., k) their weights): softmax
    scores over every published expert, the k largest, renormalised."""
    sc = jax.nn.softmax(mm(x, p[pre + "gate_w"]), axis=-1)
    _, idx = jax.lax.top_k(sc + p[pre + "expert_bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(sc, idx, axis=-1)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def expert_slots(cfg, held=None):
    """Published expert -> the slot of the held leaves that computes it, -1
    where none does ("drop"); under "stand_in" an absent expert e has slot
    e mod the number held."""
    held = list(cfg["held_experts"] if held is None else held)
    slots = [-1] * cfg["num_experts_published"]
    if cfg.get("absent_experts", "drop") == "stand_in":
        slots = [e % len(held) for e in range(len(slots))]
    for slot, expert in enumerate(held):
        slots[expert] = slot
    return jnp.asarray(slots)


def expert_ff(p, pre, x, cfg, mm, held=None):
    """Every held expert applied to every token, weighed by the routing
    weights of the token's picks that it computes, zero where it computes
    none. `held` (ids) with the leaves' slots in that order; the
    configuration's by default."""
    idx, w = route(p, pre, x, cfg, mm)
    picked_slot = expert_slots(cfg, held)[idx]
    out = jnp.zeros_like(x)
    for slot in range(p[pre + "e_w1"].shape[0]):
        w_e = jnp.sum(jnp.where(picked_slot == slot, w, 0.0), axis=-1, keepdims=True)
        y = mm(silu(mm(x, p[pre + "e_w1"][slot])) * mm(x, p[pre + "e_w3"][slot]),
               p[pre + "e_w2"][slot])
        out = out + w_e * y
    return out


def block(p, i, x, positions, cfg, mm, note=None, **fault):
    pre, eps = f"l{i}.", cfg["rms_norm_eps"]
    out, index_loss = attention(p, pre, rms_norm(x, p[pre + "op_norm_g"], eps),
                                positions, cfg, mm, note, **fault)
    x = x + out
    a = rms_norm(x, p[pre + "ff_norm_g"], eps)
    if note is not None:
        note(pre + "picks", route(p, pre, a, cfg, mm)[0])
    return x + expert_ff(p, pre, a, cfg, mm), index_loss


def text_positions(ids):
    b, s = ids.shape
    return jnp.broadcast_to(jnp.arange(s)[None, None, :], (3, b, s))


def forward(p, ids, cfg, mm=jnp.matmul, positions=None, note=None, **fault):
    """(normed hidden states, the sum of the layers' index losses). `note`
    (name, value) is told each layer's sets and picks (no rematerialisation
    then: for the checks, not for the gradient)."""
    positions = text_positions(ids) if positions is None else positions
    x, index_loss = p["wte"][ids], 0.0
    for i in range(cfg["num_layers"]):
        if note is None:
            # rematerialised per block so a float32 backward fits beside the state
            x, layer_loss = jax.checkpoint(
                lambda x, i=i: block(p, i, x, positions, cfg, mm, **fault))(x)
        else:
            x, layer_loss = block(p, i, x, positions, cfg, mm, note, **fault)
        index_loss = index_loss + layer_loss
    return rms_norm(x, p["norm_g"], cfg["rms_norm_eps"]), index_loss


def loss_parts(p, ids, labels, cfg, mm=jnp.matmul, positions=None, **fault):
    """(mean next-token cross-entropy over the held slice of the vocabulary,
    the sum of the layers' index losses)."""
    h, index_loss = forward(p, ids, cfg, mm, positions, **fault)
    logp = jax.nn.log_softmax(mm(h, p["head_w"]), axis=-1)
    return (-jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1)),
            index_loss)


def loss_fn(p, ids, labels, cfg, mm=jnp.matmul, **kwargs):
    """What the step differentiates: the two parts added."""
    lm, index = loss_parts(p, ids, labels, cfg, mm, **kwargs)
    return lm + index
