"""DeepSeek-V2 decoder (DeepSeek-AI, `deepseek_v2`; arXiv 2405.04434) as
DeepSeek-V2-Lite configures it, in plain jax.numpy.

Float32 throughout, written from the architecture's public description (the
model's config.json; the report's sections on multi-head latent attention,
DeepSeekMoE and the auxiliary losses; the source's modelling code as
recalled): token embedding; blocks h = x + MLA(N1(x)), y = h + FF(N2(h)), N an
RMS norm with a learned gain; FF a dense SwiGLU in the published layers below
`first_k_dense_replace` and the expert layer after; a last RMS norm and a head
of its own. No bias. No kernels, no cache; nothing is imported from
paddle_tpu.

MLA (`q_lora_rank` null), a token t, a head i of `num_attention_heads`:
    q_i = W_Q^i x = [q^N (qk_nope_head_dim) ; q^R (qk_rope_head_dim)]
    [c (kv_lora_rank) ; k^R (qk_rope_head_dim)] = W_KVA x
    [k^N_i ; v_i] = W_KVB^i rmsnorm(c)
    score(t, j, i) = sigma (q^N_t,i . k^N_j,i + R_t(q^R_t,i) . R_j(k^R_j)),  j <= t
softmax over j, o_t,i = sum_j p v_j,i, out = W_O [o_t,1 .. o_t,heads]. One
rotated key part serves every head. R_t turns pair n, entries (2n, 2n + 1),
by t f_n; under YaRN (`rope_scaling`) f_n blends theta_n = theta^(-2n/d) with
theta_n / factor (`frequencies`), cos and sin carry m(mscale) /
m(mscale_all_dim) and sigma = (nope + rope)^-1/2 m(mscale_all_dim)^2, m(a) =
0.1 a ln(factor) + 1 (`scales`).

Expert layer on u = rmsnorm(h): s = softmax(W_G u) over all published experts;
T = the `num_experts_per_tok` largest (plain top-k: `topk_method` greedy, one
group); g_e = s_e for e in T, *not* renormalised (`norm_topk_prob` false),
times `routed_scaling_factor`; y = sum_{e in T} g_e E_e(u) + S(u), E_e a SwiGLU
of `moe_intermediate_size`, S one SwiGLU of `n_shared_experts` times that.

Balance loss (`seq_aux`), an expert layer, a sequence b of T tokens over the E
published experts and k picks a token:
    f_b,e = E / (k T) #{t : e in T_t},  P_b,e = mean_t s_t,e
    L_bal = aux_loss_alpha mean_b sum_e f_b,e P_b,e,   gradient through P only.
The training loss is the cross-entropy plus the sum of L_bal over the expert
layers held.

The chip's share (benchmarks/configs/deepseek-v2-lite.json): the router
scores all `n_routed_experts_published` experts; the sum runs over the picked
experts that a held slot computes (`expert_slots`: those in `held_experts`,
and under `absent_experts` "stand_in" every other one through slot e mod the
number held); the shared experts are whole; the vocabulary is the held
slice; f and P are over the published experts whatever is held.

Departures, all under `assumed` in the configuration:
  - the source adds the balance term's gradient and not its value to the
    loss it reports; here the value is added too, so that the compared first
    loss holds the term;
  - `expert_bias` is a leaf of zeros with no gradient that the program's
    expert layer holds; the source has none and zero adds nothing;
  - the rotated parts are kept in the order they have (pair n at 2n, 2n + 1);
    the source reorders them half-split before its rotate-half, queries and
    keys alike, which leaves every product as it is;
  - the attention runs over blocks of QUERY_ROWS query rows, each
    rematerialised, and the blocks of the model are rematerialised, so that
    the float32 backward of 8192 positions fits; no arithmetic changes.

`mm` is the matrix multiplication of every projection, the router, the
attention products, the experts and the head: `jnp.matmul` for the reference,
a rounding wrapper for the lower-precision control (benchmarks/control.py).
`fault` names one departure from the equations above, for the tests and the
limits' readings (a faulty program has to come out as not correct):
`plain_frequencies`, `no_mscale`, `half_split_pairs`, `key_rotated_by_head`,
`renormalised`, `balance_over_batch`, `no_balance_loss`.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
QUERY_ROWS = 1024   # query rows of one rematerialised block of the attention


def layer_kinds(cfg):
    """["dense" | "experts"] of the layers held: layer i here is published
    layer `first_layer` + i, dense below `first_k_dense_replace`."""
    return ["dense" if cfg["first_layer"] + i < cfg["first_k_dense_replace"]
            else "experts" for i in range(cfg["num_layers"])]


def param_shapes(cfg):
    """{leaf: (shape, init)}; init is a std for a normal draw, or the
    constant a gain (1) or the expert bias (0) starts at."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    held, routed = len(cfg["held_experts"]), cfg["n_routed_experts_published"]
    shapes = {"wte": ((v, h), INIT_STD), "norm_g": ((h,), "ones"),
              "head_w": ((h, v), INIT_STD)}
    for i, ff in enumerate(layer_kinds(cfg)):
        p = f"l{i}."
        shapes[p + "op_norm_g"] = ((h,), "ones")
        shapes[p + "ff_norm_g"] = ((h,), "ones")
        shapes[p + "q_w"] = ((h, heads * (nope + rope)), INIT_STD)
        shapes[p + "kv_a_w"] = ((h, rank + rope), INIT_STD)
        shapes[p + "kv_a_norm_g"] = ((rank,), "ones")
        shapes[p + "kv_b_w"] = ((rank, heads * (nope + dv)), INIT_STD)
        shapes[p + "o_w"] = ((heads * dv, h), INIT_STD)
        if ff == "dense":
            shapes[p + "w1"] = ((h, f), INIT_STD)
            shapes[p + "w3"] = ((h, f), INIT_STD)
            shapes[p + "w2"] = ((f, h), INIT_STD)
        else:
            shapes[p + "gate_w"] = ((h, routed), INIT_STD)
            shapes[p + "expert_bias"] = ((routed,), "zeros")
            shapes[p + "e_w1"] = ((held, h, fe), INIT_STD)
            shapes[p + "e_w3"] = ((held, h, fe), INIT_STD)
            shapes[p + "e_w2"] = ((held, fe, h), INIT_STD)
            shapes[p + "s_w1"] = ((h, fs), INIT_STD)
            shapes[p + "s_w3"] = ((h, fs), INIT_STD)
            shapes[p + "s_w2"] = ((fs, h), INIT_STD)
    return shapes


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu_ff(x, w1, w3, w2, mm):
    return mm(silu(mm(x, w1)) * mm(x, w3), w2)


# ---------------------------------------------------------------------------
# positions

def correction_range(cfg):
    """(low, high): the pair indices between which YaRN's ramp runs, the
    floor and the ceiling of the index at which a pair turns `beta_fast` and
    `beta_slow` times over the original context."""
    d, theta, rs = cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), cfg["rope_scaling"]

    def pair(rotations):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(pair(rs["beta_fast"])), 0),
            min(math.ceil(pair(rs["beta_slow"])), d - 1))


def frequencies(cfg, plain_frequencies=False):
    """(qk_rope_head_dim / 2,) float32: f_n = theta_n gamma_n + theta_n /
    factor (1 - gamma_n), gamma_n = 1 - clip((n - low) / (high - low), 0, 1);
    theta_n where the configuration has no `rope_scaling`. Float64, rounded
    once."""
    d, theta, rs = cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), cfg["rope_scaling"]
    n = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2.0 * n / d)
    if rs is None or plain_frequencies:
        return plain.astype(np.float32)
    low, high = correction_range(cfg)
    gamma = 1.0 - np.clip((n - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * gamma + plain / rs["factor"] * (1.0 - gamma)).astype(np.float32)


def scales(cfg, no_mscale=False):
    """(what cos and sin carry, sigma)."""
    rs = cfg["rope_scaling"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    if rs is None:
        return 1.0, width ** -0.5

    def m(a):
        return 1.0 if rs["factor"] <= 1 else 0.1 * a * math.log(rs["factor"]) + 1.0
    every = m(rs["mscale_all_dim"])
    return m(rs["mscale"]) / every, width ** -0.5 * (1.0 if no_mscale else every ** 2)


def rotate(x, positions, cfg, table_scale, plain_frequencies=False,
           half_split_pairs=False):
    """x (..., seq, heads, d) turned: pair n = entries (2n, 2n + 1) by
    positions[t] f_n, in the order they have. `positions` (seq,) float32."""
    angle = positions[:, None] * jnp.asarray(frequencies(cfg, plain_frequencies))
    cos, sin = jnp.cos(angle) * table_scale, jnp.sin(angle) * table_scale
    cos, sin = cos[:, None, :], sin[:, None, :]
    if half_split_pairs:        # the fault: entry n paired with entry n + d/2
        half = x.shape[-1] // 2
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def mla(p, pre, x, cfg, mm, **fault):
    b, s, _ = x.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    table_scale, sigma = scales(cfg, fault.get("no_mscale", False))
    turn = dict(plain_frequencies=fault.get("plain_frequencies", False),
                half_split_pairs=fault.get("half_split_pairs", False))
    positions = jnp.arange(s, dtype=jnp.float32)
    q = mm(x, p[pre + "q_w"]).reshape(b, s, heads, nope + rope)
    c = mm(x, p[pre + "kv_a_w"])
    latent = rms_norm(c[..., :rank], p[pre + "kv_a_norm_g"], cfg["rms_norm_eps"])
    kv = mm(latent, p[pre + "kv_b_w"]).reshape(b, s, heads, nope + dv)
    q = jnp.concatenate([q[..., :nope],
                         rotate(q[..., nope:], positions, cfg, table_scale, **turn)],
                        axis=-1)
    shared = c[:, :, None, rank:]                       # (b, s, 1, rope)
    if fault.get("key_rotated_by_head"):
        # the fault: broadcast first, and the head's index taken for the position
        wide = jnp.broadcast_to(shared, (b, s, heads, rope)).transpose(0, 2, 1, 3)
        k_pe = rotate(wide, jnp.arange(heads, dtype=jnp.float32), cfg, table_scale,
                      **turn).transpose(0, 2, 1, 3)
    else:
        k_pe = jnp.broadcast_to(rotate(shared, positions, cfg, table_scale, **turn),
                                (b, s, heads, rope))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1).transpose(0, 2, 3, 1)
    v = kv[..., nope:].transpose(0, 2, 1, 3)
    rows = min(QUERY_ROWS, s)
    if s % rows:
        raise ValueError(f"{s} positions do not split into blocks of {rows}")

    @jax.checkpoint
    def block(args):
        first, q_r = args                               # q_r (b, rows, heads, d)
        scores = mm(q_r.transpose(0, 2, 1, 3), k) * sigma
        causal = (first + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), v).transpose(0, 2, 1, 3)

    q_blocks = jnp.moveaxis(q.reshape(b, s // rows, rows, heads, nope + rope), 1, 0)
    ctx = jax.lax.map(block, (jnp.arange(0, s, rows), q_blocks))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, heads * dv)
    return mm(ctx, p[pre + "o_w"])


# ---------------------------------------------------------------------------
# experts

def route(p, pre, x, cfg, mm, renormalised=False):
    """(s (.., E) the scores, idx (.., k) the experts picked, w (.., k) their
    weights): softmax over every published expert; the k largest; their
    scores as they are, times the scaling factor."""
    s = jax.nn.softmax(mm(x, p[pre + "gate_w"]), axis=-1)
    _, idx = jax.lax.top_k(s + p[pre + "expert_bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalised:            # the fault (`norm_topk_prob` true)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return s, idx, w * cfg["routed_scaling_factor"]


def expert_slots(cfg, held=None):
    """Published expert -> the slot of the held leaves that computes it, -1
    where none does ("drop"); under "stand_in" an absent expert e has slot
    e mod the number held."""
    held = list(cfg["held_experts"] if held is None else held)
    slots = [-1] * cfg["n_routed_experts_published"]
    if cfg.get("absent_experts", "drop") == "stand_in":
        slots = [e % len(held) for e in range(len(slots))]
    for slot, expert in enumerate(held):
        slots[expert] = slot
    return jnp.asarray(slots)


def routed_part(p, pre, x, idx, w, cfg, mm, held=None):
    """Every held slot applied to every token, weighed by the routing
    weights of the token's picks that it computes, zero where it computes
    none. `held` (ids) with the leaves' slots in that order; the
    configuration's by default."""
    picked_slot = expert_slots(cfg, held)[idx]
    out = jnp.zeros_like(x)
    for slot in range(p[pre + "e_w1"].shape[0]):
        w_e = jnp.sum(jnp.where(picked_slot == slot, w, 0.0), axis=-1, keepdims=True)
        out = out + w_e * swiglu_ff(x, p[pre + "e_w1"][slot], p[pre + "e_w3"][slot],
                                    p[pre + "e_w2"][slot], mm)
    return out


def balance_loss(s, idx, cfg, balance_over_batch=False):
    """L_bal of one expert layer; s (b, T, E), idx (b, T, k)."""
    e, k = s.shape[-1], idx.shape[-1]
    picked = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=2)    # (b, T, E)
    if balance_over_batch:      # the fault: one f and one P for the whole batch
        picked, s = picked.reshape(1, -1, e), s.reshape(1, -1, e)
    f = jax.lax.stop_gradient(jnp.sum(picked, axis=1) * e / (k * s.shape[1]))
    return cfg["aux_loss_alpha"] * jnp.mean(jnp.sum(f * jnp.mean(s, axis=1), axis=-1))


def expert_ff(p, pre, x, cfg, mm, held=None, **fault):
    """(the layer's result, its balance loss)."""
    s, idx, w = route(p, pre, x, cfg, mm, fault.get("renormalised", False))
    out = routed_part(p, pre, x, idx, w, cfg, mm, held) + swiglu_ff(
        x, p[pre + "s_w1"], p[pre + "s_w3"], p[pre + "s_w2"], mm)
    return out, balance_loss(s, idx, cfg, fault.get("balance_over_batch", False))


def block(p, i, kind, x, cfg, mm, **fault):
    pre, eps = f"l{i}.", cfg["rms_norm_eps"]
    x = x + mla(p, pre, rms_norm(x, p[pre + "op_norm_g"], eps), cfg, mm, **fault)
    a = rms_norm(x, p[pre + "ff_norm_g"], eps)
    if kind == "dense":
        return x + swiglu_ff(a, p[pre + "w1"], p[pre + "w3"], p[pre + "w2"], mm), jnp.zeros(())
    out, balance = expert_ff(p, pre, a, cfg, mm, **fault)
    return x + out, balance


def forward(p, ids, cfg, mm=jnp.matmul, **fault):
    """(normed hidden states, the sum of the expert layers' balance losses)."""
    x, balance = p["wte"][ids], 0.0
    for i, kind in enumerate(layer_kinds(cfg)):
        # rematerialised per block so a float32 backward fits beside the state
        x, layer_loss = jax.checkpoint(
            lambda x, i=i, kind=kind: block(p, i, kind, x, cfg, mm, **fault))(x)
        balance = balance + layer_loss
    return rms_norm(x, p["norm_g"], cfg["rms_norm_eps"]), balance


def loss_parts(p, ids, labels, cfg, mm=jnp.matmul, **fault):
    """(mean next-token cross-entropy over the held slice of the vocabulary,
    the sum of the expert layers' balance losses)."""
    h, balance = forward(p, ids, cfg, mm, **fault)
    logp = jax.nn.log_softmax(mm(h, p["head_w"]), axis=-1)
    return (-jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1)),
            balance)


def loss_fn(p, ids, labels, cfg, mm=jnp.matmul, **fault):
    """What the step differentiates: the two parts added (the balance term
    left out under the fault `no_balance_loss`)."""
    lm, balance = loss_parts(p, ids, labels, cfg, mm, **fault)
    return lm if fault.get("no_balance_loss") else lm + balance
