"""Laguna decoder (poolside, `model_type` laguna) as Laguna-XS.2 configures
it, in plain jax.numpy.

Float32 throughout, written from the model's public config.json and the
equations of the issue that added it (benchmarks/configs/laguna-xs2.json keeps
what was assumed): token embedding; pre-norm blocks h = x + A_l(N1(x)),
y = h + FF_l(N2(h)), N an RMS norm with a learned gain; a last RMS norm and a
head of its own; mean next-token cross-entropy over the held slice of the
vocabulary. No bias. No kernels, no cache; nothing is imported from
paddle_tpu.

The layers differ, and four per-layer lists of the config say how; layer i
held is published layer `first_layer` + i:
  `layer_types`                    full_attention | sliding_attention
  `num_attention_heads_per_layer`  H_l query heads (48 full, 64 window)
  `rope_parameters[layer type]`    the rotary rule
  `mlp_layer_types`                dense | sparse

Attention, n = N1(x), H_l query heads over `num_key_value_heads` key/value
heads of `head_dim`:
    q = n W_q, k = n W_k, v = n W_v; query head h reads key head h // (H_l / kv)
    rotary, rotate-half pairing over the turned part (entry i with entry
      i + r/2 of the first r = partial_rotary_factor x head_dim): full layers
      under YaRN's blend over r entries, cos and sin times `attention_factor`;
      window layers plain frequencies over the whole head
    score(t, j) = q_t . k_j / sqrt(head_dim); full layers j <= t; window layers
      t - sliding_window < j <= t (the band, a boolean mask over the square)
    a = softmax(score) v;  g = sigmoid(n W_g) (one value a head);
    A = (g_h a_h over the heads) W_o

Feed-forward: `dense` W_2(silu(u W_1) * u W_3) at `intermediate_size`;
`sparse` s = sigmoid(u W_r) over every published expert; T = the
`num_experts_per_tok` largest of s + b (b a leaf of zeros with no gradient);
w_e = s_e / (sum_{T} s + 1e-6) x `moe_routed_scaling_factor`, applied to the
experts' outputs; y = sum_{e in T} w_e E_e(u) + S(u), E_e and the shared S
SwiGLUs of `moe_intermediate_size` and `shared_expert_intermediate_size`.

The chip's share (the configuration file): the router scores all
`num_experts_published` experts; the sum runs over the picked experts that a
held slot computes (`expert_slots`: those in `held_experts`, and under
`absent_experts` "stand_in" every other one through slot e mod the number
held); the shared expert is whole; the vocabulary is the held slice.

Departures, both without arithmetic: the attention runs over blocks of
QUERY_ROWS query rows, each rematerialised, and the blocks of the model are
rematerialised, so that the float32 backward of 8192 positions fits.

`mm` is the matrix multiplication of every projection, the gate, the router,
the attention products, the experts and the head: `jnp.matmul` for the
reference, a rounding wrapper for the lower-precision control
(benchmarks/control.py). `fault` names one departure from the equations
above, for the tests and the limits' readings (a faulty program has to come
out as not correct): `causal_window_layers` (the window layers without the
band), `no_gate`, `plain_frequencies` (plain frequencies over the whole head
in the full layers).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
QUERY_ROWS = 256    # query rows of one rematerialised block of the attention


def layer_kinds(cfg):
    """[(attention kind, query heads, feed-forward kind)] of the layers held."""
    first = cfg["first_layer"]
    return [(cfg["layer_types"][i], cfg["num_attention_heads_per_layer"][i],
             cfg["mlp_layer_types"][i])
            for i in range(first, first + cfg["num_layers"])]


def param_shapes(cfg):
    """{leaf: (shape, init)}; init is a std for a normal draw, or the
    constant a gain (1) or the expert bias (0) starts at."""
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * d
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    held, routed = len(cfg["held_experts"]), cfg["num_experts_published"]
    shapes = {"wte": ((v, h), INIT_STD), "norm_g": ((h,), "ones"),
              "head_w": ((h, v), INIT_STD)}
    for i, (_, heads, ff) in enumerate(layer_kinds(cfg)):
        p = f"l{i}."
        shapes[p + "op_norm_g"] = ((h,), "ones")
        shapes[p + "ff_norm_g"] = ((h,), "ones")
        shapes[p + "q_w"] = ((h, heads * d), INIT_STD)
        shapes[p + "k_w"] = ((h, kv), INIT_STD)
        shapes[p + "v_w"] = ((h, kv), INIT_STD)
        shapes[p + "g_w"] = ((h, heads), INIT_STD)
        shapes[p + "o_w"] = ((heads * d, h), INIT_STD)
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

def rotary_rule(cfg, kind, plain_frequencies=False):
    """(entries turned, (entries / 2,) float32 frequencies, what cos and sin
    carry) of a layer of `kind`. YaRN (`rope_type` yarn): f_n = theta_n
    gamma_n + theta_n / factor (1 - gamma_n), theta_n = theta^(-2n/r),
    gamma_n = 1 - clip((n - low) / (high - low), 0, 1), low and high the floor
    and the ceiling of the pair index that turns `beta_fast` and `beta_slow`
    times over the original context; float64, rounded once."""
    rope = cfg["rope_parameters"][kind]
    theta = float(rope["rope_theta"])
    if plain_frequencies:       # the fault: the window layers' rule, this theta
        rope = {"partial_rotary_factor": 1}
    r = int(cfg["head_dim"] * rope.get("partial_rotary_factor", 1))
    n = np.arange(r // 2, dtype=np.float64)
    plain = theta ** (-2.0 * n / r)
    if rope.get("rope_type", "default") != "yarn":
        return r, plain.astype(np.float32), 1.0

    def pair(rotations):
        return r * math.log(rope["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(pair(rope["beta_fast"])), 0)
    high = min(math.ceil(pair(rope["beta_slow"])), r - 1)
    gamma = 1.0 - np.clip((n - low) / max(high - low, 1e-3), 0.0, 1.0)
    blend = plain * gamma + plain / rope["factor"] * (1.0 - gamma)
    return r, blend.astype(np.float32), float(rope["attention_factor"])


def rotate(x, rule):
    """x (b, seq, heads, d): entries 0 ... r - 1 turned, entry i paired with
    entry i + r/2, by position t x f_i; the rest pass."""
    r, freqs, table_scale = rule
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
    cos = (jnp.cos(angle) * table_scale)[:, None, :]
    sin = (jnp.sin(angle) * table_scale)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]], axis=-1)


# ---------------------------------------------------------------------------
# attention

def attention(p, pre, kind, heads, x, cfg, mm, **fault):
    b, s, _ = x.shape
    kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    rule = rotary_rule(cfg, kind, kind == "full_attention"
                       and fault.get("plain_frequencies", False))
    q = rotate(mm(x, p[pre + "q_w"]).reshape(b, s, heads, d), rule)
    k = rotate(mm(x, p[pre + "k_w"]).reshape(b, s, kv, d), rule)
    v = mm(x, p[pre + "v_w"]).reshape(b, s, kv, d)
    # grouped heads by a repeat: query head h reads key/value head h // group
    k = jnp.repeat(k, heads // kv, axis=2).transpose(0, 2, 3, 1)
    v = jnp.repeat(v, heads // kv, axis=2).transpose(0, 2, 1, 3)
    window = None
    if kind == "sliding_attention" and not fault.get("causal_window_layers"):
        window = cfg["sliding_window"]
    rows = min(QUERY_ROWS, s)
    if s % rows:
        raise ValueError(f"{s} positions do not split into blocks of {rows}")

    @jax.checkpoint
    def block(args):
        first, q_r = args                               # q_r (b, rows, heads, d)
        scores = mm(q_r.transpose(0, 2, 1, 3), k) * d ** -0.5
        t, j = (first + jnp.arange(rows))[:, None], jnp.arange(s)[None, :]
        keep = j <= t
        if window is not None:
            keep = keep & (j > t - window)
        scores = jnp.where(keep, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), v).transpose(0, 2, 1, 3)

    q_blocks = jnp.moveaxis(q.reshape(b, s // rows, rows, heads, d), 1, 0)
    a = jnp.moveaxis(jax.lax.map(block, (jnp.arange(0, s, rows), q_blocks)), 0, 1)
    a = a.reshape(b, s, heads, d)
    if not fault.get("no_gate"):
        a = a * jax.nn.sigmoid(mm(x, p[pre + "g_w"]))[..., None]
    return mm(a.reshape(b, s, heads * d), p[pre + "o_w"])


# ---------------------------------------------------------------------------
# experts

def route(p, pre, x, cfg, mm):
    """(idx (.., k) the experts picked, w (.., k) their weights): sigmoid
    scores over every published expert; the k largest of score + bias; the
    un-biased scores normalised over the k, times the scaling factor."""
    s = jax.nn.sigmoid(mm(x, p[pre + "gate_w"]))
    _, idx = jax.lax.top_k(s + p[pre + "expert_bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return idx, w * cfg["moe_routed_scaling_factor"]


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


def routed_part(p, pre, x, cfg, mm, held=None):
    """Every held slot applied to every token, weighed by the routing
    weights of the token's picks that it computes, zero where it computes
    none: a loop over the held experts. `held` (ids) with the leaves' slots
    in that order; the configuration's by default."""
    idx, w = route(p, pre, x, cfg, mm)
    picked_slot = expert_slots(cfg, held)[idx]
    out = jnp.zeros_like(x)
    for slot in range(p[pre + "e_w1"].shape[0]):
        w_e = jnp.sum(jnp.where(picked_slot == slot, w, 0.0), axis=-1, keepdims=True)
        out = out + w_e * swiglu_ff(x, p[pre + "e_w1"][slot], p[pre + "e_w3"][slot],
                                    p[pre + "e_w2"][slot], mm)
    return out


def expert_ff(p, pre, x, cfg, mm, held=None):
    return routed_part(p, pre, x, cfg, mm, held) + swiglu_ff(
        x, p[pre + "s_w1"], p[pre + "s_w3"], p[pre + "s_w2"], mm)


def block(p, i, kinds, x, cfg, mm, **fault):
    pre, eps = f"l{i}.", cfg["rms_norm_eps"]
    kind, heads, ff = kinds
    x = x + attention(p, pre, kind, heads, rms_norm(x, p[pre + "op_norm_g"], eps),
                      cfg, mm, **fault)
    a = rms_norm(x, p[pre + "ff_norm_g"], eps)
    if ff == "dense":
        return x + swiglu_ff(a, p[pre + "w1"], p[pre + "w3"], p[pre + "w2"], mm)
    return x + expert_ff(p, pre, a, cfg, mm)


def forward(p, ids, cfg, mm=jnp.matmul, **fault):
    """The normed hidden states."""
    x = p["wte"][ids]
    for i, kinds in enumerate(layer_kinds(cfg)):
        # rematerialised per block so a float32 backward fits beside the state
        x = jax.checkpoint(
            lambda x, i=i, kinds=kinds: block(p, i, kinds, x, cfg, mm, **fault))(x)
    return rms_norm(x, p["norm_g"], cfg["rms_norm_eps"])


def loss_fn(p, ids, labels, cfg, mm=jnp.matmul, **fault):
    """Mean next-token cross-entropy over the held slice of the vocabulary."""
    logp = jax.nn.log_softmax(mm(forward(p, ids, cfg, mm, **fault), p["head_w"]), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
