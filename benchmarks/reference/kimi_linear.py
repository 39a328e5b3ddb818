"""Kimi Linear decoder (Moonshot AI, `kimi_linear`; arXiv 2510.26692) in plain
jax.numpy.

Float32 throughout, written from the architecture's public description (the
model's config.json; the report's section on Kimi Delta Attention; the
source's modelling code as recalled): token embedding; blocks
h = x + Mixer(N1(x)), y = h + FF(N2(h)), N an RMS norm with a learned gain;
Mixer is Kimi Delta Attention (KDA) or latent attention (MLA) by
`layer_kinds`; FF a dense SwiGLU in the first `first_k_dense_replace` layers
held and a sigmoid-routed expert layer with one shared expert after; a last
RMS norm and a head of its own. No positions anywhere (`mla_use_nope`). No
kernels, no cache, no chunks; nothing is imported from paddle_tpu.

KDA, per head with a state S (128, 128) from zero at each row's start:
    q = l2norm(silu(conv4(W_q x))) / sqrt(128), k = l2norm(silu(conv4(W_k x))),
    v = silu(conv4(W_v x)), g = -exp(A_log) softplus(W_fb W_fa x + dt_bias),
    beta = sigmoid(W_b x)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t,   out = W_o(rmsnorm_head(o_t) * sigmoid(W_gb W_ga x))
token by token: two nested `lax.scan`s, the outer over runs of 64 tokens and
the inner, under `jax.checkpoint`, over the tokens of a run, so that the
backward holds one state a run and not one a token. That is the only
departure and it changes no arithmetic.

MLA: q_h = W_q^h x in R^192; c = W_kva x in R^(512+64); c_kv = rmsnorm(c[:512]),
k_pe = c[512:] shared by the heads; [k_nope_h ; v_h] = W_kvb^h c_kv;
k_h = [k_nope_h ; k_pe]; causal softmax of q_h . k_h / sqrt(192); the heads of
128 concatenated through W_o. Plain softmax attention, four heads at a time
under `jax.checkpoint` so that the float32 scores of 4096 positions fit.

The chip's share (benchmarks/configs/kimi-linear-48b-a3b.json): the router
scores all `published.num_experts` experts and picks `num_experts_per_token`,
the weights are normalised over all picked and scaled, and the sum runs over
the picked experts that are in `held_experts`; the shared expert is whole;
the vocabulary is the held slice. What the absent experts would add is left
out here as in the program.

Departures, all under `assumed` in the configuration: the low-rank gates'
rank; `e_score_correction_bias` (here `expert_bias`) is a leaf that starts at
zero and has no gradient, and the balancing rule that moves it in training is
left out; `A_log` and `dt_bias` start at zero.

`mm` is the matrix multiplication of every projection, the router, the
attention products, the state's read-outs and the experts: `jnp.matmul` for
the reference, a rounding wrapper for the lower-precision control
(benchmarks/control.py).
"""
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
RUN = 64        # tokens of one inner scan of the KDA recurrence
L2_EPS = 1e-6


def layer_kinds(cfg):
    """[(mixer, feed-forward)] of the layers held. The published lists number
    layers from 1; `first_layer` counts from 0, so layer `first_layer + i`
    here is published layer `first_layer + i + 1`. The first
    `first_k_dense_replace` of those held are dense."""
    full = set(cfg["linear_attn_config"]["full_attn_layers"])
    first = cfg["first_layer"]
    return [("full_attention" if first + i + 1 in full else "kda",
             "dense" if i < cfg["first_k_dense_replace"] else "experts")
            for i in range(cfg["num_layers"])]


def param_shapes(cfg):
    """{leaf: (shape, init)}; init is a std for a normal draw, or the
    constant a gain (1), the expert bias, `a_log` or `dt_bias` (0) starts at."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    lin = cfg["linear_attn_config"]
    width, rank = lin["num_heads"] * lin["head_dim"], cfg["gate_rank"]
    heads = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["num_shared_experts"]
    held, routed = len(cfg["held_experts"]), cfg["published"]["num_experts"]
    shapes = {"wte": ((v, h), INIT_STD), "norm_g": ((h,), "ones"),
              "head_w": ((h, v), INIT_STD)}
    for i, (op, ff) in enumerate(layer_kinds(cfg)):
        p = f"l{i}."
        shapes[p + "op_norm_g"] = ((h,), "ones")
        shapes[p + "ff_norm_g"] = ((h,), "ones")
        if op == "kda":
            for name in ("q", "k", "v"):
                shapes[p + name + "_w"] = ((h, width), INIT_STD)
                shapes[p + name + "_conv"] = (
                    (width, lin["short_conv_kernel_size"]), INIT_STD)
            shapes[p + "f_a_w"] = ((h, rank), INIT_STD)
            shapes[p + "f_b_w"] = ((rank, width), INIT_STD)
            shapes[p + "a_log"] = ((lin["num_heads"],), "zeros")
            shapes[p + "dt_bias"] = ((width,), "zeros")
            shapes[p + "b_w"] = ((h, lin["num_heads"]), INIT_STD)
            shapes[p + "g_a_w"] = ((h, rank), INIT_STD)
            shapes[p + "g_b_w"] = ((rank, width), INIT_STD)
            shapes[p + "o_norm_g"] = ((lin["head_dim"],), "ones")
            shapes[p + "o_w"] = ((width, h), INIT_STD)
        else:
            shapes[p + "q_w"] = ((h, heads * qk), INIT_STD)
            shapes[p + "kv_a_w"] = (
                (h, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]), INIT_STD)
            shapes[p + "kv_a_norm_g"] = ((cfg["kv_lora_rank"],), "ones")
            shapes[p + "kv_b_w"] = (
                (cfg["kv_lora_rank"],
                 heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])), INIT_STD)
            shapes[p + "o_w"] = ((heads * cfg["v_head_dim"], h), INIT_STD)
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


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def silu(x):
    return x * jax.nn.sigmoid(x)


def conv_silu(x, taps):
    """silu(sum_j taps[:, j] * x_{t-(K-1)+j}), zero before the start; x
    (b, s, channels), taps (channels, K)."""
    k = taps.shape[1]
    conv = jnp.zeros_like(x)
    for j in range(k):
        shift = k - 1 - j
        shifted = x if shift == 0 else jnp.pad(
            x, ((0, 0), (shift, 0), (0, 0)))[:, :x.shape[1]]
        conv = conv + taps[:, j] * shifted
    return silu(conv)


def delta_rule(q, k, v, g, beta, mm):
    """The recurrence token by token; q, k, g (b, s, heads, d_k), v
    (b, s, heads, d_v), beta (b, s, heads) -> o (b, s, heads, d_v)."""
    b, s, heads, dk = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        read = mm(k_t[:, :, None, :], state)[:, :, 0]
        delta = b_t[..., None] * (v_t - read)
        state = state + k_t[..., None] * delta[:, :, None, :]
        return state, mm(q_t[:, :, None, :], state)[:, :, 0]

    @jax.checkpoint
    def run(state, xs):
        return jax.lax.scan(token, state, xs)

    def by_runs(x):
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, math.gcd(s, RUN)) + x.shape[1:])

    state = jnp.zeros((b, heads, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(run, state, tuple(by_runs(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def kda(p, pre, x, cfg, mm):
    b, s, _ = x.shape
    lin = cfg["linear_attn_config"]
    heads, hd = lin["num_heads"], lin["head_dim"]

    def mixed(name):
        return conv_silu(mm(x, p[pre + name + "_w"]),
                         p[pre + name + "_conv"]).reshape(b, s, heads, hd)

    q = l2_norm(mixed("q")) * hd ** -0.5
    k, v = l2_norm(mixed("k")), mixed("v")
    g = jax.nn.softplus(mm(mm(x, p[pre + "f_a_w"]), p[pre + "f_b_w"])
                        + p[pre + "dt_bias"]).reshape(b, s, heads, hd)
    g = -jnp.exp(p[pre + "a_log"])[:, None] * g
    beta = jax.nn.sigmoid(mm(x, p[pre + "b_w"]))
    o = rms_norm(delta_rule(q, k, v, g, beta, mm), p[pre + "o_norm_g"],
                 cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(mm(mm(x, p[pre + "g_a_w"]), p[pre + "g_b_w"]))
    return mm(o.reshape(b, s, heads * hd) * gate, p[pre + "o_w"])


def mla(p, pre, x, cfg, mm):
    b, s, _ = x.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    q = mm(x, p[pre + "q_w"]).reshape(b, s, heads, nope + rope)
    c = mm(x, p[pre + "kv_a_w"])
    latent = rms_norm(c[..., :rank], p[pre + "kv_a_norm_g"], cfg["rms_norm_eps"])
    kv = mm(latent, p[pre + "kv_b_w"]).reshape(b, s, heads, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(c[:, :, None, rank:], (b, s, heads, rope))], axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def some_heads(qg, kg, vg):
        # (b, s, group, d): the scores of a few heads at a time
        scores = mm(qg.transpose(0, 2, 1, 3), kg.transpose(0, 2, 3, 1)) \
            / math.sqrt(nope + rope)
        scores = jnp.where(causal, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1),
                  vg.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)

    group = math.gcd(heads, 4)
    ctx = [jax.checkpoint(some_heads)(q[:, :, j:j + group], k[:, :, j:j + group],
                                      v[:, :, j:j + group])
           for j in range(0, heads, group)]
    return mm(jnp.concatenate(ctx, axis=2).reshape(b, s, heads * dv), p[pre + "o_w"])


def swiglu_ff(x, w1, w3, w2, mm):
    return mm(silu(mm(x, w1)) * mm(x, w3), w2)


def route(p, pre, x, cfg, mm):
    """(idx (.., k) the experts picked, w (.., k) their weights): sigmoid
    scores over every published expert; the top k of score + bias (one group,
    so the grouped top-k is plain top-k); the un-biased scores normalised
    over the k, times the scaling factor."""
    s = jax.nn.sigmoid(mm(x, p[pre + "gate_w"]))
    _, idx = jax.lax.top_k(s + p[pre + "expert_bias"], cfg["num_experts_per_token"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return idx, w * cfg["routed_scaling_factor"]


def routed_part(p, pre, x, cfg, mm):
    """Every held expert applied to every token, weighed by its routing
    weight, zero where the token did not pick it."""
    idx, w = route(p, pre, x, cfg, mm)
    out = jnp.zeros_like(x)
    for slot, expert in enumerate(cfg["held_experts"]):
        w_e = jnp.sum(jnp.where(idx == expert, w, 0.0), axis=-1, keepdims=True)
        out = out + w_e * swiglu_ff(x, p[pre + "e_w1"][slot], p[pre + "e_w3"][slot],
                                    p[pre + "e_w2"][slot], mm)
    return out


def expert_ff(p, pre, x, cfg, mm):
    return routed_part(p, pre, x, cfg, mm) + swiglu_ff(
        x, p[pre + "s_w1"], p[pre + "s_w3"], p[pre + "s_w2"], mm)


def rows_routed_here(p, ids, cfg, mm=jnp.matmul):
    """Per expert layer, the (token, expert) pairs of `ids` (b, s) whose
    expert is held: what the program's `rows_total` counter has to count."""
    counts = []

    def note(pre, x):
        idx, _ = route(p, pre, x, cfg, mm)
        counts.append(jnp.sum(jnp.isin(idx, jnp.asarray(cfg["held_experts"]))))
    _forward(p, ids, cfg, mm, note)
    return counts


def block(p, i, kind, x, cfg, mm, note=None):
    op, ff = kind
    pre, eps = f"l{i}.", cfg["rms_norm_eps"]
    a = rms_norm(x, p[pre + "op_norm_g"], eps)
    x = x + (kda if op == "kda" else mla)(p, pre, a, cfg, mm)
    a = rms_norm(x, p[pre + "ff_norm_g"], eps)
    if ff == "experts" and note is not None:
        note(pre, a)
    if ff == "dense":
        return x + swiglu_ff(a, p[pre + "w1"], p[pre + "w3"], p[pre + "w2"], mm)
    return x + expert_ff(p, pre, a, cfg, mm)


def _forward(p, ids, cfg, mm, note=None):
    x = p["wte"][ids]
    for i, kind in enumerate(layer_kinds(cfg)):
        if note is None:
            # rematerialised per block so a float32 backward fits beside the state
            x = jax.checkpoint(lambda x, i=i, kind=kind: block(p, i, kind, x, cfg, mm))(x)
        else:
            x = block(p, i, kind, x, cfg, mm, note)
    return rms_norm(x, p["norm_g"], cfg["rms_norm_eps"])


def loss_fn(p, ids, labels, cfg, mm=jnp.matmul):
    """Mean next-token cross-entropy over every position of `ids` (b, s),
    over the held slice of the vocabulary."""
    logits = mm(_forward(p, ids, cfg, mm), p["head_w"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
