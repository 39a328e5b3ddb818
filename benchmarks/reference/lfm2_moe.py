"""LFM2 mixture-of-experts decoder (Liquid AI, `lfm2_moe`) in plain jax.numpy.

Float32 throughout, written from the architecture's public description
(`transformers`' Lfm2Moe modelling code, the model's config.json): token
embedding; blocks h = x + Op(N1(x)), y = h + FF(N2(h)) with N an RMS norm with
a learned gain; Op a gated short convolution or grouped-query attention by
`layer_types`; FF a dense SwiGLU in the first `num_dense_layers` layers and a
sigmoid-routed expert layer after; a last RMS norm and a head tied to the
embedding. No bias anywhere. No kernels, no cache; nothing is imported from
paddle_tpu.

The chip's share (benchmarks/configs/lfm2-24b-a2b.json): the router scores
all `published.num_experts` experts and picks `num_experts_per_tok` of them,
the weights are normalised over all picked, and the sum runs over the picked
experts that are in `held_experts`; the vocabulary is the held slice. What the
absent experts would add is left out here as in the program.

Departures, all under `assumed` in the configuration: the head is tied to the
embedding; `expert_bias` is a leaf that starts at zero and has no gradient
(top-k is piecewise constant), and the balancing rule that moves it in
training is not in the published config and is left out.

`mm` is the matrix multiplication of every projection, the router, both
attention products and the experts: `jnp.matmul` for the reference, a
rounding wrapper for the lower-precision control (benchmarks/control.py).
"""
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def layer_kinds(cfg):
    """[(operator, feed-forward)] of the layers held: `layer_types` is the
    published pattern, `first_layer` the published index of the first layer
    held, and the first `num_dense_layers` of those held are dense."""
    first = cfg["first_layer"]
    ops = cfg["layer_types"][first:first + cfg["num_layers"]]
    return [(op, "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, op in enumerate(ops)]


def param_shapes(cfg):
    """{leaf: (shape, init)}; init is a std for a normal draw, or the
    constant a gain (1) or the expert bias (0) starts at."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, routed = len(cfg["held_experts"]), cfg["published"]["num_experts"]
    shapes = {"wte": ((v, h), INIT_STD), "norm_g": ((h,), "ones")}
    for i, (op, ff) in enumerate(layer_kinds(cfg)):
        p = f"l{i}."
        shapes[p + "op_norm_g"] = ((h,), "ones")
        shapes[p + "ff_norm_g"] = ((h,), "ones")
        if op == "conv":
            shapes[p + "conv_in_w"] = ((h, 3 * h), INIT_STD)
            shapes[p + "conv_k"] = ((h, cfg["conv_L_cache"]), INIT_STD)
            shapes[p + "conv_out_w"] = ((h, h), INIT_STD)
        else:
            shapes[p + "q_w"] = ((h, h), INIT_STD)
            shapes[p + "k_w"] = ((h, kv), INIT_STD)
            shapes[p + "v_w"] = ((h, kv), INIT_STD)
            shapes[p + "o_w"] = ((h, h), INIT_STD)
            shapes[p + "q_norm_g"] = ((hd,), "ones")
            shapes[p + "k_norm_g"] = ((hd,), "ones")
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
    return shapes


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def short_conv(p, pre, x, mm):
    """[B, C, u] = split(x W_in); c_t = sum_j k_j * (B u)_{t-(K-1)+j}, zero
    before the start; out = (C c) W_out: K shifted multiply-adds."""
    b, c, u = jnp.split(mm(x, p[pre + "conv_in_w"]), 3, axis=-1)
    v = b * u
    taps = p[pre + "conv_k"]                            # (h, K)
    k = taps.shape[1]
    conv = jnp.zeros_like(v)
    for j in range(k):
        shift = k - 1 - j
        shifted = v if shift == 0 else jnp.pad(
            v, ((0, 0), (shift, 0), (0, 0)))[:, :v.shape[1]]
        conv = conv + taps[:, j] * shifted
    return mm(c * conv, p[pre + "conv_out_w"])


def rotate(x, theta):
    """Rotary positions over the whole head, rotate-half convention;
    x (b, s, heads, d)."""
    d, s = x.shape[-1], x.shape[1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def attention(p, pre, x, cfg, mm):
    b, s, h = x.shape
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = h // nq, cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = mm(x, p[pre + "q_w"]).reshape(b, s, nq, hd)
    k = mm(x, p[pre + "k_w"]).reshape(b, s, nkv, hd)
    v = mm(x, p[pre + "v_w"]).reshape(b, s, nkv, hd)
    q = rotate(rms_norm(q, p[pre + "q_norm_g"], eps), theta)
    k = rotate(rms_norm(k, p[pre + "k_norm_g"], eps), theta)
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = nq // nkv

    def one_kv_head(qg, kh, vh):
        # qg (b, s, group, hd): the query heads this key/value head serves
        scores = mm(qg.transpose(0, 2, 1, 3), kh.transpose(0, 2, 1)[:, None]) / math.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), vh[:, None]).transpose(0, 2, 1, 3)

    # one key/value head at a time, rematerialised, so that the float32
    # scores of 4096 positions fit the chip: (b, group, s, s) at once
    ctx = [jax.checkpoint(one_kv_head)(q[:, :, j * group:(j + 1) * group],
                                       k[:, :, j], v[:, :, j])
           for j in range(nkv)]
    return mm(jnp.concatenate(ctx, axis=2).reshape(b, s, h), p[pre + "o_w"])


def dense_ff(p, pre, x, mm):
    return mm(silu(mm(x, p[pre + "w1"])) * mm(x, p[pre + "w3"]), p[pre + "w2"])


def route(p, pre, x, cfg, mm):
    """(idx (.., k) the experts picked, w (.., k) their weights): sigmoid
    scores over every published expert; the top k of score + bias; the
    un-biased scores normalised over the k, times the scaling factor."""
    s = jax.nn.sigmoid(mm(x, p[pre + "gate_w"]))
    _, idx = jax.lax.top_k(s + p[pre + "expert_bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return idx, w * cfg["routed_scaling_factor"]


def expert_ff(p, pre, x, cfg, mm):
    """Every held expert applied to every token, weighed by its routing
    weight, zero where the token did not pick it."""
    idx, w = route(p, pre, x, cfg, mm)
    out = jnp.zeros_like(x)
    for slot, expert in enumerate(cfg["held_experts"]):
        w_e = jnp.sum(jnp.where(idx == expert, w, 0.0), axis=-1, keepdims=True)
        y = mm(silu(mm(x, p[pre + "e_w1"][slot])) * mm(x, p[pre + "e_w3"][slot]),
               p[pre + "e_w2"][slot])
        out = out + w_e * y
    return out


def rows_routed_here(p, ids, cfg, mm=jnp.matmul):
    """Per expert layer, the (token, expert) pairs of `ids` (b, s) whose
    expert is held: what the program's `rows_total` counter has to count."""
    counts = []

    def note(pre, x):
        idx, _ = route(p, pre, x, cfg, mm)
        held = jnp.isin(idx, jnp.asarray(cfg["held_experts"]))
        counts.append(jnp.sum(held))
    _forward(p, ids, cfg, mm, note)
    return counts


def block(p, i, kind, x, cfg, mm, note=None):
    op, ff = kind
    pre, eps = f"l{i}.", cfg["norm_eps"]
    a = rms_norm(x, p[pre + "op_norm_g"], eps)
    x = x + (short_conv(p, pre, a, mm) if op == "conv"
             else attention(p, pre, a, cfg, mm))
    a = rms_norm(x, p[pre + "ff_norm_g"], eps)
    if ff == "experts" and note is not None:
        note(pre, a)
    return x + (dense_ff(p, pre, a, mm) if ff == "dense"
                else expert_ff(p, pre, a, cfg, mm))


def _forward(p, ids, cfg, mm, note=None):
    x = p["wte"][ids]
    for i, kind in enumerate(layer_kinds(cfg)):
        if note is None:
            # rematerialised per block so a float32 backward fits beside the state
            x = jax.checkpoint(lambda x, i=i, kind=kind: block(p, i, kind, x, cfg, mm))(x)
        else:
            x = block(p, i, kind, x, cfg, mm, note)
    return rms_norm(x, p["norm_g"], cfg["norm_eps"])


def loss_fn(p, ids, labels, cfg, mm=jnp.matmul):
    """Mean next-token cross-entropy over every position of `ids` (b, s),
    over the held slice of the vocabulary."""
    logits = mm(_forward(p, ids, cfg, mm), p["wte"].T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
