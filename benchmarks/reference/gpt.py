"""GPT decoder (Radford et al. 2019; Brown et al. 2020) in plain jax.numpy.

Float32 throughout, written from the papers: learned token and position
embeddings, pre-LayerNorm blocks (causal multi-head attention, then a 4h MLP
with the tanh GELU of GPT-2), a final LayerNorm, and an output head tied to
the token embedding. No kernels, no cache, no batching tricks; nothing is
imported from paddle_tpu. The fused qkv projection is laid out as
(3, heads, head_dim) along its output axis, as the public GPT-2 weights are.

`mm` is the matrix multiplication every projection and both attention
products go through: `jnp.matmul` for the reference, a rounding wrapper for
the lower-precision control (benchmarks/control.py).
"""
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def param_shapes(cfg):
    """{leaf: (shape, init)}; init is a std for a normal draw, or the
    constant a LayerNorm gain (1) or a bias (0) starts at."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n = cfg["num_layers"]
    res_std = INIT_STD / math.sqrt(2.0 * n)   # residual writes, GPT-2 sec. 2.3
    shapes = {"wte": ((v, h), INIT_STD),
              "wpe": ((cfg["max_position_embeddings"], h), INIT_STD),
              "lnf_g": ((h,), "ones"), "lnf_b": ((h,), "zeros")}
    for i in range(n):
        shapes.update({
            f"h{i}.ln1_g": ((h,), "ones"), f"h{i}.ln1_b": ((h,), "zeros"),
            f"h{i}.qkv_w": ((h, 3 * h), INIT_STD),
            f"h{i}.qkv_b": ((3 * h,), "zeros"),
            f"h{i}.proj_w": ((h, h), res_std), f"h{i}.proj_b": ((h,), "zeros"),
            f"h{i}.ln2_g": ((h,), "ones"), f"h{i}.ln2_b": ((h,), "zeros"),
            f"h{i}.fc1_w": ((h, f), INIT_STD), f"h{i}.fc1_b": ((f,), "zeros"),
            f"h{i}.fc2_w": ((f, h), res_std), f"h{i}.fc2_b": ((h,), "zeros"),
        })
    return shapes


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, i, x, cfg, mm):
    b, s, h = x.shape
    nh = cfg["num_heads"]
    hd = h // nh
    eps = cfg["layer_norm_eps"]
    a = layer_norm(x, p[f"h{i}.ln1_g"], p[f"h{i}.ln1_b"], eps)
    qkv = mm(a, p[f"h{i}.qkv_w"]) + p[f"h{i}.qkv_b"]
    qkv = qkv.reshape(b, s, 3, nh, hd)
    q, k, v = (qkv[:, :, j].transpose(0, 2, 1, 3) for j in range(3))
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = mm(jax.nn.softmax(scores, axis=-1), v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    x = x + mm(ctx, p[f"h{i}.proj_w"]) + p[f"h{i}.proj_b"]
    a = layer_norm(x, p[f"h{i}.ln2_g"], p[f"h{i}.ln2_b"], eps)
    a = gelu_tanh(mm(a, p[f"h{i}.fc1_w"]) + p[f"h{i}.fc1_b"])
    return x + mm(a, p[f"h{i}.fc2_w"]) + p[f"h{i}.fc2_b"]


def loss_fn(p, ids, labels, cfg, mm=jnp.matmul):
    """Mean next-token cross-entropy over every position of `ids` (b, s)."""
    s = ids.shape[1]
    x = p["wte"][ids] + p["wpe"][jnp.arange(s)][None]
    for i in range(cfg["num_layers"]):
        # rematerialised per block so a float32 backward fits beside the state
        x = jax.checkpoint(lambda x, i=i: block(p, i, x, cfg, mm))(x)
    x = layer_norm(x, p["lnf_g"], p["lnf_b"], cfg["layer_norm_eps"])
    logits = mm(x, p["wte"].T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
