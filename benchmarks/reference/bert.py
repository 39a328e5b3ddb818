"""BERT encoder with a sequence-classification head (Devlin et al. 2018) in
plain jax.numpy.

Float32 throughout, written from the paper and Vaswani et al. 2017: word,
position and segment embeddings summed and normalised, post-LayerNorm
encoder layers (bidirectional multi-head attention, then a 4h MLP with the
exact erf GELU), a tanh pooler over the first token, and a linear
classifier. No padding mask: the job's sequences are full. Nothing is
imported from paddle_tpu. `mm` as in reference/gpt.py.
"""
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt import layer_norm


def param_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg["initializer_range"]
    shapes = {"word": ((cfg["vocab_size"], h), std),
              "pos": ((cfg["max_position_embeddings"], h), std),
              "type": ((cfg["type_vocab_size"], h), std),
              "emb_ln_g": ((h,), "ones"), "emb_ln_b": ((h,), "zeros"),
              "pool_w": ((h, h), std), "pool_b": ((h,), "zeros"),
              "cls_w": ((h, cfg["num_labels"]), std),
              "cls_b": ((cfg["num_labels"],), "zeros")}
    for i in range(cfg["num_layers"]):
        for name in ("q", "k", "v", "o"):
            shapes[f"l{i}.{name}_w"] = ((h, h), std)
            shapes[f"l{i}.{name}_b"] = ((h,), "zeros")
        shapes.update({
            f"l{i}.ln1_g": ((h,), "ones"), f"l{i}.ln1_b": ((h,), "zeros"),
            f"l{i}.fc1_w": ((h, f), std), f"l{i}.fc1_b": ((f,), "zeros"),
            f"l{i}.fc2_w": ((f, h), std), f"l{i}.fc2_b": ((h,), "zeros"),
            f"l{i}.ln2_g": ((h,), "ones"), f"l{i}.ln2_b": ((h,), "zeros"),
        })
    return shapes


def layer(p, i, x, cfg, mm):
    b, s, h = x.shape
    nh = cfg["num_heads"]
    hd = h // nh
    eps = cfg["layer_norm_eps"]

    def heads(name):
        y = mm(x, p[f"l{i}.{name}_w"]) + p[f"l{i}.{name}_b"]
        return y.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)

    q, k, v = heads("q"), heads("k"), heads("v")
    probs = jax.nn.softmax(mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(hd),
                           axis=-1)
    ctx = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, s, h)
    x = layer_norm(x + mm(ctx, p[f"l{i}.o_w"]) + p[f"l{i}.o_b"],
                   p[f"l{i}.ln1_g"], p[f"l{i}.ln1_b"], eps)
    a = jax.nn.gelu(mm(x, p[f"l{i}.fc1_w"]) + p[f"l{i}.fc1_b"],
                    approximate=False)
    return layer_norm(x + mm(a, p[f"l{i}.fc2_w"]) + p[f"l{i}.fc2_b"],
                      p[f"l{i}.ln2_g"], p[f"l{i}.ln2_b"], eps)


def loss_fn(p, ids, labels, cfg, mm=jnp.matmul):
    """Mean cross-entropy of the sequence label; `ids` (b, s), `labels` (b,)."""
    s = ids.shape[1]
    x = p["word"][ids] + p["pos"][jnp.arange(s)][None] + p["type"][0]
    x = layer_norm(x, p["emb_ln_g"], p["emb_ln_b"], cfg["layer_norm_eps"])
    for i in range(cfg["num_layers"]):
        x = jax.checkpoint(lambda x, i=i: layer(p, i, x, cfg, mm))(x)
    pooled = jnp.tanh(mm(x[:, 0], p["pool_w"]) + p["pool_b"])
    logits = mm(pooled, p["cls_w"]) + p["cls_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
