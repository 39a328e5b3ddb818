"""paddle.nn.functional parity (python/paddle/nn/functional/__init__.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import apply, unwrap
from ...core.tensor import Tensor

from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .sparse_attention import sparse_attention  # noqa: F401
from .vision import *  # noqa: F401,F403


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    from ...core.dtypes import convert_dtype
    lv = unwrap(lengths)
    m = int(maxlen) if maxlen is not None else int(jnp.max(lv))
    mask = jnp.arange(m)[None, :] < lv[..., None]
    return Tensor(mask.astype(convert_dtype(dtype)))


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None, key_set=None,
                                 return_lse=False, scale=None, window=None):
    """Fused attention entry point (reference: operators/fused/fused_attention).

    Shapes: (batch, seq, heads, head_dim) — paddle convention. Uses the Pallas
    flash-attention kernel when available on TPU, else the XLA softmax path.
    `key_set` gives every query the keys it attends to: (batch, seq, seq),
    or whatever `sparse_attention_index` returned; `return_lse` gives (out,
    the logsumexp of each query's scores (batch, heads, seq)). `scale`
    multiplies the scores before the softmax: head_dim ** -0.5 of the
    query/key heads by default (a model under YaRN passes its own).
    `window` (with `is_causal`) keeps the last `window` causal keys of each
    query, its own among them (sliding-window attention): on a TPU the flash
    pair over its banded grid, elsewhere a masked square.
    """
    from ...ops.attention import scaled_dot_product_attention as sdpa
    return sdpa(query, key, value, attn_mask=attn_mask, dropout_p=dropout_p,
                is_causal=is_causal, training=training, key_set=key_set,
                return_lse=return_lse, scale=scale, window=window)


def sparse_attention_index(q_index, k_index, weights, topk, name=None):
    """The keys each query attends to under a learned sparse-attention index
    (DeepSeek Sparse Attention; ops/sparse_index.py): the `topk` causal keys
    with the largest I[t, s] = sum_j weights[t, j] relu(q_index[t, j] .
    k_index[s]), every causal key where a row holds at most `topk`; ties at
    the threshold are all kept.

    Shapes: q_index (batch, seq, heads, d), k_index (batch, seq, d), weights
    (batch, seq, heads). Returns (key_set for
    `scaled_dot_product_attention(..., key_set=)` and
    `sparse_attention_index_loss`: (batch, seq, seq) int8, or on a TPU, where
    the index runs as kernels, the pair (sets, table) in the layout the
    attention and loss kernels read; stats (3,) float32: pairs selected,
    tiles under the diagonal with none, queries). No gradient flows through
    any of them.
    """
    from ...ops.sparse_index import sparse_attention_index as index
    return index(q_index, k_index, weights, topk)


def sparse_attention_index_loss(q_index, k_index, weights, key_set, query, key,
                                scale=None, lse=None, name=None):
    """The index's training loss: KL from the main attention's probabilities
    over `key_set` (softmax of query . key * scale over the set, averaged
    over the heads, gradient stopped) to the softmax of the index scores
    over the same set, averaged over the positions. A float32 scalar that
    differentiates in q_index, k_index and weights only. `lse` is the main
    attention's logsumexp over the sets (`scaled_dot_product_attention(...,
    return_lse=True)`): with it the probabilities are formed tile by tile in
    a kernel on a TPU; without it, or elsewhere, by whole rows in XLA.
    """
    from ...ops.sparse_index import sparse_attention_index_loss as loss
    return loss(q_index, k_index, weights, key_set, query, key, scale=scale,
                lse=lse)


def embedding_renorm_(*args, **kwargs):
    raise NotImplementedError


def diag_embed(input, offset=0, dim1=-2, dim2=-1):  # noqa: A002
    def prim(v):
        base = jnp.zeros(v.shape + (v.shape[-1],), dtype=v.dtype)
        idx = jnp.arange(v.shape[-1])
        base = base.at[..., idx, idx].set(v)
        if offset or dim1 != -2 or dim2 != -1:
            base = jnp.moveaxis(base, (-2, -1), (dim1, dim2))
        return base
    return apply(prim, input, name="diag_embed")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def prim(a, p, lab):
        batch = a.shape[0]
        sim = a @ p.T
        lab2 = lab.reshape(-1, 1)
        same = (lab2 == lab2.T).astype(a.dtype)
        same = same / jnp.sum(same, axis=1, keepdims=True)
        ce = jnp.mean(-jnp.sum(same * jax.nn.log_softmax(sim, axis=1), axis=1))
        reg = l2_reg * (jnp.mean(jnp.sum(a * a, axis=1))
                        + jnp.mean(jnp.sum(p * p, axis=1))) / 2
        return ce + reg
    return apply(prim, anchor, positive, labels, name="npair_loss")


def gather_tree(ids, parents):
    """Beam-search backtrack (reference operators/gather_tree_op.*): walk
    parent pointers from the last step to recover full sequences.
    ids/parents: (max_time, batch, beam) int tensors."""
    from ..decode import _backtrack
    return apply(_backtrack, ids, parents, name="gather_tree")


def kimi_delta_attention(query, key, value, g, beta, scale=None, name=None):
    """Kimi Delta Attention (ops/kda.py): the gated delta rule with a decay
    per channel, chunked, with a chunked backward.

    Shapes: query, key and g (batch, seq, heads, d_k), value (batch, seq,
    heads, d_v), beta (batch, seq, heads). g <= 0 is the log of the decay
    (float32), beta in [0, 1] the write strength; the queries are multiplied
    by `scale` (d_k ** -0.5 by default). The state starts at zero in every
    row of the batch. Returns (batch, seq, heads, d_v) in value's dtype.
    """
    from ...ops.kda import delta_attention
    return delta_attention(query, key, value, g, beta, scale=scale)
