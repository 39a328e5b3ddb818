"""The learned index of sparse attention (DeepSeek Sparse Attention's
"lightning indexer": DeepSeek-V3.2-Exp's report): which keys a query attends
to, and the loss that teaches the index.

With `q_index` (batch, seq, heads, d) the index queries, `k_index` (batch,
seq, d) the one index key a position has and `weights` (batch, seq, heads) a
weight a query head,

    I[t, s] = sum_j weights[t, j] * relu(q_index[t, j] . k_index[s]),  s <= t

`index_key_set` gives a query the `topk` keys of its row with the largest I: every
causal key while the row holds at most `topk` of them, else the keys with
I[t, s] >= tau_t, tau_t the `topk`-th largest of the row. Ties at tau_t are
all kept, so a row may hold more than `topk`. The set carries no gradient.

`index_loss` is the index's own training signal: with p[t, .] the main
attention's probabilities over the set, averaged over its heads, gradient
stopped,

    L = mean_t sum_{s in S_t} p[t, s] (log p[t, s] - log softmax_{S_t}(I[t, .])[s])

whose gradient in I is (softmax_{S_t}(I) - p) / T on the set. Only `q_index`,
`k_index` and `weights` take a gradient from it; the main queries and keys
are read and not differentiated.

Neither function ever holds a (seq, seq) float array: both walk the queries in
chunks of `chunk` rows (the published `q_chunk_size`), a chunk's (heads,
chunk, seq) float32 scores being the largest thing alive. A row's threshold
is found without a sort (`lax.top_k` at k = 2048 over 8192 is a full sort on
a TPU): floats are mapped to unsigned integers of the same order and the
`topk`-th largest is built bit by bit from the top, one count over the row a
bit, 32 passes over a chunk whatever the row length (docs/kernels.md).

Two forms of each, one result. The plain XLA form below runs everywhere. On
a TPU, by `takes_kernels`' rule of shapes (never a measurement), the sets
and the loss run as the kernels of ops/pallas/sparse_index.py, which keep a
block of queries' scores in VMEM; the loss then forms the main probabilities
from the flash forward's logsumexp (`lse`), tile by tile. The flash pair
that consumes the set is in ops/pallas/flash_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.dispatch import apply, unwrap

__all__ = ["sparse_attention_index", "sparse_attention_index_loss",
           "INDEX_CHUNK"]

INDEX_CHUNK = 512     # queries a pass: the published q_chunk_size

_SIGN = 0x80000000


def _chunked(seq, chunk):
    """The chunk actually walked: the largest divisor of `seq` at most
    `chunk`."""
    c = min(chunk, seq)
    while seq % c:
        c -= 1
    return c


def _scores(q_c, k, w_c):
    """I of one chunk of queries against every key: q_c (b, c, heads, d),
    k (b, s, d), w_c (b, c, heads) -> (b, c, s) float32."""
    dots = jnp.einsum("bthd,bsd->bhts", q_c, k,
                      preferred_element_type=jnp.float32)
    w = jnp.swapaxes(w_c.astype(jnp.float32), 1, 2)[..., None]   # (b, heads, c, 1)
    # + 0.0: a row of negative weights over relu's zeros sums to -0.0, which
    # compares equal to 0.0 as a float and not in the integer order below
    return jnp.sum(w * jax.nn.relu(dots), axis=1) + 0.0


def _ordered(x):
    """float32 -> uint32 with the floats' order (-inf lowest, no NaN)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = bits ^ ((bits >> 31) & 0x7FFFFFFF)
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(_SIGN)


def _kth_largest(u, k):
    """The `k`-th largest of each row of `u` (..., n) uint32, built from the
    top bit down: a candidate bit stays where at least k entries reach it.
    A row with fewer than k entries above 0 gives 0."""
    def bit(i, found):
        cand = found | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        reach = jnp.sum((u >= cand[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(reach >= k, cand, found)
    return jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))


def _chunk_set(q_c, k, w_c, first, topk):
    """(b, c, s) bool: the set of each query of a chunk whose first row is
    position `first`."""
    c, s = q_c.shape[1], k.shape[1]
    causal = (first + jnp.arange(c))[:, None] >= jnp.arange(s)[None, :]
    # what lies above the diagonal orders below every score, so a row with at
    # most topk causal keys finds the threshold 0 and keeps them all
    u = jnp.where(causal, _ordered(_scores(q_c, k, w_c)), jnp.uint32(0))
    return causal & (u >= _kth_largest(u, topk)[..., None])


def _by_chunk(x, c):
    """(b, s, ...) -> (s / c, b, c, ...): the axis lax.map walks first."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, s // c, c, *x.shape[2:]), 1, 0)


def takes_kernels(q_index_shape, dtype, platform, on_mesh=False):
    """Whether the index of these (batch, seq, heads, d) index queries runs
    the kernels of ops/pallas/sparse_index.py: on a TPU, on one device,
    floating operands, a row that tiles (a multiple of 128 positions, from
    `attention.FLASH_MIN_SEQ_Q` on) and an index head of whole 64-lane
    halves. Everything else runs the XLA form."""
    from . import attention
    _, s, _, d = q_index_shape
    return (platform == "tpu" and not on_mesh and s % 128 == 0
            and s >= attention.FLASH_MIN_SEQ_Q and d % 64 == 0
            and jnp.issubdtype(dtype, jnp.floating))


def _mode(q_index):
    """"kernel", "interpret" or "xla" for operands like `q_index` (an array
    or a tracer), by `takes_kernels`."""
    from . import attention
    from .pallas.flash_attention import _interpret
    if not takes_kernels(q_index.shape, q_index.dtype, attention._platform(),
                         attention._on_mesh(q_index)):
        return "xla"
    # as the flash pair: interpreted wherever the operands are not on a TPU
    return "interpret" if _interpret(q_index) else "kernel"


def index_key_set(q_index, k_index, weights, topk, chunk=INDEX_CHUNK,
                  mode="xla"):
    """(the set; stats (3,) float32: the pairs selected, the flash pair's
    tiles at or under the diagonal that hold none, the queries). No
    gradient. `mode`: "xla", "kernel", or "interpret" (the kernel under the
    Pallas interpreter). The XLA form gives the set as (b, s, s) int8, 1
    where key s is in query t's set. The kernel writes it once in the layout
    the flash pair over a set and the loss kernel read, and that is what
    comes back: the pair (sets (b, s / tile, s, tile) int8, table (b *
    tiles^2,) int32 the pairs a tile), `flash_attention.set_tiles`' form."""
    from .pallas.flash_attention import (SET_BLOCK, _clamp, tile_counts,
                                         tiled_counts)
    q_index, k_index, weights = (jax.lax.stop_gradient(x)
                                 for x in (q_index, k_index, weights))
    b, s = k_index.shape[:2]
    if mode != "xla":
        from .pallas import sparse_index as kernels
        tile = _clamp(chunk, s)
        sets = kernels.index_sets(q_index, k_index, weights, topk, block=tile,
                                  interpret=mode == "interpret")
        per_tile = tiled_counts(sets, tile)
        picked = (sets, per_tile.reshape(-1))
    else:
        c = _chunked(s, chunk)

        def one(args):
            first, q_c, w_c = args
            return _chunk_set(q_c, k_index, w_c, first, topk).astype(jnp.int8)
        picked = jax.lax.map(one, (jnp.arange(0, s, c), _by_chunk(q_index, c),
                                   _by_chunk(weights, c)))
        picked = jnp.moveaxis(picked, 0, 1).reshape(b, s, s)
        tile = _clamp(SET_BLOCK, s)           # the flash pair's tile over a set
        per_tile = tile_counts(picked, tile, tile)
    under = jnp.tril(jnp.ones(per_tile.shape[1:], bool))
    stats = jnp.stack([jnp.sum(per_tile), jnp.sum((per_tile == 0) & under),
                       b * s])
    return picked, stats.astype(jnp.float32)


def _chunk_loss(q_c, k, w_c, in_set, p):
    """sum over the chunk's rows of KL(p || softmax over the set of I)."""
    logits = jnp.where(in_set, _scores(q_c, k, w_c), -jnp.inf)
    log_index = jax.nn.log_softmax(logits, axis=-1)
    terms = p * (jnp.log(jnp.where(p > 0, p, 1.0))
                 - jnp.where(in_set, log_index, 0.0))
    return jnp.sum(jnp.where(in_set & (p > 0), terms, 0.0))


def _chunk_main_probs(query_c, key, in_set, scale):
    """p (b, c, s) float32: the main heads' softmax over the set, averaged
    over the heads, a key/value head's group of query heads at a time."""
    b, c, heads, _ = query_c.shape
    kv_heads = key.shape[2]
    group = heads // kv_heads
    total = jnp.zeros(in_set.shape, jnp.float32)
    for g in range(kv_heads):
        dots = jnp.einsum("bthd,bsd->bhts",
                          query_c[:, :, g * group:(g + 1) * group], key[:, :, g],
                          preferred_element_type=jnp.float32) * scale
        dots = jnp.where(in_set[:, None], dots, -jnp.inf)
        total = total + jnp.sum(jax.nn.softmax(dots, axis=-1), axis=1)
    return total / heads


def _loss_walk(q_index, k_index, weights, picked, query, key, scale, chunk,
               with_grads):
    """(loss, grads or None): the chunks walked once; with `with_grads` each
    chunk's loss is differentiated as it is formed, so nothing of it is kept
    for a backward pass."""
    b, s = k_index.shape[:2]
    c = _chunked(s, chunk)
    rows = b * s

    def one(dk, args):
        q_c, w_c, set_c, query_c = args
        in_set = set_c != 0
        p = _chunk_main_probs(query_c, key, in_set, scale)
        if not with_grads:
            return dk, (_chunk_loss(q_c, k_index, w_c, in_set, p) / rows,)
        loss, (dq_c, dk_c, dw_c) = jax.value_and_grad(
            _chunk_loss, argnums=(0, 1, 2))(q_c, k_index, w_c, in_set, p)
        return dk + dk_c.astype(jnp.float32) / rows, (
            loss / rows, (dq_c / rows).astype(q_index.dtype),
            (dw_c / rows).astype(weights.dtype))

    dk, out = jax.lax.scan(
        one, jnp.zeros(k_index.shape, jnp.float32),
        (_by_chunk(q_index, c), _by_chunk(weights, c), _by_chunk(picked, c),
         _by_chunk(query, c)))
    loss = jnp.sum(out[0])
    if not with_grads:
        return loss, None

    def whole(x):
        return jnp.moveaxis(x, 0, 1).reshape(b, s, *x.shape[3:])
    return loss, (whole(out[1]), dk.astype(k_index.dtype), whole(out[2]))


def _walk(q_index, k_index, weights, picked, query, key, lse, scale, chunk,
          mode, with_grads):
    """`_loss_walk` in the form `mode` names; the kernel reads the set as
    the flash pair does and the main attention's logsumexp."""
    from .pallas.flash_attention import (_clamp, set_square, set_tiles,
                                         tile_blocks)
    if mode == "xla":
        return _loss_walk(q_index, k_index, weights, set_square(picked), query,
                          key, scale, chunk, with_grads)
    from .pallas import sparse_index as kernels
    block = _clamp(chunk, k_index.shape[1])
    tiles = set_tiles(picked, block, block)
    return kernels.index_loss_walk(
        q_index, k_index, weights, *tiles, query, key, lse, scale,
        block=tile_blocks(tiles)[0], interpret=mode == "interpret",
        with_grads=with_grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def index_loss(q_index, k_index, weights, picked, query, key, lse, scale,
               chunk=INDEX_CHUNK, mode="xla"):
    """The index's loss (module docstring), a float32 scalar: mean over the
    batch's positions. `picked` is `index_key_set`'s set in either of its
    forms; `query` (b, s, heads, d)
    and `key` (b, s, kv_heads, d) are the main attention's, as it multiplies
    them (normed, rotated), `scale` its scale and `lse` (b, heads, s) its
    logsumexp over the sets (read by the kernel form only; None for "xla",
    which forms whole rows)."""
    return _walk(q_index, k_index, weights, picked, query, key, lse, scale,
                 chunk, mode, False)[0]


def _index_loss_fwd(q_index, k_index, weights, picked, query, key, lse, scale,
                    chunk, mode):
    loss, grads = _walk(q_index, k_index, weights, picked, query, key, lse,
                        scale, chunk, mode, True)
    return loss, (grads, query, key, lse)


def _index_loss_bwd(scale, chunk, mode, res, g):
    (dq, dk, dw), query, key, lse = res
    return ((g * dq).astype(dq.dtype), (g * dk).astype(dk.dtype),
            (g * dw).astype(dw.dtype), None, jnp.zeros_like(query),
            jnp.zeros_like(key), None if lse is None else jnp.zeros_like(lse))


index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def sparse_attention_index(q_index, k_index, weights, topk, chunk=INDEX_CHUNK):
    """The keys each query attends to, chosen by the index (module
    docstring). q_index (batch, seq, heads, d), k_index (batch, seq, d),
    weights (batch, seq, heads). Returns (`key_set` for
    `F.scaled_dot_product_attention(..., key_set=)` and
    `F.sparse_attention_index_loss`: (batch, seq, seq) int8, or where the
    kernels run (`takes_kernels`) the pair (sets, table) in their layout,
    `index_key_set`; stats (3,) float32 for the caller's counters: pairs
    selected, tiles under the diagonal with none, queries). No output
    carries a gradient."""
    mode = _mode(unwrap(q_index))

    def prim(q, k, w):
        picked, stats = index_key_set(q, k, w, topk, chunk, mode)
        return (*picked, stats) if mode != "xla" else (picked, stats)
    # detached operands: the tape records nothing, and the outputs are marked
    # as the constants they are
    *picked, stats = apply(prim, *(t.detach() if hasattr(t, "detach") else t
                                   for t in (q_index, k_index, weights)),
                           name="dsa_index")
    return (tuple(picked) if mode != "xla" else picked[0]), stats


def sparse_attention_index_loss(q_index, k_index, weights, key_set, query, key,
                                scale=None, chunk=INDEX_CHUNK, lse=None):
    """The index's loss against the main attention's probabilities over
    `key_set` (module docstring). Differentiable in q_index, k_index and
    weights only. With `lse`, the main attention's logsumexp over the sets
    (`scaled_dot_product_attention(..., return_lse=True)`), the kernel form
    runs where `takes_kernels` says so; without it the XLA form."""
    if scale is None:
        scale = unwrap(query).shape[-1] ** -0.5
    mode = "xla" if lse is None else _mode(unwrap(q_index))
    extra = [] if mode == "xla" else [lse]
    tiled = isinstance(key_set, (tuple, list))
    stop = jax.lax.stop_gradient

    def prim(q, k, w, mq, mk, *rest):
        picked = tuple(rest[:2]) if tiled else rest[0]
        return index_loss(q, k, w, picked, stop(mq), stop(mk),
                          stop(rest[-1]) if extra else None, scale, chunk, mode)
    return apply(prim, q_index, k_index, weights, query, key,
                 *(key_set if tiled else [key_set]), *extra,
                 name="dsa_index_loss")
