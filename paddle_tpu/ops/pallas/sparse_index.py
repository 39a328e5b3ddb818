"""The sparse-attention index on the chip (Pallas/TPU): the sets, and the
index loss with its gradient. ops/sparse_index.py has the mathematics and the
plain XLA forms these replace where `takes_kernels` says so.

Both kernels work on one block of `block` queries against the whole row of
keys, tile by tile, every tile formed transposed as the flash pair forms its
tiles (ops/pallas/flash_attention.py): keys down the sublanes, queries along
the lanes, so that a query's statistics are lane-dense rows and the sets
leave in the layout the flash pair reads, (batch, query block, key, query in
block) int8. Nothing of (seq, seq) but that byte a pair ever crosses HBM.

`_sets_kernel`: the index scores of the block's causal tiles, I^T = sum_j
w_j relu(kI qI_j^T), are kept in VMEM as integers with the floats' order (16
MB at 512 queries against 8192 keys); the `topk`-th largest of each query's
row is built bit by bit from the top, one count over the tiles a bit, and
the tiles are compared with it on the way out. What lies above the diagonal
is the lowest integer, which no score maps to, so a short row keeps every
causal key.

`_loss_kernel`: for a block of queries, the logsumexp of I over each set (a
first walk over the tiles), then tile by tile the main heads' probabilities
p^T = mean_h exp(k q_h^T scale - L_h) from the flash forward's L, the loss
terms p (log p - log softmax(I)), dI = (softmax(I) - p) / T on the set, and
dI pulled back to the index queries, the index keys and the weights, the
products of a head formed again rather than kept. dkI accumulates over the
query blocks in a resident float32 output (the block axis is sequential).
Tiles that hold no pair of the set are skipped by the prefetched table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_NT, SET_BLOCK as BLOCK, _clamp, _lanes,
                              _tiles_restored, _tiles_transposed, _to_bh,
                              _tpu_params)

_LOWEST = -2 ** 31              # what no float orders to: above the diagonal


def _index_tile(ki_tile, qi_ref, w_ref, heads):
    """I^T (block_k, block_q) float32 of one key tile: ki_tile (block_k, d),
    qi_ref (heads, block_q, d), w_ref (heads, block_q) float32."""
    def head(j, acc):
        dots = jax.lax.dot_general(ki_tile, qi_ref[j], _NT,
                                   preferred_element_type=jnp.float32)
        return acc + w_ref[pl.ds(j, 1), :] * jnp.maximum(dots, 0.0)
    zero = jnp.zeros((ki_tile.shape[0], qi_ref.shape[1]), jnp.float32)
    return jax.lax.fori_loop(0, heads, head, zero)


# ---------------------------------------------------------------------------
# the sets
# ---------------------------------------------------------------------------

def _sets_kernel(qi_ref, ki_ref, w_ref, set_ref, keys_ref, *, topk, block_k):
    # qi_ref (heads, block_q, d); ki_ref (seq_k, d); w_ref (heads, block_q);
    # set_ref (seq_k, block_q) int8 out; keys_ref (tiles_k, block_k, block_q)
    # int32 scratch: the scores as ordered integers
    heads, block_q, _ = qi_ref.shape
    tiles_k = keys_ref.shape[0]
    i = pl.program_id(1)
    # key tiles at or under the diagonal of this query block
    last = jnp.minimum(((i + 1) * block_q + block_k - 1) // block_k, tiles_k)

    def score(kb, carry):
        rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        # + 0.0: -0.0 (negative weights over relu's zeros) orders under 0.0
        scores = _index_tile(ki_ref[rows, :], qi_ref, w_ref, heads) + 0.0
        bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
        ordered = bits ^ ((bits >> 31) & 0x7FFFFFFF)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        keys_ref[kb] = jnp.where(q_pos >= k_pos, ordered, _LOWEST)
        return carry
    jax.lax.fori_loop(0, last, score, 0)

    # the topk-th largest of each query's row, in the unsigned order
    # (ordered ^ sign), a bit at a time from the top
    def bit(n, found):
        cand = found | jnp.left_shift(jnp.int32(1), 31 - n)
        signed = cand ^ _LOWEST

        def count(kb, reach):
            return reach + jnp.sum((keys_ref[kb] >= signed).astype(jnp.int32),
                                   axis=0, keepdims=True)
        reach = jax.lax.fori_loop(0, last, count,
                                  jnp.zeros((1, block_q), jnp.int32))
        return jnp.where(reach >= topk, cand, found)
    found = jax.lax.fori_loop(0, 32, bit, jnp.zeros((1, block_q), jnp.int32))
    threshold = found ^ _LOWEST

    def write(kb, carry):
        rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        ordered = keys_ref[kb]
        picked = (ordered >= threshold) & (ordered != _LOWEST)
        set_ref[rows, :] = picked.astype(jnp.int32).astype(jnp.int8)
        return carry
    jax.lax.fori_loop(0, last, write, 0)

    def clear(kb, carry):
        rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        set_ref[rows, :] = jnp.zeros((block_k, block_q), jnp.int8)
        return carry
    jax.lax.fori_loop(last, tiles_k, clear, 0)


@functools.partial(jax.jit, static_argnames=("topk", "block", "interpret"))
def index_sets(q_index, k_index, weights, topk, block=BLOCK, interpret=False):
    """q_index (B, S, H, D), k_index (B, S, D), weights (B, S, H) -> the sets
    as the flash pair reads them, (B, S / block, S, block) int8."""
    b, s, heads, d = q_index.shape
    bq = _clamp(block, s)
    tiles = s // bq
    vmem = 2 * (s * bq + s * _lanes(d) * k_index.dtype.itemsize) + 4 * s * bq \
        + 8 * bq * bq * 4
    return pl.pallas_call(
        functools.partial(_sets_kernel, topk=topk, block_k=bq),
        grid=(b, tiles),
        in_specs=[
            pl.BlockSpec((heads, bq, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((None, s, d), lambda b_, i: (b_, 0, 0)),
            pl.BlockSpec((None, heads, bq), lambda b_, i: (b_, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, None, s, bq), lambda b_, i: (b_, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, tiles, s, bq), jnp.int8),
        scratch_shapes=[pltpu.VMEM((tiles, bq, bq), jnp.int32)],
        interpret=interpret,
        name="dsa_index_sets",
        **_tpu_params(interpret, ("parallel", "parallel"), vmem),
    )(_to_bh(q_index), k_index,
      jnp.swapaxes(weights.astype(jnp.float32), 1, 2))


# ---------------------------------------------------------------------------
# the loss and its gradient
# ---------------------------------------------------------------------------

def _loss_kernel(tab_ref, qm_ref, km_ref, lse_ref, qi_ref, ki_ref, kit_ref,
                 w_ref, set_ref, *out_refs, scale, rows, block_k, with_grads):
    # qm_ref (heads, block_q, d) the main queries; km_ref (kv_heads, seq_k, d)
    # the main keys; lse_ref (heads, 1, block_q) the flash forward's L;
    # qi_ref (index heads, block_q, di); ki_ref (seq_k, di); kit_ref (tiles_k,
    # di, block_k) the index keys' tiles transposed; w_ref (index heads,
    # block_q) float32; set_ref (seq_k, block_q) int8; tab_ref the pairs a
    # tile. Out: loss_ref (1, block_q) the rows' KL; with gradients
    # dqit_ref (index heads, di, block_q), dw_ref (index heads, block_q),
    # dki_ref (seq_k, di) float32, resident over the query blocks
    heads, block_q, _ = qm_ref.shape
    group = heads // km_ref.shape[0]
    index_heads = qi_ref.shape[0]
    tiles_k = kit_ref.shape[0]
    i = pl.program_id(1)
    base = (pl.program_id(0) * pl.num_programs(1) + i) * tiles_k
    if with_grads:
        loss_ref, dqit_ref, dw_ref, dki_ref, dqit_acc, dw_acc = out_refs
    else:
        (loss_ref,) = out_refs

    def tile(kb):
        rows_ = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        in_set = set_ref[rows_, :].astype(jnp.int32) != 0
        return rows_, in_set, _index_tile(ki_ref[rows_, :], qi_ref, w_ref,
                                          index_heads)

    def visiting(kb, carry, fn):
        return jax.lax.cond(tab_ref[base + kb] == 0, lambda c: c,
                            lambda c: fn(kb, c), carry)

    # the logsumexp of I over each query's set
    def stat(kb, carry):
        m_prev, l_prev = carry
        _, in_set, scores = tile(kb)
        scores = jnp.where(in_set, scores, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0, keepdims=True))
        l_new = l_prev * jnp.exp(m_prev - m_new) + jnp.sum(
            jnp.where(in_set, jnp.exp(scores - m_new), 0.0), axis=0, keepdims=True)
        return m_new, l_new
    m, l = jax.lax.fori_loop(
        0, tiles_k, lambda kb, c: visiting(kb, c, stat),
        (jnp.full((1, block_q), -1e30, jnp.float32),
         jnp.zeros((1, block_q), jnp.float32)))
    lse_index = m + jnp.log(jnp.maximum(l, 1e-30))

    if with_grads:
        dqit_acc[...] = jnp.zeros(dqit_acc.shape, jnp.float32)
        dw_acc[...] = jnp.zeros(dw_acc.shape, jnp.float32)

        @pl.when(i == 0)
        def _():
            dki_ref[...] = jnp.zeros(dki_ref.shape, jnp.float32)

    def pairs(kb, kl):
        rows_, in_set, scores = tile(kb)

        def main_head(h, total):
            dots = jax.lax.dot_general(km_ref[h // group, rows_, :], qm_ref[h],
                                       _NT, preferred_element_type=jnp.float32)
            return total + jnp.exp(dots * scale - lse_ref[h])
        p = jax.lax.fori_loop(0, heads, main_head,
                              jnp.zeros((block_k, block_q), jnp.float32))
        p = jnp.where(in_set, p / heads, 0.0)
        log_index = scores - lse_index
        live = in_set & (p > 0.0)
        kl = kl + jnp.sum(
            jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) - log_index), 0.0),
            axis=0, keepdims=True)
        if with_grads:
            d_scores = jnp.where(in_set, jnp.exp(log_index) - p, 0.0) / rows
            ki_tile, kit_tile = ki_ref[rows_, :], kit_ref[kb]

            def index_head(j, dki):
                q_j = qi_ref[j]
                dots = jax.lax.dot_general(ki_tile, q_j, _NT,
                                           preferred_element_type=jnp.float32)
                on = dots > 0.0
                row = pl.ds(j, 1)
                dw_acc[row, :] += jnp.sum(
                    jnp.where(on, d_scores * dots, 0.0), axis=0, keepdims=True)
                pulled = jnp.where(on, d_scores * w_ref[row, :], 0.0).astype(q_j.dtype)
                dqit_acc[j] += jnp.dot(kit_tile, pulled,
                                       preferred_element_type=jnp.float32)
                return dki + jnp.dot(pulled, q_j,
                                     preferred_element_type=jnp.float32)
            dki_ref[rows_, :] += jax.lax.fori_loop(
                0, index_heads, index_head,
                jnp.zeros((block_k, ki_tile.shape[1]), jnp.float32))
        return kl

    loss_ref[...] = jax.lax.fori_loop(
        0, tiles_k, lambda kb, c: visiting(kb, c, pairs),
        jnp.zeros((1, block_q), jnp.float32))
    if with_grads:
        dqit_ref[...] = dqit_acc[...].astype(dqit_ref.dtype)
        dw_ref[...] = dw_acc[...]


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret",
                                             "with_grads"))
def index_loss_walk(q_index, k_index, weights, sets, table, query, key, lse,
                    scale, block=BLOCK, interpret=False, with_grads=True):
    """(loss, (dq_index, dk_index, dweights) or None) as
    ops/sparse_index._loss_walk gives them. `sets` and `table` are the flash
    pair's (`_set_tiles`), `lse` (B, H, S) its forward's logsumexp over the
    sets, `query` (B, S, H, D) and `key` (B, S, Hkv, D) its operands."""
    b, s, heads, d = query.shape
    kv_heads = key.shape[2]
    index_heads, di = q_index.shape[2:]
    bq = _clamp(block, s)
    tiles = s // bq
    vmem = (2 * (heads * bq * _lanes(d) + kv_heads * s * _lanes(d)) * query.dtype.itemsize
            + 2 * s * bq + 6 * s * _lanes(di) * k_index.dtype.itemsize
            + 2 * s * _lanes(di) * 4 + 2 * index_heads * di * bq * 4
            + 10 * bq * bq * 4)

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map)
    in_specs = [
        spec((heads, bq, d), lambda b_, i, tab: (b_, i, 0)),
        spec((kv_heads, s, d), lambda b_, i, tab: (b_, 0, 0)),
        spec((heads, 1, bq), lambda b_, i, tab: (b_, 0, i)),
        spec((index_heads, bq, di), lambda b_, i, tab: (b_, i, 0)),
        spec((None, s, di), lambda b_, i, tab: (b_, 0, 0)),
        spec((None, tiles, di, bq), lambda b_, i, tab: (b_, 0, 0, 0)),
        spec((None, index_heads, bq), lambda b_, i, tab: (b_, 0, i)),
        spec((None, None, s, bq), lambda b_, i, tab: (b_, i, 0, 0)),
    ]
    out_specs = [spec((None, 1, bq), lambda b_, i, tab: (b_, 0, i))]
    out_shape = [jax.ShapeDtypeStruct((b, 1, s), jnp.float32)]
    scratch = []
    if with_grads:
        out_specs += [
            spec((index_heads, None, di, bq), lambda b_, i, tab: (b_, i, 0, 0)),
            spec((None, index_heads, bq), lambda b_, i, tab: (b_, 0, i)),
            spec((None, s, di), lambda b_, i, tab: (b_, 0, 0)),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((b * index_heads, tiles, di, bq), q_index.dtype),
            jax.ShapeDtypeStruct((b, index_heads, s), jnp.float32),
            jax.ShapeDtypeStruct((b, s, di), jnp.float32),
        ]
        scratch = [pltpu.VMEM((index_heads, di, bq), jnp.float32),
                   pltpu.VMEM((index_heads, bq), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_loss_kernel, scale=scale, rows=float(b * s),
                          block_k=bq, with_grads=with_grads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, tiles), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        interpret=interpret,
        name="dsa_index_loss",
        # dkI accumulates over the query blocks: that axis is sequential
        **_tpu_params(interpret, ("parallel", "arbitrary"), vmem),
    )(table, _to_bh(query), _to_bh(key), lse.reshape(b * heads, 1, s),
      _to_bh(q_index), k_index, _tiles_transposed(k_index, bq),
      jnp.swapaxes(weights.astype(jnp.float32), 1, 2), sets)
    loss = jnp.sum(out[0]) / (b * s)
    if not with_grads:
        return loss, None
    dq = _tiles_restored(out[1]).reshape(b, index_heads, s, di)
    return loss, (jnp.swapaxes(dq, 1, 2), out[3].astype(k_index.dtype),
                  jnp.swapaxes(out[2], 1, 2).astype(weights.dtype))
