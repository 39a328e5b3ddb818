"""Flash attention (Pallas/TPU): one forward kernel and one backward kernel.

Reference analog: operators/fused/fused_attention_op.cu + fmha_ref.h (cuDNN
FMHA fwd/bwd). TPU-native: online-softmax tiled attention in VMEM, O(S)
memory instead of the O(S^2) probability matrix; the MXU does every product
of a tile pair. Tiles the causal mask zeroes are never visited, and only the
tile pairs the diagonal crosses build the mask: the pairs wholly under it run
a body with no iota, compare or select.

Both kernels form every tile TRANSPOSED, keys down the sublanes and queries
along the lanes. The per-row statistics (the running maximum and sum, the
logsumexp L, D = rowsum(dO * O)) are then lane-dense rows: they enter and
leave HBM with the sequence in the lane dimension ((B*H, 1, S) float32; no
128-lane copy of them is ever written), their maxima and sums run down the
sublanes, and they broadcast over a tile for nothing. The products whose
result is d wide are formed transposed too, (d, block) = x^T @ tile from the
transposed key or value tile, so no (block_k, block_q) tile is ever
transposed on the chip; XLA transposes k, v tile by tile on the way in and
the output and dq on the way out, inside the (B, S, H, D) <-> (B*H, S, D)
copies it already makes.

Forward, for query block i over the key tiles j at or under the diagonal:
    S^T = k_j q_i^T * scale,  online softmax down the sublanes,
    O_i^T = sum_j v_j^T P_ij^T / l_i,   L_i = m_i + log l_i

Backward follows the FlashAttention-2 recompute scheme in ONE pass: the
forward saves only L; for key tile j the backward loops over the query heads
that read it and over their query blocks i at or under the diagonal,
re-forms each tile once and adds
    P^T  = exp(k_j q_i^T * scale - L_i)
    dV_j += P^T dO_i
    dS^T = P^T * (v_j dO_i^T - D_i)
    dK_j += dS^T q_i * scale
    dQ_i^T += k_j^T dS^T * scale
five products a tile pair. dK_j and dV_j accumulate in float32 over the loop
and over the group's query heads and are written once, in k's and v's dtype.
dQ^T accumulates in a float32 VMEM scratch that stays resident while the key
tiles go by (the key-tile grid axis is sequential) and is written after the
last one. Operands reach the MXU in the input dtype and accumulate in
float32; P and dS are cast to the input dtype just before their products;
maxima, sums, the exponential, L, D and every accumulator are float32. The
scale is folded into the key tile (into q in the forward) where that is
exact, a power of two, and multiplies S in float32 otherwise.

Layout: inputs (B, S, H, D) paddle convention; kernels work on (B*H, S, D).
Head dims of 64 are supported (VMEM pads the lane dim of a (block, 64) tile;
every product then fills half of the 128 x 128 MXU: docs/kernels.md).

The value heads may be of another size than the query/key heads (latent
attention: 192 against 128): scores, dK and dQ^T contract or give d, the
query/key size; O^T, dV and dP the value size d_v. Nothing is padded to the
larger; where the two are equal the staged program is what it was.

Grouped-query attention: k and v may hold H / group heads. Rows
r * group ... r * group + group - 1 of q read row r of k and v, which the
block specs say, so no repeated copy of k or v is ever written and dk, dv
come out at (B*H / group, S, D).

How much of dQ is resident is a rule of shapes (`_bwd_q_span`): where a
group's q, dO and dQ over the whole sequence pass VMEM_RESIDENT_BYTES, the
query range is cut into spans, a grid axis outside the key tiles; each span
then writes float32 partial dk, dv of (spans, B*H / group, S, D) that one XLA
sum adds. The cell's and the models' shapes hold one span.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The kernels' tiles, set from device time on a v5e and never drawn by a clock
# (docs/kernels.md, "Tiles", has the table): 512 x 512 in both passes, and
# query blocks of 1024 from LONG_SEQ_Q positions on, where the masked work a
# larger query block adds on the diagonal, block_q / s_q of the pass, has
# shrunk under what it saves.
BLOCK = 512
LONG_SEQ_Q = 8192

# What a group's q, dO (double-buffered), dQ block and float32 dQ scratch may
# take of a v5e's 128 MiB of VMEM before the query range is cut into spans;
# the rest is the key tiles, the (block_k, block_q) float32 intermediates and
# the compiler's own.
VMEM_RESIDENT_BYTES = 48 * 2 ** 20
VMEM_LIMIT_CAP = 100 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_MASKED = -1e30


def _interpret(x=None):
    """True where the kernels must run under the pallas interpreter: any
    placement that is not a TPU (CPU CI has no Mosaic backend, and the
    interpreter keeps numerics/tests covering this path there). Decided from
    the concrete input's placement; a tracer carries none, so a staged trace
    resolves to the backend it stages for. On a TPU the kernels are never
    interpreted."""
    if x is not None and not isinstance(x, jax.core.Tracer):
        return all(d.platform != "tpu" for d in x.devices())
    return jax.default_backend() != "tpu"


def _tpu_params(interpret, semantics, vmem_bytes=None):
    """Mosaic compiler params: the grid axes' semantics ("parallel" where
    each instance writes its own output tile, "arbitrary" for an axis an
    accumulator stays resident over) and, where the kernel's blocks pass the
    default scoped limit, a VMEM limit that follows the shapes. Skipped under
    the interpreter (no Mosaic)."""
    if interpret:
        return {}
    limit = None
    if vmem_bytes is not None and vmem_bytes > 12 * 2 ** 20:
        limit = int(min(VMEM_LIMIT_CAP, vmem_bytes + 16 * 2 ** 20))
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=tuple(semantics), vmem_limit_bytes=limit)}


def _scale_folds(scale):
    """Whether multiplying an operand by `scale` is exact in any floating
    type: a power of two (0.125 at heads of 64; not 128 ** -0.5)."""
    return math.frexp(scale)[0] == 0.5


def _lanes(d):
    return -(-d // 128) * 128


def _tiles_transposed(x, block):
    """(rows, S, D) -> (rows, S / block, D, block): each tile of `block`
    positions transposed, the positions in the lane dimension. XLA's copy,
    beside the (B, S, H, D) -> (B*H, S, D) one it already makes."""
    rows, seq, d = x.shape
    return jnp.swapaxes(x.reshape(rows, seq // block, block, d), 2, 3)


def _tiles_restored(x_t):
    """`_tiles_transposed` undone: (rows, tiles, D, block) -> (rows, S, D)."""
    rows, tiles, d, block = x_t.shape
    return jnp.swapaxes(x_t, 2, 3).reshape(rows, tiles * block, d)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _attn_fwd_kernel(q_ref, k_ref, vt_ref, ot_ref, l_ref, *, scale, causal,
                     block_k, window=None):
    # q_ref: (block_q, d); k_ref: (seq_k, d); vt_ref: (seq_k / block_k, d_v,
    # block_k), each value tile transposed; ot_ref: (d_v, block_q), the output
    # tile transposed; l_ref: (1, block_q), the logsumexp rows lane-dense.
    # Every tile is formed transposed, keys down the sublanes and queries
    # along the lanes: the row statistics are lane-dense rows, their maxima
    # and sums run down the sublanes, and they broadcast for nothing.
    block_q = q_ref.shape[0]
    q_idx = pl.program_id(1)
    fold = _scale_folds(scale)
    q = q_ref[...] * scale if fold else q_ref[...]
    num_k_blocks, d_v = vt_ref.shape[:2]

    def step(kb, carry, masked):
        m_prev, l_prev, acc = carry
        k_tile = k_ref[pl.ds(pl.multiple_of(kb * block_k, block_k), block_k), :]
        vt_tile = vt_ref[kb]
        s_t = jax.lax.dot_general(k_tile, q, _NT,
                                  preferred_element_type=jnp.float32)
        if not fold:
            s_t = s_t * scale
        if masked:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            keep = q_pos >= k_pos
            if masked == "band":
                keep = keep & (q_pos - k_pos < window)
            s_t = jnp.where(keep, s_t, _MASKED)
        m_new = jnp.maximum(m_prev, jnp.max(s_t, axis=0, keepdims=True))
        p_t = jnp.exp(s_t - m_new)                      # (block_k, block_q)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p_t, axis=0, keepdims=True)
        acc = acc * correction + jnp.dot(
            vt_tile, p_t.astype(vt_tile.dtype),
            preferred_element_type=jnp.float32)         # (d_v, block_q)
        return m_new, l_new, acc

    carry = (jnp.full((1, block_q), _MASKED, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32),
             jnp.zeros((d_v, block_q), jnp.float32))
    if causal:
        # k-tiles wholly under the diagonal, then the ones it crosses; the
        # tiles wholly above it are never visited
        clear = jnp.minimum((q_idx * block_q + 1) // block_k, num_k_blocks)
        last = jnp.minimum(((q_idx + 1) * block_q + block_k - 1) // block_k,
                           num_k_blocks)
        first = 0
        if window is not None:
            # the band's lower edge: the tiles wholly under it are never
            # visited, the ones it crosses build both masks (a short window
            # lets the diagonal cross them too), and `clear` starts after them
            first = jnp.maximum(q_idx * block_q - (window - 1), 0) // block_k
            inner = jnp.clip((jnp.maximum((q_idx + 1) * block_q - window, 0)
                              + block_k - 1) // block_k, first, last)
            carry = jax.lax.fori_loop(
                first, inner, lambda kb, c: step(kb, c, "band"), carry)
            first, clear = inner, jnp.clip(clear, inner, last)
        carry = jax.lax.fori_loop(
            first, clear, lambda kb, c: step(kb, c, False), carry)
        carry = jax.lax.fori_loop(
            clear, last, lambda kb, c: step(kb, c, True), carry)
    else:
        carry = jax.lax.fori_loop(
            0, num_k_blocks, lambda kb, c: step(kb, c, False), carry)
    m, l, acc = carry
    l_safe = jnp.maximum(l, 1e-30)
    ot_ref[...] = (acc / l_safe).astype(ot_ref.dtype)
    l_ref[...] = m + jnp.log(l_safe)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret", "window"))
def _flash_fwd_bh(q, k, v, causal, scale, block_q, block_k, interpret,
                  window=None):
    # q: (BH, S, D), k: (BH / group, S, D), v: (BH / group, S, Dv)
    # -> out (BH, S, Dv), lse (BH, S)
    bh, seq_q, d = q.shape
    seq_k, d_v = v.shape[1:]
    group = bh // k.shape[0]
    tiles_q, tiles_k = seq_q // block_q, seq_k // block_k
    vmem = (2 * seq_k * (_lanes(d) + d_v) * k.dtype.itemsize
            + 4 * block_q * block_k * 4)
    out_t, lse = pl.pallas_call(
        functools.partial(_attn_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, window=window),
        grid=(bh, tiles_q),
        interpret=interpret,
        name=window and "flash_window_fwd",
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, seq_k, d), lambda b, i: (b // group, 0, 0)),
            pl.BlockSpec((None, tiles_k, d_v, block_k),
                         lambda b, i: (b // group, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, d_v, block_q), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tiles_q, d_v, block_q), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        **_tpu_params(interpret, ("parallel", "parallel"), vmem),
    )(q, k, _tiles_transposed(v, block_k))
    return _tiles_restored(out_t), lse.reshape(bh, seq_q)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _attn_bwd_kernel(q_ref, do_ref, l_ref, dd_ref, k_ref, v_ref, kt_ref,
                     dqt_ref, dk_ref, dv_ref, dqt_acc, *, scale, causal,
                     block_q, window=None):
    # one key tile j of one key/value head, against one span of the group's
    # query rows. q_ref: (group, span, d); do_ref: (group, span, d_v); l_ref,
    # dd_ref: (group, span / block_q, block_q) float32, a query block a row;
    # k_ref, dk_ref: (block_k, d); v_ref, dv_ref: (block_k, d_v); kt_ref:
    # (d, block_k), the key tile
    # transposed; dqt_ref: (group, span / block_q, d, block_q), dq a query
    # block transposed; dqt_acc: the same in float32, resident over the key
    # tiles
    group, span, d = q_ref.shape
    block_k = k_ref.shape[0]
    q_off = pl.program_id(1) * span
    j = pl.program_id(2)
    num_q_blocks = span // block_q
    fold = _scale_folds(scale)
    k_tile = k_ref[...] * scale if fold else k_ref[...]
    v_tile = v_ref[...]
    kt_tile = kt_ref[...] * scale if fold else kt_ref[...]

    def for_each_block(fn):
        def head(h, carry):
            def block(i, carry):
                fn(h, i)
                return carry
            return jax.lax.fori_loop(0, num_q_blocks, block, carry)
        jax.lax.fori_loop(0, group, head, 0)

    @pl.when(j == 0)
    def _():
        def zero(h, i):
            dqt_acc[h, i] = jnp.zeros((d, block_q), jnp.float32)
        for_each_block(zero)

    def pair(h, i, carry, masked):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q, do = q_ref[h, rows, :], do_ref[h, rows, :]
        lse, delta = l_ref[h, pl.ds(i, 1), :], dd_ref[h, pl.ds(i, 1), :]
        s_t = jax.lax.dot_general(k_tile, q, _NT,
                                  preferred_element_type=jnp.float32)
        if not fold:
            s_t = s_t * scale
        if masked:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = q_off + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            keep = q_pos >= k_pos
            if masked == "band":
                keep = keep & (q_pos - k_pos < window)
            s_t = jnp.where(keep, s_t, _MASKED)
        p_t = jnp.exp(s_t - lse)                        # (block_k, block_q)
        dv = dv + jnp.dot(p_t.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(v_tile, do, _NT,
                                   preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - delta)).astype(q.dtype)
        dk = dk + jnp.dot(ds_t, q, preferred_element_type=jnp.float32)
        dqt_acc[h, i] += jnp.dot(kt_tile, ds_t,
                                 preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # the query blocks that see this key tile: first the ones the
        # diagonal crosses, then the ones wholly under it
        first = jnp.minimum(
            jnp.maximum(j * block_k - q_off, 0) // block_q, num_q_blocks)
        clear = jnp.clip(
            (jnp.maximum((j + 1) * block_k - 1 - q_off, 0) + block_q - 1)
            // block_q, first, num_q_blocks)
    else:
        first = clear = 0
    end = num_q_blocks
    if window is not None:
        # the band's lower edge: the query blocks wholly past it are never
        # visited; from `edge` on it crosses the blocks, which build both
        # masks (a short window lets the diagonal cross them too)
        end = jnp.clip(jnp.maximum(
            (j + 1) * block_k + window - 2 - q_off + block_q, 0) // block_q,
            first, num_q_blocks)
        edge = jnp.maximum(j * block_k + window - q_off, 0) // block_q
        clear = jnp.clip(jnp.minimum(clear, edge), first, end)
        edge = jnp.clip(edge, clear, end)

    def head(h, carry):
        carry = jax.lax.fori_loop(
            first, clear, lambda i, c: pair(h, i, c, True), carry)
        if window is None:
            return jax.lax.fori_loop(
                clear, end, lambda i, c: pair(h, i, c, False), carry)
        carry = jax.lax.fori_loop(
            clear, edge, lambda i, c: pair(h, i, c, False), carry)
        return jax.lax.fori_loop(
            edge, end, lambda i, c: pair(h, i, c, "band"), carry)

    zeros = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(
        0, group, head,
        (zeros, zeros if v_ref.shape == zeros.shape
         else jnp.zeros(v_ref.shape, jnp.float32)))
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        def write(h, i):
            dq_t = dqt_acc[h, i]
            dqt_ref[h, i] = (dq_t if fold else dq_t * scale).astype(
                dqt_ref.dtype)
        for_each_block(write)


def _bwd_resident_bytes(group, rows, d, itemsize, d_v=None):
    """VMEM a grid step of the backward holds for `rows` query rows of a
    group: q (rows of d) and dO (rows of d_v) double-buffered (a row pads to
    whole 128-lane tiles), the transposed dQ block double-buffered, its
    float32 scratch."""
    d_v = d if d_v is None else d_v
    return group * rows * (2 * (_lanes(d) + _lanes(d_v)) * itemsize
                           + d * (2 * itemsize + 4))


def _bwd_q_span(group, seq_q, d, itemsize, block_q, d_v=None):
    """Query rows a grid step of the backward holds resident: the whole
    sequence where that fits VMEM_RESIDENT_BYTES, else the largest whole
    number of query blocks that divides the sequence and fits."""
    spans = 1
    while (seq_q // spans > block_q and _bwd_resident_bytes(
            group, seq_q // spans, d, itemsize, d_v) > VMEM_RESIDENT_BYTES):
        spans += 1
        while seq_q % (spans * block_q):
            spans += 1
    return seq_q // spans


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "q_span", "window"))
def _flash_bwd_bh(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                  interpret, q_span=None, window=None):
    # q (BH, S, D), k (BH / group, S, D), v (BH / group, S, Dv), o and do
    # (BH, S, Dv), lse (BH, S); returns dq (BH, S, D) and dk, dv in k's and
    # v's shapes. `q_span` pins the rows resident a step (tests); None takes
    # the rule of shapes.
    bh, seq_q, d = q.shape
    rows_kv, seq_k, d_v = v.shape
    group = bh // rows_kv
    span = q_span or _bwd_q_span(group, seq_q, d, q.dtype.itemsize, block_q,
                                 d_v)
    spans, blocks = seq_q // span, span // block_q
    # D = rowsum(dO * O), float32: a product of two bf16 is exact in float32
    delta = jnp.einsum("rsd,rsd->rs", do, o, precision="highest",
                       preferred_element_type=jnp.float32)
    # a query block a row, (BH, S) -> (BH, spans, blocks, block_q): no copy
    stats = [x.reshape(bh, spans, blocks, block_q) for x in (lse, delta)]
    # one span: dk, dv leave in k's, v's dtype; more: float32 partials
    part = (k.dtype, v.dtype) if spans == 1 else (jnp.float32,) * 2
    vmem = (_bwd_resident_bytes(group, span, d, q.dtype.itemsize, d_v)
            + block_k * (6 * _lanes(d) + 4 * _lanes(d_v)) * k.dtype.itemsize
            + 6 * block_q * block_k * 4)

    def wide(width):
        return pl.BlockSpec((group, span, width), lambda r, c, j: (r, c, 0))

    def tile(width):
        return pl.BlockSpec((None, block_k, width), lambda r, c, j: (r, j, 0))

    def part_tile(width):
        return pl.BlockSpec((None, None, block_k, width),
                            lambda r, c, j: (c, r, j, 0))
    stat = pl.BlockSpec((group, None, blocks, block_q),
                        lambda r, c, j: (r, c, 0, 0))
    tile_t = pl.BlockSpec((None, None, d, block_k),
                          lambda r, c, j: (r, j, 0, 0))
    dq_t, dk, dv = pl.pallas_call(
        functools.partial(_attn_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, window=window),
        grid=(rows_kv, spans, seq_k // block_k),
        interpret=interpret,
        name=window and "flash_window_bwd",
        in_specs=[wide(d), wide(d_v), stat, stat, tile(d), tile(d_v), tile_t],
        out_specs=[pl.BlockSpec((group, blocks, d, block_q),
                                lambda r, c, j: (r, c, 0, 0)),
                   part_tile(d), part_tile(d_v)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, spans * blocks, d, block_q), q.dtype),
            jax.ShapeDtypeStruct((spans, rows_kv, seq_k, d), part[0]),
            jax.ShapeDtypeStruct((spans, rows_kv, seq_k, d_v), part[1]),
        ],
        scratch_shapes=[pltpu.VMEM((group, blocks, d, block_q), jnp.float32)],
        **_tpu_params(interpret, ("parallel", "arbitrary", "arbitrary"), vmem),
    )(q, do, *stats, k, v, _tiles_transposed(k, block_k))
    dq = _tiles_restored(dq_t)
    if spans == 1:
        return dq, dk[0], dv[0]
    return dq, dk.sum(0).astype(k.dtype), dv.sum(0).astype(v.dtype)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def supports(q_shape, k_shape, v_shape=None):
    """Whether the kernels tile these (batch, seq, heads, head_dim) shapes;
    `v_shape` where the value heads are of another size than the keys'."""
    b, s_q, h, d = q_shape
    s_k = k_shape[1]
    d_v = d if v_shape is None else v_shape[3]
    return (s_q % 128 == 0 and s_k % 128 == 0 and d % 64 == 0
            and d_v % 64 == 0 and s_q == s_k and h % k_shape[2] == 0)


def _clamp(block, seq):
    """Largest block <= `block` that DIVIDES seq — the grids/inner loops use
    integer division, so a non-dividing block would silently truncate the
    trailing rows (supports() admits any s % 128 == 0, e.g. 768)."""
    b = min(block, seq)
    while seq % b:
        b //= 2
    return b


def _to_bh(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _from_bh(x, b, h):
    bh, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


def tiles(s_q, s_k, window=None):
    """(block_q, block_k) of the forward kernel, which tiles q over the grid
    and loops the key tiles, and of the one-pass backward, which tiles k over
    the grid and loops the query blocks: a rule of shapes, the same on every
    platform and in every process (docs/kernels.md, "Tiles"), clamped to a
    divisor of the sequence, so short-seq callers (BERT s=128) get seq-sized
    blocks. Under a `window` shorter than the keys the query block stays at
    BLOCK however long the row: a block of `block_q` queries visits the key
    tiles that hold its window + block_q - 1 keys, so what a larger block
    adds is work outside the band (docs/kernels.md, "The banded grid")."""
    long_row = s_q >= LONG_SEQ_Q and band(window, s_k) is None
    return _clamp(2 * BLOCK if long_row else BLOCK, s_q), _clamp(BLOCK, s_k)


def band(window, s_k):
    """The window the kernels are built for: None where there is none or it
    is at least as long as the keys, which is the causal grid as it stands."""
    return None if window is None or window >= s_k else int(window)


def flash_attention(q, k, v, causal=False, scale=1.0,
                    block_q=None, block_k=None, interpret=None, window=None):
    """q, k: (B, S, H, D), v: (B, S, H, Dv) -> (B, S, H, Dv). Forward only; use
    flash_attention_vjp for the Pallas-backward pair (attention.py wires it
    through jax.custom_vjp). interpret=None resolves per call from placement
    (_interpret); pass an explicit bool when the caller already resolved it
    (attention.py bakes it through the custom_vjp static args). block_q /
    block_k default to the rule's (`tiles`); pass explicit values to pin
    them. `window` (with `causal`): query t reads the keys j with
    t - window < j <= t, over the banded grid."""
    out, _ = flash_attention_fwd(q, k, v, causal, scale, block_q, block_k,
                                 interpret, window)
    return out


def flash_attention_fwd(q, k, v, causal=False, scale=1.0,
                        block_q=None, block_k=None, interpret=None,
                        window=None):
    """Returns (out, lse) with lse (B, H, S) float32 — the residual the
    Pallas backward needs."""
    b, s, h, d = q.shape
    s_k = k.shape[1]
    interp = _interpret(q) if interpret is None else interpret
    window = band(window, s_k)
    if window is not None and not causal:
        raise ValueError("a window is a band under the causal diagonal")
    bq, bk = tiles(s, s_k, window)
    out, lse = _flash_fwd_bh(_to_bh(q), _to_bh(k), _to_bh(v), causal, scale,
                             _clamp(block_q or bq, s), _clamp(block_k or bk, s_k),
                             interp, window=window)
    return _from_bh(out, b, h), lse.reshape(b, h, s)


def flash_attention_bwd(q, k, v, out, lse, do, causal=False, scale=1.0,
                        block_q=None, block_k=None, interpret=None,
                        window=None):
    """FlashAttention-2 backward in one pass: dq (B, S, H, D) and dk, dv in
    k's and v's shape and dtype. With no explicit blocks the kernel's
    (block_q, block_k) is the rule's (`tiles`); explicit values pin it."""
    b, s, h, d = q.shape
    s_k = k.shape[1]
    interp = _interpret(q) if interpret is None else interpret
    window = band(window, s_k)
    bq, bk = tiles(s, s_k, window)
    dq, dk, dv = _flash_bwd_bh(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(out),
        lse.reshape(b * h, s), _to_bh(do), causal, scale,
        _clamp(block_q or bq, s), _clamp(block_k or bk, s_k), interp,
        window=window)
    h_kv = k.shape[2]
    return (_from_bh(dq, b, h), _from_bh(dk, b, h_kv), _from_bh(dv, b, h_kv))


# ---------------------------------------------------------------------------
# attention over a set a query (a learned sparse index: ops/sparse_index.py)
# ---------------------------------------------------------------------------
#
# The same pair with two more operands. `sets` (B, tiles_q, S_k, block_q) int8
# says, transposed a query block at a time as the kernels form their tiles,
# which keys each query attends to (1) and which not (0): causality is part
# of the set, so no tile builds an iota. `table` (B * tiles_q * tiles_k,)
# int32, prefetched into SMEM, holds the pairs of the set a tile: a tile
# with none is never multiplied, a tile that is whole runs the body with no
# select. The statistics, L and every gradient are over the set. A query
# whose set is empty gets no defined output. Tiles are `block` x `block`
# (the published chunk sizes, 512).

SET_BLOCK = 512


def _set_tiles(picked, block_q, block_k):
    """`picked` (B, S_q, S_k) int8 of 0 and 1 -> (sets (B, tiles_q, S_k,
    block_q), table (B * tiles_q * tiles_k,) int32): XLA's one pass over the
    set."""
    b, s_q, s_k = picked.shape
    sets = jnp.swapaxes(picked.reshape(b, s_q // block_q, block_q, s_k), 2, 3)
    return sets, tile_counts(picked, block_q, block_k).reshape(-1)


def tile_counts(picked, block_q, block_k):
    """(B, S_q / block_q, S_k / block_k) int32: pairs of the set a tile."""
    b, s_q, s_k = picked.shape
    tiles = picked.reshape(b, s_q // block_q, block_q, s_k // block_k, block_k)
    return jnp.sum(tiles.astype(jnp.int32), axis=(2, 4))


def tiled_counts(sets, block_k):
    """`tile_counts` of sets already in the kernels' layout, (B, tiles_q,
    S_k, block_q)."""
    b, tiles_q, s_k, block_q = sets.shape
    tiles = sets.reshape(b, tiles_q, s_k // block_k, block_k, block_q)
    return jnp.sum(tiles.astype(jnp.int32), axis=(3, 4))


def set_tiles(key_set, block_q, block_k):
    """The (sets, table) the set kernels read: a pair is taken as it is, in
    the tiles it came in (the index kernel writes that layout; `tile_blocks`
    says which), a square (B, S_q, S_k) int8 of 0 and 1 is tiled by XLA at
    `block_q` x `block_k`."""
    if isinstance(key_set, (tuple, list)):
        return tuple(key_set)
    return _set_tiles(key_set, block_q, block_k)


def tile_blocks(tiles):
    """(block_q, block_k) of a (sets, table) pair."""
    sets, table = tiles
    b, tiles_q, s_k, block_q = sets.shape
    return block_q, s_k // (table.shape[0] // (b * tiles_q))


def set_square(key_set):
    """The set as (B, S_q, S_k): query t of block i of a (sets, table) pair
    is row i * block + t; a square is returned as it is."""
    if not isinstance(key_set, (tuple, list)):
        return key_set
    b, tiles, s_k, block = key_set[0].shape
    return jnp.swapaxes(key_set[0], 2, 3).reshape(b, tiles * block, s_k)


def _attn_set_fwd_kernel(tab_ref, q_ref, k_ref, vt_ref, set_ref, ot_ref, l_ref,
                         *, scale, block_k):
    # as _attn_fwd_kernel; set_ref: (seq_k, block_q) int8, the query block's
    # sets with the keys down the sublanes; tab_ref: the pairs a tile
    block_q = q_ref.shape[0]
    fold = _scale_folds(scale)
    q = q_ref[...] * scale if fold else q_ref[...]
    num_k_blocks, d_v = vt_ref.shape[:2]
    base = (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)) * num_k_blocks

    def step(kb, carry, masked):
        m_prev, l_prev, acc = carry
        keys = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        k_tile = k_ref[keys, :]
        vt_tile = vt_ref[kb]
        s_t = jax.lax.dot_general(k_tile, q, _NT,
                                  preferred_element_type=jnp.float32)
        if not fold:
            s_t = s_t * scale
        if masked:
            s_t = jnp.where(set_ref[keys, :].astype(jnp.int32) != 0, s_t, _MASKED)
        m_new = jnp.maximum(m_prev, jnp.max(s_t, axis=0, keepdims=True))
        p_t = jnp.exp(s_t - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p_t, axis=0, keepdims=True)
        acc = acc * correction + jnp.dot(
            vt_tile, p_t.astype(vt_tile.dtype),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    def visit(kb, carry):
        pairs = tab_ref[base + kb]
        return jax.lax.cond(
            pairs == 0, lambda c: c,
            lambda c: jax.lax.cond(pairs == block_q * block_k,
                                   lambda c: step(kb, c, False),
                                   lambda c: step(kb, c, True), c), carry)

    m, l, acc = jax.lax.fori_loop(0, num_k_blocks, visit, (
        jnp.full((1, block_q), _MASKED, jnp.float32),
        jnp.zeros((1, block_q), jnp.float32),
        jnp.zeros((d_v, block_q), jnp.float32)))
    l_safe = jnp.maximum(l, 1e-30)
    ot_ref[...] = (acc / l_safe).astype(ot_ref.dtype)
    l_ref[...] = m + jnp.log(l_safe)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "block_q",
                                             "block_k", "interpret"))
def _flash_set_fwd_bh(q, k, v, sets, table, heads, scale, block_q, block_k,
                      interpret):
    # q (B*H, S, D), k (B*Hkv, S, D), v (B*Hkv, S, Dv), sets and table of
    # `_set_tiles` -> out (B*H, S, Dv), lse (B*H, S). The heads are the
    # innermost grid axis: a query block's sets are fetched once for all of
    # them, a key/value head's k and v once for its group.
    bh, seq_q, d = q.shape
    seq_k, d_v = v.shape[1:]
    batch = bh // heads
    kv_heads = k.shape[0] // batch
    group = heads // kv_heads
    tiles_q, tiles_k = seq_q // block_q, seq_k // block_k
    vmem = (2 * seq_k * (_lanes(d) + d_v) * k.dtype.itemsize
            + 2 * seq_k * block_q + 4 * block_q * block_k * 4)
    out_t, lse = pl.pallas_call(
        functools.partial(_attn_set_fwd_kernel, scale=scale, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, tiles_q, heads),
            in_specs=[
                pl.BlockSpec((None, block_q, d),
                             lambda b, i, h, tab: (b * heads + h, i, 0)),
                pl.BlockSpec((None, seq_k, d),
                             lambda b, i, h, tab: (b * kv_heads + h // group, 0, 0)),
                pl.BlockSpec((None, tiles_k, d_v, block_k),
                             lambda b, i, h, tab: (b * kv_heads + h // group, 0, 0, 0)),
                pl.BlockSpec((None, None, seq_k, block_q),
                             lambda b, i, h, tab: (b, i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, d_v, block_q),
                             lambda b, i, h, tab: (b * heads + h, i, 0, 0)),
                pl.BlockSpec((None, 1, block_q),
                             lambda b, i, h, tab: (b * heads + h, 0, i)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tiles_q, d_v, block_q), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        interpret=interpret,
        name="flash_set_fwd",
        **_tpu_params(interpret, ("parallel", "parallel", "arbitrary"), vmem),
    )(table, q, k, _tiles_transposed(v, block_k), sets)
    return _tiles_restored(out_t), lse.reshape(bh, seq_q)


def _attn_set_bwd_kernel(tab_ref, q_ref, do_ref, l_ref, dd_ref, k_ref, v_ref,
                         kt_ref, set_ref, dqt_ref, dk_ref, dv_ref, dqt_acc, *,
                         scale, block_q, kv_heads, tiles_q):
    # as _attn_bwd_kernel; set_ref: (span / block_q, block_k, block_q) int8,
    # the span's query blocks against this key tile; tab_ref as the forward's
    group, span, d = q_ref.shape
    block_k = k_ref.shape[0]
    j = pl.program_id(2)
    num_q_blocks = span // block_q
    tiles_k = pl.num_programs(2)
    base = ((pl.program_id(0) // kv_heads) * tiles_q
            + pl.program_id(1) * num_q_blocks) * tiles_k + j
    fold = _scale_folds(scale)
    k_tile = k_ref[...] * scale if fold else k_ref[...]
    v_tile = v_ref[...]
    kt_tile = kt_ref[...] * scale if fold else kt_ref[...]

    def for_each_block(fn):
        def head(h, carry):
            def block(i, carry):
                fn(h, i)
                return carry
            return jax.lax.fori_loop(0, num_q_blocks, block, carry)
        jax.lax.fori_loop(0, group, head, 0)

    @pl.when(j == 0)
    def _():
        def zero(h, i):
            dqt_acc[h, i] = jnp.zeros((d, block_q), jnp.float32)
        for_each_block(zero)

    def pair(h, i, carry, masked):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q, do = q_ref[h, rows, :], do_ref[h, rows, :]
        lse, delta = l_ref[h, pl.ds(i, 1), :], dd_ref[h, pl.ds(i, 1), :]
        s_t = jax.lax.dot_general(k_tile, q, _NT,
                                  preferred_element_type=jnp.float32)
        if not fold:
            s_t = s_t * scale
        if masked:
            s_t = jnp.where(set_ref[i].astype(jnp.int32) != 0, s_t, _MASKED)
        p_t = jnp.exp(s_t - lse)
        dv = dv + jnp.dot(p_t.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(v_tile, do, _NT,
                                   preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - delta)).astype(q.dtype)
        dk = dk + jnp.dot(ds_t, q, preferred_element_type=jnp.float32)
        dqt_acc[h, i] += jnp.dot(kt_tile, ds_t,
                                 preferred_element_type=jnp.float32)
        return dk, dv

    def visit(h, i, carry):
        pairs = tab_ref[base + i * tiles_k]
        return jax.lax.cond(
            pairs == 0, lambda c: c,
            lambda c: jax.lax.cond(pairs == block_q * block_k,
                                   lambda c: pair(h, i, c, False),
                                   lambda c: pair(h, i, c, True), c), carry)

    def head(h, carry):
        return jax.lax.fori_loop(
            0, num_q_blocks, lambda i, c: visit(h, i, c), carry)

    dk, dv = jax.lax.fori_loop(
        0, group, head, (jnp.zeros((block_k, d), jnp.float32),
                         jnp.zeros(v_ref.shape, jnp.float32)))
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(j == tiles_k - 1)
    def _():
        def write(h, i):
            dq_t = dqt_acc[h, i]
            dqt_ref[h, i] = (dq_t if fold else dq_t * scale).astype(
                dqt_ref.dtype)
        for_each_block(write)


@functools.partial(jax.jit, static_argnames=(
    "heads", "scale", "block_q", "block_k", "interpret", "q_span"))
def _flash_set_bwd_bh(q, k, v, o, lse, do, sets, table, heads, scale, block_q,
                      block_k, interpret, q_span=None):
    # as _flash_bwd_bh, over the sets of `_set_tiles`
    bh, seq_q, d = q.shape
    rows_kv, seq_k, d_v = v.shape
    group = bh // rows_kv
    kv_heads = heads // group
    span = q_span or _bwd_q_span(group, seq_q, d, q.dtype.itemsize, block_q,
                                 d_v)
    spans, blocks = seq_q // span, span // block_q
    delta = jnp.einsum("rsd,rsd->rs", do, o, precision="highest",
                       preferred_element_type=jnp.float32)
    stats = [x.reshape(bh, spans, blocks, block_q) for x in (lse, delta)]
    part = (k.dtype, v.dtype) if spans == 1 else (jnp.float32,) * 2
    vmem = (_bwd_resident_bytes(group, span, d, q.dtype.itemsize, d_v)
            + block_k * (6 * _lanes(d) + 4 * _lanes(d_v)) * k.dtype.itemsize
            + 2 * span * block_k + 6 * block_q * block_k * 4)

    def wide(width):
        return pl.BlockSpec((group, span, width),
                            lambda r, c, j, tab: (r, c, 0))

    def tile(width):
        return pl.BlockSpec((None, block_k, width),
                            lambda r, c, j, tab: (r, j, 0))

    def part_tile(width):
        return pl.BlockSpec((None, None, block_k, width),
                            lambda r, c, j, tab: (c, r, j, 0))
    stat = pl.BlockSpec((group, None, blocks, block_q),
                        lambda r, c, j, tab: (r, c, 0, 0))
    tile_t = pl.BlockSpec((None, None, d, block_k),
                          lambda r, c, j, tab: (r, j, 0, 0))
    set_tile = pl.BlockSpec((None, blocks, block_k, block_q),
                            lambda r, c, j, tab: (r // kv_heads, c, j, 0))
    dq_t, dk, dv = pl.pallas_call(
        functools.partial(_attn_set_bwd_kernel, scale=scale, block_q=block_q,
                          kv_heads=kv_heads, tiles_q=seq_q // block_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows_kv, spans, seq_k // block_k),
            in_specs=[wide(d), wide(d_v), stat, stat, tile(d), tile(d_v),
                      tile_t, set_tile],
            out_specs=[pl.BlockSpec((group, blocks, d, block_q),
                                    lambda r, c, j, tab: (r, c, 0, 0)),
                       part_tile(d), part_tile(d_v)],
            scratch_shapes=[pltpu.VMEM((group, blocks, d, block_q),
                                       jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, spans * blocks, d, block_q), q.dtype),
            jax.ShapeDtypeStruct((spans, rows_kv, seq_k, d), part[0]),
            jax.ShapeDtypeStruct((spans, rows_kv, seq_k, d_v), part[1]),
        ],
        interpret=interpret,
        name="flash_set_bwd",
        **_tpu_params(interpret, ("parallel", "arbitrary", "arbitrary"), vmem),
    )(table, q, do, *stats, k, v, _tiles_transposed(k, block_k), sets)
    dq = _tiles_restored(dq_t)
    if spans == 1:
        return dq, dk[0], dv[0]
    return dq, dk.sum(0).astype(k.dtype), dv.sum(0).astype(v.dtype)


def flash_attention_set_fwd(q, k, v, picked, scale=1.0, block=SET_BLOCK,
                            interpret=None):
    """Attention of q (B, S, H, D) over k, v (B, S, Hkv, D / Dv), query t of
    row b reading the keys s with `picked[b, t, s]` 1 (int8 of 0 and 1; a
    causal set holds no key after its query), or `picked` the (sets, table)
    pair of `set_tiles`, whose own tiles are then walked whatever `block`
    says. Returns (out, lse (B, H, S) float32, the set's tiles
    for the backward)."""
    b, s, h, d = q.shape
    interp = _interpret(q) if interpret is None else interpret
    tiles = set_tiles(picked, _clamp(block, s), _clamp(block, k.shape[1]))
    bq, bk = tile_blocks(tiles)
    out, lse = _flash_set_fwd_bh(_to_bh(q), _to_bh(k), _to_bh(v), *tiles, h,
                                 scale, bq, bk, interp)
    return _from_bh(out, b, h), lse.reshape(b, h, s), tiles


def flash_attention_set_bwd(q, k, v, out, lse, do, tiles, scale=1.0,
                            interpret=None, q_span=None):
    """The backward over the same sets, in the forward's tiles: dq, dk, dv."""
    b, s, h, d = q.shape
    interp = _interpret(q) if interpret is None else interpret
    dq, dk, dv = _flash_set_bwd_bh(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(out), lse.reshape(b * h, s),
        _to_bh(do), *tiles, h, scale, *tile_blocks(tiles), interp, q_span)
    h_kv = k.shape[2]
    return (_from_bh(dq, b, h), _from_bh(dk, b, h_kv), _from_bh(dv, b, h_kv))
