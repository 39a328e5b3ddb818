"""Row moves (Pallas/TPU) between the tokens and the sorted buffer of a
dropless expert layer.

The buffer (R, H) is laid out for the grouped products (grouped_matmul.py):
tiles of `tm` rows, only the first `num_tiles` in use, each tile's rows a
prefix of it. Which token a row holds, and which rows a token's picks got,
is data. XLA moves such rows with gathers over the whole buffer, whatever is
present; these kernels move the rows that are there, one DMA a row, steered
by scalar-prefetched indices, under a grid or a loop bound that is a
run-time value:

    pack_rows:        (R, H) -> packed rows          tiles in use only
    rows_from_tokens: out[r] = x[t] (* w[t, j]) for the pair (t, j) row r
                      holds, 0 on a tile's padding rows   grid over tiles in use
    tokens_from_rows: out[t] = sum_j w[t, j] * y[pair_row[t, j]]
    pair_dots:        out[t, j] = <y[pair_row[t, j]], g[t]>
                      (a pair with pair_row >= R adds nothing)

Why the packed form. A copy takes whole sublane rows of a tiled array, and
in (R, H) a sublane row of a tile is 128 columns of one row (of two rows in
bfloat16): a row is H / 128 pieces in as many tiles, no slice a copy takes.
So the array a row is read from by index is the row's C 32-bit words laid
over C / 128 sublane rows of 128, its lane chunks: (R * C / 128, 128) uint32,
where row r is the sublane rows r * C / 128 onward, contiguous, and one copy
whatever C / 128 is: a copy need not start or end on an (8, 128) tile (the
chip, PERF.md PR 38: 9 sublane rows from any offset are moved right, and
sooner than 16 from a tile's start). C = H for float32; H / 2 for bfloat16,
word c of a row holding column c in its low half and column c + C in its
high half; C is any multiple of 128 (`words`), and the packed form is the
array's bytes.
`pack_rows` makes it from the (R, H) array in VMEM, for the tiles in use, and
the kernels that read rows by index unpack in VMEM (shifts and bitcasts:
exact), so what they write is an ordinary (., H) array again. Between the
two forms nothing is shuffled: lane chunk q of rows r, r+1, ... is every
(C / 128)-th sublane row of the packed form, one strided access. Sums and
products are float32, as the jnp rules in incubate/moe.py have them, which
stay the path off the TPU and the oracle (tests/test_moe_row_moves.py).

Rows of tiles at or beyond `num_tiles` are never read and never written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import ROW_TILE

TOKEN_BLOCK = 256   # tokens of one grid step of the kernels that write tokens
CHUNK = 32          # rows unpacked at a time: what the vector registers hold
UNROLL = 8          # copies the scalar core issues a trip of its loop
LANE_BODIES = 32    # lane chunks x picks a chunk's text holds as copies of its body
VMEM_LIMIT_BYTES = 32 * 2 ** 20


def words(h, dtype):
    """32-bit words of one packed row of `h` elements, or None where the
    kernels do not take the row: other types than float32 and bfloat16, or a
    row that is not whole lane chunks of 128 words."""
    if dtype == jnp.float32:
        c = h
    elif dtype == jnp.bfloat16 and h % 2 == 0:
        c = h // 2
    else:
        return None
    return c if c % 128 == 0 else None


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT_BYTES)}


def _bits(v):
    return jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)


def _pack2(lo, hi=None):
    """The words of a row's columns: `lo` alone (float32), or columns c of
    bfloat16 `lo` in the low half and c + C of `hi` in the high half."""
    if hi is None:
        return _bits(lo)
    return (_bits(lo) >> 16) | (_bits(hi) & jnp.uint32(0xFFFF0000))


def _unpack(u, dtype):
    """(r, C) uint32 -> the row's halves in float32, [(r, C)] or two of them
    (columns 0..C-1 and C..2C-1)."""
    as_f32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.float32)
    if dtype == jnp.float32:
        return [as_f32(u)]
    return [as_f32(u << 16), as_f32(u & jnp.uint32(0xFFFF0000))]


def _row(ref, r, per_row):
    """Packed row r of a packed ref: `per_row` sublane rows."""
    return ref.at[pl.ds(pl.multiple_of(r * per_row, per_row), per_row), :]


def _cols(start):
    """The 128 columns from `start`, a multiple of 128."""
    if isinstance(start, int):
        return slice(start, start + 128)
    return pl.ds(pl.multiple_of(start, 128), 128)


def _over_lanes(ref, picks, first, per_row, body, carry=None):
    """carry = body(q, views, carry) for every lane chunk q of a packed row:
    views[j] is chunk q of the CHUNK rows of the packed `ref` from row
    first(j), (CHUNK, 128). Rows r, r+1, ... of a chunk lie `per_row` sublane
    rows apart, which is one strided access and no shuffle. The kernel's
    text holds a copy of the body a lane chunk while those are at most
    LANE_BODIES with `picks` views each, and beyond that one body in a loop,
    q a run-time value: the text is what the host traces and lowers, in the
    discovery pass and twice in the step, and it would grow with the picks
    times the row's width."""
    def view(at, q):
        return ref.at[pl.ds(at * per_row + q, CHUNK, stride=per_row), :]
    if picks * per_row <= LANE_BODIES:
        views = [[view(at, q) for q in range(per_row)]
                 for at in map(first, range(picks))]
        for q in range(per_row):
            carry = body(q, [of_pick[q] for of_pick in views], carry)
        return carry
    return jax.lax.fori_loop(
        0, per_row,
        lambda q, c: body(q, [view(first(j), q) for j in range(picks)], c), carry)


def _wait(count, buf, sem, per_row):
    """Wait for `count` row copies into `buf`, all signalled on `sem`. A
    wait takes what its own copy would bring off the semaphore, so a copy of
    2^b rows waits for as many single rows at once: one wait a set bit of
    `count`, not one a row."""
    bit = 1
    while bit * per_row <= buf.shape[0]:
        @pl.when(count & bit != 0)
        def _(bit=bit):
            rows = buf.at[pl.ds(0, bit * per_row), :]
            pltpu.make_async_copy(rows, rows, sem).wait()
        bit *= 2


def _chunks(rows, body):
    """body(s) for every CHUNK rows of `rows`, s their first row: a loop, not
    `rows / CHUNK` copies of the body in the kernel's text (the step lowers
    each kernel twice; unrolled, the copies ran 7% faster and lowered three
    times slower)."""
    def step(i, carry):
        body(pl.multiple_of(i * CHUNK, CHUNK))
        return carry
    jax.lax.fori_loop(0, rows // CHUNK, step, None)


def _each(count, body):
    """body(i) for i in 0..count-1, `count` a run-time value: unrolled by
    UNROLL, the scalar core issuing that many copies a trip."""
    def trip(i, carry):
        for u in range(UNROLL):
            body(i * UNROLL + u)
        return carry
    whole = count // UNROLL
    jax.lax.fori_loop(0, whole, trip, None)

    def rest(i, carry):
        body(i)
        return carry
    jax.lax.fori_loop(whole * UNROLL, count, rest, None)


# ---------------------------------------------------------------------------
# (R, H) -> packed rows

def _pack_kernel(x_ref, o_ref):
    per_row = o_ref.shape[0] // x_ref.shape[0]
    c = per_row * 128

    def chunk(s):
        at = pl.ds(s, CHUNK)

        def lane(q, views, _):
            halves = [x_ref[at, _cols(p * c + q * 128)]
                      for p in range(4 // x_ref.dtype.itemsize)]
            views[0][...] = _pack2(*halves)
        _over_lanes(o_ref, 1, lambda _: s, per_row, lane)
    _chunks(x_ref.shape[0], chunk)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def pack_rows(x, num_tiles=None, tm=ROW_TILE, interpret=False):
    """x (R, H) -> packed (R' * C / 128, 128) uint32, R' = R rounded up to a
    multiple of tm: the first `num_tiles` tiles of tm rows written, all of
    them by default."""
    h = x.shape[1]
    c = words(h, x.dtype)
    assert c and tm % CHUNK == 0, (x.shape, x.dtype, tm)
    if x.shape[0] % tm:
        x = jnp.pad(x, ((0, -x.shape[0] % tm), (0, 0)))
    rows = x.shape[0]
    if num_tiles is None:
        num_tiles = rows // tm
    return pl.pallas_call(
        _pack_kernel,
        grid=(num_tiles,),
        in_specs=[pl.BlockSpec((tm, h), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tm * c // 128, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows * c // 128, 128), jnp.uint32),
        interpret=interpret,
        name="moe_pack_rows",
        **_params(interpret),
    )(x)


# ---------------------------------------------------------------------------
# tokens -> buffer rows

def _from_tokens_kernel(row_pair_ref, tile_rows_ref, *rest, tm, k, dtype, scaled):
    w_ref = rest[0] if scaled else None
    x_hbm, o_ref, buf, sem = rest[-4:]
    i = pl.program_id(0)
    count, base = tile_rows_ref[i], i * tm
    per_row = buf.shape[0] // tm

    def start(r):
        pltpu.make_async_copy(
            _row(x_hbm, row_pair_ref[base + r] // k, per_row),
            _row(buf, r, per_row), sem).start()
    _each(count, start)
    _wait(count, buf, sem, per_row)
    if scaled:
        def scale(r):   # the row times its pair's weight, rounded as it is stored
            row = _row(buf, r, per_row)
            weight = w_ref[row_pair_ref[base + r]]
            row[...] = _pack2(*[(v * weight).astype(dtype) for v in
                                _unpack(row[...], dtype)])
        _each(count, scale)

    c = per_row * 128

    def chunk(s):
        keep = s + jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 128), 0) < count

        def lane(q, views, _):
            for p, v in enumerate(_unpack(jnp.where(keep, views[0][...], 0), dtype)):
                o_ref[pl.ds(s, CHUNK), _cols(p * c + q * 128)] = v.astype(dtype)
        _over_lanes(buf, 1, lambda _: s, per_row, lane)
    _chunks(tm, chunk)


@functools.partial(jax.jit, static_argnames=("k", "h", "dtype", "tm", "interpret"))
def rows_from_tokens(packed_x, row_pair, tile_rows, num_tiles, w=None, *, k, h, dtype,
                     tm=ROW_TILE, interpret=False):
    """packed_x packed tokens (N * C / 128, 128); row_pair (R,) the pair t * k + j each
    buffer row holds; tile_rows (R / tm,) the rows each tile holds, a prefix
    of it; w (N, k) float32 or None -> (R, H) in `dtype`: out[r] = x[t]
    (* w[t, j], in float32), zero on the other rows of the first `num_tiles`
    tiles."""
    rows = row_pair.shape[0]
    dtype = jnp.dtype(dtype)
    c = words(h, dtype)
    assert c and rows % tm == 0 and tm % CHUNK == 0, (h, dtype, rows, tm)
    prefetch = [row_pair, tile_rows]
    if w is not None:
        prefetch.append(w.astype(jnp.float32).reshape(-1))
    return pl.pallas_call(
        functools.partial(_from_tokens_kernel, tm=tm, k=k, dtype=dtype,
                          scaled=w is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(num_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, h), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tm * c // 128, 128), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, h), dtype),
        interpret=interpret,
        name="moe_rows_from_tokens",
        **_params(interpret),
    )(*prefetch, packed_x)


# ---------------------------------------------------------------------------
# buffer rows -> tokens

def _to_tokens_kernel(held_pair_ref, held_row_ref, block_start_ref, y_hbm,
                      pair_row_ref, *rest, tb, k, rows, dtype, weighted, dots):
    """One block of tb tokens: the rows of its pairs that have one, copied to
    buf row j * tb + t for pair j of token t, then summed over j (times w),
    or each multiplied into the token's g and summed over the columns
    (`dots`)."""
    w_ref = rest[0] if weighted else None
    g_ref = rest[0] if dots else None
    o_ref, buf, sem = rest[-3:]
    b = pl.program_id(0)
    per_row = buf.shape[0] // (k * tb)
    first, count = block_start_ref[b], block_start_ref[b + 1] - block_start_ref[b]

    def start(i):
        pair = held_pair_ref[first + i] - b * tb * k
        t = pair // k
        pltpu.make_async_copy(_row(y_hbm, held_row_ref[first + i], per_row),
                              _row(buf, (pair - t * k) * tb + t, per_row),
                              sem).start()
    _each(count, start)
    _wait(count, buf, sem, per_row)

    c = per_row * 128

    def chunk(s):
        at = pl.ds(s, CHUNK)
        # a pair that has no row left its place in buf as it was: masked
        keep = [jnp.broadcast_to(pair_row_ref[at, j:j + 1] < rows, (CHUNK, 128))
                for j in range(k)]
        if weighted:
            w = [jnp.broadcast_to(w_ref[at, j:j + 1], (CHUNK, 128)) for j in range(k)]
        dot = [jnp.zeros((CHUNK, 128), jnp.float32) for _ in range(k)]

        def lane(q, views, dot):
            cols = [_cols(p * c + q * 128) for p in range(4 // dtype.itemsize)]
            total = None
            for j in range(k):
                halves = _unpack(jnp.where(keep[j], views[j][...], 0), dtype)
                if dots:
                    dot[j] = dot[j] + sum(
                        v * g_ref[at, col].astype(jnp.float32)
                        for v, col in zip(halves, cols))
                    continue
                if weighted:
                    halves = [v * w[j] for v in halves]
                total = halves if total is None else [
                    a + v for a, v in zip(total, halves)]
            if not dots:
                for v, col in zip(total, cols):
                    o_ref[at, col] = v.astype(dtype)
            return dot
        dot = _over_lanes(buf, k, lambda j: j * tb + s, per_row, lane,
                          dot if dots else [])
        if dots:
            o_ref[at, :] = jnp.concatenate(
                [jnp.sum(d, axis=1, keepdims=True) for d in dot], axis=1)
    _chunks(tb, chunk)


def token_block(n, k, h, dtype):
    """Tokens of one grid step of the kernels that write tokens, of `n` with
    `k` pairs each and rows of `h` elements: TOKEN_BLOCK, halved while the
    k packed rows a token of the block would take more than half of
    VMEM_LIMIT_BYTES (the operands' blocks, held twice, take the rest), and
    no more than the tokens there are, in whole chunks."""
    tb = TOKEN_BLOCK
    while tb > CHUNK and k * tb * words(h, dtype) * 4 > VMEM_LIMIT_BYTES // 2:
        tb //= 2
    return tb if n >= tb else -(-n // CHUNK) * CHUNK


@functools.partial(jax.jit, static_argnames=("rows", "tb"))
def held_pairs(pair_row, rows, tb):
    """The pairs that have a row, listed for the kernels that write tokens
    `tb` = token_block(...) at a time: (held_pair, held_row, block_start).
    held_pair holds the pairs t * k + j with pair_row[t, j] < rows, in their
    order, then the others; held_row the row of each; block_start[b] where
    the pairs of token block b start in both, block_start[-1] their number.
    One stable sort of N * k keys that take two values and a sum: computed
    once per plan, beside it."""
    n, k = pair_row.shape
    flat = jnp.pad(pair_row, ((0, -n % tb), (0, 0)), constant_values=rows).reshape(-1)
    absent = (flat >= rows).astype(jnp.int32)
    _, held_pair, held_row = jax.lax.sort(
        (absent, jnp.arange(flat.shape[0], dtype=jnp.int32), flat.astype(jnp.int32)),
        num_keys=1, is_stable=True)
    per_block = jnp.sum(1 - absent.reshape(-1, tb * k), axis=1, dtype=jnp.int32)
    block_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(per_block, dtype=jnp.int32)])
    return held_pair, held_row, block_start


def _to_tokens(packed_y, pair_row, held, w, g, h, dtype, interpret):
    n, k = pair_row.shape
    dtype = jnp.dtype(dtype)
    c = words(h, dtype)
    assert c, (h, dtype)
    rows = packed_y.shape[0] * 128 // c
    tb = token_block(n, k, h, dtype)
    pad = -n % tb
    assert held[2].shape == ((n + pad) // tb + 1,), (held[2].shape, n, tb)
    if pad:   # whole blocks: the tokens added hold no pair
        pair_row = jnp.pad(pair_row, ((0, pad), (0, 0)), constant_values=rows)
        w = None if w is None else jnp.pad(w, ((0, pad), (0, 0)))
        g = None if g is None else jnp.pad(g, ((0, pad), (0, 0)))
    block = lambda width: pl.BlockSpec((tb, width), lambda b, *_: (b, 0))  # noqa: E731
    operands, in_specs = [packed_y, pair_row], [pl.BlockSpec(memory_space=pl.ANY), block(k)]
    if w is not None:
        operands.append(w.astype(jnp.float32))
        in_specs.append(block(k))
    if g is not None:
        operands.append(g)
        in_specs.append(block(h))
    out = pl.pallas_call(
        functools.partial(_to_tokens_kernel, tb=tb, k=k, rows=rows, dtype=dtype,
                          weighted=w is not None, dots=g is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=((n + pad) // tb,),
            in_specs=in_specs,
            out_specs=block(k if g is not None else h),
            scratch_shapes=[pltpu.VMEM((k * tb * c // 128, 128), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n + pad, k if g is not None else h),
            jnp.float32 if g is not None else dtype),
        interpret=interpret,
        name="moe_pair_dots" if g is not None else "moe_tokens_from_rows",
        **_params(interpret),
    )(*held, *operands)
    return out[:n] if pad else out


@functools.partial(jax.jit, static_argnames=("h", "dtype", "interpret"))
def tokens_from_rows(packed_y, pair_row, held, w=None, *, h, dtype, interpret=False):
    """packed_y packed rows (R * C / 128, 128); pair_row (N, k) the row of each of a
    token's pairs, R or more where there is none; held = held_pairs(pair_row,
    R, token_block(N, k, h, dtype)); w (N, k) float32 or None -> (N, H) in
    `dtype`: out[t] = sum_j w[t, j] * y[pair_row[t, j]], a float32 sum."""
    return _to_tokens(packed_y, pair_row, held, w, None, h, dtype, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_dots(packed_y, pair_row, held, g, interpret=False):
    """out[t, j] = sum_h y[pair_row[t, j], h] * g[t, h] in float32, 0 where
    the pair has no row: (N, k) float32. packed_y packed from g's dtype."""
    return _to_tokens(packed_y, pair_row, held, None, g, g.shape[1], g.dtype, interpret)
