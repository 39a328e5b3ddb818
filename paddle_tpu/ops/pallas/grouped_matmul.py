"""Grouped matrix products (Pallas/TPU) for a dropless expert layer.

The rows of `x` (R, K) are laid out in tiles of `tm` rows, and every tile
belongs to one group (one expert): `tile_group[i]` is the group of tile i,
and only the first `num_tiles` tiles hold rows. Both are data, R is static
(sized for the worst routing), and the kernels' grids run over `num_tiles`,
so the work done follows the rows present and not the buffer:

    gmm:   out[tile i] = x[tile i] @ w[tile_group[i]]          (R, N)
    tgmm:  dw[g] = sum over the tiles i of group g of
                   x[tile i]^T @ dy[tile i]                      (G, K, N)

Rows of tiles at or beyond `num_tiles` are never written: they hold whatever
the buffer held, and the caller masks them. tgmm needs every group to own at
least one tile (an empty group owns one tile of zero rows), so that every
block of `dw` is written; the layout in incubate/moe.py guarantees it.

Technique after the megablox kernels that ship with jax (Gale et al. 2022,
arXiv:2211.15841): scalar-prefetched group metadata steers the block specs'
index maps. Because a tile never straddles two groups here, there is no
masking and no revisiting of output tiles. The whole contraction axis is one
block (K is a model width of a few thousand), so gmm needs no accumulator.

The column tile `tn` follows the shapes of the call (`_col_tile`). The row
operand's block (tm, K) changes at every grid step, so with the grid
(N / tn, num_tiles) all of it crosses HBM N / tn times, while a group's
weight block is fetched once whatever tn is (a group's tiles are
consecutive). So tn is the whole width N, the grid (1, num_tiles) and the
row operand read once, whenever a step's blocks fit `VMEM_BUDGET_BYTES`
(`gmm_vmem_bytes`, `tgmm_vmem_bytes`: reckoned from tm, K, N and the item
size); where they do not, the largest divisor of N that is a multiple of
128 and fits. At 51,200 rows of bf16 a product of 2048 x 1408 under the
old rule (halve from 512 until the tile divides N: 128, as 1408 = 11 x 128)
read its row operand eleven times, 2.31 GB and 2.8 ms at 819 GB/s for 1.5
ms of products, and took 3.4 ms; one of 1408 x 2048 (tn 512, four passes,
0.58 GB) 1.7; with the width as one block both take 1.55 (docs/kernels.md,
"The expert layer's kernels"). The contraction stays one block, so tn
changes no sum's order.

`grouped_matmul` is the differentiable entry: its backward is a gmm against
the transposed weights (read transposed by the block spec, never copied) and
a tgmm. `gmm_flops` and friends for a roofline live with the benchmark
(benchmarks/kernel_costs.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 256      # tm: rows of a tile, and the alignment of a group's rows
# x tile, weight block and output block, double-buffered, and tgmm's float32
# accumulator pass the 16 MiB the compiler allows a kernel by default (the
# chip has 128)
VMEM_LIMIT_BYTES = 64 * 2 ** 20
# what a step's blocks may take of it: the rest is the compiler's own
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES * 3 // 4


def gmm_vmem_bytes(tm, k, tn, itemsize):
    """What one grid step of gmm holds: the row tile, the weight block and
    the output tile, each twice (the next is fetched while this one is
    computed), and the float32 product."""
    return 2 * itemsize * (tm * k + k * tn + tm * tn) + 4 * tm * tn


def tgmm_vmem_bytes(tm, k, tn, itemsize):
    """One grid step of tgmm: the two row tiles and the output block, each
    twice, and the float32 accumulator (the product is added to it in
    pieces: the compiler takes 62 MiB so reckoned and refuses 70)."""
    return 2 * itemsize * (tm * k + tm * tn + k * tn) + 4 * k * tn


def _col_tile(vmem_bytes, tm, k, n, itemsize):
    """The widest block of output columns whose step fits the budget by
    `vmem_bytes` (one of the two above): N itself, else the largest divisor
    of N that is a multiple of 128 (a block's last dimension is that or the
    whole)."""
    widths = [n] + [tn for tn in range((n - 1) // 128 * 128, 0, -128)
                    if n % tn == 0]
    for tn in widths:
        if vmem_bytes(tm, k, tn, itemsize) <= VMEM_BUDGET_BYTES:
            return tn
    return widths[-1]


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}


def _gmm_kernel(tile_group_ref, x_ref, w_ref, o_ref, *, transpose_w):
    del tile_group_ref   # read by the index maps
    contract = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], contract,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("transpose_w", "tm", "interpret"))
def gmm(x, w, tile_group, num_tiles, transpose_w=False, tm=ROW_TILE,
        interpret=False):
    """x (R, K); w (G, K, N), or (G, N, K) with `transpose_w`; tile_group
    (R / tm,) int32; num_tiles () int32 -> (R, N) in x's dtype."""
    rows, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    assert rows % tm == 0 and tile_group.shape == (rows // tm,), (x.shape, tm)
    tn = _col_tile(gmm_vmem_bytes, tm, k, n,
                   max(x.dtype.itemsize, w.dtype.itemsize))
    if transpose_w:
        w_spec = pl.BlockSpec((None, tn, k), lambda j, i, tg: (tg[i], j, 0))
    else:
        w_spec = pl.BlockSpec((None, k, tn), lambda j, i, tg: (tg[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # columns outside, tiles inside (one block of columns where the
            # width fits): successive tiles of one group keep their weight
            # block
            grid=(n // tn, num_tiles),
            in_specs=[pl.BlockSpec((tm, k), lambda j, i, tg: (i, 0)), w_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, tg: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        interpret=interpret,
        name="moe_gmm",
        **_params(interpret),
    )(tile_group, x, w)


def _tgmm_kernel(tile_group_ref, x_ref, dy_ref, o_ref, acc_ref):
    i, last = pl.program_id(1), pl.num_programs(1) - 1
    group = tile_group_ref[i]
    opens = jnp.logical_or(i == 0,
                           tile_group_ref[jnp.maximum(i - 1, 0)] != group)
    closes = jnp.logical_or(i == last,
                            tile_group_ref[jnp.minimum(i + 1, last)] != group)

    @pl.when(opens)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(closes)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups", "tm", "interpret"))
def tgmm(x, dy, tile_group, num_tiles, groups, tm=ROW_TILE, interpret=False):
    """x (R, K); dy (R, N) -> (groups, K, N) in x's dtype: each group's
    x^T @ dy over its own tiles. Every group owns at least one tile."""
    rows, k = x.shape
    n = dy.shape[1]
    assert rows % tm == 0 and dy.shape[0] == rows, (x.shape, dy.shape, tm)
    tn = _col_tile(tgmm_vmem_bytes, tm, k, n, x.dtype.itemsize)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, num_tiles),
            in_specs=[pl.BlockSpec((tm, k), lambda j, i, tg: (i, 0)),
                      pl.BlockSpec((tm, tn), lambda j, i, tg: (i, j))],
            out_specs=pl.BlockSpec((None, k, tn), lambda j, i, tg: (tg[i], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), x.dtype),
        interpret=interpret,
        name="moe_tgmm",
        **_params(interpret),
    )(tile_group, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(x, w, tile_group, num_tiles, tm=ROW_TILE, interpret=False):
    """out[tile i] = x[tile i] @ w[tile_group[i]] for the first `num_tiles`
    tiles of `tm` rows; differentiable in `x` and `w`."""
    return gmm(x, w, tile_group, num_tiles, tm=tm, interpret=interpret)


def _grouped_fwd(x, w, tile_group, num_tiles, tm, interpret):
    out = gmm(x, w, tile_group, num_tiles, tm=tm, interpret=interpret)
    return out, (x, w, tile_group, num_tiles)


def _grouped_bwd(tm, interpret, res, g):
    x, w, tile_group, num_tiles = res
    dx = gmm(g, w, tile_group, num_tiles, transpose_w=True, tm=tm,
             interpret=interpret)
    dw = tgmm(x, g, tile_group, num_tiles, groups=w.shape[0], tm=tm,
              interpret=interpret).astype(w.dtype)
    return dx, dw, None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
