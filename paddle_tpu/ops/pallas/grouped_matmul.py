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
COL_TILE = 512      # tn: output columns of a block
# x tile, weight block and output block, double-buffered, and tgmm's float32
# accumulator pass the 16 MiB the compiler allows a kernel by default
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _col_tile(n):
    tn = min(COL_TILE, n)
    while n % tn:
        tn //= 2
    return tn


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
    tn = _col_tile(n)
    if transpose_w:
        w_spec = pl.BlockSpec((None, tn, k), lambda j, i, tg: (tg[i], j, 0))
    else:
        w_spec = pl.BlockSpec((None, k, tn), lambda j, i, tg: (tg[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # columns outside, tiles inside: successive tiles of one group
            # keep their weight block
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
    tn = _col_tile(n)
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
