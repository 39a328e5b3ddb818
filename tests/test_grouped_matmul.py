"""The grouped products of ops/pallas/grouped_matmul.py on their own, at the
widths the benchmark's expert cells run them (interpreted on the CPU): each
kernel and the custom_vjp's two gradients against the plain jnp forms, and
the rule that chooses the column tile."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import grouped_matmul as gm

# (k, n) of the expert cells' up projections and one down projection:
# DeepSeek-V2-Lite both ways, Keye, LFM2, Kimi Linear
WIDTHS = [(2048, 1408), (1408, 2048), (2048, 768), (2048, 1536), (1024, 2304)]
TM = gm.ROW_TILE
# five tiles hold rows, two are the worst case's spare; group 1 is empty and
# owns one tile of zero rows
TILE_GROUP, NUM_TILES, GROUPS, EMPTY_TILE = (0, 0, 1, 2, 2, 2, 2), 5, 3, 2


@functools.lru_cache(maxsize=None)
def _case(k, n):
    rng = np.random.default_rng(k * 7 + n)
    rows = len(TILE_GROUP) * TM
    held = np.repeat(np.arange(len(TILE_GROUP)) != EMPTY_TILE, TM)[:, None]
    x = jnp.asarray(rng.standard_normal((rows, k)) * held, jnp.bfloat16)
    dy = jnp.asarray(rng.standard_normal((rows, n)) * held, jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((GROUPS, k, n)) * k ** -0.5, jnp.bfloat16)
    return x, dy, w, jnp.asarray(TILE_GROUP, jnp.int32), jnp.asarray(NUM_TILES, jnp.int32)


def _tiles(a):
    return a.reshape(len(TILE_GROUP), TM, a.shape[-1])


def _used(a):
    """The rows the kernels write: those of the first NUM_TILES tiles."""
    return np.asarray(a[:NUM_TILES * TM], np.float32)


def _plain_gmm(x, w, tile_group):
    return jnp.einsum("tmk,tkn->tmn", _tiles(x), w[tile_group],
                      preferred_element_type=jnp.float32).reshape(x.shape[0], -1)


def _plain_tgmm(x, dy, tile_group):
    per_tile = jnp.einsum("tmk,tmn->tkn", _tiles(x)[:NUM_TILES], _tiles(dy)[:NUM_TILES],
                          preferred_element_type=jnp.float32)
    return jax.ops.segment_sum(per_tile, tile_group[:NUM_TILES], num_segments=GROUPS)


def _close(got, want):
    # bf16 results of float32 sums: one rounding apart at most
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=2 ** -7, atol=2 ** -7)


@pytest.mark.parametrize("form", ["gmm", "gmm-transposed", "tgmm", "vjp"])
@pytest.mark.parametrize("k,n", WIDTHS, ids=[f"{k}x{n}" for k, n in WIDTHS])
def test_the_products_are_the_plain_forms_at_the_cells_widths(k, n, form):
    x, dy, w, tile_group, num_tiles = _case(k, n)
    if form == "gmm":
        out = gm.gmm(x, w, tile_group, num_tiles, interpret=True)
        assert out.shape == (x.shape[0], n) and out.dtype == x.dtype
        _close(_used(out), _used(_plain_gmm(x, w, tile_group)))
    elif form == "gmm-transposed":
        out = gm.gmm(dy, w, tile_group, num_tiles, transpose_w=True, interpret=True)
        assert out.shape == x.shape
        _close(_used(out), _used(_plain_gmm(dy, jnp.swapaxes(w, 1, 2), tile_group)))
    elif form == "tgmm":
        dw = gm.tgmm(x, dy, tile_group, num_tiles, groups=GROUPS, interpret=True)
        assert dw.shape == w.shape and dw.dtype == x.dtype
        assert not np.asarray(dw[1], np.float32).any()      # the empty group's block is written
        _close(dw, _plain_tgmm(x, dy, tile_group))
    else:
        valid = (jnp.arange(x.shape[0]) < NUM_TILES * TM)[:, None]

        def loss(product):
            def f(x_, w_):
                out = product(x_, w_).astype(jnp.float32)
                return jnp.sum(jnp.where(valid, out * dy.astype(jnp.float32), 0.0))
            return f
        dx, dw = jax.grad(loss(lambda x_, w_: gm.grouped_matmul(
            x_, w_, tile_group, num_tiles, TM, True)), argnums=(0, 1))(x, w)
        # the plain form differentiated in float32: jax's own transposes of
        # a bf16 product round more than once
        dx_plain, dw_plain = jax.grad(loss(lambda x_, w_: _plain_gmm(
            x_, w_, tile_group)), argnums=(0, 1))(
                x.astype(jnp.float32), w.astype(jnp.float32))
        assert dx.dtype == x.dtype and dw.dtype == w.dtype
        _close(_used(dx), _used(dx_plain))
        _close(dw, dw_plain)


def test_the_column_tile_is_the_width_wherever_the_blocks_fit():
    for reckon in (gm.gmm_vmem_bytes, gm.tgmm_vmem_bytes):
        for k, n in WIDTHS + [(n, k) for k, n in WIDTHS]:
            for itemsize in (2, 4):
                assert gm._col_tile(reckon, TM, k, n, itemsize) == n, (reckon, k, n)
                assert reckon(TM, k, n, itemsize) <= gm.VMEM_BUDGET_BYTES
        # a dense model's feed-forward width does not fit as one block
        for k, n in [(4096, 14336), (14336, 4096), (8192, 28672), (2048, 1408 * 64)]:
            tn = gm._col_tile(reckon, TM, k, n, 2)
            assert tn < n and n % tn == 0 and tn % 128 == 0, (reckon, k, n, tn)
            assert reckon(TM, k, tn, 2) <= gm.VMEM_BUDGET_BYTES < gm.VMEM_LIMIT_BYTES
            # the widest such divisor: the next one up does not fit
            wider = min(t for t in range(tn + 128, n + 1, 128) if n % t == 0)
            assert reckon(TM, k, wider, 2) > gm.VMEM_BUDGET_BYTES, (reckon, k, n, tn)

    def fits_under(width):
        return lambda tm, k, tn, itemsize: 0 if tn < width else gm.VMEM_LIMIT_BYTES
    # 1408 = 11 x 128 has no divisor between 128 and itself; 1536 has 768
    assert gm._col_tile(fits_under(1408), TM, 2048, 1408, 2) == 128
    assert gm._col_tile(fits_under(1536), TM, 2048, 1536, 2) == 768
    # a width that is no multiple of 128 is one block whatever it takes (a
    # block's last dimension is a multiple of 128 or the whole)
    assert gm._col_tile(fits_under(0), TM, 2048, 1000, 2) == 1000
    assert gm._col_tile(fits_under(1000), TM, 2048, 96, 2) == 96
