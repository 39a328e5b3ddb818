"""The flash-attention kernels, compiled by the TPU's own compiler at the
widths the models use — for a chip that is described, not attached.

Interpret mode (every other flash test on CPU) cannot see what Mosaic
refuses: a block that does not fit VMEM, a slice off the tiling, compiler
params that no longer exist. These compiles can, and cost no chip time: the
forward kernel and the one-pass backward kernel of
ops/pallas/flash_attention.py, at the tiles its rule gives (`tiles`: what the
chip runs), are lowered with interpret=False for one v5e chip and must
contain the kernel (`tpu_custom_call`).

The topology is described inside a module-scoped fixture and nowhere else:
only one process at a time may load libtpu, so nothing here may touch it at
import or collection time, and the compiles run in the test's own process.
"""
import os

import pytest

# (query rows b x h, key/value rows, positions, query/key size, value size, dtype)
SHAPES = [
    pytest.param(32, 32, 1024, 128, 128, "bfloat16", id="gpt1p3b-bh32-s1024-d128"),
    pytest.param(64, 64, 1024, 64, 64, "bfloat16", id="gpt-medium-bh64-s1024-d64"),
    pytest.param(32, 32, 2048, 128, 128, "bfloat16", id="bh32-s2048-d128"),
    pytest.param(64, 64, 2048, 64, 64, "bfloat16", id="bh64-s2048-d64"),
    pytest.param(64, 16, 4096, 64, 64, "bfloat16", id="lfm2-group4-s4096-d64"),
    pytest.param(64, 64, 4096, 192, 128, "bfloat16", id="kimi-linear-s4096-d192-dv128"),
    pytest.param(16, 16, 8192, 192, 128, "bfloat16", id="deepseek-v2-lite-s8192-d192-dv128"),
    pytest.param(32, 32, 2048, 192, 128, "bfloat16", id="latent-s2048-d192-dv128"),
    pytest.param(32, 4, 8192, 128, 128, "bfloat16", id="group8-s8192-d128"),
    pytest.param(48, 8, 8192, 128, 128, "bfloat16", id="laguna-full-group6-s8192-d128"),
    pytest.param(8, 2, 16384, 64, 64, "bfloat16", id="group4-s16384-d64-in-spans"),
    pytest.param(2, 2, 32768, 128, 128, "bfloat16", id="s32768-d128-in-spans"),
    pytest.param(8, 8, 768, 64, 64, "bfloat16", id="s768-a-length-512-does-not-divide"),
    pytest.param(4, 2, 256, 64, 64, "bfloat16", id="s256-shorter-than-a-tile"),
    pytest.param(8, 8, 2048, 128, 128, "float32", id="float32-s2048-d128"),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_at_the_rules_tiles(one_chip, backward, bh, rows_kv, s, d, d_v, dtype,
                                window=None):
    from paddle_tpu.ops.pallas import flash_attention as fa
    q = _sds((bh, s, d), dtype, one_chip)
    k = _sds((rows_kv, s, d), dtype, one_chip)
    v = _sds((rows_kv, s, d_v), dtype, one_chip)
    block_q, block_k = fa.tiles(s, s, window)
    kw = dict(causal=True, scale=d ** -0.5, block_q=block_q, block_k=block_k,
              interpret=False, window=window)
    if not backward:
        return fa._flash_fwd_bh.lower(q, k, v, **kw).compile()
    o = _sds((bh, s, d_v), dtype, one_chip)
    lse = _sds((bh, s), "float32", one_chip)
    return fa._flash_bwd_bh.lower(q, k, v, o, lse, o, **kw).compile()


def _assert_kernel(compiled, n_kernels):
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= n_kernels, text[:2000]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 16 * 2 ** 30


def test_compiler_params_carry_dimension_semantics():
    """`pltpu.CompilerParams` really reaches the kernels (the old class name
    was swallowed by an except and the kernels lowered without it): the grid
    axes' semantics as given, and a VMEM limit only where the blocks pass
    the default one."""
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.ops.pallas import flash_attention as fa
    params = fa._tpu_params(False, ("parallel", "parallel"))["compiler_params"]
    assert isinstance(params, pltpu.CompilerParams)
    assert tuple(params.dimension_semantics) == ("parallel", "parallel")
    assert params.vmem_limit_bytes is None
    params = fa._tpu_params(False, ("parallel", "arbitrary", "arbitrary"),
                            40 * 2 ** 20)["compiler_params"]
    assert tuple(params.dimension_semantics)[1:] == ("arbitrary", "arbitrary")
    assert 40 * 2 ** 20 < params.vmem_limit_bytes <= fa.VMEM_LIMIT_CAP
    assert fa._tpu_params(True, ("parallel", "parallel")) == {}


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("bh,rows_kv,s,d,d_v,dtype", SHAPES)
def test_the_rules_tiles_compile(one_chip, bh, rows_kv, s, d, d_v, dtype, backward):
    """What the chip runs at these shapes: a tile Mosaic refuses (VMEM, a
    slice off the tiling) fails here, without a chip, and the rule changes."""
    _assert_kernel(_compile_at_the_rules_tiles(
        one_chip, backward, bh, rows_kv, s, d, d_v, dtype), 1)


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("bh, rows_kv, s, window", [
    pytest.param(64, 8, 8192, 512, id="laguna-window-group8-s8192-w512"),
    pytest.param(12, 2, 4096, 100, id="group6-s4096-a-window-shorter-than-a-tile"),
    pytest.param(8, 8, 2048, 1536, id="s2048-a-window-of-three-tiles"),
])
def test_the_banded_grid_compiles(one_chip, bh, rows_kv, s, window, backward):
    """The flash pair with a window, at the banded rule's tiles: Mosaic takes
    the loops that start at the band's edge and the second mask, and the
    kernels carry their own names into the compiled program."""
    compiled = _compile_at_the_rules_tiles(
        one_chip, backward, bh, rows_kv, s, 128, 128, "bfloat16", window)
    _assert_kernel(compiled, 1)
    assert ("flash_window_bwd" if backward else "flash_window_fwd") in compiled.as_text()


def test_public_vjp_pair_compiles_at_gpt1p3b_widths(one_chip):
    """What the train step holds: the custom_vjp pair behind
    scaled_dot_product_attention, (b 2, s 1024, h 16, d 128) bf16 causal,
    differentiated, with interpret resolved to False as on a TPU."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import _flash_attention_diff

    def loss(q, k, v):
        out = _flash_attention_diff(q, k, v, True, 128 ** -0.5, False)
        return jnp.sum(out.astype(jnp.float32))

    x = _sds((2, 1024, 16, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    _assert_kernel(compiled, 2)
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_flash_under_a_dp2_mp2_mesh_compiles(topo):
    """Mosaic kernels cannot be partitioned automatically: under a mesh
    ops.attention maps them over it by hand (batch over 'data', heads over
    'model'). The program for four described chips holds the kernels and
    needs no collective for attention itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.mesh import trace_mesh
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas import flash_attention as fa

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    shape = (4, 1024, 16, 128)

    def prim(q, k, v):
        # as on a TPU: Mosaic, not the interpreter this CPU process picks
        with pytest.MonkeyPatch.context() as mp, trace_mesh(mesh):
            mp.setattr(fa, "_interpret", lambda x=None: False)
            return attention._flash_prim(q, True, 128 ** -0.5)(q, k, v)

    def loss(q, k, v):
        return jnp.sum(prim(q, k, v).astype(jnp.float32))

    x = _sds(shape, jnp.bfloat16,
             NamedSharding(mesh, P("data", None, "model", None)))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert " all-gather(" not in text and " all-reduce(" not in text


# ---------------------------------------------------------------------------
# LFM2-24B-A2B widths: grouped heads at 4096 positions, and the expert
# layer's grouped products over one chip's 8 experts

def test_grouped_head_vjp_pair_compiles_at_lfm2_widths(one_chip):
    """(b 2, s 4096, 32 query heads over 8 key/value heads, d 64) bf16
    causal, differentiated: k and v are read through the index maps, so the
    program holds no repeated copy of them, and dk, dv come back with 8
    heads, added over each group inside the one backward kernel: no float32
    array of the operands' size, no 128-lane statistic."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import _flash_attention_diff

    def loss(q, k, v):
        out = _flash_attention_diff(q, k, v, True, 64 ** -0.5, False)
        return jnp.sum(out.astype(jnp.float32))

    q = _sds((2, 4096, 32, 64), jnp.bfloat16, one_chip)
    kv = _sds((2, 4096, 8, 64), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    _assert_kernel(compiled, 2)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    # what the program materialises: the entry computation's instructions
    # (a fusion's body may widen an element on its way through)
    entry = text[text.index("\nENTRY "):]
    assert "f32[64,1,4096]" in entry                    # lse, lane-dense
    for partial_or_wide in ("f32[64,4096,64]", "f32[16,4096,64]",
                            "f32[1,16,4096,64]", "f32[64,4096,128]",
                            "f32[64,8,64,512]"):
        assert partial_or_wide not in entry, partial_or_wide
    assert [o.shape for o in compiled.out_info] == [
        (2, 4096, 32, 64), (2, 4096, 8, 64), (2, 4096, 8, 64)]


@pytest.mark.parametrize("bh,rows_kv,s,d", [
    pytest.param(8, 2, 16384, 64, id="group4-s16384-d64"),
    pytest.param(4, 1, 32768, 64, id="group4-s32768-d64"),
    pytest.param(2, 2, 32768, 128, id="group1-s32768-d128"),
])
def test_backward_in_spans_compiles_at_long_sequences(one_chip, bh, rows_kv, s, d):
    """Where a group's q, dO and dQ over the whole sequence pass what VMEM
    may hold resident, `_bwd_q_span` cuts the query range; the same kernel,
    one span a grid step, and the forward beside it."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa._bwd_q_span(bh // rows_kv, s, d, 2, 512) < s or bh == rows_kv
    q = _sds((bh, s, d), jnp.bfloat16, one_chip)
    kv = _sds((rows_kv, s, d), jnp.bfloat16, one_chip)
    lse = _sds((bh, s), jnp.float32, one_chip)
    _assert_kernel(fa._flash_fwd_bh.lower(
        q, kv, kv, causal=True, scale=d ** -0.5, block_q=512, block_k=512,
        interpret=False).compile(), 1)
    _assert_kernel(fa._flash_bwd_bh.lower(
        q, kv, kv, q, lse, q, causal=True, scale=d ** -0.5, block_q=512,
        block_k=512, interpret=False).compile(), 1)


def _compile_grouped_products(one_chip, k, n, rows, groups):
    """The three kernels a projection of the expert layer runs in a train
    step (product, product against the transposed weights, weight gradient)
    over a buffer of `rows` bf16 rows, with the grid a run-time value."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    x = _sds((rows, k), jnp.bfloat16, one_chip)
    dy = _sds((rows, n), jnp.bfloat16, one_chip)
    w = _sds((groups, k, n), jnp.bfloat16, one_chip)
    tiles = _sds((rows // gm.ROW_TILE,), jnp.int32, one_chip)
    used = _sds((), jnp.int32, one_chip)
    _assert_kernel(gm.gmm.lower(x, w, tiles, used).compile(), 1)
    _assert_kernel(gm.gmm.lower(dy, w, tiles, used, transpose_w=True).compile(), 1)
    _assert_kernel(gm.tgmm.lower(x, dy, tiles, used, groups=groups).compile(), 1)


@pytest.mark.parametrize("k,n", [(2048, 1536), (1536, 2048)],
                         ids=["up-2048x1536", "down-1536x2048"])
def test_grouped_products_compile_at_lfm2_widths(one_chip, k, n):
    """Over the worst-case buffer of 2 x 4096 tokens x 4 experts."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    _compile_grouped_products(one_chip, k, n, 2 * 4096 * 4 + 8 * gm.ROW_TILE, 8)


ROW_MOVES = ["pack", "gather", "weighted-rows", "add-back", "combine", "pair-dots"]


def _compile_row_move(one_chip, move, n, k, h, dt):
    """One of the expert layer's row moves compiled for `n` tokens of `h`
    columns, `k` picks a token, 8 experts held, tiles of 256; the grid or the
    loop bound a run-time value. Returns the bytes of the kernel's scratch."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    from paddle_tpu.ops.pallas import row_moves as rm
    rows = n * k + 8 * gm.ROW_TILE
    per_row, tb = rm.words(h, dt) // 128, rm.token_block(n, k, h, dt)
    i32 = jnp.int32
    tokens3, rows3 = (_sds((r * per_row, 128), jnp.uint32, one_chip) for r in (n, rows))
    row_pair, tile_rows = _sds((rows,), i32, one_chip), _sds((rows // gm.ROW_TILE,), i32, one_chip)
    used, pair_row = _sds((), i32, one_chip), _sds((n, k), i32, one_chip)
    w = _sds((n, k), jnp.float32, one_chip)
    held = (_sds((n * k,), i32, one_chip), _sds((n * k,), i32, one_chip),
            _sds((n // tb + 1,), i32, one_chip))
    lowered = {
        "pack": lambda: rm.pack_rows.lower(_sds((rows, h), dt, one_chip), used),
        "gather": lambda: rm.rows_from_tokens.lower(
            tokens3, row_pair, tile_rows, used, k=k, h=h, dtype=dt),
        "weighted-rows": lambda: rm.rows_from_tokens.lower(
            tokens3, row_pair, tile_rows, used, w, k=k, h=h, dtype=dt),
        "add-back": lambda: rm.tokens_from_rows.lower(rows3, pair_row, held, h=h, dtype=dt),
        "combine": lambda: rm.tokens_from_rows.lower(rows3, pair_row, held, w, h=h, dtype=dt),
        "pair-dots": lambda: rm.pair_dots.lower(rows3, pair_row, held, _sds((n, h), dt, one_chip)),
    }[move]()
    compiled = lowered.compile()
    _assert_kernel(compiled, 1)
    want = {"pack": (rows * per_row, 128), "gather": (rows, h), "weighted-rows": (rows, h),
            "add-back": (n, h), "combine": (n, h), "pair-dots": (n, k)}[move]
    assert compiled.out_info.shape == want
    scratch_rows = {"pack": 0, "gather": gm.ROW_TILE, "weighted-rows": gm.ROW_TILE}.get(
        move, k * tb)
    return scratch_rows * per_row * 128 * 4


@pytest.mark.parametrize("move", ROW_MOVES)
def test_row_moves_compile_at_lfm2_widths(one_chip, move):
    """The expert layer's row moves at the cell's shapes: a buffer of 34,816
    rows of 2048 bf16, 8192 tokens, 4 picks, tiles of 256; the grid or the
    loop bound a run-time value. A refusal by Mosaic (a slice off the tiling,
    scalar memory, VMEM) fails here and not on the chip."""
    import jax.numpy as jnp
    _compile_row_move(one_chip, move, 2 * 4096, 4, 2048, jnp.bfloat16)


# ---------------------------------------------------------------------------
# Kimi-Linear-48B-A3B widths: latent attention's two head sizes through the
# flash pair, the expert layer at hidden 2304, and Kimi Delta Attention's
# chunked op

def test_two_head_sizes_vjp_pair_compiles_at_kimi_linear_widths(one_chip):
    """(b 2, s 4096, 32 heads, query/key 192, value 128) bf16 causal,
    differentiated: one forward and one backward kernel, dq and dk at 192,
    dv at 128, and no v, dO or dv padded to 192 anywhere in the program."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import _flash_attention_diff

    def loss(q, k, v):
        out = _flash_attention_diff(q, k, v, True, 192 ** -0.5, False)
        return jnp.sum(out.astype(jnp.float32))

    qk = _sds((2, 4096, 32, 192), jnp.bfloat16, one_chip)
    v = _sds((2, 4096, 32, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(qk, qk, v).compile()
    _assert_kernel(compiled, 2)
    assert [o.shape for o in compiled.out_info] == [
        (2, 4096, 32, 192), (2, 4096, 32, 192), (2, 4096, 32, 128)]


@pytest.mark.parametrize("k,n", [(2304, 1024), (1024, 2304)],
                         ids=["up-2304x1024", "down-1024x2304"])
def test_grouped_products_compile_at_kimi_linear_widths(one_chip, k, n):
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    _compile_grouped_products(one_chip, k, n, 2 * 4096 * 8 + 8 * gm.ROW_TILE, 8)


@pytest.mark.parametrize("k,n,pairs,groups", [
    (2048, 1408, 8192 * 6, 8), (1408, 2048, 8192 * 6, 8), (2048, 768, 8192 * 8, 16)],
    ids=["deepseek-up-2048x1408", "deepseek-down-1408x2048", "keye-up-2048x768"])
def test_grouped_products_compile_at_deepseek_v2_lite_widths(one_chip, k, n, pairs, groups):
    """The DeepSeek-V2-Lite cell's expert width, 1408 = 11 x 128, and the Keye
    cell's 768, over a rank's rows by stand-ins: the whole output width is one
    block there (the row operand crosses HBM once), which interpret mode
    cannot hold against VMEM; Mosaic can."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    for reckon in (gm.gmm_vmem_bytes, gm.tgmm_vmem_bytes):
        assert gm._col_tile(reckon, gm.ROW_TILE, k, n, 2) == n
    _compile_grouped_products(one_chip, k, n, pairs + groups * gm.ROW_TILE, groups)


@pytest.mark.parametrize("move", ROW_MOVES)
def test_row_moves_compile_at_kimi_linear_widths(one_chip, move):
    """The row moves at the Kimi Linear cell's shapes: a buffer of 67,584 rows
    of 2304 bf16 (9 lane chunks a row: a copy of one neither starts nor ends
    on a tile), 8192 tokens, 8 picks. The scratch of the kernels that write
    tokens, 8 x 256 packed rows, stays under the limit the kernels ask for."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import row_moves as rm
    assert rm.words(2304, jnp.bfloat16) == 1152
    scratch = _compile_row_move(one_chip, move, 2 * 4096, 8, 2304, jnp.bfloat16)
    assert scratch <= rm.VMEM_LIMIT_BYTES // 2      # 9 MiB where tokens are written


@pytest.mark.parametrize("move", ["combine", "pair-dots"])
def test_the_token_block_follows_the_picks_and_the_rows_bytes(one_chip, move):
    """8 picks of float32 rows of 4096: 256 tokens a grid step would be 32 MiB
    of packed rows, the whole limit; `token_block` halves the block and the
    kernels that write tokens compile."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import row_moves as rm
    assert rm.token_block(1024, 8, 4096, jnp.float32) == 128
    scratch = _compile_row_move(one_chip, move, 1024, 8, 4096, jnp.float32)
    assert scratch == rm.VMEM_LIMIT_BYTES // 2


def test_kda_op_compiles_with_its_backward(one_chip):
    """The whole op at (2, 4096, 32, 128) bf16, differentiated, for a v5e:
    batched products and scans over the chunks inside the loop over the head
    groups, whose temporaries are a group's; the running sums as a product."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kda

    def loss(q, k, v, g, beta):
        out = kda.kimi_delta_attention(q, k, v, g, beta, 128 ** -0.5, 64)
        return jnp.sum(out.astype(jnp.float32))

    x = _sds((2, 4096, 32, 128), jnp.bfloat16, one_chip)
    g = _sds((2, 4096, 32, 128), jnp.float32, one_chip)
    beta = _sds((2, 4096, 32), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(x, x, x, g, beta).compile()
    # 1.52 GB through 8 heads at a time; 2.46 GiB with all 32 at once
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
    # the running sums of g and their pull-back are products with a triangle
    # of ones: no `reduce-window`, the TPU's cumsum, in either pass
    assert "reduce-window" not in compiled.as_text()


@pytest.mark.parametrize("norm", [pytest.param(128, id="heads-of-128"),
                                  pytest.param(None, id="plain")])
def test_short_conv_pass_compiles_at_kimi_linear_widths(one_chip, norm):
    """A stream of the Kimi Linear mixer, (2, 4096, 4096) bf16 with 4 taps,
    with the per-head norm (q, k) and without (v): the forward kernel, and the
    op differentiated, whose backward holds no float32 array of a stream's
    size (134 MB): it keeps the bf16 input and forms the taps again."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import short_conv as sc
    x = _sds((2, 4096, 4096), jnp.bfloat16, one_chip)
    w = _sds((4096, 4), jnp.bfloat16, one_chip)
    assert sc.takes(x.shape, x.dtype, 4, norm, "tpu")
    forward = sc.stream_forward.lower(x, w, norm, 1e-6, False).compile()
    _assert_kernel(forward, 1)
    assert forward.memory_analysis().temp_size_in_bytes < 2 ** 20

    def pulled(v, taps, dy):
        y, pull = jax.vjp(lambda a, b: sc.short_conv_silu(a, b, norm, 1e-6, False), v, taps)
        return (y, *pull(dy))
    compiled = jax.jit(pulled).lower(x, w, x).compile()
    _assert_kernel(compiled, 2)
    assert compiled.memory_analysis().temp_size_in_bytes < 134 * 10 ** 6
    assert "f32[2,4096,4096]" not in compiled.as_text()


# ---------------------------------------------------------------------------
# Keye-VL-2.0 widths: attention over a set a query at 8192 positions

def test_the_pair_over_a_set_compiles_at_keye_widths(one_chip):
    """(b 1, s 8192, 32 query heads over 4 key/value heads, d 128) bf16 over
    an int8 set a query, differentiated through the custom_vjp that
    scaled_dot_product_attention(key_set=) holds: two kernels, the set's
    tiles and the prefetched table made by XLA, no float32 array of (heads,
    seq, seq) or (seq, seq) anywhere in the program."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import _flash_set_diff

    def loss(q, k, v, picked):
        out, _ = _flash_set_diff(q, k, v, picked, 128 ** -0.5, False)
        return jnp.sum(out.astype(jnp.float32))

    q = _sds((1, 8192, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 8192, 4, 128), jnp.bfloat16, one_chip)
    picked = _sds((1, 8192, 8192), jnp.int8, one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, picked).compile()
    _assert_kernel(compiled, 2)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "f32[1,8192,8192]" not in text and "f32[32,8192,8192]" not in text
    # the scores' bytes would be 8.6 GB: the program's temporaries are the
    # set's tiles and the backward's float32 partials
    assert compiled.memory_analysis().temp_size_in_bytes < 1 * 2 ** 30


def test_the_index_hands_its_sets_to_the_pair_and_the_loss_at_keye_widths(one_chip):
    """One layer's path over the sets at (1, 8192), differentiated: the sets
    kernel writes the sets in the layout the flash pair and the loss kernel
    read, and they go from one to the others as they are: four kernels, and
    no (8192, 8192) square of the set (int8 or bool) anywhere in the
    program, so no transpose of it either; XLA's share is the table, one
    reduction over the sets."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import sparse_index
    from paddle_tpu.ops.attention import _flash_set_diff

    def loss(q, k, v, qi, ki, w):
        tiles, stats = sparse_index.index_key_set(qi, ki, w, 2048, mode="kernel")
        out, lse = _flash_set_diff(q, k, v, tiles, 128 ** -0.5, False)
        index = sparse_index.index_loss(qi, ki, w, tiles, q, k, lse, 128 ** -0.5,
                                        sparse_index.INDEX_CHUNK, "kernel")
        return jnp.sum(out.astype(jnp.float32)) + index, stats

    q = _sds((1, 8192, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 8192, 4, 128), jnp.bfloat16, one_chip)
    qi = _sds((1, 8192, 16, 64), jnp.bfloat16, one_chip)
    ki = _sds((1, 8192, 64), jnp.bfloat16, one_chip)
    w = _sds((1, 8192, 16), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=range(6), has_aux=True)).lower(
        q, kv, kv, qi, ki, w).compile()
    _assert_kernel(compiled, 4)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    for square in ("s8[1,8192,8192]", "pred[1,8192,8192]", "f32[1,8192,8192]"):
        assert square not in text
    assert "s8[1,16,8192,512]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 * 2 ** 30
