"""The short convolution's pass (Pallas/TPU): one stream of a linear-attention
mixer, y = l2norm_head(silu(taps(x))), the norm optional, as one op with its
own backward.

    taps(x)_t = sum_j w[:, j] * x_{t - (K-1) + j}      zero before a row's start
    a = silu(taps(x))                                   float32, rounded to x's dtype
    y = a / sqrt(sum_head(a^2) + epsilon)               float32, rounded once more

x is (batch, seq, channels), w (channels, K). XLA stages this as fusions that
hand float32 arrays of the stream's size to each other and keeps them for the
backward (docs/kernels.md, "The short convolution's pass"). Here a grid step
takes a block of sequence rows with all their channels, reads it once in x's
dtype, holds float32 in registers and the compiler's own scratch only, and
writes once. Inside, one loop over the lane chunks (a head of the norm, 128
channels without it), each worked through all the block's rows at once: a
chunk costs the vector unit about 75 cycles whatever its size (the chip,
PERF.md PR 48), so the rows are not cut further. The K - 1 rows before a
block come as a second view of x, the HALO rows that end where the block
starts, zero at a row's first block; the shifts are sublane rolls of the
block stacked under the last 8 of those rows. A head is whole lane chunks, so
its sum of squares is one lane reduction.

The backward keeps x and w and nothing else. It walks the same blocks and
forms the taps again. dx_t takes the taps' cotangents of rows t .. t + K - 1,
so a block needs those of the K - 1 rows after it: x and dy of the HALO rows
after the block come as views too, the taps are formed for them as well, and
they are zero at a row's last block. dw (K, channels) is summed in float32 in
an output block that stays resident over the whole grid.

The roundings are those of the jnp rule (nn/functional/conv.py, which stays
the path off the TPU and the oracle: tests/test_short_conv_fused.py holds the
forward to it bit for bit): float32 taps and SiLU, rounded to x's dtype,
taken back to float32 for the norm, float32 statistics, rounded once more;
the backward rounds the cotangent at the same boundary.

Fixed tiles from the shapes, no search; a loop over the lane chunks, not
copies of the text (row_moves._over_lanes says why).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HALO = 16            # rows of the views beside a block: one bfloat16 sublane tile
BLOCK_ELEMENTS = 256 * 128   # rows x lanes the body works at once: 256 rows a head of 128
VMEM_LIMIT_BYTES = 48 * 2 ** 20
_F32 = jnp.float32


def takes(shape, dtype, taps, norm_head_dim, platform, on_mesh=False):
    """Whether a stream x of `shape` (batch, seq, channels) with `taps` taps
    runs the kernels: on a TPU, on one device, float32 or bfloat16, channels
    in whole lane chunks of 128, the taps' reach inside the 8 rows taken of
    the tile before a block, and with the norm a head that is whole lane
    chunks. Everything else runs the jnp rule."""
    channels = shape[-1]
    width = 128 if norm_head_dim is None else norm_head_dim
    return (platform == "tpu" and not on_mesh and len(shape) == 3
            and dtype in (jnp.float32, jnp.bfloat16)
            and 2 <= taps <= 9 and width % 128 == 0 and channels % width == 0)


def _rows(seq, width):
    """Rows of a grid step for a sequence of `seq` rows worked through `width`
    lanes at a time: whole HALO tiles, no more than the sequence has."""
    rows = max(HALO, BLOCK_ELEMENTS // width // HALO * HALO)
    return min(rows, -(-seq // HALO) * HALO)


def _params(interpret, semantics):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES)}


def _lanes(q, width):
    return pl.ds(pl.multiple_of(q * width, 128), width)


def _last8(ref, at, cols):
    """The last 8 of the HALO rows of `ref` from row `at`, in float32."""
    return ref[pl.ds(at, HALO), cols].astype(_F32)[HALO - 8:]


def _shifted(prev, cur, k):
    """[x_{t - (k-1) + j} for j in 0..k-1] for the rows t of `cur` (r, lanes),
    `prev` the 8 rows before them: rolls of the two stacked."""
    z = jnp.concatenate([prev, cur], axis=0)
    return [pltpu.roll(z, k - 1 - j, 0)[8:] for j in range(k - 1)] + [cur]


def _preact(prev, cur, taps):
    """(the taps' sum, the shifted rows it was formed from), float32; summed
    as nn.functional.conv._causal_taps sums it."""
    shifted = _shifted(prev, cur, len(taps))
    return sum(s * w for s, w in zip(shifted, taps)), shifted


def _inv_norm(y, epsilon):
    """1 / sqrt(sum(y^2) + epsilon) over the lanes, (rows, 1)."""
    return jax.lax.rsqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True) + epsilon)


# ---------------------------------------------------------------------------
# forward

def _fwd_kernel(x_ref, before_ref, w_ref, o_ref, *, width, norm, epsilon):
    k = w_ref.shape[0]
    dtype = x_ref.dtype
    first = pl.program_id(1) == 0

    def lane(q, _):
        cols = _lanes(q, width)
        taps = [w_ref[j:j + 1, cols] for j in range(k)]
        before = jnp.where(first, 0.0, _last8(before_ref, 0, cols))
        pre, _ = _preact(before, x_ref[:, cols].astype(_F32), taps)
        y = jax.nn.silu(pre).astype(dtype)
        if norm:
            f = y.astype(_F32)
            y = (f * _inv_norm(f, epsilon)).astype(dtype)
        o_ref[:, cols] = y
    jax.lax.fori_loop(0, x_ref.shape[1] // width, lane, None)


def _padded(x, rows):
    """x with its sequence padded to whole blocks of `rows`: zeros, whose
    outputs are dropped and whose cotangents are zero."""
    extra = -x.shape[1] % rows
    return jnp.pad(x, ((0, 0), (0, extra), (0, 0))) if extra else x


def _block_specs(rows, channels, blocks):
    """The specs of a block of a (batch, seq, channels) array, of the HALO
    rows before it and of those after it (the first and last of a row clamped:
    the kernels put zeros there)."""
    per = rows // HALO
    return (pl.BlockSpec((None, rows, channels), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, HALO, channels),
                         lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((None, HALO, channels),
                         lambda b, i: (b, jnp.minimum((i + 1) * per, blocks * per - 1), 0)))


@functools.partial(jax.jit, static_argnames=("norm_head_dim", "epsilon", "interpret"))
def stream_forward(x, w, norm_head_dim=None, epsilon=1e-6, interpret=False):
    """x (batch, seq, channels), w (channels, K) -> y like x."""
    batch, seq, channels = x.shape
    width = norm_head_dim or 128
    rows = _rows(seq, width)
    xp = _padded(x, rows)
    blocks = xp.shape[1] // rows
    block, before, _ = _block_specs(rows, channels, blocks)
    taps = w.astype(_F32).T
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, width=width, epsilon=epsilon,
                          norm=norm_head_dim is not None),
        grid=(batch, blocks),
        in_specs=[block, before, pl.BlockSpec(taps.shape, lambda b, i: (0, 0))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        interpret=interpret,
        name="short_conv_fwd",
        **_params(interpret, ("parallel", "parallel")),
    )(xp, xp, taps)
    return out[:, :seq]


# ---------------------------------------------------------------------------
# backward

def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                dx_ref, dw_ref, *, width, norm, epsilon):
    rows, channels = x_ref.shape
    k = w_ref.shape[0]
    dtype = x_ref.dtype
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1

    @pl.when((pl.program_id(0) == 0) & first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def lane(q, _):
        cols = _lanes(q, width)
        taps = [w_ref[j:j + 1, cols] for j in range(k)]

        def cotangent(prev, cur, dy):
            """(the taps' cotangent for the rows of `cur`, the shifted rows)."""
            pre, shifted = _preact(prev, cur, taps)
            gate = jax.nn.sigmoid(pre)
            g = dy.astype(_F32)
            if norm:   # through the norm, rounded where autodiff rounds it
                y = (pre * gate).astype(dtype).astype(_F32)
                r = _inv_norm(y, epsilon)
                g = g * r - y * (r * r * r * jnp.sum(g * y, axis=-1, keepdims=True))
                g = g.astype(dtype).astype(_F32)
            return g * (gate * (1.0 + pre * (1.0 - gate))), shifted

        after, _ = cotangent(_last8(x_ref, rows - HALO, cols),
                             after_ref[:, cols].astype(_F32), dy_after_ref[:, cols])
        d_pre, shifted = cotangent(jnp.where(first, 0.0, _last8(before_ref, 0, cols)),
                                   x_ref[:, cols].astype(_F32), dy_ref[:, cols])
        # dx_t = sum_j w_j * d_pre_{t + (k-1) - j}
        z = jnp.concatenate([d_pre, jnp.where(last, 0.0, after[:8])], axis=0)
        ahead = [pltpu.roll(z, rows + 8 - (k - 1 - j), 0)[:rows]
                 for j in range(k - 1)] + [d_pre]
        dx_ref[:, cols] = sum(a * w for a, w in zip(ahead, taps)).astype(dtype)
        for j, s in enumerate(shifted):
            dw_ref[j:j + 1, cols] += jnp.sum(d_pre * s, axis=0, keepdims=True)
    jax.lax.fori_loop(0, channels // width, lane, None)


@functools.partial(jax.jit, static_argnames=("norm_head_dim", "epsilon", "interpret"))
def stream_backward(x, w, dy, norm_head_dim=None, epsilon=1e-6, interpret=False):
    """(dx like x, dw like w) from the stream's input and its output's
    cotangent."""
    batch, seq, channels = x.shape
    width = norm_head_dim or 128
    rows = _rows(seq, width)
    xp, dyp = _padded(x, rows), _padded(dy.astype(x.dtype), rows)
    blocks = xp.shape[1] // rows
    block, before, after = _block_specs(rows, channels, blocks)
    taps = w.astype(_F32).T
    whole = pl.BlockSpec(taps.shape, lambda b, i: (0, 0))
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, width=width, epsilon=epsilon,
                          norm=norm_head_dim is not None),
        grid=(batch, blocks),
        in_specs=[block, before, after, block, after, whole],
        out_specs=[block, whole],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, x.dtype),
                   jax.ShapeDtypeStruct(taps.shape, _F32)],
        interpret=interpret,
        name="short_conv_bwd",
        **_params(interpret, ("arbitrary", "arbitrary")),
    )(xp, xp, xp, dyp, dyp, taps)
    return dx[:, :seq], dw.T.astype(w.dtype)


# ---------------------------------------------------------------------------
# the op

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def short_conv_silu(x, w, norm_head_dim, epsilon, interpret):
    """The stream, differentiable: the forward kernel, and a backward that
    keeps x and w alone."""
    return stream_forward(x, w, norm_head_dim, epsilon, interpret)


def _vjp_fwd(x, w, norm_head_dim, epsilon, interpret):
    return stream_forward(x, w, norm_head_dim, epsilon, interpret), (x, w)


def _vjp_bwd(norm_head_dim, epsilon, interpret, res, dy):
    return stream_backward(*res, dy, norm_head_dim, epsilon, interpret)


short_conv_silu.defvjp(_vjp_fwd, _vjp_bwd)
