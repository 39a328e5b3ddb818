"""Flash attention (Pallas/TPU) — forward AND backward kernels.

Reference analog: operators/fused/fused_attention_op.cu + fmha_ref.h (cuDNN
FMHA fwd/bwd). TPU-native: online-softmax tiled attention in VMEM — O(S)
memory instead of the O(S^2) probability matrix; the MXU does the q@k^T and
p@v matmuls per tile. Causal masking skips fully-masked k-tiles via the grid.

Backward follows the FlashAttention-2 recompute scheme: the forward saves
only the per-row logsumexp L; the backward re-forms each P tile from
(q, k, L) in VMEM and accumulates
    dV_j += P_ij^T dO_i
    dS_ij = P_ij * (dO_i V_j^T - D_i),   D = rowsum(dO * O)
    dK_j += dS_ij^T (q_i * scale)
    dQ_i += dS_ij (k_j * scale)
in two kernels (dkv over k-tiles, dq over q-tiles) so no tile ever needs
atomics. Head dims of 64 are supported (VMEM pads the lane dim; the
s^2-materializing XLA fallback costs far more than the padding).

Layout: inputs (B, S, H, D) paddle convention; kernels work on (B*H, S, D).

Grouped-query attention: k and v may hold H / group heads. Row b*H + h of q
then reads row (b*H + h) // group of k and v, which the block specs' index
maps say, so no repeated copy of k or v is ever written. The dkv pass still
runs per query head and writes float32 partial dk, dv of (B*H, S, D), which
one XLA reduction adds over each group: 2 x B*H*S*D*4 bytes written and read
once more, for a kernel that stays free of cross-instance accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 512-blocks measured 2.7x faster than 128-blocks on v5e (0.66 vs 1.78
# ms/iter fwd+bwd at b4/s1024/h16/d64): bigger MXU matmuls, fewer inner-loop
# trips. Public entry points clamp to the sequence length, so short-seq
# callers (BERT s=128) degrade gracefully to seq-sized blocks. These are the
# f32 deterministic fallbacks; on TPU the autotuner (ops/autotune.py)
# searches the candidate grids below and caches the winner per signature.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512

# Fwd candidates: (block_q, block_k).
_FWD_CANDIDATES = (
    (512, 512), (256, 512), (512, 256), (256, 256), (1024, 512),
)

# Bwd candidates: (block_q_dkv, block_k_dkv, block_q_dq, block_k_dq) — the
# dkv pass tiles k (parallel) and loops q (reduction); the dq pass tiles q
# and loops k. The two passes have different working sets, so their blocks
# tune independently (ISSUE 5 tentpole).
_BWD_CANDIDATES = (
    (512, 512, 512, 512),
    (256, 512, 512, 256),
    (512, 256, 256, 512),
    (256, 256, 256, 256),
    (128, 512, 512, 128),
)


def _bwd_default_blocks(dtype):
    """bf16-aware deterministic fallback for the backward blocks. The f32
    P/dS intermediates of shape (block_q, block_k) dominate backward VMEM
    and do NOT shrink with bf16 inputs, so for bf16 we halve the
    reduction-loop tile of each pass (q for dkv, k for dq) while keeping
    the parallel-axis tile at 512 for MXU depth. f32 keeps the measured
    512/512 blocks."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return (256, 512, 512, 256)
    return (512, 512, 512, 512)


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


def _tpu_params(interpret, n_grid):
    """Mosaic compiler params marking every grid axis parallel — each grid
    instance writes its own output tile with no cross-instance dependency,
    so the (bh, tiles) axes can be scheduled freely. Skipped under the
    interpreter (no Mosaic)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_grid)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, scale, causal,
                     block_k, seq_k):
    # q_ref: (block_q, d); k_ref/v_ref: (seq_k, d); o_ref: (block_q, d);
    # l_ref: (block_q, 128) logsumexp rows broadcast across the lane dim —
    # Mosaic requires the last two block dims to be (8k, 128), so per-row
    # scalars ride in a 128-wide lane (the official TPU flash kernels use
    # the same MIN_BLOCK_SIZE padding)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q_idx = pl.program_id(1)
    q = q_ref[:].astype(jnp.float32) * scale

    m0 = jnp.full((block_q,), -1e30, dtype=jnp.float32)
    l0 = jnp.zeros((block_q,), dtype=jnp.float32)
    acc0 = jnp.zeros((block_q, d), dtype=jnp.float32)

    num_k_blocks = seq_k // block_k

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k_tile = k_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        v_tile = v_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_tile.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=1)
        acc = acc * correction[:, None] + jnp.dot(
            p, v_tile, preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    if causal:
        # skip k-blocks strictly above the diagonal for this q-block
        last_kb = jnp.minimum(
            ((q_idx + 1) * block_q + block_k - 1) // block_k, num_k_blocks)
        m, l, acc = jax.lax.fori_loop(0, last_kb, body, (m0, l0, acc0))
    else:
        m, l, acc = jax.lax.fori_loop(0, num_k_blocks, body, (m0, l0, acc0))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse = m + jnp.log(l_safe)
    l_ref[:] = jnp.broadcast_to(lse[:, None], (block_q, 128))


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def _flash_fwd_bh(q, k, v, causal, scale, block_q, block_k, interpret):
    # q: (BH, S, D), k,v: (BH / group, S, D) -> out (BH, S, D), lse (BH, S)
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    group = bh // k.shape[0]
    grid = (bh, seq_q // block_q)
    out, lse = pl.pallas_call(
        functools.partial(_attn_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=seq_k),
        grid=grid,
        interpret=interpret,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, seq_k, d), lambda b, i: (b // group, 0, 0)),
            pl.BlockSpec((None, seq_k, d), lambda b, i: (b // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 128), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, 128), jnp.float32),
        ],
        **_tpu_params(interpret, 2),
    )(q, k, v)
    return out, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _attn_bwd_dkv_kernel(q_ref, do_ref, l_ref, dd_ref, k_ref, v_ref,
                         dk_ref, dv_ref, *, scale, causal, block_q, seq_q):
    # k_ref/v_ref: (block_k, d) this k-tile; q_ref/do_ref: (seq_q, d);
    # l_ref/dd_ref: (seq_q, 128) lane-broadcast rows; dk/dv: (block_k, d)
    block_k = k_ref.shape[0]
    d = k_ref.shape[1]
    k_idx = pl.program_id(1)
    k_tile = k_ref[:].astype(jnp.float32)
    v_tile = v_ref[:].astype(jnp.float32)

    dk0 = jnp.zeros((block_k, d), dtype=jnp.float32)
    dv0 = jnp.zeros((block_k, d), dtype=jnp.float32)
    num_q_blocks = seq_q // block_q

    def body(qb, carry):
        dk, dv = carry
        q_tile = (q_ref[pl.dslice(qb * block_q, block_q), :]
                  .astype(jnp.float32) * scale)
        do_tile = do_ref[pl.dslice(qb * block_q, block_q), :].astype(
            jnp.float32)
        l_col = l_ref[pl.dslice(qb * block_q, block_q), :][:, :1]
        d_col = dd_ref[pl.dslice(qb * block_q, block_q), :][:, :1]
        s = jnp.dot(q_tile, k_tile.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - l_col)  # (block_q, block_k)
        dv = dv + jnp.dot(p.T, do_tile, preferred_element_type=jnp.float32)
        dp = jnp.dot(do_tile, v_tile.T, preferred_element_type=jnp.float32)
        ds = p * (dp - d_col)
        dk = dk + jnp.dot(ds.T, q_tile, preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # only q-blocks at/below the diagonal see this k-tile
        start_qb = (k_idx * block_k) // block_q
        dk, dv = jax.lax.fori_loop(start_qb, num_q_blocks, body, (dk0, dv0))
    else:
        dk, dv = jax.lax.fori_loop(0, num_q_blocks, body, (dk0, dv0))

    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _attn_bwd_dq_kernel(q_ref, do_ref, l_ref, dd_ref, k_ref, v_ref, dq_ref,
                        *, scale, causal, block_k, seq_k):
    # q_ref/do_ref/dq_ref: (block_q, d); k_ref/v_ref: (seq_k, d);
    # l_ref/dd_ref: (block_q, 128) lane-broadcast rows
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q_idx = pl.program_id(1)
    q_tile = q_ref[:].astype(jnp.float32) * scale
    do_tile = do_ref[:].astype(jnp.float32)
    l_col = l_ref[:][:, :1]
    d_col = dd_ref[:][:, :1]

    dq0 = jnp.zeros((block_q, d), dtype=jnp.float32)
    num_k_blocks = seq_k // block_k

    def body(kb, dq):
        k_tile = k_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        v_tile = v_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q_tile, k_tile.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - l_col)
        dp = jnp.dot(do_tile, v_tile.T, preferred_element_type=jnp.float32)
        ds = p * (dp - d_col)
        return dq + jnp.dot(ds, k_tile, preferred_element_type=jnp.float32)

    if causal:
        last_kb = jnp.minimum(
            ((q_idx + 1) * block_q + block_k - 1) // block_k, num_k_blocks)
        dq = jax.lax.fori_loop(0, last_kb, body, dq0)
    else:
        dq = jax.lax.fori_loop(0, num_k_blocks, body, dq0)

    # dS was formed against q*scale, so the q cotangent carries the scale
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q_dkv", "block_k_dkv", "block_q_dq",
    "block_k_dq", "interpret"))
def _flash_bwd_bh(q, k, v, o, lse, do, causal, scale, block_q_dkv,
                  block_k_dkv, block_q_dq, block_k_dq, interpret):
    # all (BH, S, D) except k, v (BH / group, S, D) and lse (BH, S); returns
    # dq, dk, dv. The dkv and dq passes tile different sequence axes, so each
    # takes its own (block_q, block_k) pair.
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    group = bh // k.shape[0]
    # per query head; float32 where a group's heads are still to be added
    dkv_dtype = (k.dtype, v.dtype) if group == 1 else (jnp.float32,) * 2
    # D = rowsum(dO * O): one fused elementwise+reduce pass, reads dO/O once.
    # lse/delta ride in (bh, seq, 128) lane-broadcast form (Mosaic block
    # constraint — see _attn_fwd_kernel note).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse3 = jnp.broadcast_to(lse[:, :, None], (bh, seq_q, 128))
    delta3 = jnp.broadcast_to(delta[:, :, None], (bh, seq_q, 128))

    dkv = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q_dkv, seq_q=seq_q),
        grid=(bh, seq_k // block_k_dkv),
        interpret=interpret,
        in_specs=[
            pl.BlockSpec((None, seq_q, d), lambda b, j: (b, 0, 0)),    # q
            pl.BlockSpec((None, seq_q, d), lambda b, j: (b, 0, 0)),    # do
            pl.BlockSpec((None, seq_q, 128), lambda b, j: (b, 0, 0)),  # lse
            pl.BlockSpec((None, seq_q, 128), lambda b, j: (b, 0, 0)),  # delta
            pl.BlockSpec((None, block_k_dkv, d),
                         lambda b, j: (b // group, j, 0)),              # k
            pl.BlockSpec((None, block_k_dkv, d),
                         lambda b, j: (b // group, j, 0)),              # v
        ],
        out_specs=[
            pl.BlockSpec((None, block_k_dkv, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k_dkv, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, d), dkv_dtype[0]),
            jax.ShapeDtypeStruct((bh, seq_k, d), dkv_dtype[1]),
        ],
        **_tpu_params(interpret, 2),
    )(q, do, lse3, delta3, k, v)
    dk, dv = dkv
    if group > 1:
        dk = dk.reshape(bh // group, group, seq_k, d).sum(1).astype(k.dtype)
        dv = dv.reshape(bh // group, group, seq_k, d).sum(1).astype(v.dtype)

    dq = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k_dq, seq_k=seq_k),
        grid=(bh, seq_q // block_q_dq),
        interpret=interpret,
        in_specs=[
            pl.BlockSpec((None, block_q_dq, d), lambda b, i: (b, i, 0)),  # q
            pl.BlockSpec((None, block_q_dq, d), lambda b, i: (b, i, 0)),  # do
            pl.BlockSpec((None, block_q_dq, 128),
                         lambda b, i: (b, i, 0)),                       # lse
            pl.BlockSpec((None, block_q_dq, 128),
                         lambda b, i: (b, i, 0)),                       # dlt
            pl.BlockSpec((None, seq_k, d),
                         lambda b, i: (b // group, 0, 0)),              # k
            pl.BlockSpec((None, seq_k, d),
                         lambda b, i: (b // group, 0, 0)),              # v
        ],
        out_specs=pl.BlockSpec((None, block_q_dq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        **_tpu_params(interpret, 2),
    )(q, do, lse3, delta3, k, v)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def supports(q_shape, k_shape):
    b, s_q, h, d = q_shape
    s_k = k_shape[1]
    return (s_q % 128 == 0 and s_k % 128 == 0
            and d % 64 == 0 and s_q == s_k and h % k_shape[2] == 0)


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


def _synth_bh(shapes, dtypes):
    """Concrete probe operands for a tuning run (fixed seed: the timings are
    value-independent, the arrays just have to exist on device)."""
    import numpy as np
    rng = np.random.default_rng(0)
    out = []
    for shape, dtype in zip(shapes, dtypes):
        if jnp.issubdtype(jnp.dtype(dtype), jnp.inexact):
            out.append(jnp.asarray(
                rng.standard_normal(shape, dtype=np.float32)).astype(dtype))
        else:
            out.append(jnp.zeros(shape, dtype))
    return out


def _group_tag(group):
    """A signature's suffix for grouped-query shapes; none for plain
    multi-head attention, whose cached configurations stay valid."""
    return "" if group == 1 else "|g%d" % group


def _tuned_fwd_blocks(bh, s_q, s_k, d, dtype, causal, interp, group=1):
    """(block_q, block_k) for the forward kernel: deterministic defaults
    under interpret/CPU, autotuned (and cached) on TPU."""
    fallback = (_clamp(DEFAULT_BLOCK_Q, s_q), _clamp(DEFAULT_BLOCK_K, s_k))
    if interp:
        return fallback
    from ..autotune import get_tuner, shape_bucket, short_dtype, \
        source_version
    cands = list(dict.fromkeys(
        (_clamp(bq, s_q), _clamp(bk, s_k)) for bq, bk in _FWD_CANDIDATES))
    if len(cands) == 1:
        return cands[0]
    sig = "fwd|bh%d|s%dx%d|d%d|%s|c%d" % (
        shape_bucket((bh,))[0], s_q, s_k, d, short_dtype(dtype), int(causal)
    ) + _group_tag(group)

    def build(cand):
        return functools.partial(
            _flash_fwd_bh, causal=causal, scale=1.0,
            block_q=cand[0], block_k=cand[1], interpret=False)

    def make_args():
        return _synth_bh([(bh, s_q, d), (bh // group, s_k, d),
                          (bh // group, s_k, d)], [dtype] * 3)

    return get_tuner().get(
        "flash_attention", sig, candidates=cands, build=build,
        make_args=make_args, fallback=fallback,
        version=source_version(__name__))


def _tuned_bwd_blocks(bh, s_q, s_k, d, dtype, causal, interp, group=1):
    """(block_q_dkv, block_k_dkv, block_q_dq, block_k_dq) for the backward
    pair: bf16-aware deterministic defaults under interpret/CPU, autotuned
    (and cached) on TPU."""
    def clamp4(c):
        return (_clamp(c[0], s_q), _clamp(c[1], s_k),
                _clamp(c[2], s_q), _clamp(c[3], s_k))
    fallback = clamp4(_bwd_default_blocks(dtype))
    if interp:
        return fallback
    from ..autotune import get_tuner, shape_bucket, short_dtype, \
        source_version
    cands = list(dict.fromkeys(clamp4(c) for c in _BWD_CANDIDATES))
    if len(cands) == 1:
        return cands[0]
    sig = "bwd|bh%d|s%dx%d|d%d|%s|c%d" % (
        shape_bucket((bh,))[0], s_q, s_k, d, short_dtype(dtype), int(causal)
    ) + _group_tag(group)

    def build(cand):
        return functools.partial(
            _flash_bwd_bh, causal=causal, scale=1.0,
            block_q_dkv=cand[0], block_k_dkv=cand[1],
            block_q_dq=cand[2], block_k_dq=cand[3], interpret=False)

    def make_args():
        args = _synth_bh(
            [(bh, s_q, d), (bh // group, s_k, d), (bh // group, s_k, d),
             (bh, s_q, d)], [dtype] * 4)
        lse = jnp.zeros((bh, s_q), jnp.float32)
        do = _synth_bh([(bh, s_q, d)], [dtype])[0]
        return args + [lse, do]

    return get_tuner().get(
        "flash_attention", sig, candidates=cands, build=build,
        make_args=make_args, fallback=fallback,
        version=source_version(__name__))


def flash_attention(q, k, v, causal=False, scale=1.0,
                    block_q=None, block_k=None, interpret=None):
    """q,k,v: (B, S, H, D) -> (B, S, H, D). Forward only; use
    flash_attention_vjp for the Pallas-backward pair (attention.py wires it
    through jax.custom_vjp). interpret=None resolves per call from placement
    (_interpret); pass an explicit bool when the caller already resolved it
    (attention.py bakes it through the custom_vjp static args). block_q /
    block_k default to the tuned (or fallback) configuration; pass explicit
    values to pin them."""
    out, _ = flash_attention_fwd(q, k, v, causal, scale, block_q, block_k,
                                 interpret)
    return out


def flash_attention_fwd(q, k, v, causal=False, scale=1.0,
                        block_q=None, block_k=None, interpret=None):
    """Returns (out, lse) with lse (B, H, S) float32 — the residual the
    Pallas backward needs."""
    b, s, h, d = q.shape
    s_k = k.shape[1]
    interp = _interpret(q) if interpret is None else interpret
    if block_q is None and block_k is None:
        bq, bk = _tuned_fwd_blocks(b * h, s, s_k, d, q.dtype, causal, interp,
                                   group=h // k.shape[2])
    else:
        bq = _clamp(block_q or DEFAULT_BLOCK_Q, s)
        bk = _clamp(block_k or DEFAULT_BLOCK_K, s_k)
    out, lse = _flash_fwd_bh(_to_bh(q), _to_bh(k), _to_bh(v), causal, scale,
                             bq, bk, interp)
    return _from_bh(out, b, h), lse.reshape(b, h, s)


def flash_attention_bwd(q, k, v, out, lse, do, causal=False, scale=1.0,
                        block_q=None, block_k=None, interpret=None):
    """FlashAttention-2 backward: (dq, dk, dv), all (B, S, H, D). With no
    explicit blocks the dkv and dq passes get independently tuned
    (block_q, block_k) pairs; explicit block_q/block_k pin both passes
    (legacy single-pair interface)."""
    b, s, h, d = q.shape
    s_k = k.shape[1]
    interp = _interpret(q) if interpret is None else interpret
    if block_q is None and block_k is None:
        blocks = _tuned_bwd_blocks(b * h, s, s_k, d, q.dtype, causal, interp,
                                   group=h // k.shape[2])
    else:
        bq = block_q or DEFAULT_BLOCK_Q
        bk = block_k or DEFAULT_BLOCK_K
        blocks = (bq, bk, bq, bk)
    blocks = (_clamp(blocks[0], s), _clamp(blocks[1], s_k),
              _clamp(blocks[2], s), _clamp(blocks[3], s_k))
    dq, dk, dv = _flash_bwd_bh(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(out),
        lse.reshape(b * h, s), _to_bh(do), causal, scale,
        blocks[0], blocks[1], blocks[2], blocks[3], interp)
    h_kv = k.shape[2]
    return (_from_bh(dq, b, h), _from_bh(dk, b, h_kv), _from_bh(dv, b, h_kv))
