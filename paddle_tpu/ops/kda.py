"""Kimi Delta Attention (Kimi Linear, arXiv 2510.26692): a gated delta rule
with one decay per channel, in chunks.

Per head, with a matrix state S (d_k, d_v) that starts at zero in every row
of the batch:
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t,      alpha_t = exp(g_t) in (0, 1]^d_k
A token-by-token scan would be 4096 dependent steps of rank-one work. The op
cuts the sequence into chunks of C tokens. With G the running sum of g inside
a chunk, Gamma = exp(G) and S_0 the state at the chunk's start:
    A_ri = beta_r ((k_r * Gamma_r / Gamma_i) . k_i)   for i < r
    T = (I + A)^-1 Diag(beta),   U = T V,   W = T (K * Gamma)
    Delta = U - W S_0
    o_r = S_0^T (q_r * Gamma_r) + sum_(i<=r) ((q_r * Gamma_r / Gamma_i) . k_i) Delta_i
    S_C = Diag(Gamma_C) S_0 + sum_i (k_i * Gamma_C / Gamma_i) Delta_i^T

Three stages, none of them a scan over tokens:
  1. inside the chunks, for every (batch, head, chunk) at once: G (a product
     with a triangle of ones, `_running_sums`), A, the query-key products, T,
     U, W and the decayed copies of q and k, as batched matrix products and
     elementwise work;
  2. across the chunks: Delta and the next chunk's state from the last, the one
     sequential part, two small products a chunk, by `lax.scan` over the 64
     chunks of a row;
  3. the outputs of every chunk at once from its start state and Delta.
Two kernels were built for this, measured on the chip and taken out
(docs/kernels.md has the readings): a Pallas pair for stage 2 alone inside
the loop over the head groups (PR 36: three times slower in the op for the
forward pass), and the whole forward pass as one kernel outside any loop
(PR 40: 5.84 ms against this form's 6.43; the exact float32 inverse of one
chunk of 8 heads does not fill the vector unit as the chunks of a group
side by side in the lanes do).

The overflow rule. Every ratio Gamma_r / Gamma_i with i <= r is at most 1, but
its two factors apart are not: at alpha = 0.5 exp(-G) over a chunk of 64 is
2^64, and at alpha = 0.05 it is not a float32. So no exponential is taken of a
positive sum wider than half a sub-chunk (SUB = 16 tokens): a chunk's products
are formed a block row of SUB queries at a time against a reference point, the
running sum at that block row's middle token. The keys of earlier blocks
carry exp(G_ref - G_i) <= 1; the block row's own queries and keys carry
exp(G_r - G_ref) and exp(G_ref - G_i), each the exponential of at most 8
tokens' decay either way (float32 holds both while g >= -10 a token,
alpha >= 5e-5, with room for the operand beside it). tests/test_kda.py holds
the op to this.

(I + A)^-1 is exact float32 work and no matrix product: 16 x 16 diagonal
blocks by forward substitution, then merged twice, [[T11, 0], [-T22 A21 T11,
T22]], all as multiply-adds with the chunks in the minor dimension (a 64 x 64
float32 product at full precision is six passes of a quarter-filled MXU a
chunk; the substitution is the same work for the vector unit once).

The backward is chunked too and is not the autodiff of a scan: it keeps the
inputs and the chunk-start states (one (d_k, d_v) float32 a chunk a head),
recomputes stage 1, runs the state's cotangent backwards over the chunks (two
products a chunk again), and pulls the cotangents of A, U, W and the decayed
copies back through stage 1.

g, its running sums, the decays, the state and every accumulator are float32.
The products take their operands in the dtype of q (bfloat16 operands are
what one MXU pass rounds float32 ones to anyway, at half the bytes) and
accumulate in float32, as the attention kernels do; with float32 operands
they run at the backend's default precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.dispatch import apply, unwrap
from ..profiler import metrics as _metrics

CHUNK = 64
SUB = 16
SLAB_BYTES = 32 * 2 ** 20


# ---------------------------------------------------------------------------
# (I + A)^-1 for strictly lower triangular A, chunks in the minor dimension

def _mac(a, b):
    """(n, m, B) x (m, p, B) -> (n, p, B): sum_j a[:, j] b[j], multiply-adds
    over arrays whose minor dimension is the batch (one fused reduction)."""
    return jnp.sum(a[:, :, None, :] * b[None, :, :, :], axis=1)


def _inverse_minor(a):
    """(I + a)^-1 for a (n, n, B) whose [:, :, b] are strictly lower
    triangular: forward substitution up to SUB rows, and above that the two
    diagonal halves (side by side in the batch) merged,
    [[T11, 0], [-T22 a21 T11, T22]]."""
    n, _, batch = a.shape
    eye = jnp.eye(n, dtype=a.dtype)
    if n <= SUB:
        # row r = e_r - sum_(j<r) a[r, j] row j; the rows not yet made are zero
        t = jnp.zeros_like(a).at[0].set(eye[0][:, None])
        for r in range(1, n):
            t = t.at[r].set(eye[r][:, None]
                            - jnp.sum(a[r][:, None, :] * t, axis=0))
        return t
    h = n // 2
    t = _inverse_minor(jnp.concatenate([a[:h, :h], a[h:, h:]], axis=2))
    t11, t22 = t[..., :batch], t[..., batch:]
    t21 = -_mac(t22, _mac(a[h:, :h], t11))
    top = jnp.concatenate([t11, jnp.zeros_like(t11)], axis=1)
    return jnp.concatenate([top, jnp.concatenate([t21, t22], axis=1)], axis=0)


def _inverse(a):
    lead, n = a.shape[:-2], a.shape[-1]
    a = jnp.tril(a, -1).reshape((-1, n, n))
    return jnp.moveaxis(_inverse_minor(jnp.moveaxis(a, 0, -1)), -1, 0).reshape(
        lead + (n, n))


@jax.custom_vjp
def unit_lower_inverse(a):
    """(I + a)^-1 over the last two axes of `a` (..., n, n), strictly lower
    triangular there (what is on or above the diagonal is taken as zero)."""
    return _inverse(a)


def _inverse_fwd(a):
    t = _inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    # d(I + a)^-1 = -T da T
    tt = jnp.swapaxes(t, -1, -2)
    return (jnp.tril(-(tt @ dt @ tt), -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# ---------------------------------------------------------------------------
# stage 1: inside the chunks

def _mm(a, b, dtype):
    """a @ b over the last two axes, operands in `dtype`, float32 out."""
    return jnp.matmul(a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _chunked(x, chunk):
    """(B, S, H, D) -> (S / chunk, B * H, chunk, D): the chunks lead, as the
    pass over them reads them."""
    b, s, h, d = x.shape
    x = x.reshape(b, s // chunk, chunk, h, d)
    return jnp.transpose(x, (1, 0, 3, 2, 4)).reshape(s // chunk, b * h, chunk, d)


def _unchunked(x, b, h):
    """`_chunked` undone."""
    n, _, c, d = x.shape
    x = x.reshape(n, b, h, c, d)
    return jnp.transpose(x, (1, 0, 3, 2, 4)).reshape(b, n * c, h, d)


def _running_sums(g):
    """The running sums of g (n, r, C, D) over a chunk's tokens, as a product
    with a (C, C) lower triangle of ones, float32 at full precision (g reaches
    -10 a token and a chunk's sum -640: one bfloat16 pass is not enough).
    `jnp.cumsum` is a `reduce-window` on the TPU, four plain passes, and its
    pull-back another; this one's pull-back is the transposed triangle."""
    c = g.shape[2]
    return jnp.matmul(jnp.tril(jnp.ones((c, c), g.dtype)), g,
                      precision=jax.lax.Precision.HIGHEST)


def _intra(q, k, v, g, beta, scale, chunk):
    """From the op's operands ((B, S, H, D); beta (B, S, H)), per chunk:
    qk (n, r, C, C) the decayed query-key products at or under the diagonal,
    U, W, the queries decayed from the chunk's start, the keys decayed to its
    end, and the decay over the whole chunk (n, r, d_k); all float32."""
    dtype, f32 = q.dtype, jnp.float32
    q, k, v, g = (_chunked(x.astype(f32), chunk) for x in (q, k, v, g))
    beta = _chunked(beta.astype(f32)[..., None], chunk)[..., 0]      # (n, r, C)
    q = q * scale
    c, sub = chunk, min(SUB, chunk)
    gsum = _running_sums(g)
    rows_k, rows_q = [], []
    for a in range(c // sub):
        lo, hi = a * sub, (a + 1) * sub
        # the reference point of block row a: the running sum at its middle
        # token. The keys before the block carry exp(G_ref - G_i) <= 1; the
        # block's own rows and keys an exponential of at most sub / 2
        # tokens' decay, of either sign
        ref = gsum[:, :, lo + sub // 2:lo + sub // 2 + 1]
        row = jnp.exp(gsum[:, :, lo:hi] - ref)
        kc = k[:, :, :hi] * jnp.exp(ref - gsum[:, :, :hi])
        kc_t = jnp.swapaxes(kc, 2, 3)
        pad = ((0, 0), (0, 0), (0, 0), (0, c - hi))
        rows_k.append(jnp.pad(_mm(k[:, :, lo:hi] * row, kc_t, dtype), pad))
        rows_q.append(jnp.pad(_mm(q[:, :, lo:hi] * row, kc_t, dtype), pad))
    tri = jnp.tril(jnp.ones((c, c), bool))
    kk = jnp.where(tri, jnp.concatenate(rows_k, axis=2), 0.0)
    qk = jnp.where(tri, jnp.concatenate(rows_q, axis=2), 0.0)
    t = unit_lower_inverse(kk * beta[..., None]) * beta[:, :, None, :]
    gamma = jnp.exp(gsum)
    last = gsum[:, :, -1:]
    return (qk, _mm(t, v, dtype), _mm(t, k * gamma, dtype), q * gamma,
            k * jnp.exp(last - gsum), jnp.exp(last[:, :, 0]))


# ---------------------------------------------------------------------------
# stage 2: across the chunks

def _states(u, w, k_end, decay, dtype):
    """The state at every chunk's start (n, r, d_k, d_v) and Delta
    (n, r, C, d_v), by `lax.scan` over the chunks."""
    def step(s, x):
        u_n, w_n, k_n, d_n = x
        delta = u_n - _mm(w_n, s, dtype)
        return (d_n[:, :, None] * s + _mm(jnp.swapaxes(k_n, 1, 2), delta, dtype),
                (s, delta))

    s0 = jnp.zeros((u.shape[1], w.shape[3], u.shape[3]), jnp.float32)
    _, out = jax.lax.scan(step, s0, (u, w, k_end, decay))
    return out


def _cotangents(w, k_end, decay, d_delta_o, ds_o, dtype):
    """Backwards over the chunks: the cotangent of each chunk's end state
    (n, r, d_k, d_v) and of its Delta (n, r, C, d_v), from the cotangents
    the outputs gave Delta and the start states."""
    def step(ds_end, x):
        w_n, k_n, d_n, dd_n, dso_n = x
        d_delta = dd_n + _mm(k_n, ds_end, dtype)
        ds = (d_n[:, :, None] * ds_end + dso_n
              - _mm(jnp.swapaxes(w_n, 1, 2), d_delta, dtype))
        return ds, (ds_end, d_delta)

    zero = jnp.zeros(ds_o.shape[1:], jnp.float32)
    _, out = jax.lax.scan(step, zero, (w, k_end, decay, d_delta_o, ds_o),
                          reverse=True)
    return out


# ---------------------------------------------------------------------------
# the op

def _padded(q, k, v, g, beta, chunk):
    """The sequence padded to whole chunks with tokens that leave the state
    as it is (k, v, beta and g zero) and whose outputs are dropped."""
    extra = -q.shape[1] % chunk
    if not extra:
        return q, k, v, g, beta
    pad = ((0, 0), (0, extra), (0, 0), (0, 0))
    return (*(jnp.pad(x, pad) for x in (q, k, v, g)), jnp.pad(beta, pad[:3]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def kimi_delta_attention(q, k, v, g, beta, scale, chunk=CHUNK):
    """o (B, S, H, d_v) in v's dtype from q, k, g (B, S, H, d_k), v
    (B, S, H, d_v) and beta (B, S, H); g <= 0 is the log of the decay. The
    state starts at zero in every row."""
    return _forward(q, k, v, g, beta, scale, chunk)[0]


def _slabs(q):
    """How many groups of heads the op works through one after another
    (`lax.map`): the stage-1 arrays of all of 2 x 4096 x 32 heads at once are
    2.4 GB in the backward, of 8 heads 0.6 GB, and a batch of 8 x 2 x 64
    chunks still fills the products. Halved until one float32 operand of a
    group is at most SLAB_BYTES."""
    b, s, h, d = q.shape
    n = 1
    while h % (2 * n) == 0 and b * s * (h // n) * d * 4 > SLAB_BYTES:
        n *= 2
    return n


def _split(x, n):
    """(B, S, H, ...) -> (n, B, S, H / n, ...)."""
    x = x.reshape(x.shape[:2] + (n, x.shape[2] // n) + x.shape[3:])
    return jnp.moveaxis(x, 2, 0)


def _merged(x):
    """`_split` undone."""
    x = jnp.moveaxis(x, 0, 2)
    return x.reshape(x.shape[:2] + (x.shape[2] * x.shape[3],) + x.shape[4:])


def _forward_slab(q, k, v, g, beta, scale, chunk):
    seq, dtype = q.shape[1], q.dtype
    ops = _padded(q, k, v, g, beta, chunk)
    qk, u, w, q_start, k_end, decay = _intra(*ops, scale, chunk)
    starts, delta = _states(u, w, k_end, decay, dtype)
    o = _mm(q_start, starts, dtype) + _mm(qk, delta, dtype)
    o = _unchunked(o, q.shape[0], q.shape[2])[:, :seq].astype(v.dtype)
    return o, starts


# one program each where the op runs eagerly (to_static's discovery pass),
# not one an operation
@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(q, k, v, g, beta, scale, chunk):
    """(o, the chunk-start states (groups, n, r, d_k, d_v))."""
    n = _slabs(q)
    o, starts = jax.lax.map(
        lambda x: _forward_slab(*x, scale, chunk),
        tuple(_split(x, n) for x in (q, k, v, g, beta)))
    return _merged(o), starts


def _fwd(q, k, v, g, beta, scale, chunk):
    o, starts = _forward(q, k, v, g, beta, scale, chunk)
    return o, (q, k, v, g, beta, starts)


def _bwd_slab(ops, starts, do, scale, chunk):
    q = ops[0]
    dtype = q.dtype
    (qk, u, w, q_start, k_end, decay), pull = jax.vjp(
        lambda *x: _intra(*_padded(*x, chunk), scale, chunk), *ops)
    do = jnp.pad(do, ((0, 0), (0, -q.shape[1] % chunk), (0, 0), (0, 0)))
    do = _chunked(do.astype(jnp.float32), chunk)
    delta = u - _mm(w, starts, dtype)
    # o = q_start S_0 + qk Delta
    starts_t = jnp.swapaxes(starts, 2, 3)
    d_q_start = _mm(do, starts_t, dtype)
    d_qk = _mm(do, jnp.swapaxes(delta, 2, 3), dtype)
    ds_end, d_delta = _cotangents(
        w, k_end, decay, _mm(jnp.swapaxes(qk, 2, 3), do, dtype),
        _mm(jnp.swapaxes(q_start, 2, 3), do, dtype), dtype)
    # S_C = Diag(decay) S_0 + k_end^T Delta;  Delta = U - W S_0
    d_k_end = _mm(delta, jnp.swapaxes(ds_end, 2, 3), dtype)
    d_decay = jnp.sum(ds_end * starts, axis=3)
    d_w = -_mm(d_delta, starts_t, dtype)
    return pull((d_qk, d_delta, d_w, d_q_start, d_k_end, d_decay))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _bwd(scale, chunk, res, do):
    *ops, starts = res
    n = starts.shape[0]
    grads = jax.lax.map(
        lambda x: _bwd_slab(x[0], x[1], x[2], scale, chunk),
        (tuple(_split(x, n) for x in ops), starts, _split(do, n)))
    return tuple(_merged(x) for x in grads)


kimi_delta_attention.defvjp(_fwd, _bwd)


def delta_attention(query, key, value, g, beta, scale=None, chunk=CHUNK):
    """The op on Tensors, through the tape (scope `kda`). Counted per call in
    eager mode and per trace in a compiled step: `kda.calls_total`,
    `kda.tokens_total` (docs/observability.md)."""
    qv = unwrap(query)
    if scale is None:
        scale = float(qv.shape[-1]) ** -0.5
    registry = _metrics.get_registry()
    registry.inc_counter("kda.calls_total")
    registry.inc_counter("kda.tokens_total", qv.shape[0] * qv.shape[1])

    def prim(q, k, v, g_, b):
        return kimi_delta_attention(q, k, v, g_, b, float(scale), chunk)
    return apply(prim, query, key, value, g, beta, name="kda")
