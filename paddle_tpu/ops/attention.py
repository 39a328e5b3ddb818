"""Attention kernels.

Reference parity: operators/fused/fused_attention_op.cu + fmha_ref.h. TPU-native
design: one XLA attention path (softmax fused by XLA) and a Pallas
flash-attention kernel pair (ops/pallas/flash_attention.py) behind one
functional entry point. Which of the two a call runs is `takes_flash`, a
rule of the operands' shapes, the mask, the dropout and the platform and of
nothing measured at run time: the same code on the same shapes stages the
same program in every process (docs/kernels.md, "Which kernel runs").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.dispatch import apply, unwrap
from ..profiler import metrics as _metrics

# Key length from which the flash pair is taken: the smallest of 1024, 2048
# and 4096 at which its device time, forward and backward, was under XLA's
# attention by more than the runs' spread (docs/kernels.md has the table).
FLASH_MIN_SEQ_K = 2048
# under this many query positions the s^2 buffers are small and XLA's fused
# softmax attention is the faster (v5e: BERT s=128 151k -> 121k tok/s under
# flash)
FLASH_MIN_SEQ_Q = 256
# XLA's attention holds its float32 scores, their softmax and both gradients
# at once: about four score tensors
XLA_SCORE_TENSORS = 4


def _xla_attention(q, k, v, mask, scale, is_causal, dropout_p, dropout_key,
                   return_lse=False, window=None):
    # q,k,v: (B, S, H, D) paddle layout -> compute in (B, H, S, D)
    group = q.shape[2] // k.shape[2]
    if group > 1:
        # grouped-query attention: query head h reads key/value head
        # h // group, here by a repeat that XLA materialises
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    q = jnp.swapaxes(q, 1, 2)
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if is_causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        if window is not None:
            # the band: the `window` keys up to and with the query's own
            causal = causal & ~jnp.tril(causal, k=s_k - s_q - window)
        logits = jnp.where(causal, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(probs.dtype)
    out = jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", probs, v), 1, 2)
    if return_lse:
        return out, jax.lax.stop_gradient(
            jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1))
    return out


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, use_pallas=None, scale=None,
                                 key_set=None, return_lse=False, window=None):
    """(batch, seq, heads, head_dim) attention. `key` and `value` may hold
    fewer heads than `query`, a divisor of its count (grouped-query
    attention): query head h reads key/value head h // (heads / kv_heads).
    `value`'s heads may be of another size than `query`'s and `key`'s (latent
    attention: 192 against 128); the result has value's head size and the
    default scale is the query/key size's.
    `key_set` (batch, seq_q, seq_k), integer or bool, gives every query the
    keys it attends to (not 0), the same for all heads: a learned sparse
    index's choice (`F.sparse_attention_index`). With `is_causal` a key after
    its query is out whatever the set says; every query must keep a key. The
    softmax and the gradients are over the set; the set takes none. Where
    the index ran as kernels its `key_set` is the pair (sets, table) in the
    set kernels' own layout, handed on as it is: it is causal already, and
    only XLA's attention forms the square from it.
    `return_lse` gives (out, lse): the logsumexp of each query's scaled scores
    over the keys it attends to, (batch, heads, seq) float32, no gradient:
    what forms the probabilities again (`F.sparse_attention_index_loss`).
    `window` (with `is_causal`; no mask and no `key_set` beside it) keeps of
    the causal keys the `window` last, the query's own among them: query t
    reads the keys j with t - window < j <= t. The flash pair walks the band
    alone (its banded grid: `attention.window_total` counts the calls that
    took it); XLA's attention masks the square. A window at least as long as
    the keys is plain causal attention.
    `use_pallas=None` lets `takes_flash` choose the path from the shapes;
    True or False is the caller's own choice (True with a mask or dropout
    raises). The path a call took is counted: `attention.flash_total`,
    `attention.xla_total` (docs/observability.md)."""
    qv = unwrap(query)
    if qv.shape[2] % unwrap(key).shape[2]:
        raise ValueError(
            f"{qv.shape[2]} query heads do not divide over "
            f"{unwrap(key).shape[2]} key/value heads")
    head_dim = qv.shape[-1]
    if scale is None:
        scale = 1.0 / (head_dim ** 0.5)
    if window is not None:
        if not is_causal or attn_mask is not None or key_set is not None:
            raise ValueError(
                "window: a band under the causal diagonal; it takes is_causal=True "
                "and neither attn_mask nor key_set")
        if int(window) < 1:
            raise ValueError(f"a window of {window} keys holds no key")
        from .pallas.flash_attention import band
        window = band(window, unwrap(key).shape[1])
    dropout_kd = None
    if dropout_p > 0.0 and training:
        from ..core.random import next_key_data
        dropout_kd = next_key_data()
    if not training:
        dropout_p = 0.0

    if use_pallas is None:
        use_pallas = takes_flash(qv.shape, unwrap(key).shape, qv.dtype,
                                 attn_mask is not None, dropout_p,
                                 _platform(), unwrap(value).shape,
                                 key_set=key_set is not None,
                                 on_mesh=key_set is not None
                                 and _on_mesh(qv))
    elif use_pallas and (attn_mask is not None or dropout_p > 0.0):
        raise ValueError(
            "use_pallas=True is incompatible with attn_mask/dropout_p: the "
            "flash kernel computes plain (optionally causal) attention")
    _metrics.get_registry().inc_counter(
        "attention.flash_total" if use_pallas else "attention.xla_total")
    tiled = isinstance(key_set, (tuple, list))
    if use_pallas and key_set is not None:
        out, lse = apply(_flash_set_prim(qv, is_causal, scale), query, key,
                         value, *(key_set if tiled else [key_set]),
                         name="flash_attention")
        return (out, lse.detach()) if return_lse else out
    if return_lse and use_pallas:
        raise ValueError("return_lse: the plain flash pair keeps its logsumexp")
    if use_pallas:
        if window is not None:
            _metrics.get_registry().inc_counter("attention.window_total")
        return apply(_flash_prim(qv, is_causal, scale, window), query, key,
                     value, name="flash_attention")

    def prim(q, k, v, *rest):
        rest = list(rest)
        kd = rest.pop() if dropout_kd is not None else None
        picked = None
        if key_set is not None:
            from .pallas.flash_attention import set_square
            table = rest.pop() if tiled else None
            picked = set_square((rest.pop(), table)) if tiled else rest.pop()
        m = rest[0] if rest else None
        if picked is not None:
            # the set as a boolean mask over the heads; an additive mask
            # beside it is folded into one
            in_set = (picked != 0)[:, None]
            m = in_set if m is None else jnp.where(
                in_set, m if m.dtype != jnp.bool_ else jnp.where(m, 0.0, -1e30),
                -1e30)
        dk = jax.random.wrap_key_data(kd) if kd is not None else None
        return _xla_attention(q, k, v, m, scale, is_causal, dropout_p, dk,
                              return_lse, window)

    extra = [attn_mask] if attn_mask is not None else []
    if key_set is not None:
        extra.extend(key_set if tiled else [key_set])
    if dropout_kd is not None:
        extra.append(dropout_kd)
    out = apply(prim, query, key, value, *extra, name="sdpa")
    return (out[0], out[1].detach()) if return_lse else out


def _flash_prim(qv, is_causal, scale, window=None):
    """The flash-attention primitive for operands like `qv`.

    The interpret decision is resolved HERE, from the unwrapped value: its
    placement in eager, the default backend for a tracer (to_static compile,
    recompute's checkpoint trace). It is baked through the custom_vjp as a
    STATIC arg so the fwd and bwd rules, which jax re-invokes later (e.g.
    while differentiating a jax.checkpoint region), agree with the forward.

    Mosaic kernels cannot be partitioned automatically, so where the
    operands are spread over a mesh the kernel is mapped over it by hand.
    Attention is independent per (batch, head): the batch splits over 'data'
    and the heads over 'model' where those axes exist and divide them; along
    every other axis each device computes a replica."""
    from ..distributed.mesh import operand_mesh, shard_map
    from .pallas.flash_attention import _interpret
    interp = _interpret(qv)

    def prim(q, k, v):
        return _flash_attention_diff(q, k, v, is_causal, scale, interp, window)

    mesh = operand_mesh(qv)
    if mesh is None:
        return prim
    from jax.sharding import PartitionSpec as P

    def axis(name, dim):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and dim % n == 0 else None

    spec = P(axis("data", qv.shape[0]), None, axis("model", qv.shape[2]),
             None)
    return shard_map(prim, mesh, in_specs=(spec, spec, spec), out_specs=spec,
                     check_rep=False)


def _flash_set_prim(qv, is_causal, scale):
    """The flash pair over a set a query (ops/pallas/flash_attention.py) for
    operands like `qv`, on one device: the interpret decision is baked as in
    `_flash_prim`. A square set has `is_causal` folded into it by one XLA
    pass; a (sets, table) pair is the index kernel's, causal as written, and
    goes to the kernels untouched."""
    from .pallas.flash_attention import _interpret
    interp = _interpret(qv)

    def prim(q, k, v, *picked):
        if len(picked) == 2:
            return _flash_set_diff(q, k, v, picked, scale, interp)
        picked = (picked[0] != 0)
        if is_causal:
            s_q, s_k = picked.shape[1:]
            picked = picked & jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        return _flash_set_diff(q, k, v, picked.astype(jnp.int8), scale, interp)
    return prim


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_set_diff(q, k, v, picked, scale, interpret):
    """(out, lse (B, H, S) float32); lse carries no gradient. `picked`: the
    square set, or the kernels' (sets, table)."""
    from .pallas.flash_attention import flash_attention_set_fwd
    return flash_attention_set_fwd(q, k, v, picked, scale=scale,
                                   interpret=interpret)[:2]


def _flash_set_fwd(q, k, v, picked, scale, interpret):
    from .pallas.flash_attention import flash_attention_set_fwd
    out, lse, tiles = flash_attention_set_fwd(q, k, v, picked, scale=scale,
                                              interpret=interpret)
    return (out, lse), (q, k, v, out, lse, tiles)


def _flash_set_bwd(scale, interpret, res, g):
    from .pallas.flash_attention import flash_attention_set_bwd
    q, k, v, out, lse, tiles = res
    return (*flash_attention_set_bwd(q, k, v, out, lse, g[0], tiles,
                                     scale=scale, interpret=interpret), None)


_flash_set_diff.defvjp(_flash_set_fwd, _flash_set_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_diff(q, k, v, is_causal, scale, interpret, window=None):
    """Pallas flash attention, forward AND backward.

    The forward saves only (q, k, v, out, lse); the backward re-forms each
    probability tile in VMEM (FlashAttention-2 recompute scheme,
    ops/pallas/flash_attention.py) — neither direction ever materializes the
    S x S matrix in HBM. Parity vs the XLA path is asserted for both
    directions in tests/test_tpu_native.py (TestFlashAttentionBackward)."""
    from .pallas.flash_attention import flash_attention
    return flash_attention(q, k, v, causal=is_causal, scale=scale,
                           interpret=interpret, window=window)


def _flash_fwd(q, k, v, is_causal, scale, interpret, window):
    from .pallas.flash_attention import flash_attention_fwd
    out, lse = flash_attention_fwd(q, k, v, causal=is_causal, scale=scale,
                                   interpret=interpret, window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(is_causal, scale, interpret, window, res, g):
    from .pallas.flash_attention import flash_attention_bwd
    q, k, v, out, lse = res
    return flash_attention_bwd(q, k, v, out, lse, g, causal=is_causal,
                               scale=scale, interpret=interpret, window=window)


_flash_attention_diff.defvjp(_flash_fwd, _flash_bwd)


def takes_flash(q_shape, k_shape, dtype, masked, dropout_p, platform,
                v_shape=None, key_set=False, on_mesh=False):
    """Whether attention over operands of these (batch, seq, heads, head_dim)
    shapes runs the flash kernel pair: on a TPU, plain or causal attention
    (no mask, no dropout) of a floating type over shapes the kernels tile,
    from FLASH_MIN_SEQ_Q query positions, where the keys are at least
    FLASH_MIN_SEQ_K long or XLA's attention could not hold its scores.
    Everything else runs XLA's attention. `v_shape` where the value heads
    are of another size than the keys'. A set of keys a query (`key_set`)
    is no mask in this sense: the pair takes it by the same rule, except
    where the operands are spread over a mesh (`on_mesh`: the kernels over a
    set are not mapped over one), and XLA's attention reads it as a boolean
    mask where the rule says no. Nor is a window: the band is a second bound
    on the causal grid, taken by the same rule as plain causal attention and
    mapped over a mesh as it is (batch and heads split, the band whole)."""
    if (platform != "tpu" or masked or dropout_p > 0.0
            or (key_set and on_mesh)
            or q_shape[1] < FLASH_MIN_SEQ_Q
            or not jnp.issubdtype(dtype, jnp.floating)
            or not _pallas_supports(q_shape, k_shape, v_shape)):
        return False
    return k_shape[1] >= FLASH_MIN_SEQ_K or not _xla_holds_its_scores(
        q_shape, k_shape)


def _on_mesh(value):
    from ..distributed.mesh import operand_mesh
    return operand_mesh(value) is not None


def _xla_holds_its_scores(q_shape, k_shape):
    """Whether the XLA path's float32 score tensor (batch, heads, seq_q,
    seq_k), XLA_SCORE_TENSORS times over for its forward and backward, fits
    in half the device's memory beside whatever the program already holds
    (LFM2's 2 x 32 heads x 4096^2 does not: 4.3 GB a tensor)."""
    scores = 4 * q_shape[0] * q_shape[2] * q_shape[1] * k_shape[1]
    return XLA_SCORE_TENSORS * scores <= _device_memory_bytes() // 2


@functools.lru_cache(maxsize=1)
def _device_memory_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit", 16 * 2 ** 30)


def _pallas_supports(q_shape, k_shape, v_shape=None):
    from .pallas.flash_attention import supports
    return supports(tuple(q_shape), tuple(k_shape),
                    None if v_shape is None else tuple(v_shape))


@functools.lru_cache(maxsize=1)
def _platform():
    return jax.devices()[0].platform
