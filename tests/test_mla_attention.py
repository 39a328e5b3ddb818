"""Attention with value heads of another size than the query/key heads
(latent attention: 192 against 128) through both paths under
F.scaled_dot_product_attention, XLA's and the flash pair run interpreted,
against plain softmax attention in float32, forward and gradients; and the
equal-size case, whose traced kernels are the parent's to the character.

Tolerance 2e-5 of the largest entry in float32 (1e-6 to 4e-6 measured: the
same sums in another order); one bf16 rounding of an operand (4e-3) fails it."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import attention
from paddle_tpu.ops.pallas import flash_attention as fa

TOL = 2e-5
B, S, D, DV = 2, 256, 192, 128


def operands(heads, kv_heads, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, S, heads, D)),
            jax.random.normal(ks[1], (B, S, kv_heads, D)),
            jax.random.normal(ks[2], (B, S, kv_heads, DV)),
            jax.random.normal(ks[3], (B, S, heads, DV)))


def plain(q, k, v):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def xla(q, k, v):
    return attention._xla_attention(q, k, v, None, D ** -0.5, True, 0.0, None)


def flash(q, k, v):
    return attention._flash_attention_diff(q, k, v, True, D ** -0.5, True)


def gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("path", [xla, flash], ids=["xla", "flash"])
@pytest.mark.parametrize("heads, kv_heads", [(4, 4), (4, 2)],
                         ids=["multi-head", "grouped"])
def test_two_head_sizes_against_plain_softmax(path, heads, kv_heads):
    q, k, v, w = operands(heads, kv_heads)
    out = path(q, k, v)
    assert out.shape == (B, S, heads, DV)
    assert gap(out, plain(q, k, v)) < TOL
    mine = jax.grad(lambda *a: jnp.sum(path(*a) * w), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", mine, ref):
        assert a.shape == b.shape and gap(a, b) < TOL, name


def test_the_backward_in_spans_at_two_head_sizes():
    q, k, v, w = operands(4, 2, seed=1)
    out, lse = fa.flash_attention_fwd(q, k, v, True, D ** -0.5, interpret=True)
    dq, dk, dv = fa._flash_bwd_bh(
        fa._to_bh(q), fa._to_bh(k), fa._to_bh(v), fa._to_bh(out),
        lse.reshape(B * 4, S), fa._to_bh(w), True, D ** -0.5, 128, 128, True,
        q_span=128)
    ref = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(q, k, v)
    assert gap(fa._from_bh(dq, B, 4), ref[0]) < TOL
    assert gap(fa._from_bh(dk, B, 2), ref[1]) < TOL
    assert gap(fa._from_bh(dv, B, 2), ref[2]) < TOL
    # the rule of shapes takes both sizes: dO rows of 128 beside q rows of 192
    assert fa._bwd_resident_bytes(1, 4096, 192, 2, 128) \
        < fa._bwd_resident_bytes(1, 4096, 192, 2)
    assert fa._bwd_q_span(1, 4096, 192, 2, 512, 128) == 4096


def test_the_path_rule_and_the_tile_search_take_both_sizes():
    q, k, v = (2, 4096, 32, 192), (2, 4096, 32, 192), (2, 4096, 32, 128)
    args = (jnp.bfloat16, False, 0.0, "tpu")
    assert attention.takes_flash(q, k, *args, v)
    assert not attention.takes_flash(q, k, *args, (2, 4096, 32, 80))
    assert not attention.takes_flash(q, k, jnp.bfloat16, False, 0.0, "cpu", v)
    assert fa.supports(q, k, v) and fa.supports(q, k)
    assert fa.tiles(4096, 4096) == (512, 512)


# the parent's (commit f494db6) forward and backward kernels traced at
# (8 over 4 heads, 256, 64) bf16, causal, blocks of 128: SHA-256 of the jaxpr's
# text with the source positions taken out
PARENT_FWD = "27e9cc26496e557c210e4586cb544aa38dbcc286ce993e1b053e7e57988f2698"
PARENT_BWD = "75524e5e1446b145a2ba96f5572699abce4b4630c947ebc150199bacbbad88bf"


def test_equal_sizes_trace_the_parents_kernels():
    def digest(f, *a, **kw):
        text = str(jax.make_jaxpr(lambda *x: f(*x, **kw))(*a))
        return hashlib.sha256(
            re.sub(r" at [^\s\]]*:\d+", "", text).encode()).hexdigest()
    q = jnp.zeros((8, 256, 64), jnp.bfloat16)
    kv = jnp.zeros((4, 256, 64), jnp.bfloat16)
    lse = jnp.zeros((8, 256), jnp.float32)
    kw = dict(causal=True, scale=0.125, block_q=128, block_k=128, interpret=True)
    assert digest(fa._flash_fwd_bh, q, kv, kv, **kw) == PARENT_FWD
    assert digest(fa._flash_bwd_bh, q, kv, kv, q, lse, q, **kw) == PARENT_BWD


def test_through_the_functional_entry():
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    q, k, v, w = operands(4, 4, seed=2)
    tq, tk, tv = (paddle.to_tensor(np.asarray(x), stop_gradient=False)
                  for x in (q, k, v))
    out = F.scaled_dot_product_attention(tq, tk, tv, is_causal=True)
    assert list(out.shape) == [B, S, 4, DV]
    assert gap(out._val, plain(q, k, v)) < TOL
    (out * paddle.to_tensor(np.asarray(w))).sum().backward()
    ref = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for t, r in zip((tq, tk, tv), ref):
        assert gap(t.grad._val, r) < TOL
