"""Kimi Delta Attention's chunked op (paddle_tpu/ops/kda.py) against the
token-by-token recurrence it stands for, in float32 on the CPU: forward and
all five gradients, whole and ragged sequences, the decay's whole range, the
overflow rule, beta's two ends, the state's restart in every row, bfloat16
operands, and the running sums of g as a triangle product against sums taken
in float64.

Tolerances. In float32 both sides do the same arithmetic in another order
(sums over a chunk against sums a token at a time): 3e-5 of the largest entry
holds every reading here (1e-6 to 4e-6 measured), and one bf16 rounding of
an operand (4e-3) would fail it. With bfloat16 operands every product takes
bf16 operands (float32 sums): 2e-2 of the largest entry (6e-3 measured), which
an op that kept the state or the running sums of g in bf16 would fail."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import kda

F32_TOL = 3e-5
SCALE = 0.25


def operands(seed, b, s, h, dk, dv, lo=0.05, hi=0.999, beta=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, s, h, dk))
    k = jax.random.normal(ks[1], (b, s, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = jnp.log(jax.random.uniform(ks[3], (b, s, h, dk), minval=lo, maxval=hi))
    bt = jax.random.uniform(ks[4], (b, s, h)) if beta is None \
        else jnp.full((b, s, h), beta)
    return q, k, v, g, bt


def recurrence(q, k, v, g, beta, scale):
    """The op token by token in float32 (`lax.scan` over the sequence): what
    the chunked form stands for."""
    f32 = jnp.float32
    q, k, v, g, beta = (jnp.moveaxis(x.astype(f32), 1, 0)
                        for x in (q, k, v, g, beta))

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x                       # (B, H, D), b_t (B, H)
        s = jnp.exp(g_t)[..., None] * s
        delta = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, s))
        s = s + k_t[..., None] * delta[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t * scale, s)

    s0 = jnp.zeros(q.shape[1:] + v.shape[-1:], f32)
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, 1)


def chunked(*ops):
    return kda.kimi_delta_attention(*ops, SCALE, kda.CHUNK)


def gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def both_gradients(ops):
    w = jax.random.normal(jax.random.PRNGKey(99), ops[2].shape)
    mine = jax.grad(lambda *a: jnp.sum(chunked(*a) * w),
                    argnums=range(5))(*ops)
    ref = jax.grad(lambda *a: jnp.sum(recurrence(*a, SCALE) * w),
                   argnums=range(5))(*ops)
    return mine, ref


@pytest.mark.parametrize("seq", [64, 128, 320, 70],
                         ids=["1-chunk", "2-chunks", "5-chunks", "ragged"])
def test_forward_and_five_gradients_against_the_recurrence(seq):
    ops = operands(seq, 2, seq, 2, 16, 24)           # alpha in (0.05, 0.999)
    assert gap(chunked(*ops), recurrence(*ops, SCALE)) < F32_TOL
    for name, a, b in zip("q k v g beta".split(), *both_gradients(ops)):
        assert gap(a, b) < F32_TOL, name


def test_many_heads_go_through_in_groups(monkeypatch):
    # 8 heads in 4 groups of 2 (`lax.map`), the saved states a group apart
    monkeypatch.setattr(kda, "SLAB_BYTES", 2 * 192 * 2 * 16 * 4)
    ops = operands(7, 2, 192, 8, 16, 24)
    assert kda._slabs(ops[0]) == 4
    kda._forward.clear_cache()
    kda._bwd.clear_cache()
    assert kda._forward(*ops, SCALE, kda.CHUNK)[1].shape == (4, 3, 4, 16, 24)
    assert gap(chunked(*ops), recurrence(*ops, SCALE)) < F32_TOL
    for name, a, b in zip("q k v g beta".split(), *both_gradients(ops)):
        assert gap(a, b) < F32_TOL, name
    kda._forward.clear_cache()
    kda._bwd.clear_cache()


def chunk_wide_form(q, k, v, g, beta, reach):
    """A chunked form that takes its decays apart over `reach` tokens,
    Gamma_r / Gamma_i as exp(G_r) * exp(-G_i): what the op must not do."""
    b, s, h, dk = q.shape
    out, state = [], jnp.zeros((b, h, dk, v.shape[-1]))
    for lo in range(0, s, reach):
        sl = slice(lo, lo + reach)
        gsum = jnp.cumsum(g[:, sl], axis=1)
        up, down = jnp.exp(gsum), jnp.exp(-gsum)        # down overflows
        kk = jnp.einsum("brhd,bihd->bhri", k[:, sl] * up, k[:, sl] * down)
        qk = jnp.einsum("brhd,bihd->bhri", q[:, sl] * SCALE * up, k[:, sl] * down)
        bt = jnp.moveaxis(beta[:, sl], 1, 2)
        a = jnp.tril(kk, -1) * bt[..., None]
        t = jnp.linalg.inv(jnp.eye(reach) + a) * bt[:, :, None, :]
        u = jnp.einsum("bhri,bihd->bhrd", t, v[:, sl])
        w = jnp.einsum("bhri,bihd->bhrd", t, k[:, sl] * up)
        delta = u - w @ state
        o = jnp.einsum("brhd,bhdv->bhrv", q[:, sl] * SCALE * up, state) \
            + jnp.tril(qk) @ delta
        end = jnp.exp(gsum[:, -1:] - gsum)
        state = jnp.moveaxis(up[:, -1], 1, 1)[..., None] * state + jnp.einsum(
            "bihd,bhiv->bhdv", k[:, sl] * end, delta)
        out.append(jnp.moveaxis(o, 2, 1))
    return jnp.concatenate(out, axis=1)


@pytest.mark.parametrize("alpha, seq, reach", [(0.5, 256, 256), (0.05, 64, 64)],
                         ids=["half-over-256", "twentieth-over-64"])
def test_no_exponential_of_a_positive_sum_wider_than_a_sub_chunk(alpha, seq, reach):
    """alpha 0.5 over 256 tokens (exp(-G) = 2^256) and alpha 0.05 over one
    chunk (20^64): a form that takes Gamma_r / Gamma_i apart over that reach
    is not finite; the op, which never reaches past half a sub-chunk of 16
    tokens with a positive sum, agrees with the recurrence, gradients too."""
    ops = operands(3, 1, seq, 2, 16, 16, lo=alpha, hi=alpha)
    with np.errstate(all="ignore"):
        assert not bool(jnp.all(jnp.isfinite(chunk_wide_form(*ops, reach))))
    # at alpha 0.999 the same form is sound: the fault is the overflow alone
    mild = operands(3, 1, seq, 2, 16, 16, lo=0.999, hi=0.999)
    assert gap(chunk_wide_form(*mild, reach), recurrence(*mild, SCALE)) < 1e-3
    assert gap(chunked(*ops), recurrence(*ops, SCALE)) < F32_TOL
    mine, ref = both_gradients(ops)
    for name, a, b in zip("q k v g beta".split(), mine, ref):
        assert bool(jnp.all(jnp.isfinite(a))) and gap(a, b) < F32_TOL, name


def test_the_strongest_decay_float32_allows_half_a_sub_chunk():
    # g = -9 a token: 8 tokens' sum either way of a block row's reference is
    # exp(+-72), inside float32 with room for the operand; a whole sub-chunk's
    # 15 would be exp(135) and a chunk's 64 exp(576)
    ops = list(operands(4, 1, 128, 1, 16, 16))
    ops[3] = jnp.full_like(ops[3], -9.0)
    out = chunked(*ops)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert gap(out, recurrence(*ops, SCALE)) < F32_TOL
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in both_gradients(ops)[0])
    # the gradients agree where the recurrence's own are not all cancellation:
    # g = -5.5 (alpha 0.004), still exp(+-44) round a reference; g's own
    # gradient there is a difference of terms a thousand times its size
    # (5e-5 measured), so it gets 2e-4
    ops[3] = jnp.full_like(ops[3], -5.5)
    for name, a, b in zip("q k v g beta".split(), *both_gradients(ops)):
        assert gap(a, b) < (2e-4 if name == "g" else F32_TOL), name


@pytest.mark.parametrize("case", ["strongest", "mixed", "short-chunk"])
def test_running_sums_as_a_triangle_product(case):
    """The running sums of g inside a chunk are a product with a lower
    triangle of ones at full float32 precision, not `jnp.cumsum`: at g = -10
    a token a chunk's sum reaches -640, and they agree with sums taken in
    float64 to float32's rounding of 64 terms, forward and pulled back (the
    transposed triangle); one bfloat16 pass of the same product does not."""
    c = 16 if case == "short-chunk" else 64
    key = jax.random.PRNGKey(11)
    g = jnp.full((3, 2, c, 24), -10.0) if case == "strongest" \
        else jax.random.uniform(key, (3, 2, c, 24), minval=-10.0, maxval=0.0)
    want = np.cumsum(np.asarray(g, np.float64), axis=2)
    got, pull = jax.vjp(kda._running_sums, g)
    assert got.dtype == jnp.float32
    assert float(np.max(np.abs(np.asarray(got) - want))) < 1e-6 * 10.0 * c
    assert gap(got, jnp.cumsum(g, axis=2)) < 1e-6
    ct = jax.random.normal(jax.random.PRNGKey(12), g.shape)
    back = np.flip(np.cumsum(np.flip(np.asarray(ct, np.float64), 2), axis=2), 2)
    assert float(np.max(np.abs(np.asarray(pull(ct)[0]) - back))) < 1e-5
    tri = jnp.tril(jnp.ones((c, c), jnp.bfloat16))
    one_pass = jnp.matmul(tri, g.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if case != "strongest":             # -10 itself is a bfloat16
        assert float(np.max(np.abs(np.asarray(one_pass) - want))) > 0.05


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_no_cumsum_is_left_in_the_op(direction):
    """Neither pass holds a `cumsum` (a `reduce-window` on the TPU, four
    plain passes over a group's float32 array): the sums and their pull-back
    are products."""
    ops = operands(1, 1, 128, 2, 16, 16)
    fn = chunked if direction == "forward" else \
        jax.grad(lambda *a: jnp.sum(chunked(*a)), argnums=range(5))
    text = str(jax.make_jaxpr(fn)(*ops))
    assert "cumsum" not in text and "reduce_window" not in text
    assert "dot_general" in text


@pytest.mark.parametrize("beta", [0.0, 1.0])
def test_beta_at_its_two_ends(beta):
    ops = operands(5, 2, 128, 2, 16, 16, beta=beta)
    out = chunked(*ops)
    if beta == 0.0:
        assert float(jnp.max(jnp.abs(out))) == 0.0      # nothing is ever written
    else:
        assert gap(out, recurrence(*ops, SCALE)) < F32_TOL
    for name, a, b in zip("q k v g beta".split(), *both_gradients(ops)):
        assert float(jnp.max(jnp.abs(a - b))) <= F32_TOL * max(
            float(jnp.max(jnp.abs(b))), 1e-3), name


def test_the_state_starts_at_zero_in_every_row():
    ops = operands(6, 2, 128, 2, 16, 16)
    both = chunked(*ops)
    for row in range(2):
        alone = chunked(*(x[row:row + 1] for x in ops))
        assert gap(both[row:row + 1], alone) < 1e-6
    # and the second half of a row is not what it would be from a fresh state
    fresh = chunked(*(x[:, 64:] for x in ops))
    assert gap(both[:, 64:], fresh) > 1e-2


def test_bfloat16_operands():
    ops = operands(8, 2, 128, 2, 16, 16)
    low = tuple(x.astype(jnp.bfloat16) for x in ops[:3]) + (ops[3], ops[4])
    out = chunked(*low)
    assert out.dtype == jnp.bfloat16
    ref = recurrence(*(x.astype(jnp.float32) for x in low), SCALE)
    assert gap(out.astype(jnp.float32), ref) < 2e-2
    grads = jax.grad(lambda *a: jnp.sum(chunked(*a).astype(jnp.float32)),
                     argnums=range(5))(*low)
    assert [g.dtype for g in grads] == [x.dtype for x in low]


def test_unit_lower_inverse():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 5, 64, 64)), -1) * 0.3
    want = jnp.linalg.inv(jnp.eye(64) + a)
    assert gap(kda.unit_lower_inverse(a), want) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    mine = jax.grad(lambda x: jnp.sum(kda.unit_lower_inverse(x) * w))(a)
    ref = jax.grad(lambda x: jnp.sum(jnp.linalg.inv(jnp.eye(64) + jnp.tril(x, -1)) * w))(a)
    assert gap(mine, ref) < 1e-4


def test_through_the_tape_with_its_counters():
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.profiler import metrics

    def counters():
        snap = metrics.get_registry().snapshot()["counters"]
        return [snap.get(f"kda.{n}", 0.0) for n in ("calls_total", "tokens_total")]
    ops = operands(9, 2, 128, 2, 16, 16)
    tensors = [paddle.to_tensor(np.asarray(x), stop_gradient=False) for x in ops]
    before = counters()
    out = F.kimi_delta_attention(*tensors, scale=SCALE)
    after = counters()
    assert [b - a for a, b in zip(before, after)] == [1.0, 256.0]
    assert gap(out._val, recurrence(*ops, SCALE)) < F32_TOL
    out.sum().backward()
    ref = jax.grad(lambda *a: jnp.sum(recurrence(*a, SCALE)), argnums=range(5))(*ops)
    for t, r in zip(tensors, ref):
        assert gap(t.grad._val, r) < F32_TOL
