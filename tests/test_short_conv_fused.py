"""The short convolution's pass (ops/pallas/short_conv.py) against the jnp rule
of `F.short_conv_silu`, which stays the path off the TPU and is the oracle
here: the kernels interpreted, the forward bit for bit against the two-op form
it replaced (`l2_norm` over the heads of `short_conv_silu`) and against
`short_conv_silu` alone, the backward against `jax.vjp` of the jnp rule, the
op inside a rematerialised region, and which widths take which path.

Both sides of a comparison are staged (`jax.jit`): the CPU contracts a
multiply and an add inside a fusion and not between two eager operations, so
an eager oracle differs from itself staged in the last bit; the TPU has no
such contraction."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from paddle_tpu.distributed.fleet.utils import recompute  # noqa: E402
from paddle_tpu.nn.functional import conv  # noqa: E402
from paddle_tpu.ops import attention  # noqa: E402
from paddle_tpu.ops.pallas import short_conv  # noqa: E402
from paddle_tpu.profiler import metrics  # noqa: E402

CHANNELS, HEAD, EPS = 256, 128, 1e-6
# two whole blocks of 256 rows, and a block and a bit: padded inside
SEQS = [pytest.param(512, id="two-blocks"), pytest.param(296, id="a-block-and-40-rows")]
DTYPES = [pytest.param(jnp.bfloat16, id="bf16"), pytest.param(jnp.float32, id="f32")]
TAPS = [pytest.param(4, id="K4"), pytest.param(3, id="K3")]
NORMS = [pytest.param(HEAD, id="norm"), pytest.param(None, id="plain")]


def operands(dtype, seq, k, channels=CHANNELS, seed=0):
    """x, w and a cotangent: batch 2, so that row 1's first positions would
    see row 0's last if the taps ran over the boundary."""
    kx, kw, kd = jax.random.split(jax.random.PRNGKey(seed + seq + k), 3)
    x = jax.random.normal(kx, (2, seq, channels), jnp.float32).astype(dtype)
    w = jax.random.uniform(kw, (channels, k), jnp.float32, -0.5, 0.5).astype(dtype)
    dy = jax.random.normal(kd, (2, seq, channels), jnp.float32).astype(dtype)
    return x, w, dy


def two_op_form(x, w, norm):
    """What the mixer called before: the public ops, one after the other."""
    y = F.short_conv_silu(paddle.Tensor(x), paddle.Tensor(w))
    if norm is None:
        return y._val
    heads = F.l2_norm(y.reshape([*x.shape[:2], -1, norm]), epsilon=EPS)
    return heads.reshape(list(x.shape))._val


@pytest.mark.parametrize("norm", NORMS)
@pytest.mark.parametrize("k", TAPS)
@pytest.mark.parametrize("seq", SEQS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_forward_bit_for_bit(dtype, seq, k, norm):
    x, w, _ = operands(dtype, seq, k)
    got = short_conv.stream_forward(x, w, norm, EPS, interpret=True)
    want = jax.jit(two_op_form, static_argnums=2)(x, w, norm)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert bool(jnp.all(got == want))
    # and the jnp rule the op keeps is that form
    rule = jax.jit(conv._silu_taps, static_argnums=(2, 3))(x, w, norm, EPS)
    assert bool(jnp.all(rule == want))
    # row 1 starts from zeros: its first rows are those of row 1 alone
    alone = short_conv.stream_forward(x[1:], w, norm, EPS, interpret=True)
    assert bool(jnp.all(got[1, :8] == alone[0, :8]))


@pytest.mark.parametrize("norm", NORMS)
@pytest.mark.parametrize("k", TAPS)
@pytest.mark.parametrize("seq", SEQS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_backward_against_autodiff_of_the_rule(dtype, seq, k, norm):
    x, w, dy = operands(dtype, seq, k)
    dx, dw = short_conv.stream_backward(x, w, dy, norm, EPS, interpret=True)
    want_dx, want_dw = jax.jit(
        lambda a, b, g: jax.vjp(lambda p, q: conv._silu_taps(p, q, norm, EPS), a, b)[1](g)
    )(x, w, dy)
    assert dx.dtype == x.dtype and dw.dtype == w.dtype and dw.shape == w.shape
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    gap, size = np.abs(f32(dx) - f32(want_dx)), np.abs(f32(want_dx))
    if dtype == jnp.bfloat16:
        # one ulp of the value. With the norm its cotangent is rounded to
        # bfloat16 at the ops' boundary; a last float32 bit flips that rounding
        # here and there, and four taps that cancel show the flip as more
        off = gap > 2.0 ** -7 * size
        assert off.mean() < (1e-4 if norm else 1e-30)
        assert (gap <= 2.0 ** -7 * (size + size.mean())).all()
    else:   # float32 rounding of a sum of K terms that may cancel
        assert (gap <= 2.0 ** -19 * (size + size.mean())).all()
    # float32 sums of 2 * seq rows in another order (bfloat16: and those flips)
    gap = np.abs(f32(dw) - f32(want_dw)).max() / np.abs(f32(want_dw)).max()
    assert gap < (2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5)


def grads(x, w, dy, norm, region):
    xt, wt = paddle.Tensor(x, stop_gradient=False), paddle.Tensor(w, stop_gradient=False)

    def stream(v):
        return F.short_conv_silu(v, wt, norm_head_dim=norm, epsilon=EPS)
    y = recompute(stream, xt) if region else stream(xt)
    (y * paddle.Tensor(dy)).sum().backward()
    return y._val, xt.grad._val, wt.grad._val


@pytest.mark.parametrize("norm", NORMS)
def test_in_a_rematerialised_region_the_same_gradients(monkeypatch, norm):
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    x, w, dy = operands(jnp.bfloat16, 296, 4)
    before = metrics.get_registry().snapshot()["counters"].get("short_conv.kernel_total", 0.0)
    plain, inside = grads(x, w, dy, norm, False), grads(x, w, dy, norm, True)
    assert metrics.get_registry().snapshot()["counters"]["short_conv.kernel_total"] > before
    for a, b in zip(plain, inside):
        assert bool(jnp.all(a == b))
    want = jax.vjp(lambda p, q: conv._silu_taps(p, q, norm, EPS), x, w)[1](dy)
    assert float(jnp.max(jnp.abs((plain[1] - want[0]).astype(jnp.float32)))) < 2.0 ** -5


@pytest.mark.parametrize("platform,dtype,channels,k,norm,path", [
    pytest.param("tpu", jnp.bfloat16, 256, 4, 128, "kernel", id="bf16-heads-of-128"),
    pytest.param("tpu", jnp.float32, 256, 3, None, "kernel", id="f32-plain-K3"),
    pytest.param("tpu", jnp.bfloat16, 192, 4, None, "xla", id="192-channels"),
    pytest.param("tpu", jnp.float16, 256, 4, None, "xla", id="float16"),
    pytest.param("tpu", jnp.bfloat16, 256, 4, 64, "xla", id="heads-of-64"),
    pytest.param("cpu", jnp.bfloat16, 256, 4, 128, "xla", id="off-the-tpu"),
])
def test_the_path_is_read_from_the_input(monkeypatch, platform, dtype, channels, k, norm, path):
    monkeypatch.setattr(attention, "_platform", lambda: platform)
    x, w, _ = operands(dtype, 40, k, channels)
    names = ("short_conv.kernel_total", "short_conv.xla_total")
    counters = metrics.get_registry().snapshot()["counters"]
    before = [counters.get(n, 0.0) for n in names]
    got = F.short_conv_silu(paddle.Tensor(x), paddle.Tensor(w), norm_head_dim=norm,
                            epsilon=EPS)._val
    counters = metrics.get_registry().snapshot()["counters"]
    moved = [counters.get(n, 0.0) - b for n, b in zip(names, before)]
    assert moved == ([1.0, 0.0] if path == "kernel" else [0.0, 1.0])
    want = conv._silu_taps(x, w, norm, EPS)
    assert got.dtype == x.dtype
    assert float(jnp.max(jnp.abs((got - want).astype(jnp.float32)))) < 2.0 ** -6
