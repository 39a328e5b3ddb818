"""Which kernel an op runs is a rule of code, shapes and platform
(docs/kernels.md, "Which kernel runs"): attention chooses by
`ops.attention.takes_flash`, the fused ops run their custom-vjp path, and
nothing between an op's entry point and `dispatch.apply` times anything.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops import (attention, autotune, fused_conv_bn, fused_ffn,
                            fused_residual_ln)
from paddle_tpu.profiler import metrics

K = attention.FLASH_MIN_SEQ_K
GIB = 2 ** 30


def attention_counters():
    got = metrics.get_registry().snapshot()["counters"]
    return {path: got.get("attention.%s_total" % path, 0)
            for path in ("flash", "xla")}


def traced_attention(q_shape, k_shape, dtype=jnp.bfloat16, mask=False,
                     **kwargs):
    """One call of the op on operands of these shapes, staged and never
    run (the counters move once a trace): a key length of 4096 costs
    nothing here."""
    shapes = [jax.ShapeDtypeStruct(q_shape, dtype),
              jax.ShapeDtypeStruct(k_shape, dtype),
              jax.ShapeDtypeStruct(k_shape, dtype)]
    if mask:
        shapes.append(jax.ShapeDtypeStruct(
            (q_shape[0], 1, q_shape[1], k_shape[1]), jnp.bool_))

    def call(q, k, v, *m):
        return unwrap(attention.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(v),
            attn_mask=Tensor(m[0]) if m else None, **kwargs))
    return jax.eval_shape(call, *shapes)


# (case, platform, device memory, query shape, key/value shape, call, path)
RULE = [
    ("at the constant", "tpu", 16 * GIB, (1, K, 2, 64), (1, K, 2, 64), {}, "flash"),
    ("one tile under the constant", "tpu", 16 * GIB, (1, K - 128, 2, 64),
     (1, K - 128, 2, 64), {}, "xla"),
    ("twice the constant", "tpu", 16 * GIB, (1, 2 * K, 1, 64), (1, 2 * K, 1, 64),
     {}, "flash"),
    ("cell 1's heads at 1024 keys", "tpu", 16 * GIB, (2, 1024, 16, 128),
     (2, 1024, 16, 128), {"is_causal": True}, "xla"),
    ("float32 at the constant", "tpu", 16 * GIB, (1, K, 1, 64), (1, K, 1, 64),
     {"dtype": jnp.float32, "is_causal": True}, "flash"),
    ("no TPU", "cpu", 16 * GIB, (1, 2 * K, 2, 64), (1, 2 * K, 2, 64), {}, "xla"),
    ("a mask", "tpu", 16 * GIB, (1, K, 2, 64), (1, K, 2, 64), {"mask": True}, "xla"),
    ("dropout", "tpu", 16 * GIB, (1, K, 2, 64), (1, K, 2, 64),
     {"dropout_p": 0.1}, "xla"),
    ("dropout outside training", "tpu", 16 * GIB, (1, K, 2, 64), (1, K, 2, 64),
     {"dropout_p": 0.1, "training": False}, "flash"),
    ("a head size the kernels do not tile", "tpu", 16 * GIB, (1, K, 2, 80),
     (1, K, 2, 80), {}, "xla"),
    ("fewer key/value heads, long", "tpu", 16 * GIB, (1, K, 4, 64), (1, K, 2, 64),
     {"is_causal": True}, "flash"),
    ("fewer key/value heads, short", "tpu", 16 * GIB, (1, 512, 4, 64),
     (1, 512, 2, 64), {"is_causal": True}, "xla"),
    # 4 x (2 heads x 256 x 256 x 4 B) = 2 MiB of scores against half of 1 MiB
    ("scores XLA could not hold, short", "tpu", 2 ** 20, (1, 256, 2, 64),
     (1, 256, 1, 64), {"is_causal": True}, "flash"),
    ("the same under 256 query positions", "tpu", 2 ** 10, (1, 128, 2, 64),
     (1, 128, 1, 64), {}, "xla"),
    # LFM2's attention layer: 4 x (2 x 32 heads x 4096^2 x 4 B) = 17 GB
    ("LFM2's heads at 4096", "tpu", 16 * GIB, (2, 4096, 32, 64), (2, 4096, 8, 64),
     {"is_causal": True}, "flash"),
    ("use_pallas=True under every threshold", "cpu", 16 * GIB, (1, 256, 2, 64),
     (1, 256, 2, 64), {"use_pallas": True}, "flash"),
    ("use_pallas=False over every threshold", "tpu", 2 ** 10, (1, 2 * K, 2, 64),
     (1, 2 * K, 2, 64), {"use_pallas": False}, "xla"),
]


@pytest.mark.parametrize("case", RULE, ids=[c[0] for c in RULE])
def test_attention_takes_its_path_from_shapes_and_platform(case, monkeypatch):
    _, platform, memory, q_shape, k_shape, call, path = case
    monkeypatch.setattr(attention, "_platform", lambda: platform)
    monkeypatch.setattr(attention, "_device_memory_bytes", lambda: memory)
    before = attention_counters()
    out = traced_attention(q_shape, k_shape, **call)
    assert out.shape == q_shape
    after = attention_counters()
    moved = {p: after[p] - before[p] for p in after}
    assert moved == {"flash": int(path == "flash"), "xla": int(path == "xla")}


def test_the_constant_keeps_both_cells_on_their_side(monkeypatch):
    # the benchmark has a cell on each side of the one choice that is real:
    # 1024 keys stay with XLA and 4096 go to the flash pair, whatever the
    # device's memory
    assert 1024 < attention.FLASH_MIN_SEQ_K <= 4096
    monkeypatch.setattr(attention, "_device_memory_bytes", lambda: 2 ** 50)
    args = (jnp.bfloat16, False, 0.0, "tpu")
    assert not attention.takes_flash((2, 1024, 16, 128), (2, 1024, 16, 128), *args)
    assert attention.takes_flash((2, 4096, 32, 64), (2, 4096, 8, 64), *args)


def test_use_pallas_true_refuses_a_mask_and_dropout():
    with pytest.raises(ValueError, match="incompatible with attn_mask"):
        traced_attention((1, 256, 2, 64), (1, 256, 2, 64), mask=True,
                         use_pallas=True)
    with pytest.raises(ValueError, match="incompatible with attn_mask"):
        traced_attention((1, 256, 2, 64), (1, 256, 2, 64), dropout_p=0.5,
                         use_pallas=True)


# ---------------------------------------------------------------------------
# the fused ops run what their names say, and ask no tuner

class FFN(nn.Layer):
    diff = fused_ffn._fused_ffn_diff
    x_shape = (2, 8, 16)

    def __init__(self):
        super().__init__()
        self.fc1, self.fc2 = nn.Linear(16, 32), nn.Linear(32, 16)

    def forward(self, x):
        return fused_ffn.fused_ffn(x, self.fc1.weight, self.fc1.bias, self.fc2.weight,
                                   self.fc2.bias, activation="gelu_tanh")


class ResidualLN(nn.Layer):
    diff = fused_residual_ln._fused_residual_ln_diff
    x_shape = (2, 8, 16)

    def __init__(self):
        super().__init__()
        self.proj, self.norm = nn.Linear(16, 16), nn.LayerNorm(16)

    def forward(self, x):
        z, out = fused_residual_ln.fused_residual_ln(
            x, self.proj(x), self.norm.weight, self.norm.bias,
            return_residual=True)
        return z + out


class ConvBN(nn.Layer):
    diff = fused_conv_bn._fused_conv_bn_diff
    x_shape = (2, 4, 8, 8)

    def __init__(self):
        super().__init__()
        self.conv = nn.Conv2D(4, 4, 3, padding=1, bias_attr=False)
        self.bn = nn.BatchNorm2D(4)

    def forward(self, x):
        return fused_conv_bn.fused_conv_bn(
            x, self.conv.weight, self.bn.weight, self.bn.bias, stride=1,
            padding=1)


def eager(layer, x):
    layer(x).sum().backward()


def to_static_step(layer, x):
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=layer.parameters())

    @paddle.jit.to_static
    def step(x):
        loss = layer(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step(x)              # the eager discovery pass
    return lambda: step(x)   # the call that traces and compiles


def recomputed(layer, x):
    from paddle_tpu.distributed.fleet.utils import recompute
    recompute(layer, x).sum().backward()


@pytest.fixture
def tuner_that_may_not_time(tmp_path):
    """A tuner on a placement that could search, whose every measurement
    fails: a choice that timed its candidates would raise AutotuneError."""
    def measure(fn, args):
        raise AssertionError("a kernel choice timed a candidate")
    old = autotune.set_tuner(autotune.Autotuner(
        cache_dir=str(tmp_path / "autotune"), searchable=lambda: True,
        measure_fn=measure))
    autotune.reset_counters()
    yield autotune.get_tuner()
    autotune.set_tuner(old)


@pytest.mark.parametrize("mode", [eager, to_static_step, recomputed],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("make", [FFN, ResidualLN, ConvBN],
                         ids=lambda c: c.__name__)
def test_a_fused_op_runs_its_custom_vjp_and_searches_nothing(
        make, mode, tuner_that_may_not_time, monkeypatch, tmp_path):
    diff, backward_rule_calls = make.diff, []
    rule = diff.bwd

    def spied(*args):
        backward_rule_calls.append(1)
        return rule(*args)
    monkeypatch.setattr(diff, "bwd", spied)

    paddle.seed(0)
    layer = make()
    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal(make.x_shape).astype("float32"))
    x.stop_gradient = False
    staged = mode(layer, x)
    if staged is not None:
        # what the compiled step holds is what its trace differentiates
        del backward_rule_calls[:]
        staged()
    assert backward_rule_calls, "the op's own backward rule never ran"
    assert autotune.counters()["searches"] == 0
    assert autotune.counters()["candidate_failures"] == 0
    assert tuner_that_may_not_time.decisions() == {}
    assert not (tmp_path / "autotune").exists()


# ---------------------------------------------------------------------------
# one program text for one code, whatever a clock would say

def lowered_gpt_step(cache_dir, fastest):
    """The lowered text of a small GPT train step built under a fresh tuner
    on a placement that could search, whose clock prefers the `fastest`-th
    candidate of whatever it is asked to time."""
    from paddle_tpu.jit.to_static import _flatten_tensors
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    def measure(fn, args):
        measure.calls += 1
        return 1.0 if measure.calls % 2 == fastest else 2.0
    measure.calls = 0
    old = autotune.set_tuner(autotune.Autotuner(
        cache_dir=str(cache_dir), searchable=lambda: True, measure_fn=measure))
    try:
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64, max_position_embeddings=32, dropout=0.0))
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())

        @paddle.jit.to_static
        def step(x, y):
            loss = model(x, labels=y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        ids = np.arange(64, dtype="int32").reshape(2, 32) % 128
        x, y = paddle.to_tensor(ids), paddle.to_tensor(ids)
        step(x, y)
        (prog,) = step.programs.values()
        step._build(prog, (x, y), {})
        text = jax.jit(prog.pure_fn).lower(
            tuple(t._val for t in prog.mutated),
            tuple(t._val for t in prog.ro),
            tuple(t._val for t in _flatten_tensors(((x, y), {}), []))).as_text()
        return text, measure.calls
    finally:
        autotune.set_tuner(old)


def test_a_gpt_step_lowers_to_one_text_under_two_clocks(tmp_path):
    first, timed_first = lowered_gpt_step(tmp_path / "a", fastest=0)
    second, timed_second = lowered_gpt_step(tmp_path / "b", fastest=1)
    assert timed_first == timed_second == 0
    assert first == second
    assert not (tmp_path / "a").exists() and not (tmp_path / "b").exists()
