"""Which kernel an op runs is a rule of code, shapes and platform
(docs/kernels.md, "Which kernel runs"): attention chooses by
`ops.attention.takes_flash`, the flash pair's tiles by
`flash_attention.tiles` ("Tiles"), the fused ops run their custom-vjp path,
and nothing between an op's entry point and `dispatch.apply` times anything.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.dispatch import unwrap
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops import (attention, fused_conv_bn, fused_ffn,
                            fused_residual_ln)
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.profiler import metrics, setup_timeline

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
# the flash pair's tiles: `flash_attention.tiles`, a rule of shapes

# (case, query shape, key shape, value shape, dtype, (block_q, block_k))
TILES = [
    ("LFM2's heads at 4096", (2, 4096, 32, 64), (2, 4096, 8, 64), (2, 4096, 8, 64),
     jnp.bfloat16, (512, 512)),
    ("Kimi Linear's latent layer at 4096", (2, 4096, 32, 192), (2, 4096, 32, 192),
     (2, 4096, 32, 128), jnp.bfloat16, (512, 512)),
    ("DeepSeek-V2-Lite's heads at 8192", (1, 8192, 16, 192), (1, 8192, 16, 192),
     (1, 8192, 16, 128), jnp.bfloat16, (1024, 512)),
    ("GPT's heads at 2048", (2, 2048, 16, 128), (2, 2048, 16, 128), (2, 2048, 16, 128),
     jnp.bfloat16, (512, 512)),
    ("a sequence shorter than a tile", (1, 256, 2, 64), (1, 256, 1, 64), (1, 256, 1, 64),
     jnp.bfloat16, (256, 256)),
    ("BERT's 128 positions", (8, 128, 12, 64), (8, 128, 12, 64), (8, 128, 12, 64),
     jnp.float32, (128, 128)),
    ("a length 512 does not divide", (1, 768, 2, 64), (1, 768, 2, 64), (1, 768, 2, 64),
     jnp.bfloat16, (256, 256)),
    ("a long length 1024 does not divide", (1, 8704, 2, 64), (1, 8704, 2, 64),
     (1, 8704, 2, 64), jnp.bfloat16, (512, 512)),
    ("float32", (1, 2048, 4, 128), (1, 2048, 4, 128), (1, 2048, 4, 128),
     jnp.float32, (512, 512)),
    ("16384 positions, the backward in spans", (1, 16384, 8, 64), (1, 16384, 2, 64),
     (1, 16384, 2, 64), jnp.bfloat16, (1024, 512)),
    ("32768 positions, the backward in spans", (1, 32768, 2, 128), (1, 32768, 2, 128),
     (1, 32768, 2, 128), jnp.bfloat16, (1024, 512)),
]


def staged_tiles(backward, q_shape, k_shape, v_shape, dtype, monkeypatch):
    """The (block_q, block_k) the public entry point hands its kernel for
    operands of these shapes: staged, never run."""
    seen = []
    kernel = "_flash_bwd_bh" if backward else "_flash_fwd_bh"

    def spy(*args, **kwargs):
        seen.append(args[-3:-1])
        raise StopIteration
    monkeypatch.setattr(fa, kernel, spy)
    q, k, v = (jax.ShapeDtypeStruct(s, dtype) for s in (q_shape, k_shape, v_shape))
    with pytest.raises(StopIteration):
        if backward:
            out = jax.ShapeDtypeStruct(q_shape[:3] + v_shape[3:], dtype)
            lse = jax.ShapeDtypeStruct((q_shape[0], q_shape[2], q_shape[1]), jnp.float32)
            jax.eval_shape(lambda *a: fa.flash_attention_bwd(*a, causal=True),
                           q, k, v, out, lse, out)
        else:
            jax.eval_shape(lambda *a: fa.flash_attention_fwd(*a, causal=True), q, k, v)
    (blocks,) = seen
    return blocks


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("case", TILES, ids=[c[0] for c in TILES])
def test_the_tiles_are_a_rule_of_shapes(case, backward, monkeypatch):
    _, q_shape, k_shape, v_shape, dtype, want = case
    assert fa.tiles(q_shape[1], k_shape[1]) == want
    # and what the kernel is handed is the rule's
    assert staged_tiles(backward, q_shape, k_shape, v_shape, dtype, monkeypatch) == want
    assert q_shape[1] % want[0] == 0 and k_shape[1] % want[1] == 0


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("case", TILES[:3], ids=[c[0] for c in TILES[:3]])
def test_the_tiles_are_the_same_on_a_tpu_as_on_the_cpu(case, backward, monkeypatch):
    _, q_shape, k_shape, v_shape, dtype, _ = case
    got = {}
    for platform in ("cpu", "tpu"):
        with monkeypatch.context() as patch:
            patch.setattr(attention, "_platform", lambda: platform)
            patch.setattr(jax, "default_backend", lambda: platform)
            assert fa._interpret() == (platform == "cpu")
            got[platform] = (fa.tiles(q_shape[1], k_shape[1]), staged_tiles(
                backward, q_shape, k_shape, v_shape, dtype, patch))
    assert got["cpu"] == got["tpu"]


def test_pinned_tiles_change_the_schedule_and_not_the_numbers():
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(1, 256, 2, 64).astype("float32")) for _ in range(3)]
    out, lse = fa.flash_attention_fwd(q, k, v, causal=True, scale=0.125)
    pinned_out, pinned_lse = fa.flash_attention_fwd(q, k, v, causal=True, scale=0.125,
                                                    block_q=64, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(pinned_out), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(pinned_lse), rtol=2e-5, atol=2e-5)
    do = jnp.asarray(rng.randn(*out.shape).astype("float32"))
    ruled = fa.flash_attention_bwd(q, k, v, out, lse, do, causal=True, scale=0.125)
    pinned = fa.flash_attention_bwd(q, k, v, out, lse, do, causal=True, scale=0.125,
                                    block_q=128, block_k=64)
    for a, b in zip(ruled, pinned):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the fused ops and the flash pair run what their names say, on the thread
# that called them

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


class Flash(nn.Layer):
    diff = attention._flash_attention_diff
    x_shape = (1, 256, 128)

    def __init__(self):
        super().__init__()
        self.qkv = nn.Linear(128, 3 * 128)

    def forward(self, x):
        q, k, v = (t.reshape([1, 256, 2, 64])
                   for t in paddle.split(self.qkv(x), 3, axis=-1))
        return attention.scaled_dot_product_attention(
            q, k, v, is_causal=True, use_pallas=True)


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


@pytest.mark.parametrize("mode", [eager, to_static_step, recomputed],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("make", [FFN, ResidualLN, ConvBN, Flash],
                         ids=lambda c: c.__name__)
def test_a_fused_op_runs_its_custom_vjp_and_searches_nothing(
        make, mode, monkeypatch):
    diff, backward_rule_calls = make.diff, []
    rule = diff.bwd

    def spied(*args):
        backward_rule_calls.append(1)
        return rule(*args)
    monkeypatch.setattr(diff, "bwd", spied)

    threads = []
    monkeypatch.setattr(threading.Thread, "start",
                        lambda thread: threads.append(thread.name))
    at = len(setup_timeline())

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
    assert threads == []
    # set-up spans of the step itself and none inside them
    records = setup_timeline()[at:]
    assert [r["parent"] for r in records] == [None] * len(records)
    assert {r["name"] for r in records} <= {
        "to_static.discover", "to_static.probe", "to_static.compile"}


# ---------------------------------------------------------------------------
# one program text for one code, whatever a clock would say

def lowered_gpt_step(monkeypatch, clock_rate):
    """The lowered text of a small GPT train step built in a process whose
    host clock runs `clock_rate` times as fast."""
    import time

    from paddle_tpu.jit.to_static import _flatten_tensors
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    real = time.perf_counter
    with monkeypatch.context() as patch:
        patch.setattr(time, "perf_counter", lambda: real() * clock_rate)
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
        return jax.jit(prog.pure_fn).lower(
            tuple(t._val for t in prog.mutated),
            tuple(t._val for t in prog.ro),
            tuple(t._val for t in _flatten_tensors(((x, y), {}), []))).as_text()


def test_a_gpt_step_lowers_to_one_text_under_two_clocks(monkeypatch):
    assert lowered_gpt_step(monkeypatch, 1.0) == lowered_gpt_step(monkeypatch, 1000.0)


def test_the_registry_holds_no_search_and_no_phase_of_one():
    # after everything above ran in this process: eager calls, to_static
    # steps and rematerialised steps, the flash pair among them
    counters = metrics.get_registry().snapshot()["counters"]
    assert not [name for name in counters if name.startswith("autotune.")]
    phases = {name.partition('phase="')[2].rstrip('"}') for name in counters
              if name.startswith("compile.requests_total{")}
    assert phases and phases <= {"discover", "probe", "compile", "eager", "user"}
