"""The operators the LFM2 decoder brought, each against the plain reference's
lines (benchmarks/reference/lfm2_moe.py), and the model's loss and gradients
against the reference's, seeded, at a small size on the CPU."""
import json
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
import rematerialised_step  # noqa: E402
from benchmarks import harness  # noqa: E402
from benchmarks.families import lfm2_moe as family  # noqa: E402
from benchmarks.reference import lfm2_moe as ref  # noqa: E402

RNG = np.random.default_rng(11)


def normal(*shape):
    return RNG.standard_normal(shape).astype(np.float32)


def grads_of(fn, *arrays):
    """(value, gradients) of sum(sin(fn(...))) through the program's tape."""
    tensors = [paddle.to_tensor(a, stop_gradient=False) for a in arrays]
    out = fn(*tensors)
    out = out if isinstance(out, (tuple, list)) else [out]
    total = sum(paddle.sum(paddle.sin(o)) for o in out)
    total.backward()
    return [np.asarray(o._val) for o in out], [np.asarray(t.grad._val) for t in tensors]


def reference_grads(fn, *arrays):
    def total(*a):
        out = fn(*a)
        out = out if isinstance(out, (tuple, list)) else [out]
        return sum(jnp.sum(jnp.sin(o)) for o in out), out
    (_, out), g = jax.value_and_grad(total, argnums=tuple(range(len(arrays))),
                                     has_aux=True)(*map(jnp.asarray, arrays))
    return [np.asarray(o) for o in out], [np.asarray(x) for x in g]


def assert_same(got, want, tol=2e-5):
    for a, b in zip(got[0] + got[1], want[0] + want[1]):
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


def test_rms_norm_matches_the_reference():
    x, g = normal(2, 5, 16), 1 + 0.1 * normal(16)
    assert_same(grads_of(lambda x, g: F.rms_norm(x, g, 1e-5), x, g),
                reference_grads(lambda x, g: ref.rms_norm(x, g, 1e-5), x, g))
    layer = paddle.nn.RMSNorm(16, epsilon=1e-5)
    np.testing.assert_allclose(
        np.asarray(layer(paddle.to_tensor(x))._val),
        np.asarray(ref.rms_norm(jnp.asarray(x), jnp.ones(16), 1e-5)), rtol=1e-5, atol=1e-5)


def test_rms_norm_statistics_are_float32_under_bf16():
    x = (100 * normal(4, 256)).astype(jnp.bfloat16)
    out = F.rms_norm(paddle.to_tensor(x), paddle.ones([256]).astype("bfloat16"))
    assert out.dtype == paddle.bfloat16
    want = ref.rms_norm(jnp.asarray(x, jnp.float32), jnp.ones(256), 1e-5)
    np.testing.assert_allclose(np.asarray(out._val, np.float32), np.asarray(want),
                               rtol=1e-2, atol=1e-2)


def test_rotary_positions_match_the_reference():
    q, k = normal(2, 12, 4, 8), normal(2, 12, 2, 8)
    assert_same(
        grads_of(lambda q, k: F.rotary_position_embedding(q, k, theta=1e6), q, k),
        reference_grads(lambda q, k: (ref.rotate(q, 1e6), ref.rotate(k, 1e6)), q, k))
    # position t of a longer sequence is position 0 of one that starts at t
    late, _ = F.rotary_position_embedding(
        paddle.to_tensor(q[:, 5:]), paddle.to_tensor(k[:, 5:]), theta=1e6,
        position_offset=5)
    whole, _ = F.rotary_position_embedding(paddle.to_tensor(q), paddle.to_tensor(k),
                                           theta=1e6)
    np.testing.assert_allclose(np.asarray(late._val), np.asarray(whole._val)[:, 5:],
                               rtol=1e-5, atol=1e-5)


def test_swiglu_matches_the_reference():
    a, b = normal(3, 7, 10), normal(3, 7, 10)
    assert_same(grads_of(F.swiglu, a, b),
                reference_grads(lambda a, b: ref.silu(a) * b, a, b))
    both = np.concatenate([a, b], axis=-1)
    np.testing.assert_allclose(np.asarray(F.swiglu(paddle.to_tensor(both))._val),
                               np.asarray(ref.silu(jnp.asarray(a)) * b), rtol=1e-6, atol=1e-6)


def test_short_conv_forward_and_gradient_match_the_reference():
    h, k = 8, 3
    x, w_in, taps, w_out = normal(2, 9, h), normal(h, 3 * h), normal(h, k), normal(h, h)
    layer = paddle.nn.ShortConv(h, k)

    def program(x, w_in, taps, w_out):
        return F.linear(F.short_conv(F.linear(x, w_in), taps), w_out)

    def reference(x, w_in, taps, w_out):
        p = {"l0.conv_in_w": w_in, "l0.conv_k": taps, "l0.conv_out_w": w_out}
        return ref.short_conv(p, "l0.", x, jnp.matmul)

    assert_same(grads_of(program, x, w_in, taps, w_out),
                reference_grads(reference, x, w_in, taps, w_out), tol=1e-4)
    assert layer(paddle.to_tensor(x)).shape == [2, 9, h]
    assert sorted(p.shape for p in layer.parameters()) == [[h, k], [h, h], [h, 3 * h]]


def test_short_conv_is_causal_and_starts_from_zero():
    h = 4
    bcx, taps = normal(1, 6, 3 * h), normal(h, 3)
    out = np.asarray(F.short_conv(paddle.to_tensor(bcx), paddle.to_tensor(taps))._val)
    later = bcx.copy()
    later[:, 4:] += 1.0                               # the future changes
    out2 = np.asarray(F.short_conv(paddle.to_tensor(later), paddle.to_tensor(taps))._val)
    np.testing.assert_array_equal(out[:, :4], out2[:, :4])
    b, c, x = np.split(bcx, 3, axis=-1)
    np.testing.assert_allclose(out[0, 0], c[0, 0] * taps[:, 2] * (b * x)[0, 0],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", ["xla", "flash"])
def test_grouped_heads_through_sdpa_match_the_reference(path):
    from paddle_tpu.ops.attention import scaled_dot_product_attention
    b, s, nq, nkv, d = 2, 128, 4, 2, 64
    q, k, v = normal(b, s, nq, d), normal(b, s, nkv, d), normal(b, s, nkv, d)

    def program(q, k, v):
        return scaled_dot_product_attention(q, k, v, is_causal=True,
                                            use_pallas=path == "flash")

    def reference(q, k, v):
        # the reference's attention lines, one key/value head at a time
        group, causal = nq // nkv, jnp.tril(jnp.ones((s, s), bool))
        out = []
        for j in range(nkv):
            qg = q[:, :, j * group:(j + 1) * group].transpose(0, 2, 1, 3)
            scores = qg @ k[:, :, j].transpose(0, 2, 1)[:, None] / np.sqrt(d)
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            out.append((probs @ v[:, :, j][:, None]).transpose(0, 2, 1, 3))
        return jnp.concatenate(out, axis=2)

    with jax.default_matmul_precision("highest"):
        assert_same(grads_of(program, q, k, v), reference_grads(reference, q, k, v),
                    tol=2e-4)


def test_query_heads_must_divide_over_key_value_heads():
    from paddle_tpu.ops.attention import scaled_dot_product_attention
    q, kv = paddle.to_tensor(normal(1, 8, 3, 8)), paddle.to_tensor(normal(1, 8, 2, 8))
    with pytest.raises(ValueError, match="do not divide"):
        scaled_dot_product_attention(q, kv, kv)


def test_flash_is_taken_unmeasured_where_the_scores_cannot_be_probed(monkeypatch):
    # the rule's memory clause, on grouped heads under FLASH_MIN_SEQ_K keys:
    # where XLA's attention can hold its float32 scores it runs, where it
    # cannot the flash kernel does, and neither side is ever timed
    from paddle_tpu.ops import attention
    from paddle_tpu.profiler import metrics
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    counters = lambda: metrics.get_registry().snapshot()["counters"]  # noqa: E731
    q = paddle.to_tensor(normal(1, 256, 2, 64))
    kv = paddle.to_tensor(normal(1, 256, 1, 64))
    assert kv.shape[1] < attention.FLASH_MIN_SEQ_K
    before = counters()
    monkeypatch.setattr(attention, "_device_memory_bytes", lambda: 16 * 2 ** 30)
    on_xla = attention.scaled_dot_product_attention(q, kv, kv, is_causal=True)
    # 4 x (2 heads x 256 x 256 x 4 B) = 2 MiB of scores against half of 1 MiB
    monkeypatch.setattr(attention, "_device_memory_bytes", lambda: 2 ** 20)
    on_flash = attention.scaled_dot_product_attention(q, kv, kv, is_causal=True)
    after = counters()
    for name in ("attention.xla_total", "attention.flash_total"):
        assert after.get(name, 0) - before.get(name, 0) == 1, name
    assert "attention.probe_skipped_total" not in after
    np.testing.assert_allclose(np.asarray(on_flash._val), np.asarray(on_xla._val),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the model against the reference

def small_cfg(**changes):
    with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=96, moe_intermediate_size=32, vocab_size=600,
               num_experts=4, held_experts=[0, 1, 2, 3], weights_dtype="float32",
               recompute=False)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg.update(changes)
    return cfg


def seeded(cfg, seed):
    p = harness.init_params(ref.param_shapes(cfg), seed, "float32")
    # eight times the initialisation's scale, so that every branch matters
    return {k: 8 * v if v.ndim >= 2 else v for k, v in p.items()}


def built(cfg, p):
    model = family.build_model(cfg)
    names = family.program_names(cfg)
    missing, unexpected = model.set_state_dict(
        {names[k]: paddle.Tensor(v) for k, v in p.items()})
    assert not missing and not unexpected
    return model, names


@pytest.mark.parametrize("recompute", [False, True], ids=["plain", "remat"])
def test_model_loss_and_gradients_match_the_reference(recompute):
    cfg = small_cfg(recompute=recompute)
    p = seeded(cfg, 3)
    x, y = family.Stream(cfg, {"batch": 2, "seq": 128}, 3).next()
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(
            lambda p: ref.loss_fn(p, jnp.asarray(x), jnp.asarray(y), cfg))(p)
    model, names = built(cfg, p)
    loss = family.loss_of(model, paddle.to_tensor(x), paddle.to_tensor(y))
    loss.backward()
    assert float(loss.item()) == pytest.approx(float(want), rel=1e-5)
    state = model.state_dict()
    for leaf, g in want_g.items():
        got = state[names[leaf]].grad
        if leaf.endswith("expert_bias"):
            assert got is None and not np.asarray(g).any()   # takes no gradient
            continue
        err = np.linalg.norm(np.asarray(got._val) - np.asarray(g))
        assert err <= 2e-5 * max(np.linalg.norm(np.asarray(g)), 1e-3), leaf


def test_a_rematerialised_model_is_the_plain_model():
    """A `conv` block as one region, an attention block as two regions round
    its attention core with the core on the tape: the plain blocks'
    arithmetic, in the loss and in every leaf's gradient."""
    p = seeded(small_cfg(), 3)
    x, y = map(paddle.to_tensor, family.Stream(small_cfg(), {"batch": 2, "seq": 128}, 3).next())
    got = {}
    for recompute in (False, True):
        model, names = built(small_cfg(recompute=recompute), p)
        assert [b.is_conv for b in model.model.layers].count(False) == 1
        loss = family.loss_of(model, x, y)
        got[recompute] = float(loss.item()), rematerialised_step.grads_by_leaf(
            model, names, loss)
    (loss, grads), (loss_r, grads_r) = got[False], got[True]
    assert abs(loss_r - loss) <= 1e-6 * loss
    rematerialised_step.assert_the_same_gradients(grads, grads_r, tol=4e-6)


@pytest.fixture(scope="module")
def traced_step():
    """One training step over the cell's five rematerialised blocks (four
    `conv`, one attention), heads of 64 on a platform rule that says TPU so
    that attention takes the flash pair."""
    with pytest.MonkeyPatch.context() as patch:
        rematerialised_step.flash_on_a_cpu(patch)
        cfg = small_cfg(recompute=True, hidden_size=128, num_attention_heads=2,
                        num_key_value_heads=1)
        model, _ = built(cfg, seeded(cfg, 3))
        x, y = map(paddle.to_tensor, family.Stream(cfg, {"batch": 2, "seq": 128}, 3).next())
        return rematerialised_step.traced_step(model, family.loss_of, x, y)


def test_a_rematerialised_step_stages_the_scopes(traced_step):
    """`flash_attention`, the attention core, on forward and backward
    instructions and on none of a rerun: the core is outside the attention
    block's two regions. The products, the norms, the rotation, the short
    convolution and the expert layer are inside a region, on forward, rerun
    and backward instructions."""
    names = traced_step["names"]
    assert rematerialised_step.passes_of(names, "flash_attention") == {"forward", "backward"}
    assert "transpose(jvp(jvp(flash_attention)))" not in traced_step["text"]
    for scope in ("linear", "rms_norm", "rope", "short_conv", "moe_experts"):
        assert rematerialised_step.passes_of(names, scope) == {
            "forward", "rerun", "backward"}, scope
    assert "checkpoint" not in traced_step["text"]  # a custom_vjp region keeps the names


@pytest.mark.parametrize("which", ["eager", "traced"])
def test_a_rematerialised_step_runs_its_attention_core_once(traced_step, which):
    """A pass of the step's body (the eager discovery pass; each trace of
    the step program) one flash forward for the one attention layer: none in
    a region's discovery, first run or rerun, which made it three a pass
    while the block was one region."""
    passes = traced_step["passes"][which]
    assert passes > 0
    assert traced_step["moved"][which]["attention.flash_total"] == passes


def test_the_model_is_exported_and_trains_under_to_static():
    from paddle_tpu.text.models import LFM2Config, LFM2ForCausalLM
    paddle.seed(7)
    model = LFM2ForCausalLM(LFM2Config(
        vocab_size=600, hidden_size=64, num_layers=4, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2))
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, multi_precision=True,
                                 parameters=model.parameters())

    @paddle.jit.to_static
    def step(x, y):
        loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss.astype("float32")

    stream = family.Stream({}, {"batch": 2, "seq": 128}, 7)
    losses = [float(step(*map(paddle.to_tensor, stream.next())).item())
              for _ in range(12)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    assert len(step.programs) == 1
    # the expert bias took no step, the counters moved with every step
    moe = model.model.layers[1].feed_forward
    assert not np.asarray(moe.expert_bias._val, np.float32).any()
    assert float(moe.calls_total._val) == 12
    assert float(moe.rows_total._val) == 12 * 2 * 128 * 2     # every expert held
