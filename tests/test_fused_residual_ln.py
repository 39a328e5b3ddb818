"""fused_residual_ln parity: forward vs the unfused composition, backward
vs float64 autodiff truth (the fused-op test methodology established for
fused_conv_bn/fused_ffn). Reference analog:
operators/fused/fused_bias_dropout_residual_layer_norm_op.cu."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops.fused_residual_ln import fused_residual_ln


def _mk(rng, shape, dtype="float32"):
    t = paddle.to_tensor(rng.randn(*shape).astype(dtype))
    t.stop_gradient = False
    return t


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def _f64_truth(x_np, y_np, w_np, b_np, eps=1e-5):
    """Autodiff of the unfused composition in float64 — ground truth."""
    import jax
    import jax.numpy as jnp

    def f(x, y, w, b):
        z = x + y
        mean = jnp.mean(z, axis=-1, keepdims=True)
        var = jnp.var(z, axis=-1, keepdims=True)
        out = (z - mean) * jax.lax.rsqrt(var + eps) * w + b
        return jnp.sum(jnp.tanh(out))

    with jax.enable_x64(True):
        args = [jnp.asarray(np.asarray(a, np.float64))
                for a in (x_np, y_np, w_np, b_np)]
        return jax.grad(f, argnums=(0, 1, 2, 3))(*args)


def test_fwd_matches_unfused_f32_bitwise():
    rng = np.random.RandomState(0)
    x, y = _mk(rng, (2, 5, 32)), _mk(rng, (2, 5, 32))
    w = paddle.to_tensor((rng.rand(32) + 0.5).astype("float32"))
    b = paddle.to_tensor(rng.randn(32).astype("float32"))
    out = fused_residual_ln(x, y, w, b)
    ref = F.layer_norm(x + y, 32, w, b)
    # identical f32 association (two-pass var, (z-mean)*rstd*w+b)
    np.testing.assert_array_equal(out.numpy(), ref.numpy())


def test_pre_mode_returns_stream_and_out():
    rng = np.random.RandomState(1)
    x, y = _mk(rng, (2, 4, 16)), _mk(rng, (2, 4, 16))
    w = paddle.to_tensor((rng.rand(16) + 0.5).astype("float32"))
    b = paddle.to_tensor(rng.randn(16).astype("float32"))
    z, out = fused_residual_ln(x, y, w, b, return_residual=True)
    np.testing.assert_array_equal(z.numpy(), (x + y).numpy())
    np.testing.assert_array_equal(out.numpy(),
                                  F.layer_norm(x + y, 16, w, b).numpy())


@pytest.mark.parametrize("return_residual", [False, True])
def test_bwd_close_to_f64_truth(return_residual):
    rng = np.random.RandomState(2)
    x_np = rng.randn(2, 6, 48).astype("float32")
    y_np = rng.randn(2, 6, 48).astype("float32")
    w_np = (rng.rand(48) + 0.5).astype("float32")
    b_np = (rng.randn(48) * 0.2).astype("float32")
    x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
    w, b = paddle.to_tensor(w_np), paddle.to_tensor(b_np)
    for t in (x, y, w, b):
        t.stop_gradient = False
    if return_residual:
        z, out = fused_residual_ln(x, y, w, b, return_residual=True)
        # drive BOTH outputs so the dz_in + LN-backward sum path is covered
        (out.tanh().sum() + 0.3 * z.tanh().sum()).backward()

        import jax
        import jax.numpy as jnp

        def f(xv, yv, wv, bv):
            zz = xv + yv
            mean = jnp.mean(zz, axis=-1, keepdims=True)
            var = jnp.var(zz, axis=-1, keepdims=True)
            oo = (zz - mean) * jax.lax.rsqrt(var + 1e-5) * wv + bv
            return jnp.sum(jnp.tanh(oo)) + 0.3 * jnp.sum(jnp.tanh(zz))

        with jax.enable_x64(True):
            args = [jnp.asarray(np.asarray(a, np.float64))
                    for a in (x_np, y_np, w_np, b_np)]
            truth = jax.grad(f, argnums=(0, 1, 2, 3))(*args)
    else:
        out = fused_residual_ln(x, y, w, b)
        out.tanh().sum().backward()
        truth = _f64_truth(x_np, y_np, w_np, b_np)
    for t, g64, name in zip((x, y, w, b), truth, "xywb"):
        assert _rel(t.grad.numpy(), g64) < 2e-4, (name, return_residual)


def test_bf16_bwd_no_worse_than_unfused():
    """bf16 regime: the fused backward reconstructs x_hat from the bf16 LN
    output; its grads must stay in the same error class as the unfused
    bf16 composition vs f64 truth (within 2x — the reconstruction
    quantization is bounded by the same bf16 ulp that the unfused path's
    saved activations carry)."""
    rng = np.random.RandomState(3)
    x_np = rng.randn(4, 8, 64).astype("float32")
    y_np = rng.randn(4, 8, 64).astype("float32")
    w_np = (rng.rand(64) + 0.5).astype("float32")
    b_np = (rng.randn(64) * 0.2).astype("float32")
    truth = _f64_truth(x_np, y_np, w_np, b_np)

    def run(fused):
        x = paddle.to_tensor(x_np.astype("bfloat16"))
        y = paddle.to_tensor(y_np.astype("bfloat16"))
        w = paddle.to_tensor(w_np.astype("bfloat16"))
        b = paddle.to_tensor(b_np.astype("bfloat16"))
        for t in (x, y, w, b):
            t.stop_gradient = False
        if fused:
            out = fused_residual_ln(x, y, w, b)
        else:
            out = F.layer_norm(x + y, 64, w, b)
        out.astype("float32").tanh().sum().backward()
        return [t.grad.numpy().astype("float32") for t in (x, y, w, b)]

    got, ref = run(True), run(False)
    for gf, gu, g64, name in zip(got, ref, truth, "xywb"):
        ef, eu = _rel(gf, g64), _rel(gu, g64)
        assert ef < max(2.0 * eu, 0.05), (name, ef, eu)


def test_zero_weight_channel_eager_falls_back_to_exact_grads():
    """An exactly-zero LN weight channel must not be silently frozen in
    eager mode: the degenerate-weight guard routes through plain autodiff,
    so dw matches the unfused composition (same contract as
    fused_conv_bn's zero-gamma guard)."""
    rng = np.random.RandomState(5)
    x_np = rng.randn(2, 4, 16).astype("float32")
    y_np = rng.randn(2, 4, 16).astype("float32")
    w_np = (rng.rand(16) + 0.5).astype("float32")
    w_np[3] = 0.0
    b_np = (rng.randn(16) * 0.1).astype("float32")

    def run(fused):
        x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
        w, b = paddle.to_tensor(w_np), paddle.to_tensor(b_np)
        for t in (x, y, w, b):
            t.stop_gradient = False
        out = (fused_residual_ln(x, y, w, b) if fused
               else F.layer_norm(x + y, 16, w, b))
        out.tanh().sum().backward()
        return [t.grad.numpy() for t in (x, y, w, b)]

    got, ref = run(True), run(False)
    for a, r, name in zip(got, ref, "xywb"):
        np.testing.assert_allclose(a, r, rtol=1e-4, atol=1e-6, err_msg=name)
    assert got[2][3] != 0.0  # the zero-init channel LEARNS


def test_zero_weight_via_inplace_mutator_invalidates_guard_cache():
    """zero_()/fill_() re-initialization must invalidate the sticky
    degenerate-weight cache, not leave the guard acting on a stale
    verdict (code-review r5)."""
    rng = np.random.RandomState(7)
    x, y = _mk(rng, (2, 3, 8)), _mk(rng, (2, 3, 8))
    w = paddle.to_tensor((rng.rand(8) + 0.5).astype("float32"))
    b = paddle.to_tensor(np.zeros(8, "float32"))
    w.stop_gradient = False
    fused_residual_ln(x, y, w, b)  # caches "not degenerate"
    w.zero_()                      # in-place re-init into the band
    out = fused_residual_ln(x, y, w, b)
    out.tanh().sum().backward()
    # fallback path -> dw is the exact autodiff gradient, not frozen zeros
    assert np.any(w.grad.numpy() != 0.0)


def test_zero_weight_via_setitem_invalidates_guard_cache():
    """Element writes (`w[3] = 0.0` — the natural zero-init-residual move)
    must also invalidate the sticky guard cache (code-review r5)."""
    rng = np.random.RandomState(9)
    x, y = _mk(rng, (2, 3, 8)), _mk(rng, (2, 3, 8))
    w = paddle.to_tensor((rng.rand(8) + 0.5).astype("float32"))
    b = paddle.to_tensor(np.zeros(8, "float32"))
    w.stop_gradient = False
    fused_residual_ln(x, y, w, b)  # caches "not degenerate"
    w[3] = 0.0
    out = fused_residual_ln(x, y, w, b)
    out.tanh().sum().backward()
    assert w.grad.numpy()[3] != 0.0  # the zeroed channel still learns


def test_amp_keeps_stream_dtype_promotes_norm_only():
    """Under amp.auto_cast the op is f32-promoted like layer_norm, but the
    carried residual stream z must stay in the pre-promotion dtype — only
    the norm output promotes (code-review r5: a promoted stream doubles
    per-layer bytes on an HBM-bound lane)."""
    rng = np.random.RandomState(8)
    x = paddle.to_tensor(rng.randn(2, 3, 8).astype("bfloat16"))
    y = paddle.to_tensor(rng.randn(2, 3, 8).astype("bfloat16"))
    w = paddle.to_tensor(np.ones(8, "float32"))
    b = paddle.to_tensor(np.zeros(8, "float32"))
    with paddle.amp.auto_cast(dtype="bfloat16"):
        z, out = fused_residual_ln(x, y, w, b, return_residual=True)
    assert str(z.dtype).endswith("bfloat16"), z.dtype


def test_gpt_block_matches_the_plain_composition():
    """GPTBlock is the plain x + attn(ln1(x)); x + mlp(ln2(x)) composition,
    and its layer norms are nn.LayerNorm's own op."""
    from paddle_tpu.text.models.gpt import GPTBlock, GPTConfig

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=32, dropout=0.0,
                    use_flash_attention=False)
    block = GPTBlock(cfg)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(2, 8, 64).astype("float32"))

    got = block(x).numpy()

    h = x + block.dropout(block.attn(block.ln1(x)))
    ref = (h + block.mlp(block.ln2(h))).numpy()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_gpt_model_trains_and_recompute_matches():
    """End-to-end GPT fwd/bwd; recompute=True (each block a rematerialised
    region) must match recompute=False."""
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (2, 16)).astype("int32")
    labels = rng.randint(0, 128, (2, 16)).astype("int64")

    def run(recompute):
        paddle.seed(7)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_position_embeddings=32,
                        dropout=0.0, use_flash_attention=False,
                        recompute=recompute)
        model = GPTForCausalLM(cfg)
        loss = model(paddle.to_tensor(ids), labels=paddle.to_tensor(labels))
        loss.backward()
        g = model.gpt.h[0].ln1.weight.grad.numpy()
        return float(np.asarray(loss.numpy())), g

    l0, g0 = run(False)
    l1, g1 = run(True)
    assert np.isfinite(l0)
    np.testing.assert_allclose(l0, l1, rtol=1e-5)
    np.testing.assert_allclose(g0, g1, rtol=1e-4, atol=1e-6)


def test_models_never_reach_the_fused_op(monkeypatch):
    """No switch is left to throw: GPT and the post-LN encoder layer run
    the plain residual+norm composition and never reach the fused op,
    whatever PADDLE_TPU_FUSED_RESIDUAL_LN says."""
    import paddle_tpu.nn as nn
    from paddle_tpu.ops import fused_residual_ln as op
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

    def never(*args, **kwargs):
        raise AssertionError("a model called ops.fused_residual_ln")
    monkeypatch.setattr(op, "fused_residual_ln", never)
    monkeypatch.setattr(op, "_fused_residual_ln_diff", never)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 64, (2, 8)).astype("int32"))
    x = paddle.to_tensor(
        np.random.RandomState(1).randn(2, 4, 32).astype("float32"))
    losses = []
    for setting in ("0", "1"):
        monkeypatch.setenv("PADDLE_TPU_FUSED_RESIDUAL_LN", setting)
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
            max_position_embeddings=16, dropout=0.0))
        loss = model(ids, labels=ids)
        loss.backward()
        encoder = nn.TransformerEncoderLayer(32, 2, 64, dropout=0.0)
        losses.append((float(loss.numpy()), encoder(x).numpy()))
    assert losses[0][0] == losses[1][0]
    np.testing.assert_array_equal(losses[0][1], losses[1][1])


def test_decoder_layer_post_ln_matches_manual():
    """TransformerDecoderLayer's three post-LN residual writes equal the
    manual composition."""
    import paddle_tpu.nn as nn

    paddle.seed(0)
    layer = nn.TransformerDecoderLayer(32, 4, 64, dropout=0.0,
                                       activation="relu",
                                       normalize_before=False)
    layer.eval()
    rng = np.random.RandomState(2)
    tgt = paddle.to_tensor(rng.randn(2, 5, 32).astype("float32"))
    mem = paddle.to_tensor(rng.randn(2, 7, 32).astype("float32"))
    got = layer(tgt, mem).numpy()

    h = layer.norm1(tgt + layer.self_attn(tgt, tgt, tgt, None))
    h2 = layer.norm2(h + layer.cross_attn(h, mem, mem, None))
    f = layer.linear2(F.relu(layer.linear1(h2)))
    ref = layer.norm3(h2 + f).numpy()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_encoder_layer_post_ln_matches_manual():
    """TransformerEncoderLayer post-LN (BERT) path through the fused op
    equals the manual residual + norm composition."""
    import paddle_tpu.nn as nn

    paddle.seed(0)
    layer = nn.TransformerEncoderLayer(32, 4, 64, dropout=0.0,
                                       activation="gelu",
                                       normalize_before=False)
    layer.eval()
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.randn(2, 6, 32).astype("float32"))
    got = layer(x).numpy()

    h = layer.self_attn(x, x, x, None)
    h = layer.norm1(x + h)
    f = layer.linear2(F.gelu(layer.linear1(h)))
    ref = layer.norm2(h + f).numpy()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
