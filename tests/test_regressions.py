"""Regression tests for review findings (engine/API edge cases)."""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def test_pylayer_none_grad_does_not_stall_upstream():
    from paddle_tpu.autograd import PyLayer

    class Partial(PyLayer):
        @staticmethod
        def forward(ctx, a, b):
            ctx.save_for_backward(a, b)
            return a * b

        @staticmethod
        def backward(ctx, g):
            a, b = ctx.saved_tensor
            return g * paddle.to_tensor(b.numpy()), None

    x = paddle.to_tensor([2.0], stop_gradient=False)
    w = x * 3
    y = paddle.to_tensor([4.0], stop_gradient=False)
    out = Partial.apply(w, y * 2)
    out.backward()
    np.testing.assert_allclose(x.grad.numpy(), [24.0])


def test_paddle_grad_does_not_touch_other_leaves():
    lin = nn.Linear(2, 2)
    x = paddle.to_tensor(np.ones((1, 2), "float32"), stop_gradient=False)
    (gx,) = paddle.grad([lin(x).sum()], [x])
    assert lin.weight.grad is None
    assert gx is not None


def test_scaler_no_double_unscale():
    layer = nn.Linear(2, 2)
    opt = paddle.optimizer.SGD(learning_rate=1.0,
                               parameters=layer.parameters())
    sc = paddle.amp.GradScaler(init_loss_scaling=4.0)
    loss = layer(paddle.to_tensor(np.ones((1, 2), "float32"))).sum()
    sc.scale(loss).backward()
    sc.unscale_(opt)
    g1 = layer.weight.grad.numpy().copy()
    sc.step(opt)
    np.testing.assert_allclose(layer.weight.grad.numpy(), g1)


def test_scaler_inf_skips_params_and_state():
    layer = nn.Linear(2, 2)
    opt = paddle.optimizer.Adam(parameters=layer.parameters())
    sc = paddle.amp.GradScaler(init_loss_scaling=8.0)
    before = layer.weight.numpy().copy()
    x = paddle.to_tensor(np.full((2, 2), np.inf, "float32"))
    sc.scale(layer(x).mean()).backward()
    sc.step(opt)
    sc.update()
    np.testing.assert_allclose(layer.weight.numpy(), before)
    assert float(sc._scale._val) == 4.0


def test_cummax_cummin_shapes_and_values():
    v = paddle.to_tensor(np.array([1.0, 3.0, 2.0]))
    vals, idx = paddle.cummax(v)
    np.testing.assert_allclose(vals.numpy(), [1, 3, 3])
    np.testing.assert_array_equal(idx.numpy(), [0, 1, 1])
    vals, idx = paddle.cummin(v)
    np.testing.assert_allclose(vals.numpy(), [1, 1, 1])


def test_sublayer_nonpersistable_buffer_excluded():
    class Sub(nn.Layer):
        def __init__(self):
            super().__init__()
            self.register_buffer("tmp", paddle.to_tensor([1.0]),
                                 persistable=False)
            self.register_buffer("keep", paddle.to_tensor([2.0]))

    class Root(nn.Layer):
        def __init__(self):
            super().__init__()
            self.sub = Sub()

    sd = Root().state_dict()
    assert "sub.tmp" not in sd and "sub.keep" in sd


def test_param_attr_regularizer_applied():
    from paddle_tpu.regularizer import L2Decay
    l2 = nn.Linear(2, 2,
                   weight_attr=paddle.nn.ParamAttr(regularizer=L2Decay(0.5)))
    opt = paddle.optimizer.SGD(learning_rate=1.0, parameters=l2.parameters())
    x = paddle.to_tensor(np.zeros((1, 2), "float32"))
    (l2(x).sum() * 0).backward()
    before = l2.weight.numpy().copy()
    opt.step()
    np.testing.assert_allclose(l2.weight.numpy(), before * 0.5, atol=1e-6)


def test_dataloader_early_break_no_thread_leak():
    from paddle_tpu.io import DataLoader, TensorDataset
    ds = TensorDataset([np.arange(1000, dtype=np.float32)])
    before = threading.active_count()
    for _ in range(5):
        for _b in DataLoader(ds, batch_size=2, num_workers=2):
            break
    import time
    deadline = time.monotonic() + 5
    while threading.active_count() > before + 1 \
            and time.monotonic() < deadline:
        time.sleep(0.01)  # blocking-ok: poll interval, deadline above
    assert threading.active_count() <= before + 1


def test_dataloader_propagates_worker_error():
    from paddle_tpu.io import DataLoader, Dataset

    class Bad(Dataset):
        def __len__(self):
            return 10

        def __getitem__(self, i):
            if i == 5:
                raise RuntimeError("corrupt sample")
            return np.zeros(2, "float32")

    with pytest.raises(RuntimeError, match="corrupt"):
        for _ in DataLoader(Bad(), batch_size=2, num_workers=2):
            pass


def test_to_static_per_instance_programs():
    class M(nn.Layer):
        def __init__(self, scale):
            super().__init__()
            self.lin = nn.Linear(2, 2)
            self.lin.weight._value = self.lin.weight._val * 0 + scale
            self.lin.bias._value = self.lin.bias._val * 0

        @paddle.jit.to_static
        def forward(self, x):
            return self.lin(x)

    m1, m2 = M(1.0), M(2.0)
    x = paddle.to_tensor(np.ones((1, 2), "float32"))
    with paddle.no_grad():
        for _ in range(4):
            o1, o2 = m1(x), m2(x)
    np.testing.assert_allclose(o1.numpy(), np.full((1, 2), 2.0))
    np.testing.assert_allclose(o2.numpy(), np.full((1, 2), 4.0))


def test_pad_last_dim_first_ordering():
    x = paddle.to_tensor(np.zeros((1, 1, 2, 2), "float32"))
    assert F.pad(x, [1, 1, 0, 0]).shape == [1, 1, 2, 4]  # W padded
    assert F.pad(x, [0, 0, 2, 2]).shape == [1, 1, 6, 2]  # H padded


def test_embedding_padding_idx_zeroes_output():
    w = paddle.to_tensor(np.ones((5, 3), "float32"))
    e = F.embedding(paddle.to_tensor(np.array([0, 1], "int64")), w,
                    padding_idx=0)
    np.testing.assert_allclose(e.numpy()[0], 0.0)
    np.testing.assert_allclose(e.numpy()[1], 1.0)
    e2 = F.embedding(paddle.to_tensor(np.array([4], "int64")), w,
                     padding_idx=-1)
    np.testing.assert_allclose(e2.numpy()[0], 0.0)


def test_split_non_divisible_raises():
    with pytest.raises(ValueError, match="divisible"):
        paddle.split(paddle.to_tensor(np.zeros((7, 2), "float32")), 3)


def test_align_corners_resize_values():
    v = paddle.to_tensor(np.arange(3, dtype="float32").reshape(1, 1, 1, 3))
    out = F.interpolate(v, size=[1, 5], mode="bilinear", align_corners=True)
    np.testing.assert_allclose(out.numpy().ravel(), [0, 0.5, 1, 1.5, 2],
                               atol=1e-5)


def test_distribution_param_gradients_flow():
    # log_prob must propagate gradients to distribution parameters
    # (reference Normal.log_prob builds ops over the loc/scale variables)
    from paddle_tpu.distribution import Categorical, Normal
    loc = paddle.to_tensor(np.array([0.5], "float32"), stop_gradient=False)
    scale = paddle.to_tensor(np.array([2.0], "float32"), stop_gradient=False)
    lp = Normal(loc, scale).log_prob(paddle.to_tensor(
        np.array([1.0], "float32")))
    lp.backward()
    assert loc.grad is not None and scale.grad is not None
    # d/dloc log N(v;loc,scale) = (v-loc)/scale^2 = 0.5/4
    np.testing.assert_allclose(loc.grad.numpy(), [0.125], atol=1e-6)

    logits = paddle.to_tensor(np.array([1.0, 3.0], "float32"),
                              stop_gradient=False)
    lp = Categorical(logits).log_prob(paddle.to_tensor(
        np.array([1], "int64")))
    lp.backward()
    assert logits.grad is not None
    assert abs(float(logits.grad.numpy().sum())) > 0


def test_flash_attention_differentiable():
    # explicit use_pallas=True with grad-requiring inputs must not crash:
    # custom_vjp (pallas forward, XLA backward)
    from paddle_tpu.ops.attention import scaled_dot_product_attention
    rng = np.random.RandomState(0)
    q = paddle.to_tensor(rng.randn(1, 128, 2, 128).astype("float32"),
                         stop_gradient=False)
    k = paddle.to_tensor(rng.randn(1, 128, 2, 128).astype("float32"),
                         stop_gradient=False)
    v = paddle.to_tensor(rng.randn(1, 128, 2, 128).astype("float32"),
                         stop_gradient=False)
    out = scaled_dot_product_attention(q, k, v, is_causal=True,
                                       use_pallas=True)
    ref = scaled_dot_product_attention(
        paddle.to_tensor(q.numpy()), paddle.to_tensor(k.numpy()),
        paddle.to_tensor(v.numpy()), is_causal=True, use_pallas=False)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), atol=2e-3)
    out.backward(paddle.to_tensor(np.ones_like(out.numpy())))
    assert q.grad is not None and k.grad is not None and v.grad is not None


def test_sdpa_custom_scale():
    from paddle_tpu.ops.attention import scaled_dot_product_attention
    rng = np.random.RandomState(0)
    q = paddle.to_tensor(rng.randn(1, 4, 2, 8).astype("float32"))
    k = paddle.to_tensor(rng.randn(1, 4, 2, 8).astype("float32"))
    v = paddle.to_tensor(rng.randn(1, 4, 2, 8).astype("float32"))
    a = scaled_dot_product_attention(q, k, v, scale=0.125)
    b = scaled_dot_product_attention(q, k, v)  # default 1/sqrt(8)=0.3535
    assert not np.allclose(a.numpy(), b.numpy())


def test_hapi_eval_metrics_reach_callbacks():
    from paddle_tpu.hapi import Model
    from paddle_tpu.hapi.callbacks import Callback
    import paddle_tpu.nn as nn

    seen = {}

    class Spy(Callback):
        def on_train_begin(self, logs=None):
            # params must already be set when this hook runs
            seen["params"] = dict(self.params)

        def on_epoch_end(self, epoch, logs=None):
            seen["epoch_logs"] = dict(logs or {})

        def on_eval_end(self, logs=None):
            seen["eval_logs"] = dict(logs or {})

    class DS:
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return (np.ones(4, "float32") * i, np.array([i % 2], "int64"))

    net = nn.Linear(4, 2)
    m = Model(net)
    m.prepare(optimizer=paddle.optimizer.SGD(
        learning_rate=0.1, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss())
    m.fit(DS(), eval_data=DS(), batch_size=4, epochs=1, verbose=0,
          callbacks=[Spy()])
    assert seen["params"].get("epochs") == 1
    assert "loss" in seen["eval_logs"]
    assert "loss" in seen["epoch_logs"]


def test_summary_accepts_list_of_shapes():
    import paddle_tpu.nn as nn

    class TwoIn(nn.Layer):
        def __init__(self):
            super().__init__()
            self.a = nn.Linear(4, 2)
            self.b = nn.Linear(8, 2)

        def forward(self, x, y):
            return self.a(x) + self.b(y)

    res = paddle.summary(TwoIn(), [(1, 4), (1, 8)])
    assert res["total_params"] == (4 * 2 + 2) + (8 * 2 + 2)


class TestReviewRound2Fixes:
    """Regressions for the code-review findings fixed alongside the utils
    package (recompute state writes, viterbi lengths, MoE residual/init,
    dispatch dtype, VOC split correlation)."""

    def test_recompute_through_stateful_batchnorm(self):
        import paddle_tpu.nn as nn
        from paddle_tpu.distributed.fleet.utils import recompute
        bn = nn.BatchNorm1D(4)
        bn.train()
        x = paddle.to_tensor(np.random.RandomState(0).randn(8, 4)
                             .astype("float32"), stop_gradient=False)
        out = recompute(bn, x)
        out.sum().backward()
        # running stats must stay concrete arrays, not leaked tracers
        mean_val = bn._mean.numpy() if hasattr(bn, "_mean") else None
        assert x.grad is not None
        y2 = bn(paddle.to_tensor(np.ones((2, 4), "float32")))
        assert np.isfinite(y2.numpy()).all()

    def test_viterbi_lengths_respected(self):
        rng = np.random.RandomState(0)
        B, S, T = 2, 5, 3
        pot = rng.randn(B, S, T).astype("float32")
        trans = rng.randn(T, T).astype("float32")
        full_s, full_p = paddle.text.viterbi_decode(
            paddle.to_tensor(pot), paddle.to_tensor(trans))
        # corrupt padding: with lengths=2, emissions at t>=2 must not matter
        pot2 = pot.copy()
        pot2[:, 2:, :] = 1e3 * rng.randn(B, S - 2, T)
        lens = paddle.to_tensor(np.array([2, 2], "int64"))
        s_a, p_a = paddle.text.viterbi_decode(
            paddle.to_tensor(pot), paddle.to_tensor(trans), lens)
        s_b, p_b = paddle.text.viterbi_decode(
            paddle.to_tensor(pot2), paddle.to_tensor(trans), lens)
        np.testing.assert_allclose(s_a.numpy(), s_b.numpy(), rtol=1e-5)
        np.testing.assert_array_equal(p_a.numpy()[:, :2], p_b.numpy()[:, :2])
        # and the truncated score equals decoding the 2-step prefix
        s_ref, p_ref = paddle.text.viterbi_decode(
            paddle.to_tensor(pot[:, :2]), paddle.to_tensor(trans))
        np.testing.assert_allclose(s_a.numpy(), s_ref.numpy(), rtol=1e-5)
        np.testing.assert_array_equal(p_a.numpy()[:, :2], p_ref.numpy())

    def test_moe_dropped_tokens_pass_through(self):
        moe = paddle.incubate.MoELayer(d_model=8, d_hidden=16, num_experts=2,
                                       top_k=1, capacity_factor=0.01)
        x = paddle.to_tensor(np.random.RandomState(1).randn(8, 8)
                             .astype("float32"))
        out = moe(x)
        # capacity=1 → ≥6 of 8 tokens dropped; they must equal the input
        diff = np.abs(out.numpy() - x.numpy()).sum(axis=1)
        n_passthrough = int((diff < 1e-6).sum())
        assert n_passthrough >= 6, diff
        assert not np.allclose(out.numpy(), 0.0)

    def test_moe_init_respects_framework_seed(self):
        paddle.seed(1)
        m1 = paddle.incubate.MoELayer(8, 16, 2)
        m2 = paddle.incubate.MoELayer(8, 16, 2)
        assert not np.allclose(m1.w1.numpy(), m2.w1.numpy())
        paddle.seed(1)
        m3 = paddle.incubate.MoELayer(8, 16, 2)
        np.testing.assert_array_equal(m1.w1.numpy(), m3.w1.numpy())

    def test_dispatch_tokens_int_positions_large_counts(self):
        from paddle_tpu.distributed.utils import dispatch_tokens
        n = 600  # > 256 would break bf16 cumsum
        x = paddle.to_tensor(np.ones((n, 2)).astype("float32"))
        x = x.astype("bfloat16")
        idx = paddle.to_tensor(np.zeros(n, "int32"))
        buf, combine, keep = dispatch_tokens(x, idx, 1, n)
        assert int(np.asarray(keep.numpy()).sum()) == n
        # every token occupies a distinct slot
        slots = combine.numpy().astype("float32").sum(axis=(0, 1))
        np.testing.assert_allclose(slots, np.ones(n), rtol=0, atol=1e-6)

    def test_voc_splits_not_shifted_duplicates(self):
        tr = paddle.vision.datasets.VOC2012(mode="train")
        te = paddle.vision.datasets.VOC2012(mode="test")
        img_tr, _ = tr[1]
        img_te, _ = te[0]
        assert not np.allclose(img_tr, img_te)


class TestKernelTierAdviceR5:
    """ADVICE r5 regressions riding on the kernel-tier pass (ISSUE 5):
    None outputs through the dispatch seam (a region under recompute that
    returns one), and degen-cache invalidation on checkpoint-style writes."""

    def test_gpt_recompute_with_unfused_residual_ln_trains(self):
        # high: recompute traces GPTBlock, the plain residual+norm
        # composition, through dispatch.apply (its (x, None) form of the
        # time crashed on None.shape; the seam's own test is the next)
        from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_position_embeddings=16, dropout=0.0,
                        use_flash_attention=False, recompute=True)
        model = GPTForCausalLM(cfg)
        model.train()
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 64, (2, 8)).astype("int32"))
        loss = model(ids, labels=ids)
        assert np.isfinite(float(loss.numpy()))
        loss.backward()
        w = model.gpt.h[0].ln1.weight
        assert w.grad is not None
        assert np.isfinite(w.grad.numpy()).all()

    def test_dispatch_none_output_passthrough(self):
        # the seam itself: a prim returning (value, None) must wrap to
        # (Tensor, None), and backward must feed a None cotangent through
        from paddle_tpu.core.dispatch import apply

        x = paddle.to_tensor(np.ones((3,), "float32"))
        x.stop_gradient = False
        y, nothing = apply(lambda v: (v * 2.0, None), x, name="with_none")
        assert nothing is None
        y.sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), 2.0 * np.ones(3))

    def test_set_state_dict_refreshes_degenerate_guard(self):
        # med: loading a checkpoint with a zero LN channel over a WARM model
        # (sticky _degen_cache = "not degenerate") must re-route to the
        # plain path, not silently freeze the channel's gradient
        from paddle_tpu.ops.fused_residual_ln import fused_residual_ln

        def grad_of(ln):
            rng = np.random.RandomState(0)
            x = paddle.to_tensor(rng.randn(4, 8).astype("float32"))
            y = paddle.to_tensor(rng.randn(4, 8).astype("float32"))
            out = fused_residual_ln(x, y, ln.weight, ln.bias)
            ln.weight.clear_grad() if ln.weight.grad is not None else None
            out.sum().backward()
            return ln.weight.grad.numpy()

        warm = nn.LayerNorm(8)
        grad_of(warm)  # caches "not degenerate" on warm.weight

        sd = {k: v.numpy().copy() for k, v in warm.state_dict().items()}
        sd["weight"][3] = 0.0  # dead channel arrives via checkpoint
        warm.set_state_dict(sd)
        fresh = nn.LayerNorm(8)
        fresh.set_state_dict(sd)

        g_warm, g_fresh = grad_of(warm), grad_of(fresh)
        np.testing.assert_allclose(g_warm, g_fresh, rtol=1e-5, atol=1e-6)
        assert g_warm[3] != 0.0  # the zero channel still learns

    def test_replace_value_invalidates_degen_cache(self):
        # low: optimizer/functional state writes go through _replace_value
        import jax.numpy as jnp

        from paddle_tpu.ops._param_guard import degenerate_below_tol

        t = paddle.to_tensor(np.ones(4, "float32"))
        assert not degenerate_below_tol(t, 1e-6)
        t._replace_value(jnp.zeros(4, jnp.float32))
        assert degenerate_below_tol(t, 1e-6)
