"""incubate tests: MoE layer, LookAhead/ModelAverage, fused transformer,
recompute, global_scatter/gather."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


class TestMoE:
    def test_forward_shape_and_trains(self):
        paddle.seed(0)
        moe = paddle.incubate.MoELayer(d_model=16, d_hidden=32,
                                       num_experts=4, top_k=2,
                                       capacity_factor=2.0)
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=moe.parameters())
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, 10, 16).astype("float32"))
        tgt = paddle.to_tensor(rng.randn(8, 10, 16).astype("float32"))
        losses = []
        for _ in range(5):
            out = moe(x)
            assert list(out.shape) == [8, 10, 16]
            loss = F.mse_loss(out, tgt) + 0.01 * moe.aux_loss
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.item()))
        assert losses[-1] < losses[0]

    def test_aux_loss_scalar(self):
        moe = paddle.incubate.MoELayer(16, 32, 4)
        x = paddle.to_tensor(np.random.randn(4, 16).astype("float32"))
        moe(x)
        assert moe.aux_loss is not None
        assert float(moe.aux_loss.item()) > 0

    def test_under_to_static(self):
        paddle.seed(0)
        moe = paddle.incubate.MoELayer(8, 16, 2, top_k=1)
        x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))

        @paddle.jit.to_static
        def fwd(xx):
            with paddle.no_grad():
                return moe(xx)
        outs = [np.asarray(fwd(x)._val) for _ in range(4)]
        np.testing.assert_allclose(outs[2], outs[3], rtol=1e-5)

    def test_gate_noise_rejects_negative(self):
        from paddle_tpu.framework.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError):
            paddle.incubate.MoELayer(16, 32, 4, gate_noise=-0.1)

    def test_gate_noise_perturbs_training_and_is_seeded(self):
        """Regression: gate_noise used to be stored and never applied. In
        train mode it must jitter the routing (consecutive forwards draw
        fresh noise → different outputs) yet stay reproducible from
        paddle.seed like dropout."""
        paddle.seed(0)
        moe = paddle.incubate.MoELayer(d_model=16, d_hidden=32,
                                       num_experts=4, top_k=1,
                                       capacity_factor=0.5, gate_noise=4.0)
        x = paddle.to_tensor(
            np.random.RandomState(3).randn(64, 16).astype("float32"))
        paddle.seed(42)
        a = np.asarray(moe(x)._val)
        b = np.asarray(moe(x)._val)  # second draw from the stream
        assert not np.allclose(a, b)
        paddle.seed(42)
        a2 = np.asarray(moe(x)._val)
        np.testing.assert_array_equal(a, a2)

    def test_gate_noise_off_in_eval(self):
        paddle.seed(0)
        moe = paddle.incubate.MoELayer(d_model=16, d_hidden=32,
                                       num_experts=4, top_k=1,
                                       capacity_factor=0.5, gate_noise=4.0)
        x = paddle.to_tensor(
            np.random.RandomState(3).randn(64, 16).astype("float32"))
        moe.eval()
        e1 = np.asarray(moe(x)._val)
        e2 = np.asarray(moe(x)._val)
        np.testing.assert_array_equal(e1, e2)  # no stream consumed
        # eval routing matches an explicitly noise-free layer
        moe.gate_noise = 0.0
        moe.train()
        np.testing.assert_array_equal(e1, np.asarray(moe(x)._val))


class TestGlobalScatter:
    def test_scatter_gather_roundtrip(self):
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(10, 4).astype("float32"))
        counts = paddle.to_tensor(np.array([3, 2, 5], dtype="int64"))
        from paddle_tpu.distributed.utils import global_gather, global_scatter
        s = global_scatter(x, counts, counts)
        g = global_gather(s, counts, counts)
        np.testing.assert_allclose(np.asarray(g._value),
                                   np.asarray(x._value), rtol=1e-6)


class TestIncubateOptimizers:
    def _quad_problem(self):
        paddle.seed(0)
        w = paddle.to_tensor(np.ones(4, "float32"))
        w.stop_gradient = False
        from paddle_tpu.core.tensor import Parameter
        p = Parameter(np.ones(4, "float32"))
        return p

    def test_lookahead_converges(self):
        p = self._quad_problem()
        inner = paddle.optimizer.SGD(learning_rate=0.3, parameters=[p])
        opt = paddle.incubate.LookAhead(inner, alpha=0.5, k=3)
        for _ in range(20):
            loss = (p * p).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
        assert float(np.abs(np.asarray(p._value)).max()) < 0.2

    def test_model_average_apply_restore(self):
        p = self._quad_problem()
        sgd = paddle.optimizer.SGD(learning_rate=0.1, parameters=[p])
        avg = paddle.incubate.ModelAverage(parameters=[p])
        vals = []
        for _ in range(5):
            loss = (p * p).sum()
            loss.backward()
            sgd.step()
            sgd.clear_grad()
            avg.step()
            vals.append(np.asarray(p._value).copy())
        current = np.asarray(p._value).copy()
        avg.apply()
        np.testing.assert_allclose(np.asarray(p._value),
                                   np.mean(vals, axis=0), rtol=1e-5)
        avg.restore()
        np.testing.assert_allclose(np.asarray(p._value), current)


class TestFusedTransformer:
    def test_encoder_layer_matches_shapes_and_trains(self):
        paddle.seed(0)
        layer = paddle.incubate.nn.FusedTransformerEncoderLayer(
            d_model=32, nhead=4, dim_feedforward=64, dropout_rate=0.0)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=layer.parameters())
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(2, 10, 32).astype("float32"))
        tgt = paddle.to_tensor(rng.randn(2, 10, 32).astype("float32"))
        losses = []
        for _ in range(4):
            out = layer(x)
            assert list(out.shape) == [2, 10, 32]
            loss = F.mse_loss(out, tgt)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.item()))
        assert losses[-1] < losses[0]


class TestRecompute:
    def test_gradient_matches_plain(self):
        paddle.seed(0)
        from paddle_tpu.distributed.fleet.utils import recompute
        lin = paddle.nn.Linear(8, 8)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(4, 8).astype("float32"))

        def block(t):
            return F.relu(lin(t)).sum()

        loss1 = block(x)
        loss1.backward()
        g_plain = np.asarray(lin.weight.grad._value).copy()
        lin.weight.clear_gradient()
        lin.bias.clear_gradient()

        loss2 = recompute(block, x)
        loss2.backward()
        g_ckpt = np.asarray(lin.weight.grad._value)
        np.testing.assert_allclose(g_plain, g_ckpt, rtol=1e-5)

    @staticmethod
    def _dropout_region():
        """y = dropout(x W + b) with W the identity: the bias gradient of
        sum(y) is the kept entries of each column times 1 / (1 - p)."""
        lin = paddle.nn.Linear(8, 8)
        lin.weight.set_value(np.eye(8, dtype="float32"))
        return lin, lambda t: F.dropout(lin(t) + 1.0, p=0.5, training=True)

    def test_rerun_draws_the_forwards_dropout_mask(self):
        # a random op between the region's forward and its backward moves
        # the generator's key; the rerun must still see the forward's
        from paddle_tpu.distributed.fleet.utils import recompute
        paddle.seed(3)
        lin, region = self._dropout_region()
        y = recompute(region, paddle.zeros([16, 8]))
        kept = np.asarray(y._value) != 0
        F.dropout(y, p=0.5, training=True)
        paddle.rand([3])
        y.sum().backward()
        assert 0 < kept.sum() < kept.size
        np.testing.assert_allclose(np.asarray(lin.bias.grad._value),
                                   2.0 * kept.sum(0))

    def test_rerun_draws_the_forwards_mask_in_a_compiled_step(self):
        from paddle_tpu.distributed.fleet.utils import recompute
        paddle.seed(4)
        lin, region = self._dropout_region()

        @paddle.jit.to_static
        def step(x):
            y = recompute(region, x)
            loss = (F.dropout(y, p=0.5, training=True) * 0.0 + y).sum()
            loss.backward()
            grad = lin.bias.grad + 0.0
            lin.clear_gradients()
            return y, grad

        for _ in range(4):       # discovery, both compiles, a steady call
            y, grad = step(paddle.zeros([16, 8]))
            kept = np.asarray(y._value) != 0
            np.testing.assert_allclose(np.asarray(grad._value), 2.0 * kept.sum(0))

    def test_rerun_reads_the_state_the_forward_read(self):
        # a frozen tensor the region reads, changed before the backward
        from paddle_tpu.distributed.fleet.utils import recompute
        lin = paddle.nn.Linear(4, 4)
        scale = paddle.to_tensor(np.full([4], 2.0, "float32"))   # stop_gradient
        x = paddle.to_tensor(np.ones([2, 4], "float32"))
        y = recompute(lambda t: lin(t) * scale, x)
        scale.set_value(np.full([4], 5.0, "float32"))
        y.sum().backward()
        np.testing.assert_allclose(np.asarray(lin.bias.grad._value), 2 * 2.0)


class TestFusedSoftmaxMask:
    def test_softmax_mask_fuse_matches_numpy(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import softmax_mask_fuse
        rng = np.random.RandomState(0)
        x = rng.randn(2, 2, 4, 4).astype("float32")
        m = np.where(rng.rand(2, 1, 4, 4) < 0.3, -1e4, 0.0).astype("float32")
        out = softmax_mask_fuse(paddle.to_tensor(x),
                                paddle.to_tensor(m)).numpy()
        z = x + m
        e = np.exp(z - z.max(-1, keepdims=True))
        np.testing.assert_allclose(out, e / e.sum(-1, keepdims=True),
                                   rtol=1e-4, atol=1e-6)

    def test_upper_triangle_is_causal(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import softmax_mask_fuse_upper_triangle
        x = paddle.to_tensor(
            np.random.RandomState(1).randn(1, 1, 5, 5).astype("float32"))
        out = softmax_mask_fuse_upper_triangle(x).numpy()[0, 0]
        assert np.allclose(np.triu(out, 1), 0.0)
        np.testing.assert_allclose(out.sum(-1), np.ones(5), rtol=1e-5)


class TestFleetMetrics:
    def test_global_metrics_single_process(self):
        import numpy as np
        from paddle_tpu.distributed.fleet import metrics as M
        assert M.acc(np.array([8.0]), np.array([10.0])) == 0.8
        assert M.mae(np.array([5.0]), np.array([10.0])) == 0.5
        assert M.rmse(np.array([40.0]), np.array([10.0])) == 2.0
        # perfect separation → auc 1; symmetric → 0.5
        pos = np.array([0.0, 0, 0, 5, 5])
        neg = np.array([5.0, 5, 0, 0, 0])
        assert M.auc(pos, neg) == 1.0
        assert abs(M.auc(pos, pos) - 0.5) < 1e-9
        np.testing.assert_allclose(M.sum(np.array([1.0, 2.0])), [1.0, 2.0])
