"""auto_parallel parity tests (SURVEY.md §2.7 auto-parallel block).

Runs on the virtual 8-device CPU mesh (conftest). Checks: ProcessMesh
topology, shard_tensor actually lays buffers out across devices, gradients
flow through sharding constraints, shard_op annotation, reshard, Engine
fit/evaluate/predict end-to-end, and the analytic cost model.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.auto_parallel import (
    DistAttr, Engine, ProcessMesh, Strategy, estimate_cost, reshard,
    shard_op, shard_tensor,
)

NDEV = len(jax.devices())
pytestmark = pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")


@pytest.fixture(autouse=True)
def _no_mesh_left_by_another_file():
    """`Engine.prepare` refuses a global mesh it did not build. A test file
    that ran earlier in the same worker may have left one active (which file
    that is turns with the scheduling of `--dist loadfile`), so it is put
    aside for the test and put back after."""
    from paddle_tpu.distributed import mesh
    saved = dict(mesh._STATE)
    mesh._STATE.update(mesh=None, axis_degrees=None)
    yield
    mesh._STATE.update(saved)


@pytest.fixture()
def mesh2d():
    return ProcessMesh(np.arange(8).reshape(4, 2), dim_names=["x", "y"])


class TestProcessMesh:
    def test_topology(self, mesh2d):
        assert mesh2d.shape == [4, 2]
        assert mesh2d.dim_names == ["x", "y"]
        assert mesh2d.process_ids == list(range(8))
        assert mesh2d.get_dim_size("x") == 4
        assert mesh2d.ndim == 2

    def test_validation(self):
        with pytest.raises(ValueError):
            ProcessMesh(np.arange(8).reshape(4, 2), dim_names=["x"])
        with pytest.raises(ValueError):
            ProcessMesh(np.arange(10_000))

    def test_default_scope(self, mesh2d):
        from paddle_tpu.distributed.auto_parallel import (
            get_default_process_mesh,
        )
        with mesh2d:
            assert get_default_process_mesh() is mesh2d
            t = shard_tensor(np.ones((8, 4), "float32"), shard_spec=["x", None])
            assert t.dist_attr.process_mesh is mesh2d
        assert get_default_process_mesh() is None


class TestShardTensor:
    def test_layout_across_devices(self, mesh2d):
        x = np.arange(32, dtype="float32").reshape(8, 4)
        t = shard_tensor(x, mesh2d, ["x", "y"])
        np.testing.assert_allclose(np.asarray(t._val), x)
        shard_devs = {s.device for s in t._val.addressable_shards}
        assert len(shard_devs) == 8          # spread over the whole mesh
        shard = t._val.addressable_shards[0]
        assert shard.data.shape == (2, 2)    # 8/4 x 4/2

    def test_grad_flows_through(self, mesh2d):
        t = paddle.to_tensor(np.ones((8, 4), "float32"))
        t.stop_gradient = False
        s = shard_tensor(t, mesh2d, ["x", None])
        loss = (s * s).sum()
        loss.backward()
        np.testing.assert_allclose(np.asarray(t.grad._val),
                                   2 * np.ones((8, 4)), rtol=1e-6)

    def test_reshard(self, mesh2d):
        x = np.ones((8, 4), "float32")
        t = shard_tensor(x, mesh2d, ["x", None])
        r = reshard(t, mesh2d, [None, "y"])
        np.testing.assert_allclose(np.asarray(r._val), x)
        assert r.dist_attr.shard_spec == [None, "y"]

    def test_dist_attr(self, mesh2d):
        da = DistAttr(mesh2d, ["x", None])
        ps = da.partition_spec()
        assert ps == jax.sharding.PartitionSpec("x", None)


class TestShardOp:
    def test_annotated_matmul(self, mesh2d):
        w = np.random.RandomState(0).randn(4, 6).astype("float32")

        def fwd(x, wt):
            return paddle.matmul(x, wt)

        f = shard_op(fwd, mesh2d, in_shard_specs=[["x", None], [None, "y"]],
                     out_shard_specs=[["x", "y"]])
        x = paddle.to_tensor(np.ones((8, 4), "float32"))
        out = f(x, paddle.to_tensor(w))
        np.testing.assert_allclose(np.asarray(out._val),
                                   np.ones((8, 4)) @ w, rtol=1e-5)


class TestEngine:
    def test_fit_eval_predict(self, mesh2d):
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=model.parameters())
        pm = ProcessMesh(np.arange(8), dim_names=["dp"])
        engine = Engine(model, loss=F.cross_entropy, optimizer=opt,
                        strategy=Strategy(), process_mesh=pm)
        rng = np.random.RandomState(0)
        x = rng.randn(64, 8).astype("float32")
        y = rng.randint(0, 4, (64, 1)).astype("int64")
        hist = engine.fit((x, y), epochs=3, batch_size=32)
        assert hist["loss"][-1] < hist["loss"][0]
        ev = engine.evaluate((x, y), batch_size=32)
        assert np.isfinite(ev["eval_loss"])
        outs = engine.predict((x, y), batch_size=32)
        assert outs[0]._val.shape == (32, 4)

    def test_cost_model(self):
        model = nn.Linear(8, 8)
        pm = ProcessMesh(np.arange(8), dim_names=["dp"])
        c = estimate_cost(model, pm)
        assert c["params"] == 8 * 8 + 8
        assert c["devices"] == 8
        assert c["param_bytes_per_device"] * 8 <= c["param_bytes"] + 8


class TestEngineRegressions:
    """Review-found edge cases: partial batches, eval-mode toggling,
    idempotent prepare, batch-shape validation, probe tracer leaks."""

    def _engine(self, dropout=False):
        paddle.seed(0)
        layers = [nn.Linear(8, 16), nn.ReLU()]
        if dropout:
            layers.append(nn.Dropout(0.5))
        layers.append(nn.Linear(16, 4))
        model = nn.Sequential(*layers)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        pm = ProcessMesh(np.arange(8), dim_names=["dp"])
        return Engine(model, loss=F.cross_entropy, optimizer=opt,
                      strategy=Strategy(), process_mesh=pm), model

    def test_partial_final_batch(self):
        engine, _ = self._engine()
        rng = np.random.RandomState(0)
        x = rng.randn(20, 8).astype("float32")   # 20 % 16 != 0
        y = rng.randint(0, 4, (20, 1)).astype("int64")
        hist = engine.fit((x, y), epochs=1, batch_size=16)
        assert len(hist["loss"]) == 2  # full batch + partial batch

    def test_eval_mode_deterministic_with_dropout(self):
        engine, model = self._engine(dropout=True)
        rng = np.random.RandomState(0)
        x = rng.randn(16, 8).astype("float32")
        y = rng.randint(0, 4, (16, 1)).astype("int64")
        a = engine.evaluate((x, y))["eval_loss"]
        b = engine.evaluate((x, y))["eval_loss"]
        assert a == b
        assert model.training  # restored

    def test_prepare_idempotent_no_double_wrap(self):
        engine, _ = self._engine()
        engine.strategy.sharding.enable = True
        engine.prepare()
        inner = engine.optimizer
        engine.prepare()
        assert engine.optimizer is inner

    def test_fit_rejects_bare_array(self):
        engine, _ = self._engine()
        with pytest.raises(ValueError, match="needs .x, y."):
            engine.fit(np.ones((16, 8), "float32"), batch_size=8)

    def test_mismatched_xy_raises(self):
        engine, _ = self._engine()
        with pytest.raises(ValueError, match="mismatched"):
            engine.fit((np.ones((10, 8), "f"), np.ones((9, 1), "i")),
                       batch_size=4)

    def test_negative_process_ids_rejected(self):
        with pytest.raises(ValueError):
            ProcessMesh(np.array([0, -1]), dim_names=["x"])

    def test_dtensor_from_fn_inplace_init(self):
        from paddle_tpu.distributed.auto_parallel import dtensor_from_fn
        pm = ProcessMesh(np.arange(8), dim_names=["dp"])
        t = dtensor_from_fn(
            lambda: paddle.zeros((8, 4)).fill_(1.0), pm, ["dp", None])
        np.testing.assert_allclose(np.asarray(t._val), np.ones((8, 4)))
