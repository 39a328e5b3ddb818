"""Whole-step compilation (PR tentpole: jit/compiled_step.py +
distributed/spec_layout.py + hapi input prefetch).

Parity contract: the eager path is the oracle. Forward-only programs are
BIT-exact under jit; a full train step (fwd+bwd+optimizer fused into one XLA
program) accumulates ~1-ULP differences from operation reordering inside
fused kernels, so multi-step train parity is asserted at ULP-scale relative
tolerance (2e-6 — measured max over 32-step toy runs is ~5e-7; see
docs/compiled_step.md#parity). Anything past 1e-5 would be a real bug, not
fusion noise.

Lane structure mirrors __graft_entry__.dryrun_multichip: the dp SpecLayout
lane is held to the hand-wired dp lane's 5e-4 gate, the ZeRO lane to the
sharded-vs-replicated 2e-5 gate.
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.distributed.spec_layout import (
    SpecLayout, shard_batch, shard_params, unshard,
)
from paddle_tpu.jit.compiled_step import (
    CompiledTrainStep, compile_stats, reset_compile_stats,
)

NDEV = len(jax.devices())


@pytest.fixture()
def mesh_guard():
    yield
    build_mesh()


@pytest.fixture()
def flag_guard():
    """Restore every flag this suite toggles."""
    names = ["FLAGS_compiled_step", "FLAGS_compiled_step_max_retraces",
             "FLAGS_input_prefetch", "FLAGS_donate_state_buffers"]
    old = paddle.get_flags(names)
    yield
    paddle.set_flags(old)


def _mlp(seed=0, din=8, dh=32, dout=4):
    """Parity harness net. Tanh, not ReLU, on purpose: a hidden unit whose
    pre-activation sits within a ULP of zero lets the 1-ULP fusion noise
    flip its ReLU mask, amplifying an invisible difference into an O(grad)
    parameter divergence (observed at step 5 of the rollback lane). A smooth
    activation keeps ULP-scale noise ULP-scale, which is the contract the
    tolerance gates encode."""
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(din, dh), nn.Tanh(), nn.Linear(dh, dout))


def _mlp_batches(steps, batch=16, din=8, dout=4, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(steps, batch, din).astype("float32")
    ys = rng.randint(0, dout, (steps, batch)).astype("int64")
    return xs, ys


def _train_step_fn(model, opt, scaler=None):
    loss_fn = nn.CrossEntropyLoss()

    def _step(x, y):
        loss = loss_fn(model(x), y)
        if scaler is not None:
            scaled = scaler.scale(loss)
            scaled.backward()
            scaler.step(opt)
        else:
            loss.backward()
            opt.step()
        opt.clear_grad()
        return loss

    return _step


def _run_mlp(compiled, steps=32, opt_cls="adamw", use_scaler=False,
             lr=0.05, seed=0):
    """Fresh model+opt from `seed`; returns (losses f64 list, final params)."""
    model = _mlp(seed=seed)
    if opt_cls == "adamw":
        opt = paddle.optimizer.AdamW(learning_rate=lr,
                                     parameters=model.parameters())
    else:
        opt = paddle.optimizer.SGD(learning_rate=lr,
                                   parameters=model.parameters())
    scaler = (paddle.amp.GradScaler(init_loss_scaling=2.0 ** 8)
              if use_scaler else None)
    raw = _train_step_fn(model, opt, scaler)
    step = CompiledTrainStep(raw, label="test.mlp") if compiled else raw
    xs, ys = _mlp_batches(steps)
    losses = []
    for i in range(steps):
        loss = step(paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i]))
        losses.append(float(np.asarray(loss.numpy(), np.float64)))
    params = [np.asarray(p._val, np.float64).copy()
              for p in model.parameters()]
    return losses, params


# ULP-scale gate for fused-vs-eager train steps (docs/compiled_step.md)
_FUSION_RTOL = 2e-6


class TestTrainParity:
    def test_mlp_adamw_32_step_parity(self):
        e_l, e_p = _run_mlp(compiled=False)
        c_l, c_p = _run_mlp(compiled=True)
        np.testing.assert_allclose(c_l, e_l, rtol=_FUSION_RTOL, atol=1e-7)
        # AdamW divides by sqrt(v)+eps: near-zero second moments amplify
        # ULP noise in the params a bit beyond the loss gate
        for a, b in zip(c_p, e_p):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=5e-6)

    def test_mlp_sgd_parity(self):
        e_l, _ = _run_mlp(compiled=False, opt_cls="sgd", steps=32)
        c_l, _ = _run_mlp(compiled=True, opt_cls="sgd", steps=32)
        np.testing.assert_allclose(c_l, e_l, rtol=_FUSION_RTOL, atol=1e-7)

    def test_amp_scaler_parity(self):
        """GradScaler state (scale, good/bad counters) is Tensor state —
        auto-captured by discovery; power-of-two scaling is exact in f32 so
        the ULP gate still applies."""
        e_l, e_p = _run_mlp(compiled=False, use_scaler=True)
        c_l, c_p = _run_mlp(compiled=True, use_scaler=True)
        np.testing.assert_allclose(c_l, e_l, rtol=_FUSION_RTOL, atol=1e-7)
        for a, b in zip(c_p, e_p):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=5e-6)

    def test_forward_only_bit_exact(self):
        """No optimizer state in the program -> jit output is BIT-identical
        to eager (the fusion tolerance exists only for the fused bwd+update
        program)."""
        from paddle_tpu.core import autograd
        from paddle_tpu.jit.to_static import StaticFunction
        model = _mlp(seed=3)
        model.eval()
        fwd = StaticFunction(lambda x: model(x))
        xs, _ = _mlp_batches(4, seed=7)
        with autograd.no_grad():
            for i in range(4):
                x = paddle.to_tensor(xs[i])
                eager = np.asarray(model(x)._val)
                out = np.asarray(fwd(x)._val)
                assert np.array_equal(out, eager)

    def test_gpt_toy_parity(self):
        """LM lane: tiny GPT decoder, 32 fused AdamW steps vs eager."""
        from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

        def run(compiled):
            paddle.seed(11)
            cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=2, max_position_embeddings=16,
                            dropout=0.0)
            model = GPTForCausalLM(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=model.parameters())

            def _step(x, y):
                loss = model(x, labels=y)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss

            step = CompiledTrainStep(_step, label="test.gpt") \
                if compiled else _step
            rng = np.random.RandomState(5)
            ids = rng.randint(0, 64, (32, 4, 17)).astype("int64")
            out = []
            for i in range(32):
                loss = step(paddle.to_tensor(ids[i, :, :-1].astype("int32")),
                            paddle.to_tensor(ids[i, :, 1:]))
                out.append(float(np.asarray(loss.numpy(), np.float64)))
            return out

        np.testing.assert_allclose(run(True), run(False),
                                   rtol=_FUSION_RTOL, atol=1e-7)


class TestGuardAndDonation:
    def test_donation_safety(self, flag_guard):
        """FLAGS_donate_state_buffers donates the state args of the jitted
        program; params must stay readable (rebound to the fresh outputs)
        and parity must hold."""
        paddle.set_flags({"FLAGS_donate_state_buffers": True})
        c_l, c_p = _run_mlp(compiled=True)
        paddle.set_flags({"FLAGS_donate_state_buffers": False})
        e_l, e_p = _run_mlp(compiled=True)
        np.testing.assert_allclose(c_l, e_l, rtol=_FUSION_RTOL, atol=1e-7)
        for a, b in zip(c_p, e_p):
            assert np.all(np.isfinite(a))
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_host_import_donation_taint(self, flag_guard):
        """Donation safety contract (core/tensor.py _donate_unsafe): a value
        assigned from the host (set_state_dict / checkpoint load) may be
        backed by an imported numpy buffer, which PJRT-CPU must NOT donate
        (donating one corrupts memory — silently wrong parameters, sometimes
        a segfault). The gate donates a copy of a tainted value instead
        (tests/test_donation_gate.py), and the launch's write-back clears
        the taint."""
        paddle.set_flags({"FLAGS_donate_state_buffers": True})
        model = _mlp(seed=3)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = CompiledTrainStep(_train_step_fn(model, opt),
                                 label="test.taint")
        xs, ys = _mlp_batches(4, seed=11)
        for i in range(3):  # discovery x1, build+run, fast path
            step(paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i]))
        p0 = list(model.parameters())[0]
        assert p0._donate_unsafe is False  # write-back arrays are XLA-owned
        snap = {k: paddle.to_tensor(np.asarray(v._val).copy())
                for k, v in model.state_dict().items()}
        model.set_state_dict(snap)
        assert p0._donate_unsafe is True   # host-imported: must not donate
        step(paddle.to_tensor(xs[3]), paddle.to_tensor(ys[3]))
        assert p0._donate_unsafe is False  # the launch wrote its own output back

    def test_stepguard_rollback_parity(self):
        """A NaN batch under the compiled step restores pre-step state
        exactly (StepGuard snapshots on the host, outside the program) and
        the run continues on the eager oracle's trajectory."""
        from paddle_tpu.resilience.guard import StepGuard

        def run(compiled):
            model = _mlp(seed=2)
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=model.parameters())
            raw = _train_step_fn(model, opt)
            step = CompiledTrainStep(raw, label="test.guard") \
                if compiled else raw
            guard = StepGuard([model, opt], max_bad_steps=3)
            xs, ys = _mlp_batches(8, seed=9)
            xs = xs.copy()
            xs[3, 0, 0] = np.nan  # poisoned batch -> NaN loss
            kept, pre_poison = [], None
            for i in range(8):
                guard.before_step()
                if i == 3:
                    pre_poison = [np.asarray(p._val).copy()
                                  for p in model.parameters()]
                loss = step(paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i]))
                kept.append(guard.after_step(loss))
                if i == 3:
                    # restore exactness: the poisoned step's NaN update must
                    # be rolled back BIT-exactly (host snapshot round-trip)
                    for p, want in zip(model.parameters(), pre_poison):
                        assert np.array_equal(np.asarray(p._val), want)
            params = [np.asarray(p._val, np.float64).copy()
                      for p in model.parameters()]
            return kept, guard.skipped, params

        c_kept, c_skip, c_p = run(True)
        e_kept, e_skip, e_p = run(False)
        assert c_kept == e_kept and c_skip == e_skip == 1
        assert c_kept[3] is False
        for a, b in zip(c_p, e_p):
            assert np.all(np.isfinite(a))
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")
class TestSpecLayoutLanes:
    """GSPMD lanes vs the replicated oracle, at the hand-wired MULTICHIP
    dryrun gates (dp 5e-4; ZeRO-vs-DP 2e-5)."""

    def _run_lane(self, layout, steps=6, seed=4):
        model = _mlp(seed=seed, din=8, dh=32, dout=4)
        if layout is not None:
            shard_params(model, layout)
        opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                     parameters=model.parameters())
        step = CompiledTrainStep(_train_step_fn(model, opt),
                                 label="test.spec")
        xs, ys = _mlp_batches(steps, batch=16, seed=6)
        losses = []
        for i in range(steps):
            x, y = paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i])
            if layout is not None:
                shard_batch(layout, x, y)
            loss = step(x, y)
            losses.append(float(np.asarray(loss.numpy(), np.float64)))
        unshard(model)
        params = [np.asarray(p._val, np.float64).copy()
                  for p in model.parameters()]
        return losses, params

    def test_dp_lane_matches_replicated(self, mesh_guard):
        base_l, base_p = self._run_lane(None)
        build_mesh({"data": 8})
        dp_l, dp_p = self._run_lane(SpecLayout())
        np.testing.assert_allclose(dp_l, base_l, rtol=5e-4, atol=5e-4)
        for a, b in zip(dp_p, base_p):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)

    def test_zero_lane_matches_dp(self, mesh_guard):
        build_mesh({"data": 4, "sharding": 2})
        dp_l, dp_p = self._run_lane(SpecLayout(shard_params=False))
        zero_layout = SpecLayout(shard_params=True)
        z_l, z_p = self._run_lane(zero_layout)
        np.testing.assert_allclose(z_l, dp_l, rtol=2e-5, atol=2e-5)
        for a, b in zip(z_p, dp_p):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    def test_param_spec_shards_divisible_dim(self, mesh_guard):
        build_mesh({"data": 4, "sharding": 2})
        lay = SpecLayout(shard_params=True)
        from jax.sharding import PartitionSpec as P
        assert lay.param_spec((32, 8)) == P("sharding", None)
        assert lay.param_spec((3, 5)) == P()   # nothing divisible
        assert lay.param_spec(()) == P()       # scalar state
        model = _mlp(seed=0)
        n = shard_params(model, lay)
        assert n >= 2  # both Linear weights shard
        unshard(model)


class TestCompileObservability:
    def test_one_compile_per_signature(self):
        from paddle_tpu.profiler.metrics import get_registry
        model = _mlp(seed=1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = CompiledTrainStep(_train_step_fn(model, opt),
                                 label="test.counters")
        xs, ys = _mlp_batches(6)
        reset_compile_stats()
        c0 = get_registry().snapshot()["counters"].get(
            "compiled_step.compiles_total", 0.0)
        h0 = get_registry().snapshot()["counters"].get(
            "compiled_step.cache_hits_total", 0.0)
        for i in range(6):
            step(paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i]))
        stats = compile_stats()
        # call 1 = eager discovery, call 2 = XLA build (the one compile),
        # calls 3..6 = steady-state cache hits
        assert stats["compiles"] == 1, stats
        assert stats["cache_hits"] == 4, stats
        counters = get_registry().snapshot()["counters"]
        assert counters.get("compiled_step.compiles_total", 0.0) - c0 == 1.0
        assert counters.get("compiled_step.cache_hits_total", 0.0) - h0 == 4.0

    def test_compile_phase_attributed(self):
        from paddle_tpu.profiler import steptimer as _steptimer
        _steptimer.reset_steptimer()
        model = _mlp(seed=1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = CompiledTrainStep(_train_step_fn(model, opt),
                                 label="test.phase")
        xs, ys = _mlp_batches(3)
        st = _steptimer.get_steptimer()
        for i in range(3):
            with st.step(n_steps=1):
                step(paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i]))
        bd = st.breakdown()
        # breakdown() shortens "step/compile" -> "compile" (steptimer._short)
        assert bd["phase_ms"].get("compile", 0.0) > 0.0
        _steptimer.reset_steptimer()

    def test_retrace_storm_warning(self, flag_guard):
        from paddle_tpu.resilience.recorder import get_recorder
        paddle.set_flags({"FLAGS_compiled_step_max_retraces": 2})
        model = _mlp(seed=1)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        step = CompiledTrainStep(_train_step_fn(model, opt),
                                 label="test.storm")
        rng = np.random.RandomState(0)
        reset_compile_stats()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for batch in (4, 5, 6, 7):  # 4 distinct signatures > bound 2
                x = paddle.to_tensor(
                    rng.randn(batch, 8).astype("float32"))
                y = paddle.to_tensor(
                    rng.randint(0, 4, (batch,)).astype("int64"))
                step(x, y)
                step(x, y)
        storm = [w for w in caught
                 if issubclass(w.category, RuntimeWarning)
                 and "retrace" in str(w.message)]
        assert len(storm) == 1, [str(w.message) for w in caught]
        assert "FLAGS_compiled_step_max_retraces" in str(storm[0].message)
        assert compile_stats()["retrace_warnings"] == 1
        tail = get_recorder().tail(10)
        assert any(e["op"] == "compiled_step.retrace_storm" for e in tail)

    def test_disabled_wrapper_is_pure_eager(self, flag_guard):
        """ProgramTranslator off -> the wrapper is a passthrough: no
        compiles, no cache hits, eager semantics."""
        paddle.jit.enable_to_static(False)
        try:
            model = _mlp(seed=1)
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=model.parameters())
            step = CompiledTrainStep(_train_step_fn(model, opt),
                                     label="test.eager")
            xs, ys = _mlp_batches(3)
            reset_compile_stats()
            for i in range(3):
                step(paddle.to_tensor(xs[i]), paddle.to_tensor(ys[i]))
            assert compile_stats() == {"compiles": 0, "cache_hits": 0,
                                       "retrace_warnings": 0}
        finally:
            paddle.jit.enable_to_static(True)


class _SeqDS:
    """Deterministic dataset: item i is a fixed function of i."""

    def __init__(self, n=24, din=8, delay_s=0.0):
        self.n, self.din, self.delay_s = n, din, delay_s

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if self.delay_s:
            import time
            # the slow-dataset stand-in proving prefetch overlap:
            # blocking-ok: the delay IS the fixture
            time.sleep(self.delay_s)
        rng = np.random.RandomState(i)
        return (rng.randn(self.din).astype("float32"),
                np.array([i % 4], "int64"))


class TestInputPrefetch:
    def _fit(self, prefetch, num_iters=None, epochs=1, delay_s=0.0,
             compiled=False, spe=1):
        from paddle_tpu.hapi.callbacks import Callback
        paddle.set_flags({"FLAGS_input_prefetch": prefetch,
                          "FLAGS_compiled_step": compiled})
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        m = paddle.Model(net)
        m.prepare(optimizer=paddle.optimizer.SGD(
            learning_rate=0.1, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        seen = []

        class Rec(Callback):
            def on_train_batch_end(self, step, logs=None):
                seen.append((step, logs["loss"][0]))

        m.fit(_SeqDS(delay_s=delay_s), batch_size=4, epochs=epochs,
              verbose=0, shuffle=False, num_iters=num_iters,
              steps_per_execution=spe, callbacks=[Rec()])
        params = [p.numpy().astype(np.float64).copy()
                  for p in net.parameters()]
        return seen, params, m._active_loader

    def test_fit_parity_prefetch_on_off(self, flag_guard):
        s_on, p_on, _ = self._fit(prefetch=True, epochs=2)
        s_off, p_off, _ = self._fit(prefetch=False, epochs=2)
        assert [s for s, _ in s_on] == [s for s, _ in s_off]
        np.testing.assert_allclose([l for _, l in s_on],
                                   [l for _, l in s_off],
                                   rtol=1e-6, atol=1e-7)
        for a, b in zip(p_on, p_off):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    def test_fit_parity_compiled_and_grouped(self, flag_guard):
        """Prefetch + FLAGS_compiled_step + steps_per_execution together:
        the staged jax arrays flow through _as_tensor into the scan."""
        s_on, p_on, _ = self._fit(prefetch=True, compiled=True, spe=3)
        s_off, p_off, _ = self._fit(prefetch=False, compiled=False, spe=1)
        assert [s for s, _ in s_on] == [s for s, _ in s_off]
        np.testing.assert_allclose([l for _, l in s_on],
                                   [l for _, l in s_off],
                                   rtol=2e-4, atol=2e-4)
        for a, b in zip(p_on, p_off):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-3)

    def test_cursor_counts_trained_not_fetched(self, flag_guard):
        """Exact-resume contract: read-ahead batches the run never trained
        on must not advance the loader cursor."""
        _, _, loader = self._fit(prefetch=True, num_iters=3)
        assert loader.state_dict()["batches_consumed"] == 3

    def test_prefetch_error_surfaces_at_step(self, flag_guard):
        class Poison(_SeqDS):
            def __getitem__(self, i):
                if i >= 8:
                    raise ValueError("poisoned shard")
                return super().__getitem__(i)

        paddle.set_flags({"FLAGS_input_prefetch": True})
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
        m = paddle.Model(net)
        m.prepare(optimizer=paddle.optimizer.SGD(
            learning_rate=0.1, parameters=net.parameters()),
            loss=nn.CrossEntropyLoss())
        with pytest.raises(ValueError, match="poisoned shard"):
            m.fit(Poison(), batch_size=4, epochs=1, verbose=0, shuffle=False)

    def test_input_wait_drops_under_prefetch(self, flag_guard):
        """With a slow loader, read-ahead overlaps fetch with compute, so
        the step/input_wait total must drop vs the synchronous path. The
        margin is deliberately loose (CI boxes are noisy); the sign of the
        effect is what's asserted."""
        from paddle_tpu.profiler import steptimer as _steptimer

        def wait_ms(prefetch):
            _steptimer.reset_steptimer()
            self._fit(prefetch=prefetch, delay_s=0.02)
            bd = _steptimer.get_steptimer().breakdown()
            _steptimer.reset_steptimer()
            # breakdown() shortens "step/input_wait" -> "input_wait"
            return bd["phase_ms"].get("input_wait", 0.0)

        sync_ms = wait_ms(False)
        pre_ms = wait_ms(True)
        # 24 items / batch 4 at 20ms/item => >= ~480ms synchronous wait;
        # overlap must reclaim a visible slice of it
        assert sync_ms > 300.0, sync_ms
        assert pre_ms < sync_ms * 0.9, (pre_ms, sync_ms)

    def test_prefetch_stage_metric_observed(self, flag_guard):
        from paddle_tpu.profiler.metrics import get_registry
        self._fit(prefetch=True, num_iters=2)
        hists = get_registry().snapshot()["histograms"]
        assert any(k.startswith("io.prefetch_stage_ms") for k in hists), \
            sorted(hists)


class TestHapiCompiledRouting:
    def test_flag_routes_train_batch(self, flag_guard):
        """FLAGS_compiled_step=True makes hapi build a CompiledTrainStep;
        losses match the default StaticFunction path."""
        def run(flag):
            paddle.set_flags({"FLAGS_compiled_step": flag,
                              "FLAGS_input_prefetch": False})
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                nn.Linear(16, 4))
            m = paddle.Model(net)
            m.prepare(optimizer=paddle.optimizer.SGD(
                learning_rate=0.1, parameters=net.parameters()),
                loss=nn.CrossEntropyLoss())
            xs, ys = _mlp_batches(4, batch=4, seed=3)
            losses = [m.train_batch([xs[i]], [ys[i]])[0] for i in range(4)]
            return m, losses

        m_c, c = run(True)
        assert isinstance(m_c._compiled_train_step, CompiledTrainStep)
        m_e, e = run(False)
        assert not isinstance(m_e._compiled_train_step, CompiledTrainStep)
        np.testing.assert_allclose(c, e, rtol=_FUSION_RTOL, atol=1e-7)

    def test_spec_layout_via_prepare(self, flag_guard, mesh_guard):
        if NDEV < 8:
            pytest.skip("needs 8 virtual devices")
        build_mesh({"data": 8})
        paddle.set_flags({"FLAGS_compiled_step": True,
                          "FLAGS_input_prefetch": False})

        def run(layout):
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                nn.Linear(16, 4))
            m = paddle.Model(net)
            m.prepare(optimizer=paddle.optimizer.SGD(
                learning_rate=0.1, parameters=net.parameters()),
                loss=nn.CrossEntropyLoss(), spec_layout=layout)
            xs, ys = _mlp_batches(4, batch=16, seed=3)
            return [m.train_batch([xs[i]], [ys[i]])[0] for i in range(4)]

        sharded = run(SpecLayout())
        build_mesh()
        plain = run(None)
        np.testing.assert_allclose(sharded, plain, rtol=5e-4, atol=5e-4)
