"""Tests for the TPU-native stretch components (SURVEY.md §5):
ring attention (sequence parallel), the SPMD circular pipeline, and the
Pallas flash-attention kernel (run under the pallas interpreter on CPU).

Each is asserted against a dense/sequential oracle — forward AND backward —
on the virtual 8-device CPU mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.ops.attention import scaled_dot_product_attention

NDEV = len(jax.devices())
pytestmark = pytest.mark.skipif(NDEV < 8, reason="needs 8 virtual devices")


@pytest.fixture()
def mesh_guard():
    yield
    build_mesh()


def _qkv(b=2, s=32, h=2, d=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.randn(b, s, h, d).astype("float32") * 0.5
    return mk(), mk(), mk()


class TestRingAttention:
    """ring_attention over the 'sep' axis vs dense SDPA oracle."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity(self, mesh_guard, causal):
        from paddle_tpu.distributed.fleet.sequence_parallel import (
            ring_attention,
        )
        q_np, k_np, v_np = _qkv()
        build_mesh({"sep": 8})
        q, k, v = (paddle.to_tensor(a) for a in (q_np, k_np, v_np))
        out_ring = np.asarray(
            ring_attention(q, k, v, is_causal=causal)._val)

        build_mesh()  # dense oracle on the default mesh
        out_ref = np.asarray(scaled_dot_product_attention(
            paddle.to_tensor(q_np), paddle.to_tensor(k_np),
            paddle.to_tensor(v_np), is_causal=causal)._val)
        np.testing.assert_allclose(out_ring, out_ref, rtol=2e-5, atol=2e-6)

    # non-causal backward exercises the same vjp path; keep one variant in
    # the default lane and the other in the slow lane (compile-bound)
    @pytest.mark.parametrize(
        "causal", [pytest.param(False, marks=pytest.mark.slow), True])
    def test_backward_parity(self, mesh_guard, causal):
        from paddle_tpu.distributed.fleet.sequence_parallel import (
            ring_attention,
        )
        q_np, k_np, v_np = _qkv(seed=1)

        def grads(attn_fn):
            ts = [paddle.to_tensor(a) for a in (q_np, k_np, v_np)]
            for t in ts:
                t.stop_gradient = False
            out = attn_fn(*ts)
            (out * out).sum().backward()
            return [np.asarray(t.grad._val) for t in ts]

        build_mesh({"sep": 8})
        g_ring = grads(lambda q, k, v: ring_attention(
            q, k, v, is_causal=causal))
        build_mesh()
        g_ref = grads(lambda q, k, v: scaled_dot_product_attention(
            q, k, v, is_causal=causal))
        for gr, gd, nm in zip(g_ring, g_ref, "qkv"):
            np.testing.assert_allclose(gr, gd, rtol=5e-4, atol=5e-6,
                                       err_msg=f"grad wrt {nm}")

    def test_split_gather_sequence_roundtrip(self, mesh_guard):
        from paddle_tpu.distributed.fleet.sequence_parallel import (
            gather_sequence, split_sequence,
        )
        build_mesh({"sep": 8})
        x = paddle.to_tensor(np.arange(64, dtype="float32").reshape(2, 16, 2))
        s = split_sequence(x)
        assert len({sh.device for sh in s._val.addressable_shards}) == 8
        g = gather_sequence(s)
        np.testing.assert_allclose(np.asarray(g._val), np.asarray(x._val))


class TestSpmdPipeline:
    """PipelineStageStack pipelined (pipe axis) vs sequential execution."""

    def _make_stack(self, num_stages, num_micro):
        from paddle_tpu.distributed.fleet.spmd_pipeline import (
            PipelineStageStack,
        )
        paddle.seed(42)
        return PipelineStageStack(
            lambda: nn.Sequential(nn.Linear(16, 16), nn.Tanh()),
            num_stages=num_stages, num_microbatches=num_micro)

    def test_pipelined_equals_sequential(self, mesh_guard):
        build_mesh({"pipe": 4})  # data axis auto-padded to 2
        stack = self._make_stack(num_stages=4, num_micro=4)
        x_np = np.random.RandomState(0).randn(8, 16).astype("float32")
        out_pipe = np.asarray(stack(paddle.to_tensor(x_np))._val)

        build_mesh()  # degree('pipe') == 1 -> sequential path, same params
        out_seq = np.asarray(stack(paddle.to_tensor(x_np))._val)
        np.testing.assert_allclose(out_pipe, out_seq, rtol=2e-5, atol=1e-6)
        # sanity: sequential path really applies all 4 stages
        assert not np.allclose(out_seq, x_np)

    def test_backward_parity_and_training(self, mesh_guard):
        build_mesh({"pipe": 4})
        stack = self._make_stack(num_stages=4, num_micro=2)
        x_np = np.random.RandomState(1).randn(4, 16).astype("float32")

        def param_grads():
            out = stack(paddle.to_tensor(x_np))
            (out * out).sum().backward()
            gs = {k: np.asarray(p.grad._val)
                  for k, p in stack.named_parameters() if p.grad is not None}
            for p in stack.parameters():
                p.clear_grad()
            return gs

        g_pipe = param_grads()
        build_mesh()
        g_seq = param_grads()
        assert set(g_pipe) == set(g_seq) and g_pipe
        for k in g_seq:
            np.testing.assert_allclose(g_pipe[k], g_seq[k], rtol=1e-4,
                                       atol=1e-6, err_msg=k)

    def test_stage_count_must_match_axis(self, mesh_guard):
        build_mesh({"pipe": 4})
        with pytest.raises(ValueError, match="must equal"):
            self._make_stack(num_stages=3, num_micro=2)


class TestFlashAttention:
    """Pallas flash attention (interpret mode on CPU) vs XLA SDPA."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_kernel_forward_parity(self, causal):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        rng = np.random.RandomState(3)
        b, s, h, d = 2, 64, 2, 16
        q = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))
        k = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))
        v = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))
        scale = 1.0 / np.sqrt(d)
        out = flash_attention(q, k, v, causal=causal, scale=scale,
                              block_q=16, block_k=16)
        ref = np.asarray(scaled_dot_product_attention(
            paddle.to_tensor(np.asarray(q)), paddle.to_tensor(np.asarray(k)),
            paddle.to_tensor(np.asarray(v)), is_causal=causal,
            use_pallas=False)._val)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-6)

    def test_sdpa_pallas_path_forward_backward(self):
        """scaled_dot_product_attention(use_pallas=True) end-to-end: pallas
        forward (interpreted on CPU), XLA-recompute backward."""
        rng = np.random.RandomState(4)
        b, s, h, d = 1, 128, 2, 128  # shapes the TPU kernel would accept
        mk = lambda: rng.randn(b, s, h, d).astype("float32") * 0.3

        def run(use_pallas):
            ts = [paddle.to_tensor(mk_np) for mk_np in arrays]
            for t in ts:
                t.stop_gradient = False
            out = scaled_dot_product_attention(*ts, is_causal=True,
                                               use_pallas=use_pallas)
            (out * out).sum().backward()
            return (np.asarray(out._val),
                    [np.asarray(t.grad._val) for t in ts])

        arrays = [mk(), mk(), mk()]
        out_p, g_p = run(True)
        out_x, g_x = run(False)
        np.testing.assert_allclose(out_p, out_x, rtol=2e-5, atol=2e-6)
        for a, b_, nm in zip(g_p, g_x, "qkv"):
            np.testing.assert_allclose(a, b_, rtol=5e-4, atol=5e-6,
                                       err_msg=f"grad wrt {nm}")

    def test_rejects_mask_with_pallas(self):
        q = paddle.to_tensor(np.zeros((1, 16, 1, 8), "float32"))
        mask = paddle.to_tensor(np.zeros((1, 1, 16, 16), "float32"))
        with pytest.raises(ValueError, match="incompatible"):
            scaled_dot_product_attention(q, q, q, attn_mask=mask,
                                         use_pallas=True)


class TestFlashAttentionBackward:
    """Dedicated Pallas-backward parity (the one-pass FlashAttention-2
    recompute kernel, ops/pallas/flash_attention.py) vs jax.vjp through the
    XLA path — including head_dim=64, the GPT/BERT geometry the r3 kernel
    rejected, grouped heads, and a length the 512-tiles do not divide."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("d", [64, 128])
    def test_grad_parity_vs_xla(self, causal, d):
        from paddle_tpu.ops.attention import _flash_attention_diff, \
            _xla_attention
        import jax
        rng = np.random.RandomState(7)
        b, s, h = 1, 256, 2
        scale = 1.0 / np.sqrt(d)
        q, k, v = (jnp.asarray(rng.randn(b, s, h, d).astype("float32")) * 0.3
                   for _ in range(3))
        g = jnp.asarray(rng.randn(b, s, h, d).astype("float32"))

        out_p, vjp_p = jax.vjp(
            lambda q_, k_, v_: _flash_attention_diff(q_, k_, v_, causal,
                                                     scale, True), q, k, v)
        out_x, vjp_x = jax.vjp(
            lambda q_, k_, v_: _xla_attention(q_, k_, v_, None, scale,
                                              causal, 0.0, None), q, k, v)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                                   rtol=2e-5, atol=2e-6)
        for gp, gx, nm in zip(vjp_p(g), vjp_x(g), "qkv"):
            np.testing.assert_allclose(np.asarray(gp), np.asarray(gx),
                                       rtol=5e-4, atol=1e-5,
                                       err_msg=f"grad wrt {nm}")

    @staticmethod
    def _operands(s, h, h_kv, d, dtype, seed=9, b=1):
        rng = np.random.RandomState(seed)
        mk = lambda heads: jnp.asarray(
            rng.randn(b, s, heads, d).astype("float32") * 0.3).astype(dtype)
        return mk(h), mk(h_kv), mk(h_kv), mk(h) / 0.3

    @staticmethod
    def _xla_grads(q, k, v, g, causal, scale):
        """float32 gradients of XLA's attention at the operands' values."""
        from paddle_tpu.ops.attention import _xla_attention
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        _, vjp = jax.vjp(lambda q_, k_, v_: _xla_attention(
            q_, k_, v_, None, scale, causal, 0.0, None), *f32)
        return vjp(g.astype(jnp.float32))

    @staticmethod
    def _assert_grads(got, want, dtype):
        for gp, gx, nm in zip(got, want, "qkv"):
            assert gp.dtype == dtype and gp.shape == gx.shape, nm
            gp = np.asarray(gp.astype(jnp.float32))
            if dtype == jnp.float32:
                np.testing.assert_allclose(gp, np.asarray(gx), rtol=5e-4,
                                           atol=1e-5, err_msg=f"grad wrt {nm}")
            else:       # bf16 results: chip_smoke.py's limit, of the largest
                err = np.abs(gp - np.asarray(gx)).max() / np.abs(gx).max()
                assert err < 2e-2, (nm, err)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("group", [1, 4])
    def test_one_pass_grad_parity_at_768(self, group, d, causal, dtype):
        """768 positions: the 512-tiles clamp to 256, three key tiles, so a
        causal call runs tile pairs on, under and (skipped) over the
        diagonal; group 4 adds four query heads into one dk, dv in VMEM."""
        from paddle_tpu.ops.attention import _flash_attention_diff
        s, h = 768, 4
        scale = 1.0 / np.sqrt(d)
        q, k, v, g = self._operands(s, h, h // group, d, dtype)
        _, vjp = jax.vjp(lambda q_, k_, v_: _flash_attention_diff(
            q_, k_, v_, causal, scale, True), q, k, v)
        self._assert_grads(vjp(g), self._xla_grads(q, k, v, g, causal, scale),
                           dtype)

    @pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128),
                                                 (128, 128), (512, 256)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_unequal_tiles_same_gradients(self, causal, block_q, block_k):
        """Tiles of unequal sides move where the diagonal crosses a tile
        pair (two query blocks a key tile, or half of one), never the
        numbers."""
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_bwd, flash_attention_fwd)
        d, scale = 64, 0.125
        q, k, v, g = self._operands(512, 4, 2, d, jnp.float32, seed=10)
        out, lse = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                       block_q=block_q, block_k=block_k)
        got = flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                  scale=scale, block_q=block_q,
                                  block_k=block_k)
        self._assert_grads(got, self._xla_grads(q, k, v, g, causal, scale),
                           jnp.float32)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("group", [1, 4])
    def test_query_range_in_spans_same_gradients(self, group, causal):
        """Where a group's q, dO, dQ would not fit VMEM the query range is
        cut into spans (`_bwd_q_span`); here three spans of 256 rows by
        hand: dq a span, dk and dv float32 partials that one sum adds."""
        from paddle_tpu.ops.pallas import flash_attention as fa
        d, scale, dtype = 64, 0.125, jnp.bfloat16
        q, k, v, g = self._operands(768, 4, 4 // group, d, dtype, seed=11)
        out, lse = fa.flash_attention_fwd(q, k, v, causal=causal, scale=scale)
        args = [fa._to_bh(x) for x in (q, k, v, out)] + [
            lse.reshape(4, 768), fa._to_bh(g)]
        whole = fa._flash_bwd_bh(*args, causal, scale, 256, 256, True)
        spans = fa._flash_bwd_bh(*args, causal, scale, 256, 256, True,
                                 q_span=256)
        for a, b_, nm in zip(spans, whole, "qkv"):
            assert a.dtype == dtype and a.shape == b_.shape
            np.testing.assert_allclose(
                np.asarray(a.astype(jnp.float32)),
                np.asarray(b_.astype(jnp.float32)), rtol=2e-2, atol=2e-3,
                err_msg=f"grad wrt {nm}")
        got = [fa._from_bh(x, 1, n) for x, n in zip(spans, (4, 4 // group,
                                                            4 // group))]
        self._assert_grads(got, self._xla_grads(q, k, v, g, causal, scale),
                           dtype)

    def test_span_rule_follows_the_shapes(self):
        from paddle_tpu.ops.pallas.flash_attention import (
            VMEM_RESIDENT_BYTES, _bwd_q_span, _bwd_resident_bytes)
        # the LFM2 cell's group (4 heads of 64 over 4096 rows, bf16) and the
        # GPT family's heads of 128 hold their whole sequence
        assert _bwd_q_span(4, 4096, 64, 2, 512) == 4096
        assert _bwd_q_span(1, 4096, 128, 2, 512) == 4096
        assert _bwd_q_span(1, 768, 64, 4, 256) == 768
        # 32k positions at group 4 do not: whole query blocks that divide
        # the sequence and fit
        for group, seq, d, size, block in [(4, 32768, 64, 2, 512),
                                           (4, 16384, 128, 4, 256),
                                           (8, 24576, 64, 2, 512)]:
            span = _bwd_q_span(group, seq, d, size, block)
            assert span < seq and seq % span == 0 and span % block == 0
            assert _bwd_resident_bytes(group, span, d, size) \
                <= VMEM_RESIDENT_BYTES < _bwd_resident_bytes(group, seq, d, size)

    @pytest.mark.parametrize("group,d", [(1, 128), (4, 64), (4, 128)])
    def test_backward_stages_one_kernel_and_no_float32_copies(self, group, d):
        """One pallas_call a backward; dk, dv leave it in k's and v's dtype
        at (B*H / group, S, D); with bf16 operands the program holds no
        float32 array of q's or k's (B*H, S, D) shape (the group's partial
        gradients, a widened operand) and no float32 operand or result of a
        kernel is 128 lanes of a per-row statistic."""
        from paddle_tpu.ops.attention import _flash_attention_diff
        b, s, h = 2, 256, 4
        q, k, v, g = self._operands(s, h, h // group, d, jnp.bfloat16, b=b)

        def bwd(q_, k_, v_, g_):
            return jax.vjp(lambda *a: _flash_attention_diff(
                *a, True, d ** -0.5, True), q_, k_, v_)[1](g_)

        calls, f32_shapes = [], set()

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    calls.append(eqn)
                    continue                    # not the kernel's own body
                for var in eqn.outvars:
                    if var.aval.dtype == jnp.float32:
                        f32_shapes.add(tuple(var.aval.shape))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(bwd)(q, k, v, g).jaxpr)
        fwd_calls = [c for c in calls if len(c.outvars) == 2]
        bwd_calls = [c for c in calls if len(c.outvars) == 3]
        assert len(fwd_calls) == 1 and len(bwd_calls) == 1, calls
        dq, dk, dv = (o.aval for o in bwd_calls[0].outvars)
        # dq a query block transposed: (B*H, blocks, D, block_q)
        assert dq.shape == (b * h, 1, d, s) and dq.dtype == jnp.bfloat16
        for part in (dk, dv):
            assert part.shape == (1, b * h // group, s, d)
            assert part.dtype == jnp.bfloat16
        for shape in ((b * h, s, d), (b * h // group, s, d),
                      (1, b * h // group, s, d), (b * h, 1, d, s)):
            assert shape not in f32_shapes, shape
        for call in calls:
            for var in list(call.invars) + list(call.outvars):
                if var.aval.dtype == jnp.float32:
                    assert var.aval.shape[-1] != 128 or s == 128, var.aval
                    assert int(np.prod(var.aval.shape)) == b * h * s

    def test_supports_head_dim_64(self):
        from paddle_tpu.ops.pallas.flash_attention import supports
        assert supports((4, 1024, 16, 64), (4, 1024, 16, 64))
        assert supports((4, 1024, 16, 128), (4, 1024, 16, 128))
        assert not supports((4, 1000, 16, 64), (4, 1000, 16, 64))  # seq%128
        assert not supports((4, 1024, 16, 80), (4, 1024, 16, 80))  # d%64

    def test_lse_matches_logsumexp(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd
        rng = np.random.RandomState(8)
        b, s, h, d = 1, 128, 1, 64
        q, k, v = (jnp.asarray(rng.randn(b, s, h, d).astype("float32")) * 0.5
                   for _ in range(3))
        scale = 1.0 / np.sqrt(d)
        _, lse = flash_attention_fwd(q, k, v, causal=False, scale=scale)
        # oracle: logsumexp over the scaled score rows
        s_mat = np.einsum("bqhd,bkhd->bhqk", np.asarray(q),
                          np.asarray(k)) * scale
        ref = np.log(np.exp(s_mat - s_mat.max(-1, keepdims=True))
                     .sum(-1)) + s_mat.max(-1)
        np.testing.assert_allclose(np.asarray(lse), ref, rtol=1e-5,
                                   atol=1e-5)


class TestFlashAttentionUnderMesh:
    """Mosaic kernels cannot be partitioned automatically, so under a mesh
    scaled_dot_product_attention maps the flash kernel over it by hand
    (ops/attention.py _flash_prim). Results and gradients must equal the
    single-device ones — eagerly (mesh read off the operand) and inside a
    to_static program (mesh read off the program's inputs)."""

    def _qkv(self, sharding=None):
        import jax
        rng = np.random.RandomState(11)
        vals = [jnp.asarray(rng.randn(4, 256, 4, 64).astype("float32")) * 0.3
                for _ in range(3)]
        if sharding is not None:
            vals = [jax.device_put(v, sharding) for v in vals]
        ts = [paddle.to_tensor(v) for v in vals]
        for t in ts:
            t.stop_gradient = False
        return ts

    def _run(self, q, k, v):
        from paddle_tpu.ops.attention import scaled_dot_product_attention
        out = scaled_dot_product_attention(q, k, v, is_causal=True,
                                           use_pallas=True)
        out.sum().backward()
        return [np.asarray(t._val) for t in (out, q.grad, k.grad, v.grad)]

    def _mesh_sharding(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "model"))
        return mesh, NamedSharding(mesh, P("data", None, "model", None))

    def test_eager_sharded_operands_match_single_device(self):
        ref = self._run(*self._qkv())
        mesh, sharding = self._mesh_sharding()
        q, k, v = self._qkv(sharding)
        got = self._run(q, k, v)
        assert len(q.grad._val.sharding.device_set) == 4
        for a, b in zip(got, ref):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_to_static_program_over_a_mesh_maps_the_kernel(self):
        from paddle_tpu.distributed.mesh import operand_mesh
        from paddle_tpu.ops.attention import scaled_dot_product_attention
        mesh, sharding = self._mesh_sharding()
        seen = []

        @paddle.jit.to_static
        def fwd(q, k, v):
            seen.append(operand_mesh(q._val))
            return scaled_dot_product_attention(q, k, v, is_causal=True,
                                                use_pallas=True)

        ref = self._run(*self._qkv())[0]
        with paddle.no_grad():
            q, k, v = self._qkv(sharding)
            outs = [fwd(q, k, v) for _ in range(3)]   # eager, compile, cached
        assert seen[0] == mesh      # eager pass: read off the operand
        assert seen[-1] == mesh     # compile trace: the program's inputs'
        for o in outs:
            np.testing.assert_allclose(np.asarray(o._val), ref, rtol=1e-5,
                                       atol=1e-6)

    def test_single_device_program_sees_no_mesh(self):
        from paddle_tpu.distributed.mesh import operand_mesh, trace_mesh
        import jax
        q = self._qkv()[0]
        assert operand_mesh(q._val) is None
        seen = []
        jax.jit(lambda x: seen.append(operand_mesh(x)) or x)(q._val)
        assert seen == [None]
        mesh, _ = self._mesh_sharding()
        with trace_mesh(mesh):
            jax.jit(lambda x: seen.append(operand_mesh(x)) or x + 1)(q._val)
        assert seen[-1] == mesh
