"""sparse_attention numpy-oracle tests (SURVEY §4.1 pattern; reference
operators/sparse_attention_op.cu semantics)."""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


def _np_sparse_attention(q, k, v, offset, columns):
    b, h, s, d = q.shape
    out = np.zeros_like(q)
    for bi in range(b):
        for hi in range(h):
            logits = (q[bi, hi] @ k[bi, hi].T) / np.sqrt(d)
            mask = np.zeros((s, s), dtype=bool)
            off = offset[bi, hi]
            cols = columns[bi, hi]
            for r in range(s):
                mask[r, cols[off[r]:off[r + 1]]] = True
            logits = np.where(mask, logits, -1e30)
            e = np.exp(logits - logits.max(-1, keepdims=True))
            p = e / e.sum(-1, keepdims=True)
            p = np.where(mask.any(-1, keepdims=True), p, 0.0)
            out[bi, hi] = p @ v[bi, hi]
    return out


def _random_csr(rng, b, h, s, keep=0.5):
    offsets = np.zeros((b, h, s + 1), dtype=np.int32)
    cols = []
    for bi in range(b):
        for hi in range(h):
            row_cols = []
            for r in range(s):
                sel = np.flatnonzero(rng.rand(s) < keep)
                if sel.size == 0:
                    sel = np.array([r])
                row_cols.append(sel.astype(np.int32))
                offsets[bi, hi, r + 1] = offsets[bi, hi, r] + sel.size
            cols.append(np.concatenate(row_cols))
    nnz = max(c.size for c in cols)
    # pad all (b,h) lanes to a common nnz so the tensor is rectangular;
    # padded entries are given row seq-1 duplicate columns (harmless: the
    # offset table never points past the real nnz for that lane)
    colmat = np.zeros((b, h, nnz), dtype=np.int32)
    i = 0
    for bi in range(b):
        for hi in range(h):
            c = cols[i]
            colmat[bi, hi, :c.size] = c
            # pad region: repeat last real column; rows beyond offset[-1]
            # are never addressed by the oracle. For the kernel, searchsorted
            # assigns pad entries to the last row — also set mask there, so
            # make pads duplicates of an already-set position.
            if c.size < nnz:
                colmat[bi, hi, c.size:] = colmat[bi, hi, c.size - 1]
            i += 1
    return offsets, colmat


class TestSparseAttention:
    def test_docstring_example(self):
        q = np.array([[[[0, 1], [2, 3], [0, 1], [2, 3]]]], dtype=np.float32)
        offset = np.array([[[0, 2, 4, 6, 8]]], dtype=np.int32)
        columns = np.array([[[0, 1, 0, 1, 2, 3, 2, 3]]], dtype=np.int32)
        out = F.sparse_attention(
            paddle.to_tensor(q), paddle.to_tensor(q), paddle.to_tensor(q),
            paddle.to_tensor(offset), paddle.to_tensor(columns))
        expect = np.array([[[[1.60885942, 2.60885954],
                             [1.99830270, 2.99830270],
                             [1.60885942, 2.60885954],
                             [1.99830270, 2.99830270]]]], dtype=np.float32)
        np.testing.assert_allclose(out.numpy(), expect, rtol=1e-5, atol=1e-6)

    def test_vs_numpy_oracle_full_csr(self):
        rng = np.random.RandomState(7)
        b, h, s, d = 2, 3, 8, 4
        q = rng.randn(b, h, s, d).astype(np.float32)
        k = rng.randn(b, h, s, d).astype(np.float32)
        v = rng.randn(b, h, s, d).astype(np.float32)
        # full attention expressed as CSR — every row has all s columns
        offset = np.tile(np.arange(0, s * s + 1, s, dtype=np.int32),
                         (b, h, 1))
        columns = np.tile(np.tile(np.arange(s, dtype=np.int32), s), (b, h, 1))
        out = F.sparse_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            paddle.to_tensor(offset), paddle.to_tensor(columns))
        expect = _np_sparse_attention(q, k, v, offset, columns)
        np.testing.assert_allclose(out.numpy(), expect, rtol=1e-4, atol=1e-5)

    def test_gradient_flows(self):
        rng = np.random.RandomState(3)
        b, h, s, d = 1, 2, 4, 4
        q = paddle.to_tensor(rng.randn(b, h, s, d).astype(np.float32),
                             stop_gradient=False)
        k = paddle.to_tensor(rng.randn(b, h, s, d).astype(np.float32),
                             stop_gradient=False)
        v = paddle.to_tensor(rng.randn(b, h, s, d).astype(np.float32),
                             stop_gradient=False)
        offset = paddle.to_tensor(
            np.tile(np.arange(0, s * s + 1, s, dtype=np.int32), (b, h, 1)))
        columns = paddle.to_tensor(
            np.tile(np.tile(np.arange(s, dtype=np.int32), s), (b, h, 1)))
        out = F.sparse_attention(q, k, v, offset, columns)
        out.sum().backward()
        assert q.grad is not None and np.isfinite(q.grad.numpy()).all()
        assert v.grad is not None and abs(v.grad.numpy()).sum() > 0


# ---------------------------------------------------------------------------
# Attention over a set a query, and the learned index that picks the sets
# ---------------------------------------------------------------------------
#
# Attention over a set a query and the learned index that picks the sets
# (paddle_tpu/ops/sparse_index.py; the set kernels of
# ops/pallas/flash_attention.py; `key_set` on ops/attention.py) against dense
# masks in plain jax.numpy, float32 on the CPU, the kernels interpreted: the
# sets with `topk` below, at and above the row length and with ties, the flash
# pair over a set forward and backward at one and several tiles and spans, the
# index loss and its gradient, sectioned rotary positions from unequal
# streams, which path `takes_flash` gives a set, and softmax routing in the
# dropless expert layer.
#
# Tolerances: the kernels do the dense form's float32 arithmetic in another
# order (2e-6 of the largest entry measured); 2e-5 holds every reading and a
# bf16 product (4e-3) fails it.
import os  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from paddle_tpu.ops import attention, sparse_index  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402

B, S, H, HKV, D = 2, 256, 4, 2, 64
HI, DI = 4, 64
TOL = 2e-5


def normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), jnp.float32)


@pytest.fixture(scope="module")
def operands():
    return {"q": normal(1, B, S, H, D), "k": normal(2, B, S, HKV, D),
            "v": normal(3, B, S, HKV, D), "qi": normal(4, B, S, HI, DI),
            "ki": normal(5, B, S, DI), "w": normal(6, B, S, HI)}


def dense_scores(qi, ki, w):
    dots = jnp.einsum("bthd,bsd->bhts", qi, ki, precision="highest")
    return jnp.sum(jnp.swapaxes(w, 1, 2)[..., None] * jax.nn.relu(dots), axis=1)


def dense_set(qi, ki, w, topk):
    s = ki.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    row = jnp.where(causal, dense_scores(qi, ki, w), -jnp.inf)
    tau = jax.lax.top_k(row, min(topk, s))[0][..., -1:]
    return causal & (row >= tau)


def dense_attention(q, k, v, in_set, scale):
    kk, vv = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    dots = jnp.einsum("bthd,bshd->bhts", q, kk, precision="highest") * scale
    probs = jax.nn.softmax(jnp.where(in_set[:, None], dots, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, vv, precision="highest"), probs


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


# ---------------------------------------------------------------------------
# the sets

@pytest.mark.parametrize("topk", [1, 64, 255, 256, 400],
                         ids=["one", "below", "one-short", "at", "above"])
@pytest.mark.parametrize("chunk", [64, 256])
@pytest.mark.parametrize("mode", ["xla", "interpret"], ids=["xla", "kernel"])
def test_the_sets_are_the_rows_largest(operands, topk, chunk, mode):
    o = operands
    picked, stats = sparse_index.index_key_set(o["qi"], o["ki"], o["w"], topk, chunk,
                                               mode)
    want = dense_set(o["qi"], o["ki"], o["w"], topk)
    if mode != "xla":
        # the kernel's own layout, handed on as it is: a block of queries'
        # sets with the keys down the rows, and the pairs a tile
        sets, table = picked
        assert sets.dtype == jnp.int8 and sets.shape == (B, S // chunk, S, chunk)
        picked = fa.set_square(picked)
        assert bool(jnp.all(table == fa.tile_counts(picked, chunk, chunk).reshape(-1)))
        # a pair stays in the tiles it came in, whatever a caller would choose
        assert fa.set_tiles((sets, table), 32, 32) == (sets, table)
        assert fa.tile_blocks((sets, table)) == (chunk, chunk)
    assert picked.dtype == jnp.int8 and picked.shape == (B, S, S)
    assert bool(jnp.all((picked != 0) == want))
    assert float(stats[0]) == float(jnp.sum(want)) and float(stats[2]) == B * S
    # every row holds min(t + 1, topk) keys, and more only where scores tie
    # at the threshold (relu's zeros under four signed weights: common here)
    rows, least = jnp.sum(want, axis=-1), jnp.minimum(jnp.arange(S) + 1, topk)
    assert bool(jnp.all(rows >= least)) and bool(jnp.any(rows == least))


def test_ties_at_the_threshold_are_all_kept():
    # one index head whose keys repeat with period 4: scores tie in runs
    s = 64
    ki = jnp.tile(jnp.eye(4, dtype=jnp.float32), (s // 4, 1))[None]      # (1, s, 4)
    qi = jnp.broadcast_to(jnp.asarray([4.0, 3.0, 2.0, 1.0]), (1, s, 1, 4))
    w = jnp.ones((1, s, 1))
    for mode in ("xla", "interpret"):
        picked = fa.set_square(sparse_index.index_key_set(qi, ki, w, 10, 16, mode)[0])
        want = dense_set(qi, ki, w, 10)
        assert bool(jnp.all((picked != 0) == want))
        # row 63 holds 16 keys of each score: the 10th largest is the top
        # score, and all 16 keys that tie there stay
        assert int(jnp.sum(picked[0, 63])) == 16
        # relu's zeros under negative weights: -0.0 and 0.0 tie too
        w_neg = -jnp.ones((1, s, 1))
        picked = fa.set_square(sparse_index.index_key_set(-qi, ki, w_neg, 10, 16, mode)[0])
        assert bool(jnp.all((picked != 0) == jnp.tril(jnp.ones((s, s), bool))))


def test_the_set_and_the_loss_carry_the_gradients_they_should(operands):
    o = operands
    t = {k: paddle.to_tensor(np.asarray(v), stop_gradient=False) for k, v in o.items()}
    key_set, stats = F.sparse_attention_index(t["qi"], t["ki"], t["w"], 64)
    assert key_set.stop_gradient and stats.stop_gradient
    loss = F.sparse_attention_index_loss(t["qi"], t["ki"], t["w"], key_set,
                                         t["q"], t["k"])
    loss.backward()
    assert t["q"].grad is None or float(jnp.max(jnp.abs(t["q"].grad._val))) == 0.0
    assert t["k"].grad is None or float(jnp.max(jnp.abs(t["k"].grad._val))) == 0.0
    for name in ("qi", "ki", "w"):
        assert float(jnp.max(jnp.abs(t[name].grad._val))) > 0.0


# ---------------------------------------------------------------------------
# attention over the sets

@pytest.fixture(scope="module")
def picked(operands):
    o = operands
    return sparse_index.index_key_set(o["qi"], o["ki"], o["w"], 64, 64)[0]


def dense_grads(o, picked, cot):
    scale = D ** -0.5
    return jax.grad(lambda q, k, v: jnp.sum(
        dense_attention(q, k, v, picked != 0, scale)[0] * cot),
        argnums=(0, 1, 2))(o["q"], o["k"], o["v"])


@pytest.mark.parametrize("block, q_span", [(256, None), (128, None), (128, 128), (64, 128)],
                         ids=["one-tile", "four-tiles", "two-spans", "sixteen-tiles"])
def test_the_flash_pair_over_a_set(operands, picked, block, q_span):
    o, scale = operands, D ** -0.5
    want, _ = dense_attention(o["q"], o["k"], o["v"], picked != 0, scale)
    out, lse, tiles = fa.flash_attention_set_fwd(
        o["q"], o["k"], o["v"], picked, scale=scale, block=block, interpret=True)
    assert worst(out, want) < TOL
    cot = normal(7, B, S, H, D)
    got = fa.flash_attention_set_bwd(o["q"], o["k"], o["v"], out, lse, cot, tiles,
                                     scale=scale, interpret=True, q_span=q_span)
    for a, b in zip(got, dense_grads(o, picked, cot)):
        assert worst(a, b) < TOL


def test_empty_tiles_are_skipped_and_whole_tiles_take_no_mask(operands):
    # a window of 32 keys: the tiles far under the diagonal hold no pair, and
    # a set of every causal key has whole tiles under it
    o, scale = operands, D ** -0.5
    t, s = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    for in_set in ((s <= t) & (s > t - 32), s <= t):
        window = jnp.broadcast_to(in_set, (B, S, S)).astype(jnp.int8)
        sets, table = fa._set_tiles(window, 64, 64)
        table = table.reshape(B, 4, 4)
        assert int(table[0, 3, 0]) in (0, 64 * 64) and int(table[0, 0, 3]) == 0
        want, _ = dense_attention(o["q"], o["k"], o["v"], window != 0, scale)
        out, lse, tiles = fa.flash_attention_set_fwd(
            o["q"], o["k"], o["v"], window, scale=scale, block=64, interpret=True)
        assert worst(out, want) < TOL
        cot = normal(8, B, S, H, D)
        got = fa.flash_attention_set_bwd(o["q"], o["k"], o["v"], out, lse, cot,
                                         tiles, scale=scale, interpret=True)
        for a, b in zip(got, dense_grads(o, window, cot)):
            assert worst(a, b) < TOL


@pytest.mark.parametrize("use_pallas", [None, True], ids=["xla", "flash"])
def test_attention_takes_a_key_set(operands, picked, use_pallas):
    o, scale = operands, D ** -0.5
    t = {k: paddle.to_tensor(np.asarray(o[k]), stop_gradient=False) for k in "qkv"}
    out = attention.scaled_dot_product_attention(
        t["q"], t["k"], t["v"], is_causal=True, key_set=paddle.Tensor(picked),
        use_pallas=use_pallas)
    want, _ = dense_attention(o["q"], o["k"], o["v"], picked != 0, scale)
    assert worst(out._val, want) < TOL
    cot = normal(9, B, S, H, D)
    (out * paddle.Tensor(cot)).sum().backward()
    for name, b in zip("qkv", dense_grads(o, picked, cot)):
        assert worst(t[name].grad._val, b) < TOL


def test_a_set_that_is_not_causal_is_cut_by_is_causal(operands):
    o, scale = operands, D ** -0.5
    everything = jnp.ones((B, S, S), jnp.int8)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)), (B, S, S))
    want, _ = dense_attention(o["q"], o["k"], o["v"], causal, scale)
    for use_pallas in (None, True):
        out = attention.scaled_dot_product_attention(
            *(paddle.Tensor(o[k]) for k in "qkv"), is_causal=True,
            key_set=paddle.Tensor(everything), use_pallas=use_pallas)
        assert worst(out._val, want) < TOL


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "flash"])
def test_the_kernels_sets_go_through_untouched(operands, picked, use_pallas, monkeypatch):
    """Where the index runs as kernels its set is the pair (sets, table) in
    the set kernels' layout: attention and the loss take it as it is (no
    square is formed on the flash path: `_set_tiles` is never called), XLA's
    attention and the XLA loss form the square from it, and every result is
    the square set's."""
    o, scale = operands, D ** -0.5
    monkeypatch.setattr(attention, "_platform", lambda: "tpu")
    t = {k: paddle.to_tensor(np.asarray(v), stop_gradient=False) for k, v in o.items()}
    key_set, stats = sparse_index.sparse_attention_index(t["qi"], t["ki"], t["w"], 64,
                                                         chunk=64)
    assert isinstance(key_set, tuple) and len(key_set) == 2
    assert all(part.stop_gradient for part in key_set) and stats.stop_gradient
    assert bool(jnp.all(fa.set_square([part._val for part in key_set]) == picked))
    assert fa.tile_blocks([part._val for part in key_set]) == (64, 64)
    if use_pallas:
        monkeypatch.setattr(fa, "_set_tiles", None)
    out, lse = attention.scaled_dot_product_attention(
        t["q"], t["k"], t["v"], is_causal=True, key_set=key_set,
        use_pallas=use_pallas, return_lse=True)
    want, _ = dense_attention(o["q"], o["k"], o["v"], picked != 0, scale)
    assert worst(out._val, want) < TOL
    loss = sparse_index.sparse_attention_index_loss(
        t["qi"], t["ki"], t["w"], key_set, t["q"], t["k"], chunk=64,
        lse=lse if use_pallas else None)
    want_loss, want_g = jax.value_and_grad(
        lambda *a: dense_index_loss(o, picked, *a), argnums=(0, 1, 2))(
            o["qi"], o["ki"], o["w"])
    assert abs(float(loss.item()) - float(want_loss)) < TOL * float(want_loss)
    cot = normal(15, B, S, H, D)
    ((out * paddle.Tensor(cot)).sum() + loss).backward()
    for name, b in zip("qkv", dense_grads(o, picked, cot)):
        assert worst(t[name].grad._val, b) < TOL
    for name, b in zip(("qi", "ki", "w"), want_g):
        assert worst(t[name].grad._val, b) < TOL


@pytest.mark.parametrize("platform, seq, on_mesh, want", [
    ("tpu", 8192, False, True), ("tpu", 2048, False, True),
    ("tpu", 8192, True, False), ("cpu", 8192, False, False),
    ("tpu", 128, False, False)])
def test_which_path_a_key_set_takes(platform, seq, on_mesh, want):
    shape, kv = (1, seq, 32, 128), (1, seq, 4, 128)
    assert attention.takes_flash(shape, kv, jnp.bfloat16, False, 0.0, platform,
                                 kv, key_set=True, on_mesh=on_mesh) is want
    # and what a plain causal call takes has not moved
    assert attention.takes_flash(shape, kv, jnp.bfloat16, False, 0.0, platform,
                                 kv) is (platform == "tpu" and seq >= 2048)


def test_the_counter_of_the_path_moves(operands, picked):
    from paddle_tpu.profiler import metrics
    o = operands
    read = lambda: metrics.get_registry().snapshot()["counters"].get(  # noqa: E731
        "attention.flash_total", 0.0)
    before = read()
    attention.scaled_dot_product_attention(
        *(paddle.Tensor(o[k]) for k in "qkv"), is_causal=True,
        key_set=paddle.Tensor(picked), use_pallas=True)
    assert read() == before + 1


# ---------------------------------------------------------------------------
# the index loss

def dense_index_loss(o, picked, qi, ki, w):
    in_set, scale = picked != 0, D ** -0.5
    _, probs = dense_attention(o["q"], o["k"], o["v"], in_set, scale)
    p = jnp.mean(probs, axis=1)
    log_index = jax.nn.log_softmax(
        jnp.where(in_set, dense_scores(qi, ki, w), -jnp.inf), axis=-1)
    live = in_set & (p > 0)
    terms = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0))
                                 - jnp.where(live, log_index, 0.0)), 0.0)
    return jnp.sum(terms) / (B * S)


@pytest.mark.parametrize("chunk", [64, 256])
@pytest.mark.parametrize("mode", ["xla", "interpret"], ids=["xla", "kernel"])
def test_the_index_loss_and_its_gradient(operands, picked, chunk, mode):
    o, scale = operands, D ** -0.5
    want, want_g = jax.value_and_grad(
        lambda *a: dense_index_loss(o, picked, *a), argnums=(0, 1, 2))(
            o["qi"], o["ki"], o["w"])
    # the kernel reads the flash forward's logsumexp over the sets
    lse = None if mode == "xla" else attention._flash_set_diff(
        o["q"], o["k"], o["v"], picked, scale, True)[1]
    fn = lambda qi, ki, w: sparse_index.index_loss(  # noqa: E731
        qi, ki, w, picked, o["q"], o["k"], lse, scale, chunk, mode)
    got, got_g = jax.value_and_grad(fn, argnums=(0, 1, 2))(o["qi"], o["ki"], o["w"])
    assert abs(float(got) - float(want)) < TOL * float(want)
    assert abs(float(fn(o["qi"], o["ki"], o["w"])) - float(want)) < TOL * float(want)
    for a, b in zip(got_g, want_g):
        assert worst(a, b) < TOL
    # a cotangent scales the gradient
    twice = jax.grad(lambda *a: 2.0 * fn(*a), argnums=0)(o["qi"], o["ki"], o["w"])
    assert worst(twice, 2.0 * want_g[0]) < TOL


def test_the_loss_is_zero_where_the_index_agrees_with_the_heads():
    # one main head, one index head, weights 1: I = relu(q . k) and the main
    # scores are q . k: where they are positive the two softmaxes are equal
    s, d = 64, 16
    k = jnp.abs(normal(10, 1, s, 1, d))
    q = jnp.abs(normal(11, 1, s, 1, d))
    picked = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), jnp.int8)), (1, s, s))
    loss = sparse_index.index_loss(q, k[:, :, 0], jnp.ones((1, s, 1)), picked, q, k,
                                   None, 1.0, 16)
    assert abs(float(loss)) < 1e-6


def test_attention_returns_its_logsumexp_on_both_paths(operands, picked):
    o, scale = operands, D ** -0.5
    kk = jnp.repeat(o["k"], H // HKV, axis=2)
    dots = jnp.einsum("bthd,bshd->bhts", o["q"], kk, precision="highest") * scale
    want = jax.nn.logsumexp(jnp.where((picked != 0)[:, None], dots, -jnp.inf), axis=-1)
    for use_pallas in (None, True):
        out, lse = attention.scaled_dot_product_attention(
            *(paddle.to_tensor(np.asarray(o[k]), stop_gradient=False) for k in "qkv"),
            is_causal=True, key_set=paddle.Tensor(picked), use_pallas=use_pallas,
            return_lse=True)
        assert lse.stop_gradient and lse.shape == [B, H, S]
        assert float(jnp.max(jnp.abs(lse._val - want))) < 1e-4


@pytest.mark.parametrize("platform, seq, d, on_mesh, want", [
    ("tpu", 8192, 64, False, True), ("tpu", 256, 64, False, True),
    ("tpu", 128, 64, False, False), ("tpu", 8192, 32, False, False),
    ("tpu", 8192, 64, True, False), ("cpu", 8192, 64, False, False)])
def test_which_form_the_index_takes(platform, seq, d, on_mesh, want):
    assert sparse_index.takes_kernels((1, seq, 16, d), jnp.bfloat16, platform,
                                      on_mesh) is want


# ---------------------------------------------------------------------------
# rotary positions in sections

def test_sectioned_rotary_positions_from_unequal_streams():
    from benchmarks.reference import keye_vl2 as ref
    q, k = normal(12, 2, 32, 4, 16), normal(13, 2, 32, 1, 16)
    rng = np.random.default_rng(14)
    pos = jnp.asarray(rng.integers(0, 500, (3, 2, 32)), jnp.int32)
    sections = (2, 3, 3)
    got_q, got_k = F.rotary_position_embedding(
        paddle.Tensor(q), paddle.Tensor(k), theta=1e7,
        position_ids=paddle.Tensor(pos), sections=sections)
    assert worst(got_q._val, ref.rotate(q, pos, sections, 1e7)) < TOL
    assert worst(got_k._val, ref.rotate(k, pos, sections, 1e7)) < TOL
    # equal streams are one stream, and text positions are the default
    text = jnp.broadcast_to(jnp.arange(32)[None, None], (3, 2, 32))
    same, _ = F.rotary_position_embedding(paddle.Tensor(q), paddle.Tensor(k), theta=1e7,
                                           position_ids=paddle.Tensor(text),
                                           sections=sections)
    plain, _ = F.rotary_position_embedding(paddle.Tensor(q), paddle.Tensor(k), theta=1e7)
    one, _ = F.rotary_position_embedding(paddle.Tensor(q), paddle.Tensor(k), theta=1e7,
                                          position_ids=paddle.Tensor(text[0]))
    assert worst(same._val, plain._val) < 1e-6 and worst(one._val, plain._val) < 1e-6
    with pytest.raises(ValueError, match="sections"):
        F.rotary_position_embedding(paddle.Tensor(q), paddle.Tensor(k),
                                    position_ids=paddle.Tensor(pos), sections=(2, 3))


# ---------------------------------------------------------------------------
# softmax routing

@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_the_dropless_layer_scores_by_an_argument(score):
    from paddle_tpu.incubate.moe import DroplessMoELayer
    layer = DroplessMoELayer(64, 32, 16, 4, held_experts=[1, 5, 6, 11], score=score)
    assert DroplessMoELayer(64, 32, 16, 4).score == "sigmoid"      # the default
    x = normal(15, 2, 64, 64)
    out, load = layer(paddle.Tensor(x))
    wg, bias = layer.gate.weight._val, layer.expert_bias._val
    logits = jnp.matmul(x, wg, precision="highest")
    sc = jax.nn.softmax(logits, -1) if score == "softmax" else jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(sc + bias, 4)
    w = jnp.take_along_axis(sc, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + (0.0 if score == "softmax" else 1e-6))
    want = jnp.zeros_like(x)
    for slot, e in enumerate(layer.held_experts):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        hidden = jax.nn.silu(x @ layer.w1._val[slot]) * (x @ layer.w3._val[slot])
        want = want + w_e * (hidden @ layer.w2._val[slot])
    assert worst(out._val, want) < 1e-4
    assert float(jnp.sum(load._val)) == float(jnp.sum(jnp.isin(idx, jnp.asarray(layer.held_experts))))
    with pytest.raises(Exception, match="neither"):
        DroplessMoELayer(64, 32, 16, 4, score="top1")
