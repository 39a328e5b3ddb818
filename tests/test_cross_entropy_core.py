"""The hard-label softmax path of `F.cross_entropy` (its `custom_vjp` core,
nn/functional/loss.py) against the float32 `log_softmax` + gather formula it
replaced, and the property it exists for: between forward and backward
nothing of the logits' shape is kept but the logits as they were given.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.core.autograd import _reachable_nodes
from paddle_tpu.core.tensor import Tensor

IGNORE = -100


def _formula(x, lab, weight=None, reduction="mean", axis=-1):
    """What the op computed before: float32 log_softmax, a gather, the mask
    and the reductions."""
    logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=axis)
    idx = jnp.expand_dims(jnp.maximum(lab, 0), axis)
    per = -jnp.squeeze(jnp.take_along_axis(logp, idx, axis=axis), axis)
    valid = lab != IGNORE
    per = jnp.where(valid, per, 0.0)
    wsel = valid.astype(jnp.float32)
    if weight is not None:
        wsel = jnp.where(valid, weight[jnp.maximum(lab, 0)], 0.0)
        per = per * wsel
    if reduction == "mean":
        return per.sum() / jnp.maximum(wsel.sum(), 1e-12 if weight is not None else 1.0)
    return per.sum() if reduction == "sum" else per


def _labels(rng, shape, classes, ignored):
    lab = rng.randint(0, classes, shape).astype("int64")
    if ignored == "some":
        lab.reshape(-1)[::3] = IGNORE
    elif ignored == "all":
        lab[...] = IGNORE
    elif ignored == "row":          # a whole row of a 3-D batch
        lab[0] = IGNORE
    return lab


CASES = {
    "plain": dict(),
    "sum": dict(reduction="sum"),
    "none": dict(reduction="none"),
    "ignore_some": dict(ignored="some"),
    "ignore_some_none": dict(ignored="some", reduction="none"),
    "ignore_all": dict(ignored="all"),
    "ignore_all_sum": dict(ignored="all", reduction="sum"),
    "weight": dict(weight=True),
    "weight_sum": dict(weight=True, reduction="sum"),
    "weight_ignore": dict(weight=True, ignored="some"),
    "weight_ignore_none": dict(weight=True, ignored="some", reduction="none"),
    "axis1_3d": dict(shape=(4, 37, 6), axis=1),
    "axis1_3d_ignored_row": dict(shape=(4, 37, 6), axis=1, ignored="row"),
    "axis1_3d_weight_none": dict(shape=(4, 37, 6), axis=1, weight=True,
                                 reduction="none"),
    "last_3d_ignored_row": dict(shape=(3, 5, 41), ignored="row"),
    "label_keeps_axis": dict(label_keeps_axis=True),
    "to_static": dict(static=True, ignored="some"),
    "to_static_weight_sum": dict(static=True, weight=True, reduction="sum"),
}


def _setup(dtype, shape=(24, 203), axis=-1, ignored=None, weight=False,
           reduction="mean", static=False, label_keeps_axis=False):
    rng = np.random.RandomState(len(shape) * 7 + shape[-1])
    x = jnp.asarray(rng.randn(*shape).astype("float32") * 3).astype(dtype)
    classes = shape[axis]
    lab_shape = tuple(n for i, n in enumerate(shape) if i != axis % len(shape))
    lab = _labels(rng, lab_shape, classes, ignored)
    w = rng.rand(classes).astype("float32") + 0.5 if weight else None
    return x, lab, w, dict(reduction=reduction, axis=axis), static, label_keeps_axis


def _run(x, lab, w, kw, static, label_keeps_axis):
    """Loss, and the gradient of its sum, through the public op on the tape."""
    def body(t, y):
        return F.cross_entropy(t, y, weight=None if w is None else paddle.to_tensor(w),
                               ignore_index=IGNORE, **kw)

    t = paddle.to_tensor(x)
    t.stop_gradient = False
    y = np.expand_dims(lab, kw["axis"]) if label_keeps_axis else lab
    loss = (paddle.jit.to_static(body) if static else body)(t, paddle.to_tensor(y))
    loss.sum().backward()
    return loss, t.grad


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_matches_the_float32_formula(dtype, case):
    x, lab, w, kw, static, keeps = _setup(dtype, **CASES[case])
    loss, grad = _run(x, lab, w, kw, static, keeps)

    wj = None if w is None else jnp.asarray(w)
    want_grad = jax.grad(
        lambda v: _formula(v, jnp.asarray(lab), wj, **kw).sum())(x.astype(jnp.float32))
    want_loss = _formula(x, jnp.asarray(lab), wj, **kw)

    # float32 out whatever came in: the op accumulates in float32
    assert loss.dtype == paddle.float32
    assert grad.dtype == getattr(paddle, dtype)
    np.testing.assert_allclose(np.asarray(loss._value), np.asarray(want_loss),
                               rtol=1e-6, atol=1e-7)
    got = np.asarray(grad._value.astype(jnp.float32))
    assert np.isfinite(got).all()
    if dtype == "float32":
        np.testing.assert_allclose(got, np.asarray(want_grad), rtol=1e-5,
                                   atol=1e-7 * float(jnp.abs(want_grad).max() + 1))
    else:
        # one rounding, at the end: the bf16 rounding of the float32 gradient
        # (the two float32 expressions differ in their last bit, which moves a
        # handful of elements across a bf16 rounding boundary)
        rounded = np.asarray(want_grad.astype(jnp.bfloat16).astype(jnp.float32))
        assert np.mean(got == rounded) > 0.998
        np.testing.assert_allclose(got, rounded, rtol=2.0 ** -7, atol=1e-30)
    if "ignore_all" in case:
        assert not got.any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_an_ignored_row_has_no_gradient(dtype):
    x, lab, w, kw, *_ = _setup(dtype, ignored="some")
    _, grad = _run(x, lab, w, kw, False, False)
    got = np.asarray(grad._value.astype(jnp.float32))
    assert not got[lab == IGNORE].any()
    assert got[lab != IGNORE].any(axis=-1).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_vocabulary_sharded_matches_unsharded(dtype):
    """Logits sharded over the class axis on four devices, as fleet's
    ParallelCrossEntropy has them: GSPMD partitions the two row reductions
    and the gather; loss and gradient as on one device."""
    x, lab, *_ = _setup(dtype, shape=(16, 4 * 52), ignored="some")

    def step(v, y):
        t = Tensor(v, stop_gradient=False)
        loss = F.cross_entropy(t, Tensor(y), reduction="none", ignore_index=IGNORE)
        loss.sum().backward()
        return loss._value, t.grad._value

    mesh = Mesh(np.array(jax.devices()[:4]), ("model",))
    sharded = jax.device_put(x, NamedSharding(mesh, P(None, "model")))
    y = jnp.asarray(lab)
    loss_s, grad_s = jax.jit(step)(sharded, jax.device_put(y, NamedSharding(mesh, P())))
    loss_1, grad_1 = jax.jit(step)(x, y)
    assert grad_s.sharding.spec == P(None, "model")
    np.testing.assert_allclose(np.asarray(loss_s), np.asarray(loss_1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grad_s.astype(jnp.float32)),
                               np.asarray(grad_1.astype(jnp.float32)),
                               rtol=2.0 ** -7 if dtype == "bfloat16" else 1e-6, atol=1e-9)


def _residuals_of_size(loss, size):
    """(op, shape, dtype) of every array of `size` elements that the tape
    holds for the backward of `loss`: the leaves its vjp functions close over."""
    return [(node.name, tuple(leaf.shape), str(leaf.dtype))
            for node in _reachable_nodes([loss._grad_node])
            for leaf in jax.tree_util.tree_leaves(node.vjp_fn)
            if getattr(leaf, "size", None) == size]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gpt_head_keeps_no_float32_copy_of_the_logits(dtype):
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    vocab, batch, seq = 97, 2, 8            # 2 * 8 * 97 elements: no other tensor's size
    model = GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=32, num_layers=1, num_heads=2,
        max_position_embeddings=16, dropout=0.0))
    if dtype == "bfloat16":
        model.bfloat16()
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, vocab, (batch, seq)))
    loss = model(ids, labels=ids)
    assert loss.dtype == paddle.float32
    # the logits as F.linear made them, once, and nothing else of their size:
    # no float32 upcast, no log-probabilities, no softmax
    assert _residuals_of_size(loss, batch * seq * vocab) == [
        ("cross_entropy", (batch * seq, vocab), dtype)]


def test_bert_head_keeps_no_float32_copy_of_the_logits():
    from paddle_tpu.text.models import BertForSequenceClassification
    from paddle_tpu.text.models.bert import BertConfig
    model = BertForSequenceClassification(BertConfig(
        vocab_size=16, hidden_size=32, num_layers=1, num_heads=2,
        intermediate_size=64, max_position=16, dropout=0.0),
        num_classes=3)
    model.bfloat16()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 16, (5, 8)))
    loss = model(ids, labels=paddle.to_tensor(rng.randint(0, 3, (5,))))
    assert loss.dtype == paddle.float32
    assert _residuals_of_size(loss, 5 * 3) == [("cross_entropy", (5, 3), "bfloat16")]
