"""Loss functionals (python/paddle/nn/functional/loss.py parity).

cross_entropy matches the reference semantics (softmax_with_cross_entropy op,
operators/softmax_with_cross_entropy_op.*): hard or soft labels, ignore_index,
class weights, reductions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.dispatch import apply, unwrap

__all__ = [
    "cross_entropy", "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "nll_loss", "mse_loss", "l1_loss",
    "smooth_l1_loss", "kl_div", "margin_ranking_loss", "ctc_loss",
    "hinge_embedding_loss", "cosine_embedding_loss", "triplet_margin_loss",
    "log_loss", "square_error_cost", "sigmoid_focal_loss", "dice_loss",
    "soft_margin_loss", "multi_label_soft_margin_loss", "poisson_nll_loss",
    "triplet_margin_with_distance_loss", "margin_cross_entropy",
    "hsigmoid_loss",
]


def _reduce(v, reduction):
    if reduction == "mean":
        return jnp.mean(v)
    if reduction == "sum":
        return jnp.sum(v)
    return v


def _nll_of_softmax_fwd(logits, labels, axis):
    acc = jnp.promote_types(logits.dtype, jnp.float32)
    x = logits.astype(acc)
    top = jnp.max(x, axis=axis, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - top), axis=axis, keepdims=True)) + top
    picked = jnp.take_along_axis(logits, jnp.expand_dims(labels, axis), axis=axis)
    return jnp.squeeze(lse - picked.astype(acc), axis), (logits, labels, lse)


def _nll_of_softmax_bwd(axis, residuals, g):
    logits, labels, lse = residuals
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape, axis)
    onehot = classes == jnp.expand_dims(labels, axis)
    softmax = jnp.exp(logits.astype(lse.dtype) - lse)
    grad = (softmax - onehot.astype(lse.dtype)) * jnp.expand_dims(g, axis)
    return grad.astype(logits.dtype), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _nll_of_softmax(logits, labels, axis):
    """Per row, `logsumexp(logits) - logits[label]` over `axis` (not
    negative; `labels` int32, in range, without that axis), accumulated in
    float32 whatever the logits' dtype. Its own vjp so that nothing of the
    logits' shape lives between forward and backward but the logits as given:
    the upcast stays inside the two row reductions, the residuals are the
    logits and one log-sum-exp per row, and the backward makes
    `softmax - onehot` in the logits' dtype in one expression, which XLA fuses
    into whatever reads it."""
    return _nll_of_softmax_fwd(logits, labels, axis)[0]


_nll_of_softmax.defvjp(_nll_of_softmax_fwd, _nll_of_softmax_bwd)


def cross_entropy(input, label, weight=None, ignore_index=-100,  # noqa: A002
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, name=None):
    def prim(logits, lab, *maybe_w):
        w = maybe_w[0] if maybe_w else None
        if soft_label:
            logp = (jax.nn.log_softmax(logits, axis=axis) if use_softmax
                    else jnp.log(jnp.maximum(logits, 1e-30)))
            return _reduce(-jnp.sum(lab * logp, axis=axis), reduction)
        li = lab.astype(jnp.int32)
        if li.ndim == logits.ndim:
            li = jnp.squeeze(li, axis)
        valid = li != ignore_index
        li = jnp.maximum(li, 0)
        if use_softmax:
            per = _nll_of_softmax(logits, li, axis % logits.ndim)
        else:
            logp = jnp.log(jnp.maximum(logits, 1e-30))
            per = -jnp.squeeze(jnp.take_along_axis(
                logp, jnp.expand_dims(li, axis), axis=axis), axis)
        per = jnp.where(valid, per, 0.0)
        if w is not None:
            wsel = jnp.where(valid, jnp.take(w, li, axis=0), 0.0)
            per = per * wsel
            if reduction == "mean":
                return jnp.sum(per) / jnp.maximum(jnp.sum(wsel), 1e-12)
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(valid.astype(per.dtype)), 1.0)
            return jnp.sum(per) / denom
        return _reduce(per, reduction)

    args = [input, label] + ([weight] if weight is not None else [])
    return apply(prim, *args, name="cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    def prim(lg, lab):
        sm = jax.nn.softmax(lg, axis=axis)
        logp = jax.nn.log_softmax(lg, axis=axis)
        if soft_label:
            loss = -jnp.sum(lab * logp, axis=axis, keepdims=True)
        else:
            li = lab.astype(jnp.int32)
            li_exp = li if li.ndim == logp.ndim else jnp.expand_dims(li, axis)
            picked = jnp.take_along_axis(logp, jnp.maximum(li_exp, 0), axis=axis)
            loss = -picked
            valid = (li_exp != ignore_index)
            loss = jnp.where(valid, loss, 0.0)
        if return_softmax:
            return loss, sm
        return loss
    return apply(prim, logits, label, name="softmax_with_cross_entropy")


def binary_cross_entropy(input, label, weight=None, reduction="mean",  # noqa: A002
                         name=None):
    def prim(p, y, *mw):
        eps = 1e-12
        per = -(y * jnp.log(jnp.maximum(p, eps))
                + (1 - y) * jnp.log(jnp.maximum(1 - p, eps)))
        if mw:
            per = per * mw[0]
        return _reduce(per, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply(prim, *args, name="bce")


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    def prim(x, y, *rest):
        i = 0
        w = None
        pw = None
        if weight is not None:
            w = rest[i]; i += 1
        if pos_weight is not None:
            pw = rest[i]; i += 1
        max_val = jnp.maximum(-x, 0)
        if pw is not None:
            log_w = (pw - 1) * y + 1
            per = (1 - y) * x + log_w * (jnp.log1p(jnp.exp(-jnp.abs(x))) + max_val)
        else:
            per = (1 - y) * x + jnp.log1p(jnp.exp(-jnp.abs(x))) + max_val
        if w is not None:
            per = per * w
        return _reduce(per, reduction)
    args = [logit, label] + [a for a in (weight, pos_weight) if a is not None]
    return apply(prim, *args, name="bce_with_logits")


def nll_loss(input, label, weight=None, ignore_index=-100,  # noqa: A002
             reduction="mean", name=None):
    def prim(logp, lab, *mw):
        li = lab.astype(jnp.int32)
        picked = jnp.take_along_axis(logp, jnp.maximum(li[:, None], 0), axis=1)[:, 0]
        per = -picked
        valid = li != ignore_index
        per = jnp.where(valid, per, 0.0)
        if mw:
            wsel = jnp.take(mw[0], jnp.maximum(li, 0))
            wsel = jnp.where(valid, wsel, 0.0)
            per = per * wsel
            if reduction == "mean":
                return jnp.sum(per) / jnp.maximum(jnp.sum(wsel), 1e-12)
        if reduction == "mean":
            return jnp.sum(per) / jnp.maximum(jnp.sum(valid.astype(per.dtype)), 1.0)
        return _reduce(per, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply(prim, *args, name="nll_loss")


def mse_loss(input, label, reduction="mean", name=None):  # noqa: A002
    return apply(lambda a, b: _reduce(jnp.square(a - b), reduction),
                 input, label, name="mse_loss")


def square_error_cost(input, label):  # noqa: A002
    return apply(lambda a, b: jnp.square(a - b), input, label,
                 name="square_error_cost")


def l1_loss(input, label, reduction="mean", name=None):  # noqa: A002
    return apply(lambda a, b: _reduce(jnp.abs(a - b), reduction),
                 input, label, name="l1_loss")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):  # noqa: A002
    def prim(a, b):
        diff = jnp.abs(a - b)
        per = jnp.where(diff < delta, 0.5 * diff * diff / delta,
                        diff - 0.5 * delta)
        return _reduce(per, reduction)
    return apply(prim, input, label, name="smooth_l1_loss")


def kl_div(input, label, reduction="mean", name=None):  # noqa: A002
    def prim(logp, y):
        per = y * (jnp.log(jnp.maximum(y, 1e-12)) - logp)
        if reduction == "batchmean":
            return jnp.sum(per) / logp.shape[0]
        return _reduce(per, reduction)
    return apply(prim, input, label, name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",  # noqa: A002
                        name=None):
    def prim(a, b, y):
        per = jnp.maximum(-y * (a - b) + margin, 0.0)
        return _reduce(per, reduction)
    return apply(prim, input, other, label, name="margin_ranking_loss")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):  # noqa: A002
    def prim(x, y):
        per = jnp.where(y == 1, x, jnp.maximum(margin - x, 0.0))
        return _reduce(per, reduction)
    return apply(prim, input, label, name="hinge_embedding_loss")


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    def prim(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        per = jnp.where(y == 1, 1 - cos, jnp.maximum(cos - margin, 0.0))
        return _reduce(per, reduction)
    return apply(prim, input1, input2, label, name="cosine_embedding_loss")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,  # noqa: A002
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    def prim(a, pos, neg):
        dp = jnp.sum(jnp.abs(a - pos) ** p, axis=-1) ** (1 / p)
        dn = jnp.sum(jnp.abs(a - neg) ** p, axis=-1) ** (1 / p)
        if swap:
            dn2 = jnp.sum(jnp.abs(pos - neg) ** p, axis=-1) ** (1 / p)
            dn = jnp.minimum(dn, dn2)
        per = jnp.maximum(dp - dn + margin, 0.0)
        return _reduce(per, reduction)
    return apply(prim, input, positive, negative, name="triplet_margin_loss")


def log_loss(input, label, epsilon=1e-4, name=None):  # noqa: A002
    def prim(p, y):
        return -(y * jnp.log(p + epsilon) + (1 - y) * jnp.log(1 - p + epsilon))
    return apply(prim, input, label, name="log_loss")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def prim(x, y, *mn):
        p = jax.nn.sigmoid(x)
        ce = jnp.maximum(x, 0) - x * y + jnp.log1p(jnp.exp(-jnp.abs(x)))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        per = a_t * ((1 - p_t) ** gamma) * ce
        if mn:
            per = per / mn[0]
        return _reduce(per, reduction)
    args = [logit, label] + ([normalizer] if normalizer is not None else [])
    return apply(prim, *args, name="sigmoid_focal_loss")


def dice_loss(input, label, epsilon=1e-5, name=None):  # noqa: A002
    def prim(p, y):
        y1 = jax.nn.one_hot(y.astype(jnp.int32).squeeze(-1), p.shape[-1],
                            dtype=p.dtype)
        reduce_dims = tuple(range(1, p.ndim))
        inter = jnp.sum(p * y1, axis=reduce_dims)
        union = jnp.sum(p, axis=reduce_dims) + jnp.sum(y1, axis=reduce_dims)
        return jnp.mean(1 - (2 * inter + epsilon) / (union + epsilon))
    return apply(prim, input, label, name="dice_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via the standard alpha-recursion in log space (lax.scan over time).

    Reference: operators/warpctc_op.* (wraps warp-ctc); here it is a pure XLA
    computation.
    """
    def prim(lp, lab, in_len, lab_len):
        # lp: (T, N, C) log-probs (paddle convention time-major)
        T, N, C = lp.shape
        L = lab.shape[1]
        S = 2 * L + 1
        lab = lab.astype(jnp.int32)
        # extended label sequence with blanks: [b, l1, b, l2, ..., b]
        ext = jnp.full((N, S), blank, dtype=jnp.int32)
        ext = ext.at[:, 1::2].set(lab)
        neg_inf = -1e30
        # init alpha at t=0
        alpha0 = jnp.full((N, S), neg_inf)
        alpha0 = alpha0.at[:, 0].set(lp[0][jnp.arange(N), ext[:, 0]])
        alpha0 = alpha0.at[:, 1].set(
            jnp.where(lab_len > 0, lp[0][jnp.arange(N), ext[:, 1]], neg_inf))

        same_as_prev2 = jnp.concatenate(
            [jnp.ones((N, 2), dtype=bool),
             ext[:, 2:] == ext[:, :-2]], axis=1)

        def step(alpha, lp_t):
            a_prev = alpha
            a_shift1 = jnp.concatenate(
                [jnp.full((N, 1), neg_inf), alpha[:, :-1]], axis=1)
            a_shift2 = jnp.concatenate(
                [jnp.full((N, 2), neg_inf), alpha[:, :-2]], axis=1)
            a_shift2 = jnp.where(same_as_prev2, neg_inf, a_shift2)
            m = jnp.maximum(jnp.maximum(a_prev, a_shift1), a_shift2)
            m_safe = jnp.maximum(m, neg_inf)
            summed = (jnp.exp(a_prev - m_safe) + jnp.exp(a_shift1 - m_safe)
                      + jnp.exp(a_shift2 - m_safe))
            new_alpha = m_safe + jnp.log(jnp.maximum(summed, 1e-30))
            emit = jnp.take_along_axis(lp_t, ext, axis=1)
            return new_alpha + emit, new_alpha

        def step2(alpha, lp_t):
            out, _ = step(alpha, lp_t)
            return out, out
        _, all_alpha = jax.lax.scan(step2, alpha0, lp[1:])
        all_alpha = jnp.concatenate([alpha0[None], all_alpha], axis=0)  # (T,N,S)
        t_idx = jnp.maximum(in_len.astype(jnp.int32) - 1, 0)
        final = all_alpha[t_idx, jnp.arange(N)]  # (N, S)
        s_last = 2 * lab_len.astype(jnp.int32)      # blank after last label
        s_last2 = jnp.maximum(s_last - 1, 0)        # last label
        a1 = jnp.take_along_axis(final, s_last[:, None], axis=1)[:, 0]
        a2 = jnp.take_along_axis(final, s_last2[:, None], axis=1)[:, 0]
        m = jnp.maximum(a1, a2)
        ll = m + jnp.log(jnp.exp(a1 - m) + jnp.exp(a2 - m))
        loss = -ll
        if reduction == "mean":
            return jnp.mean(loss / jnp.maximum(lab_len.astype(loss.dtype), 1.0))
        return _reduce(loss, reduction)

    return apply(prim, log_probs, unwrap(labels), unwrap(input_lengths),
                 unwrap(label_lengths), name="ctc_loss")


def soft_margin_loss(input, label, reduction="mean", name=None):
    """log(1 + exp(-label * input)); label in {-1, 1}
    (reference nn/functional/loss.py soft_margin_loss)."""
    def prim(x, y):
        # stable softplus form: log(1 + exp(-yx)) = -log_sigmoid(yx)
        v = -jax.nn.log_sigmoid(y.astype(x.dtype) * x)
        return _reduce(v, reduction)
    return apply(prim, input, label, name="soft_margin_loss")


def multi_label_soft_margin_loss(input, label, weight=None, reduction="mean",
                                 name=None):
    """Per-class sigmoid BCE averaged over classes (reference
    nn/functional/loss.py multi_label_soft_margin_loss); label in {0, 1}."""
    def prim(x, y, *w):
        y = y.astype(x.dtype)
        term = y * jax.nn.log_sigmoid(x) + (1 - y) * jax.nn.log_sigmoid(-x)
        if w:
            term = term * w[0]
        v = -jnp.mean(term, axis=-1)
        return _reduce(v, reduction)
    args = [weight] if weight is not None else []
    return apply(prim, input, label, *args,
                 name="multi_label_soft_margin_loss")


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction="mean", name=None):
    """Poisson negative log likelihood (reference poisson_nll_loss)."""
    def prim(x, y):
        y = y.astype(x.dtype)
        if log_input:
            v = jnp.exp(x) - y * x
        else:
            v = x - y * jnp.log(x + epsilon)
        if full:
            # Stirling approximation for log(y!) when y > 1
            stir = y * jnp.log(y) - y + 0.5 * jnp.log(2 * jnp.pi * y)
            v = v + jnp.where(y > 1, stir, jnp.zeros_like(y))
        return _reduce(v, reduction)
    return apply(prim, input, label, name="poisson_nll_loss")


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction="mean", name=None):
    """Triplet loss with a custom distance callable (reference
    triplet_margin_with_distance_loss); default distance = pairwise L2."""
    if distance_function is None:
        def distance_function(a, b):
            import paddle_tpu  # late import: avoid cycle at module load
            return paddle_tpu.norm(a - b, p=2, axis=-1)
    d_pos = distance_function(input, positive)
    d_neg = distance_function(input, negative)
    if swap:
        d_neg2 = distance_function(positive, negative)
        d_neg = apply(lambda a, b: jnp.minimum(a, b), d_neg, d_neg2,
                      name="triplet_swap_min")

    def prim(dp, dn):
        v = jnp.maximum(dp - dn + margin, 0.0)
        return _reduce(v, reduction)
    return apply(prim, d_pos, d_neg,
                 name="triplet_margin_with_distance_loss")


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5, margin3=0.0,
                         scale=64.0, group=None, return_softmax=False,
                         reduction="mean", name=None):
    """ArcFace-family margin softmax (reference
    operators/margin_cross_entropy_op.*, python margin_cross_entropy):
    target-class cosine theta is re-margined as
    cos(margin1*theta + margin2) - margin3, then scaled softmax CE.

    `group` (model-parallel class sharding) follows the SPMD design: pass a
    mesh axis name to reduce the softmax denominator with psum inside
    shard_map/pjit-traced code; the single-process path needs no group.
    """
    axis_name = group if isinstance(group, str) else None

    def prim(lg, lb):
        x = lg.astype(jnp.float32)
        theta = jnp.arccos(jnp.clip(x, -1.0 + 1e-7, 1.0 - 1e-7))
        cos_m = jnp.cos(margin1 * theta + margin2) - margin3
        n_cls = x.shape[-1]
        lb_local = lb
        if axis_name is not None:
            # class-sharded logits: labels are GLOBAL class ids — shift by
            # this shard's class offset so one_hot hits only the owning
            # shard (out-of-range ids produce all-zero rows, by design)
            lb_local = lb - jax.lax.axis_index(axis_name) * n_cls
        onehot = jax.nn.one_hot(lb_local, n_cls, dtype=x.dtype)
        logits_m = jnp.where(onehot > 0, cos_m, x) * scale
        mx = jnp.max(logits_m, axis=-1, keepdims=True)
        if axis_name is not None:
            mx = jax.lax.pmax(mx, axis_name)
        ex = jnp.exp(logits_m - mx)
        denom = jnp.sum(ex, axis=-1, keepdims=True)
        if axis_name is not None:
            denom = jax.lax.psum(denom, axis_name)
        logp = (logits_m - mx) - jnp.log(denom)
        tgt = jnp.sum(logp * onehot, axis=-1)
        if axis_name is not None:
            tgt = jax.lax.psum(tgt, axis_name)
        loss = _reduce(-tgt, reduction)
        if return_softmax:
            return loss, ex / denom
        return loss

    return apply(prim, logits, label, name="margin_cross_entropy")


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference
    operators/hierarchical_sigmoid_op.*, nn/functional/loss.py
    hsigmoid_loss). Default tree: complete binary tree over classes; the
    path of class c = binary digits of (c + num_classes) walked from the
    root (the standard Morin&Bengio layout the reference uses).
    """
    if path_table is not None or path_code is not None:
        raise NotImplementedError(
            "custom-tree hsigmoid (path_table/path_code) is not implemented; "
            "the default complete-binary-tree layout is supported")
    import numpy as _np
    depth = max(1, int(_np.ceil(_np.log2(max(2, num_classes)))))

    def prim(x, lb, w, *b):
        # codes for every class: walk from root; node ids in [0, num_classes)
        lbl = lb.reshape(-1).astype(jnp.int32)
        node = lbl + num_classes  # leaf position in the implicit heap
        losses = jnp.zeros(lbl.shape, jnp.float32)
        for _ in range(depth):
            bit = node % 2          # which child we are
            parent = node // 2
            nidx = jnp.clip(parent - 1, 0, num_classes - 1)
            logit = jnp.sum(x * w[nidx], axis=-1)
            if b:
                logit = logit + b[0].reshape(-1)[nidx]
            # sigmoid CE against the path bit; parents above root contribute 0
            active = (parent >= 1).astype(jnp.float32)
            tgt = bit.astype(jnp.float32)
            losses = losses + active * (
                jnp.maximum(logit, 0) - logit * tgt
                + jnp.log1p(jnp.exp(-jnp.abs(logit))))
            node = parent
        return losses.reshape(-1, 1)  # paddle contract: [N, 1]
    args = [a for a in (bias,) if a is not None]
    return apply(prim, input, label, weight, *args, name="hsigmoid_loss")
