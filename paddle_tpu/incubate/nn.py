"""incubate.nn fused transformer API (python/paddle/incubate/nn/layer/
fused_transformer.py over operators/fused/fused_attention_op.cu /
fused_feedforward_op).

TPU-native: "fused" means the whole block compiles as one XLA region with the
Pallas flash-attention kernel on the hot path — the same memory-locality win
the reference gets from its hand-fused CUDA kernels.
"""
from __future__ import annotations

from .. import nn
from ..ops.attention import scaled_dot_product_attention

__all__ = ["FusedMultiHeadAttention", "FusedFeedForward",
           "FusedTransformerEncoderLayer", "FusedBiasDropoutResidualLayerNorm",
           "fused_feedforward", "fused_bias_dropout_residual_layer_norm",
           "softmax_mask_fuse", "softmax_mask_fuse_upper_triangle"]


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5, epsilon=1e-5,
                                           training=True, name=None):
    """incubate.nn.functional.fused_bias_dropout_residual_layer_norm parity
    (operators/fused/fused_bias_dropout_residual_layer_norm_op.cu):
        out = layer_norm(residual + dropout(x + bias))
    One apply() seam -> one XLA fusion region (the reference needs a
    dedicated CUDA kernel; XLA fuses bias-add, mask, scale, residual-add and
    the norm reductions into the surrounding computation)."""
    import jax
    import jax.numpy as jnp

    from ..core.dispatch import apply

    dropout_kd = None
    if training and dropout_rate > 0.0:
        from ..core.random import next_key_data
        dropout_kd = next_key_data()

    def prim(xv, rv, *rest):
        rest = list(rest)
        kd = rest.pop() if dropout_kd is not None else None
        i = 0
        h = xv
        if bias is not None:
            h = h + rest[i]
            i += 1
        if kd is not None:
            key = jax.random.wrap_key_data(kd)
            keep = jax.random.bernoulli(key, 1.0 - dropout_rate, h.shape)
            h = jnp.where(keep, h / (1.0 - dropout_rate), 0.0).astype(h.dtype)
        h = rv + h
        hf = h.astype(jnp.float32)
        mean = jnp.mean(hf, axis=-1, keepdims=True)
        var = jnp.var(hf, axis=-1, keepdims=True)
        out = (hf - mean) * jax.lax.rsqrt(var + epsilon)
        if ln_scale is not None:
            out = out * rest[i].astype(jnp.float32)
            i += 1
        if ln_bias is not None:
            out = out + rest[i].astype(jnp.float32)
        return out.astype(h.dtype)

    extra = [a for a in (bias, ln_scale, ln_bias) if a is not None]
    if dropout_kd is not None:
        extra.append(dropout_kd)
    return apply(prim, x, residual, *extra,
                 name="fused_bias_dropout_residual_layer_norm")


class FusedBiasDropoutResidualLayerNorm(nn.Layer):
    """incubate.nn.FusedBiasDropoutResidualLayerNorm parity."""

    def __init__(self, embed_dim, dropout_rate=0.5, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.dropout_rate = dropout_rate
        self._epsilon = epsilon
        from ..nn import initializer as I
        self.linear_bias = self.create_parameter(
            shape=[embed_dim], attr=bias_attr, is_bias=True)
        self.ln_scale = self.create_parameter(
            shape=[embed_dim], attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.ln_bias = self.create_parameter(
            shape=[embed_dim], attr=None, is_bias=True)

    def forward(self, x, residual):
        return fused_bias_dropout_residual_layer_norm(
            x, residual, self.linear_bias, self.ln_scale, self.ln_bias,
            dropout_rate=self.dropout_rate, epsilon=self._epsilon,
            training=self.training)


def _ffn_act(F, activation):
    """Unfused-path activation lookup shared with the fused path's naming:
    'gelu' is erf-gelu (reference GeluFunctor in fused_dropout_act_bias.h is
    erf-based), 'gelu_tanh' the tanh approximation."""
    if activation == "gelu":
        return lambda h: F.gelu(h)
    if activation == "gelu_tanh":
        return lambda h: F.gelu(h, approximate=True)
    return getattr(F, activation)


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True,
                      mode="upscale_in_train", name=None):
    """incubate.nn.functional.fused_feedforward parity — signature and
    defaults match python/paddle/incubate/nn/functional/fused_transformer.py
    (operators/fused/fused_feedforward_op.cc):
        out = residual + dropout2(linear2(dropout1(act(linear1(ln1(x))))))
    with ln1 applied before when pre_layer_norm, else ln2 after the residual
    add. activation='gelu' is erf-gelu on BOTH the fused and unfused paths
    (the reference fused op's GeluFunctor is erf-based).

    The linear1->act->linear2 core runs through ops/fused_ffn.py (backward
    recomputes the activation instead of saving it) whenever both biases are
    present and the dropout between the matmuls is inactive; otherwise it
    falls back to the composed ops."""
    from ..nn import functional as F
    from ..ops.fused_ffn import fused_ffn

    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, x.shape[-1], ln1_scale, ln1_bias, ln1_epsilon)

    # a dropout is an IDENTITY (and the fused no-dropout kernel applies)
    # only when its rate is 0, or at inference under upscale_in_train;
    # downscale_in_infer still scales by (1-p) at inference (F.dropout
    # implements both reference modes)
    def _drop_identity(rate):
        return rate == 0.0 or (not training and mode == "upscale_in_train")

    if (linear1_bias is not None and linear2_bias is not None
            and _drop_identity(dropout1_rate)
            and activation in ("gelu", "gelu_tanh", "relu")):
        out = fused_ffn(x, linear1_weight, linear1_bias, linear2_weight,
                        linear2_bias, activation=activation)
    else:
        h = F.linear(x, linear1_weight, linear1_bias)
        h = _ffn_act(F, activation)(h)
        if not _drop_identity(dropout1_rate):
            h = F.dropout(h, p=dropout1_rate, training=training, mode=mode)
        out = F.linear(h, linear2_weight, linear2_bias)
    if not _drop_identity(dropout2_rate):
        out = F.dropout(out, p=dropout2_rate, training=training, mode=mode)
    out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, out.shape[-1],
                           ln2_scale if ln2_scale is not None else ln1_scale,
                           ln2_bias if ln2_bias is not None else ln1_bias,
                           ln2_epsilon)
    return out


class FusedMultiHeadAttention(nn.Layer):
    def __init__(self, embed_dim, num_heads, dropout_rate=0.0,
                 attn_dropout_rate=0.0, kdim=None, vdim=None,
                 normalize_before=False, need_weights=False, qkv_weight_attr=None,
                 **kwargs):
        super().__init__()
        assert embed_dim % num_heads == 0
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.normalize_before = normalize_before
        self.qkv = nn.Linear(embed_dim, 3 * embed_dim)
        self.out_proj = nn.Linear(embed_dim, embed_dim)
        self.norm = nn.LayerNorm(embed_dim)
        self.dropout = nn.Dropout(dropout_rate)
        self.attn_dropout_rate = attn_dropout_rate

    def forward(self, x, attn_mask=None):
        residual = x
        if self.normalize_before:
            x = self.norm(x)
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv(x).reshape([b, s, 3, self.num_heads, self.head_dim])
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]
        out = scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask,
            dropout_p=self.attn_dropout_rate, training=self.training)
        out = out.reshape([b, s, self.embed_dim])
        out = self.dropout(self.out_proj(out))
        if self.normalize_before:
            return residual + out
        return self.norm(residual + out)


class FusedFeedForward(nn.Layer):
    def __init__(self, d_model, dim_feedforward, dropout_rate=0.1,
                 activation="relu", act_dropout_rate=None,
                 normalize_before=False, **kwargs):
        super().__init__()
        self.normalize_before = normalize_before
        self.linear1 = nn.Linear(d_model, dim_feedforward)
        self.linear2 = nn.Linear(dim_feedforward, d_model)
        self.norm = nn.LayerNorm(d_model)
        self.dropout = nn.Dropout(dropout_rate)
        self.act_dropout = nn.Dropout(
            dropout_rate if act_dropout_rate is None else act_dropout_rate)
        self._activation = activation
        self.act = getattr(nn.functional, activation)

    def forward(self, x):
        return fused_feedforward(
            x, self.linear1.weight, self.linear2.weight,
            self.linear1.bias, self.linear2.bias,
            ln1_scale=self.norm.weight, ln1_bias=self.norm.bias,
            ln2_scale=self.norm.weight, ln2_bias=self.norm.bias,
            dropout1_rate=self.act_dropout.p, dropout2_rate=self.dropout.p,
            activation=self._activation,
            ln1_epsilon=self.norm._epsilon, ln2_epsilon=self.norm._epsilon,
            pre_layer_norm=self.normalize_before, training=self.training)


class FusedTransformerEncoderLayer(nn.Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout_rate=0.1,
                 activation="relu", attn_dropout_rate=None,
                 act_dropout_rate=None, normalize_before=False, **kwargs):
        super().__init__()
        self.fused_attn = FusedMultiHeadAttention(
            d_model, nhead, dropout_rate=dropout_rate,
            attn_dropout_rate=(dropout_rate if attn_dropout_rate is None
                               else attn_dropout_rate),
            normalize_before=normalize_before)
        self.ffn = FusedFeedForward(
            d_model, dim_feedforward, dropout_rate=dropout_rate,
            activation=activation, act_dropout_rate=act_dropout_rate,
            normalize_before=normalize_before)

    def forward(self, src, src_mask=None):
        return self.ffn(self.fused_attn(src, attn_mask=src_mask))


def softmax_mask_fuse(x, mask, name=None):
    """incubate/operators/softmax_mask_fuse.py parity (fused_softmax_mask op):
    softmax(x + mask) in one fused region — XLA fuses the add into the
    softmax; the reference needs a dedicated CUDA kernel for the same."""
    import jax
    import jax.numpy as jnp
    from ..core.dispatch import apply

    def prim(xv, mv):
        return jax.nn.softmax((xv + mv).astype(jnp.float32),
                              axis=-1).astype(xv.dtype)

    return apply(prim, x, mask, name="fused_softmax_mask")


def softmax_mask_fuse_upper_triangle(x):
    """softmax over the causal (lower-triangular kept) scores
    (incubate/operators/softmax_mask_fuse_upper_triangle.py)."""
    import jax
    import jax.numpy as jnp
    from ..core.dispatch import apply

    def prim(xv):
        s_q, s_k = xv.shape[-2], xv.shape[-1]
        causal = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        logits = jnp.where(causal, xv, -1e30)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.where(causal, probs, 0.0).astype(xv.dtype)

    return apply(prim, x, name="fused_softmax_mask_upper_triangle")
