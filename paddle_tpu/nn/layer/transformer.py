"""Transformer layers.

Reference parity: python/paddle/nn/layer/transformer.py (MultiHeadAttention
:109, TransformerEncoderLayer :474, TransformerEncoder :622, Decoder, full
Transformer :1112). TPU-native: attention goes through
ops/attention.scaled_dot_product_attention (Pallas flash-attention capable);
everything stays bfloat16-friendly and jit-traceable.
"""
from __future__ import annotations

import collections

from ...core.tensor import Tensor
from ...tensor import manipulation as M
from .. import functional as F
from .common import Dropout, Linear
from .container import LayerList
from .layers import Layer
from .norm import LayerNorm, RMSNorm

__all__ = ["MultiHeadAttention", "TransformerEncoderLayer",
           "TransformerEncoder", "TransformerDecoderLayer",
           "TransformerDecoder", "Transformer", "MultiHeadLatentAttention",
           "KimiDeltaAttention"]


def _convert_attn_mask(attn_mask, dtype):
    if attn_mask is None:
        return None
    import jax.numpy as jnp
    from ...core.dispatch import unwrap
    m = unwrap(attn_mask)
    if m.dtype == jnp.bool_:
        return Tensor(jnp.where(m, 0.0, -1e30).astype(dtype))
    return attn_mask if isinstance(attn_mask, Tensor) else Tensor(m)


class MultiHeadAttention(Layer):
    """transformer.py:109 parity; q/k/v projections + SDPA + out projection."""

    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None, vdim=None,
                 need_weights=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _split_heads(self, x):
        b, s, _ = x.shape
        return M.reshape(x, [b, s, self.num_heads, self.head_dim])

    def gen_cache(self, key, value=None, type=Cache):  # noqa: A002
        if type == MultiHeadAttention.StaticCache:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value if value is not None else key))
            return self.StaticCache(k, v)
        from ...tensor.creation import zeros
        b = key.shape[0]
        k = zeros([b, 0, self.num_heads, self.head_dim], dtype=key.dtype)
        v = zeros([b, 0, self.num_heads, self.head_dim], dtype=key.dtype)
        return self.Cache(k, v)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        key = query if key is None else key
        value = key if value is None else value
        q = self._split_heads(self.q_proj(query))
        if isinstance(cache, self.StaticCache):
            k, v = cache.k, cache.v
        else:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value))
            if isinstance(cache, self.Cache):
                k = M.concat([cache.k, k], axis=1)
                v = M.concat([cache.v, v], axis=1)
                cache = self.Cache(k, v)
        mask = _convert_attn_mask(attn_mask, q._value.dtype)
        from ...ops.attention import scaled_dot_product_attention
        out = scaled_dot_product_attention(
            q, k, v, attn_mask=mask, dropout_p=self.dropout,
            training=self.training)
        b, s = out.shape[0], out.shape[1]
        out = M.reshape(out, [b, s, self.embed_dim])
        out = self.out_proj(out)
        if cache is not None and isinstance(cache, self.Cache):
            return out, cache
        return out


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self._activation_name = activation
        self.activation = getattr(F, activation)

    def _ffn(self, src):
        """linear1 -> act -> (dropout) -> linear2; routed through the fused
        FFN op (ops/fused_ffn.py — backward recomputes the 4h-wide
        activation instead of saving it) whenever the inner dropout is
        inactive and the activation is relu/gelu."""
        drop_active = self.training and self.dropout.p > 0.0
        if (not drop_active and self._activation_name in ("relu", "gelu")
                and self.linear1.bias is not None
                and self.linear2.bias is not None):
            from ...ops.fused_ffn import fused_ffn
            return fused_ffn(src, self.linear1.weight, self.linear1.bias,
                             self.linear2.weight, self.linear2.bias,
                             activation=self._activation_name)
        return self.linear2(self.dropout(self.activation(self.linear1(src))))

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        if cache is None:
            src = self.self_attn(src, src, src, src_mask)
        else:
            src, cache = self.self_attn(src, src, src, src_mask, cache)
        if self.normalize_before:
            src = residual + self.dropout1(src)
        else:
            src = self.norm1(residual + self.dropout1(src))
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        src = self._ffn(src)
        if self.normalize_before:
            src = residual + self.dropout2(src)
        else:
            src = self.norm2(residual + self.dropout2(src))
        return src if cache is None else (src, cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList(
            [encoder_layer if i == 0 else copy.deepcopy(encoder_layer)
             for i in range(num_layers)])
        # deepcopy duplicates parameters with identical values; re-init
        for i, layer in enumerate(self.layers):
            if i == 0:
                continue
            _reinit(layer)
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None):
        output = src
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, src_mask)
            else:
                output, c = mod(output, src_mask, cache[i])
                new_caches.append(c)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]


def _reinit(layer):
    """Fresh init for deep-copied layers (matches the reference's per-layer
    independent initialization in TransformerEncoder, transformer.py:622)."""
    from .. import initializer as I
    for sub in layer.sublayers(include_self=True):
        if isinstance(sub, Linear):
            sub.weight._value = I.XavierNormal()(sub.weight.shape,
                                                 sub.weight._val.dtype)
            if sub.bias is not None:
                sub.bias._value = I.Constant(0.0)(sub.bias.shape,
                                                  sub.bias._val.dtype)


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr,
                                             bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
            incremental_cache = None
        else:
            tgt, incremental_cache = self.self_attn(tgt, tgt, tgt, tgt_mask,
                                                    cache[0])
        if self.normalize_before:
            tgt = residual + self.dropout1(tgt)
        else:
            tgt = self.norm1(residual + self.dropout1(tgt))
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
            static_cache = None
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
            static_cache = cache[1]
        if self.normalize_before:
            tgt = residual + self.dropout2(tgt)
        else:
            tgt = self.norm2(residual + self.dropout2(tgt))
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout(self.activation(self.linear1(tgt))))
        if self.normalize_before:
            tgt = residual + self.dropout3(tgt)
        else:
            tgt = self.norm3(residual + self.dropout3(tgt))
        if cache is None:
            return tgt
        return tgt, (incremental_cache, static_cache)

    def gen_cache(self, memory):
        incremental = self.self_attn.gen_cache(memory)
        static = self.cross_attn.gen_cache(memory, memory,
                                           MultiHeadAttention.StaticCache)
        return incremental, static


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList(
            [decoder_layer if i == 0 else copy.deepcopy(decoder_layer)
             for i in range(num_layers)])
        for i, layer in enumerate(self.layers):
            if i:
                _reinit(layer)
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        output = tgt
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, memory, tgt_mask, memory_mask)
            else:
                output, c = mod(output, memory, tgt_mask, memory_mask,
                                cache[i])
                new_caches.append(c)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False):
        caches = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            caches = list(zip(*caches))
        return caches


class Transformer(Layer):
    """Full encoder-decoder (transformer.py:1112 parity)."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers,
                                              enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers,
                                              dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    def generate_square_subsequent_mask(self, length):
        import jax.numpy as jnp
        m = jnp.where(jnp.tril(jnp.ones((length, length), dtype=bool)), 0.0,
                      -1e30).astype(jnp.float32)
        return Tensor(m)


class MultiHeadLatentAttention(Layer):
    """Causal multi-head latent attention (DeepSeek-V2's MLA with
    `q_lora_rank` null), in one of two modes: without rotation, as Kimi
    Linear uses it (`mla_use_nope` true; the default), or with the decoupled
    rotary part, as DeepSeek-V2 has it (`rope` given).

    q_h = W_q^h x, `qk_nope_head_dim + qk_rope_head_dim` wide; c = W_kva x,
    `kv_lora_rank + qk_rope_head_dim` wide: its first part, RMS-normed, is the
    latent every head's keys and values are made from, [k_nope_h ; v_h] =
    W_kvb^h c_kv, and its last `qk_rope_head_dim` entries are a key part all
    heads share; k_h = [k_nope_h ; k_shared]. The value heads are
    `v_head_dim` wide, the query/key heads wider, and
    F.scaled_dot_product_attention takes the two sizes as they are (no v
    padded to the keys' width). No bias.

    `rope`: a dict with `theta`, and `rope_scaling` (None, or YaRN's as
    F.rotary_position_embedding takes it). The last `qk_rope_head_dim`
    entries of every query head and the shared key part are then turned by
    their positions 0, 1, 2, ... (pairs of neighbouring entries, as the
    source's weights are laid out: F.rotary_position_embedding's
    `interleaved`), the first `qk_nope_head_dim` are not, and under YaRN the
    softmax scale is the heads' width ** -0.5 times m(mscale_all_dim)^2
    (F.yarn_scales). The slices,
    the rotation and the query put together again stage under the scope
    `mla_rope`; the shared part is turned once, as one head, before it is
    broadcast.

    The shared key part is broadcast over the heads when k is put together
    (scope `mla_kv`), which writes it `num_heads` times: a third of k's bytes
    at 128 + 64, 33 MB of a 100 MB k at 2 x 4096 tokens and 32 heads in
    bfloat16 (the flash kernels take one key operand). Rotated or not, what
    is broadcast is one (batch, seq, 64) part, so the rotation adds nothing
    to that cost: it reads and writes the 64 shared entries a token once and
    64 of every query head's 192."""

    def __init__(self, hidden_size, num_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, epsilon=1e-05, weight_attr=None,
                 rope=None):
        super().__init__()
        self.num_heads = num_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope = rope
        # the softmax's scale: None is the heads' width ** -0.5
        self.scale = None
        if rope is not None and rope.get("rope_scaling") is not None:
            self.scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5 \
                * F.yarn_scales(rope["rope_scaling"])[1]

        def linear(n_in, n_out):
            return Linear(n_in, n_out, weight_attr=weight_attr, bias_attr=False)
        self.q_proj = linear(hidden_size,
                             num_heads * (qk_nope_head_dim + qk_rope_head_dim))
        self.kv_a_proj = linear(hidden_size, kv_lora_rank + qk_rope_head_dim)
        self.kv_a_norm = RMSNorm(kv_lora_rank, epsilon)
        self.kv_b_proj = linear(kv_lora_rank,
                                num_heads * (qk_nope_head_dim + v_head_dim))
        self.o_proj = linear(num_heads * v_head_dim, hidden_size)

    def forward(self, x):
        """A block runs the three parts itself, the core between its
        rematerialised regions."""
        return self.project(self.core(*self.operands(x)))

    def operands(self, x):
        """(q, k, v) from the block's normed input: what the attention core
        reads, the projections, the latent's norm, the rotation and `mla_kv`
        behind them."""
        import jax.numpy as jnp
        from ...core.dispatch import apply
        b, s, _ = x.shape
        heads, nope, dv = self.num_heads, self.qk_nope_head_dim, self.v_head_dim
        q = M.reshape(self.q_proj(x),
                      [b, s, heads, nope + self.qk_rope_head_dim])
        rank = self.kv_lora_rank
        latent, shared = apply(lambda c: (c[..., :rank], c[..., rank:]),
                               self.kv_a_proj(x), name="mla_kv")
        kv = M.reshape(self.kv_b_proj(self.kv_a_norm(latent)),
                       [b, s, heads, nope + dv])
        if self.rope is not None:
            q_nope, q_pe = apply(lambda v: (v[..., :nope], v[..., nope:]), q,
                                 name="mla_rope")
            q_pe, shared = F.rotary_position_embedding(
                q_pe, M.reshape(shared, [b, s, 1, self.qk_rope_head_dim]),
                theta=self.rope["theta"], rope_scaling=self.rope.get("rope_scaling"),
                interleaved=True, name="mla_rope")
            q = apply(lambda a, c: jnp.concatenate([a, c], axis=-1), q_nope, q_pe,
                      name="mla_rope")
            shared = M.reshape(shared, [b, s, self.qk_rope_head_dim])

        def keys_values(kv_, shared_):
            pe = jnp.broadcast_to(shared_[:, :, None, :],
                                  kv_.shape[:3] + shared_.shape[-1:])
            return (jnp.concatenate([kv_[..., :nope], pe], axis=-1),
                    kv_[..., nope:])
        k, v = apply(keys_values, kv, shared, name="mla_kv")
        return q, k, v

    def core(self, q, k, v):
        """The heads' outputs (batch, seq, heads, `v_head_dim`): causal
        attention, which a rematerialised block keeps on the tape
        (docs/kernels.md, "What a rematerialised block keeps")."""
        return F.scaled_dot_product_attention(
            q, k, v, is_causal=True, training=self.training, scale=self.scale)

    def project(self, out):
        b, s = out.shape[:2]
        return self.o_proj(M.reshape(out, [b, s, self.num_heads * self.v_head_dim]))


class KimiDeltaAttention(Layer):
    """The Kimi Delta Attention mixer (Kimi Linear, arXiv 2510.26692).

    q = l2norm_head(silu(conv(W_q x))), k likewise, v = silu(conv(W_v x)),
    conv a causal depthwise convolution of `conv_kernel` taps
    (F.short_conv_silu); a decay per channel in log space,
    g = -exp(A_log_h) * softplus(W_f_up W_f_down x + dt_bias), float32; a
    write strength a head, beta = sigmoid(W_b x); o = F.kimi_delta_attention
    (q, k, v, g, beta), the chunked gated delta rule with the queries scaled
    by head_dim ** -0.5; out = W_o(rms_norm_head(o) * sigmoid(W_g_up W_g_down
    x)), the norm's gain of `head_dim` shared by the heads. Both gates are
    low-rank, hidden -> `gate_rank` -> heads * head_dim. No positions, no
    bias but `dt_bias`. The leaves are named as the source's modelling code
    names them (`q_conv1d`, `f_a_proj`, `A_log`, `o_norm`, ...)."""

    def __init__(self, hidden_size, num_heads, head_dim, conv_kernel=4,
                 gate_rank=None, epsilon=1e-05, weight_attr=None):
        super().__init__()
        from .. import initializer as I
        self.num_heads, self.head_dim = num_heads, head_dim
        width = num_heads * head_dim
        rank = head_dim if gate_rank is None else gate_rank

        def linear(n_in, n_out):
            return Linear(n_in, n_out, weight_attr=weight_attr, bias_attr=False)
        self.q_proj, self.k_proj, self.v_proj = (
            linear(hidden_size, width) for _ in range(3))
        taps = I.Uniform(-conv_kernel ** -0.5, conv_kernel ** -0.5)
        self.q_conv1d, self.k_conv1d, self.v_conv1d = (
            self.create_parameter([width, conv_kernel], attr=weight_attr,
                                  default_initializer=taps) for _ in range(3))
        self.f_a_proj, self.f_b_proj = linear(hidden_size, rank), linear(rank, width)
        self.A_log = self.create_parameter(
            [num_heads], default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [width], default_initializer=I.Constant(0.0))
        self.b_proj = linear(hidden_size, num_heads)
        self.g_a_proj, self.g_b_proj = linear(hidden_size, rank), linear(rank, width)
        self.o_norm = RMSNorm(head_dim, epsilon)
        self.o_proj = linear(width, hidden_size)

    def forward(self, x):
        """A block runs the three parts itself, the core between its
        rematerialised regions."""
        *operands, gate = self.operands(x)
        return self.project(self.core(*operands), gate)

    def operands(self, x):
        """(q, k, v, g, beta, gate) from the block's normed input: what the
        delta-rule core reads and, last, the output gate's low-rank
        activation (batch, seq, `gate_rank`), which `project` reads: all of
        x that is needed after the core, at a hundredth of the gate's bytes."""
        import jax
        import jax.numpy as jnp
        from ...core.dispatch import apply
        b, s, _ = x.shape
        heads = [b, s, self.num_heads, self.head_dim]
        q = M.reshape(F.short_conv_silu(
            self.q_proj(x), self.q_conv1d, norm_head_dim=self.head_dim), heads)
        k = M.reshape(F.short_conv_silu(
            self.k_proj(x), self.k_conv1d, norm_head_dim=self.head_dim), heads)
        v = M.reshape(F.short_conv_silu(self.v_proj(x), self.v_conv1d), heads)

        def decay(f, a_log, dt_bias):
            f32 = jnp.float32
            g = jax.nn.softplus(f.astype(f32) + dt_bias.astype(f32)).reshape(heads)
            return -jnp.exp(a_log.astype(f32))[:, None] * g
        g = apply(decay, self.f_b_proj(self.f_a_proj(x)), self.A_log,
                  self.dt_bias, name="kda_gate")
        beta = F.sigmoid(self.b_proj(x))
        return q, k, v, g, beta, self.g_a_proj(x)

    def core(self, q, k, v, g, beta):
        """o (batch, seq, heads, head_dim): the chunked gated delta rule,
        which a rematerialised block keeps on the tape (docs/kernels.md,
        "What a rematerialised block keeps")."""
        return F.kimi_delta_attention(q, k, v, g, beta)

    def project(self, o, gate):
        b, s = o.shape[:2]
        gate = M.reshape(F.sigmoid(self.g_b_proj(gate)), o.shape)
        return self.o_proj(M.reshape(self.o_norm(o) * gate,
                                     [b, s, self.num_heads * self.head_dim]))
