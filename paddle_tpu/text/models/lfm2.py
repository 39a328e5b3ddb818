"""LFM2 mixture-of-experts decoder (Liquid AI; `model_type` lfm2_moe).

A hybrid of gated short convolutions and grouped-query attention over a
sparse feed-forward: block h = x + Op(N1(x)), y = h + FF(N2(h)), N an RMS
norm; Op is `nn.ShortConv` or attention (per-head RMS norms on q and k,
rotary positions over the whole head) by `layer_types`; FF is a dense
`nn.SwiGLUFFN` in the first `num_dense_layers` layers and a
`DroplessMoELayer` (sigmoid routing, no dropped token) after. No bias
anywhere; a last RMS norm; the head is tied to the token embedding.

A model may hold a share of a deployment: `held_experts` are the experts of
each layer that live here (the router still scores all `num_experts`), and
`vocab_size` is the held slice of the vocabulary. On one chip the expert
layer runs without an exchange and gives its own experts' part of the result
(incubate/moe.py).
"""
from __future__ import annotations

from ... import nn
from ...incubate.moe import DroplessMoELayer
from ...nn import functional as F
from ...nn import initializer as I
from ...tensor import manipulation as M

__all__ = ["LFM2Config", "LFM2Model", "LFM2ForCausalLM"]

INITIALIZER_RANGE = 0.02


class LFM2Config:
    def __init__(self, vocab_size=65536, hidden_size=2048, num_layers=4,
                 layer_types=None, num_dense_layers=0,
                 num_attention_heads=32, num_key_value_heads=8,
                 intermediate_size=11776, moe_intermediate_size=1536,
                 num_experts=64, num_experts_per_tok=4, held_experts=None,
                 routed_scaling_factor=1.0, conv_kernel=3, rope_theta=1e6,
                 norm_eps=1e-5, recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        # one of "conv", "full_attention" per layer
        self.layer_types = list(layer_types) if layer_types is not None else [
            "full_attention" if i % 4 == 2 else "conv" for i in range(num_layers)]
        if len(self.layer_types) != num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{num_layers} layers")
        self.num_dense_layers = num_dense_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts            # the count the router scores
        self.num_experts_per_tok = num_experts_per_tok
        self.held_experts = held_experts          # ids held here; None: all
        self.routed_scaling_factor = routed_scaling_factor
        self.conv_kernel = conv_kernel
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        # rematerialise each block in the backward pass (fleet.utils.recompute):
        # a block keeps its input, an attention block what its attention core
        # keeps as well, which buys batch or sequence on one chip
        self.recompute = recompute


class LFM2Attention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = h // self.num_heads
        self.rope_theta = cfg.rope_theta
        w = I.Normal(0.0, INITIALIZER_RANGE)
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = nn.Linear(h, h, weight_attr=w, bias_attr=False)
        self.k_proj = nn.Linear(h, kv, weight_attr=w, bias_attr=False)
        self.v_proj = nn.Linear(h, kv, weight_attr=w, bias_attr=False)
        self.out_proj = nn.Linear(h, h, weight_attr=w, bias_attr=False)
        self.q_norm = nn.RMSNorm(self.head_dim, cfg.norm_eps)
        self.k_norm = nn.RMSNorm(self.head_dim, cfg.norm_eps)

    def forward(self, x):
        """A block runs the three parts itself, the core between its
        rematerialised regions."""
        return self.project(self.core(*self.operands(x)))

    def operands(self, x):
        """(q, k, v) from the block's normed input: what the attention core
        reads."""
        b, s, _ = x.shape
        q = M.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = M.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        q, k = F.rotary_position_embedding(self.q_norm(q), self.k_norm(k),
                                           theta=self.rope_theta)
        return q, k, v

    def core(self, q, k, v):
        """The heads' outputs (batch, seq, heads, head_dim): causal attention,
        which a rematerialised block keeps on the tape (`LFM2Block.forward`)."""
        return F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                              training=self.training)

    def project(self, out):
        b, s = out.shape[:2]
        return self.out_proj(M.reshape(out, [b, s, self.num_heads * self.head_dim]))


class LFM2Block(nn.Layer):
    def __init__(self, cfg, layer_type, dense):
        super().__init__()
        h = cfg.hidden_size
        w = I.Normal(0.0, INITIALIZER_RANGE)
        self.operator_norm = nn.RMSNorm(h, cfg.norm_eps)
        self.ffn_norm = nn.RMSNorm(h, cfg.norm_eps)
        if layer_type == "conv":
            self.conv = nn.ShortConv(h, cfg.conv_kernel, weight_attr=w)
        elif layer_type == "full_attention":
            self.self_attn = LFM2Attention(cfg)
        else:
            raise ValueError(f"layer type {layer_type!r}")
        self.is_conv = layer_type == "conv"
        self.is_dense = dense
        if dense:
            self.feed_forward = nn.SwiGLUFFN(h, cfg.intermediate_size,
                                             weight_attr=w)
        else:
            self.feed_forward = DroplessMoELayer(
                h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held_experts=cfg.held_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                weight_attr=w)

    def forward(self, x, rematerialise=False):
        """(y, load): `load` is the expert layer's rows per held expert,
        None under a dense feed-forward; the model adds it to the layer's
        counters outside any rematerialised region.

        With `rematerialise` a `conv` block is one region of
        `fleet.utils.recompute`: its mixer has no core worth its bytes. An
        attention block is two regions round the attention core, and the
        core runs once, on the tape: its rerun would be the flash forward
        for results (the heads' outputs, the logsumexp) the pair's own
        backward rule keeps (docs/kernels.md, "What a rematerialised block
        keeps")."""
        if rematerialise:
            from ...distributed.fleet.utils import recompute as region
        else:
            def region(function, *args):
                return function(*args)
        if self.is_conv:
            return region(lambda v: self._after_mixer(
                v, self.conv(self.operator_norm(v))), x)
        operands = region(
            lambda v: self.self_attn.operands(self.operator_norm(v)), x)
        out = self.self_attn.core(*operands)
        return region(lambda v, o: self._after_mixer(
            v, self.self_attn.project(o)), x, out)

    def _after_mixer(self, x, mixed):
        x = x + mixed
        a = self.ffn_norm(x)
        if self.is_dense:
            return x + self.feed_forward(a), None
        out, load = self.feed_forward(a)
        return x + out, load


class LFM2Model(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        cfg = config or LFM2Config(**kwargs)
        self.config = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE))
        self.layers = nn.LayerList([
            LFM2Block(cfg, kind, dense=i < cfg.num_dense_layers)
            for i, kind in enumerate(cfg.layer_types)])
        self.embedding_norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        remat = self.config.recompute and self.training
        for block in self.layers:
            x, load = block(x, remat)
            if load is not None:
                block.feed_forward.record_load(load)
        return self.embedding_norm(x)


class LFM2ForCausalLM(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        self.model = LFM2Model(config, **kwargs)
        self.config = self.model.config

    def forward(self, input_ids, labels=None):
        h = self.model(input_ids)
        logits = F.linear(h, self.model.embed_tokens.weight.t())
        if labels is not None:
            return F.cross_entropy(
                M.reshape(logits, [-1, self.config.vocab_size]),
                M.reshape(labels, [-1]))
        return logits
