"""Laguna decoder (poolside; `model_type` laguna), as Laguna-XS.2 configures
it.

Pre-norm blocks h = x + A(N1(x)), y = h + FF(N2(h)), N an RMS norm, whose
layers differ in kind, read from four per-layer lists of the config (as
published, one entry a published layer): `layer_types` (`full_attention`:
causal; `sliding_attention`: query t reads the `sliding_window` keys up to and
with its own), `num_attention_heads_per_layer` (the query heads, over
`num_key_value_heads` key/value heads of `head_dim` in every layer),
`rope_parameters` by layer type (rotate-half pairing over the first
`partial_rotary_factor` x head_dim entries of a head, the rest pass; plain
frequencies, or YaRN's blend with cos and sin times its `attention_factor`),
and `mlp_layer_types` (`dense`: `nn.SwiGLUFFN`; `sparse`: a
`DroplessMoELayer` with sigmoid scores, a zero correction bias, the top
`num_experts_per_tok` renormalised and times `moe_routed_scaling_factor`, and
one shared expert). With `gating` the heads' outputs are gated before the
output projection: g = sigmoid(N1(x) W_g), one value a head (scope
`attn_gate`). No bias anywhere; a last RMS norm; the head is its own matrix.
Positions are 0, 1, 2, ... in every row.

A model may hold a share of a deployment: `first_layer` and `num_layers` say
which published layers are here (layer i held is published layer
`first_layer` + i), `held_experts` the routed experts of each layer that live
here (the router still scores all `num_experts`; the shared expert is whole
everywhere; `absent_experts` as `DroplessMoELayer`'s `absent`), and
`vocab_size` the held slice of the vocabulary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import nn
from ...core.dispatch import apply
from ...incubate.moe import DroplessMoELayer
from ...nn import functional as F
from ...nn import initializer as I
from ...tensor import manipulation as M

__all__ = ["LagunaConfig", "LagunaModel", "LagunaForCausalLM"]

INITIALIZER_RANGE = 0.02


class LagunaConfig:
    def __init__(self, vocab_size=100352, hidden_size=2048, num_layers=40,
                 first_layer=0, layer_types=None,
                 num_attention_heads_per_layer=None, mlp_layer_types=None,
                 num_key_value_heads=8, head_dim=128, sliding_window=512,
                 rope_parameters=None, intermediate_size=8192,
                 moe_intermediate_size=512, shared_expert_intermediate_size=512,
                 num_experts=256, num_experts_per_tok=8, held_experts=None,
                 absent_experts="drop", moe_routed_scaling_factor=2.5,
                 gating=True, norm_eps=1e-6, recompute=False):
        published = first_layer + num_layers
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers              # layers held here
        self.first_layer = first_layer            # the published index of the first
        # one entry a published layer; the defaults are Laguna-XS.2's pattern
        self.layer_types = list(layer_types) if layer_types is not None else [
            "sliding_attention" if i % 4 else "full_attention" for i in range(published)]
        self.num_attention_heads_per_layer = list(
            num_attention_heads_per_layer if num_attention_heads_per_layer is not None
            else (48 if kind == "full_attention" else 64 for kind in self.layer_types))
        self.mlp_layer_types = list(mlp_layer_types) if mlp_layer_types is not None \
            else ["sparse" if i else "dense" for i in range(published)]
        for name in ("layer_types", "num_attention_heads_per_layer", "mlp_layer_types"):
            if len(getattr(self, name)) < published:
                raise ValueError(f"{len(getattr(self, name))} {name} for published "
                                 f"layers {first_layer} to {published - 1}")
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.sliding_window = sliding_window
        # {layer type: {"rope_theta", "rope_type", "partial_rotary_factor", YaRN's keys}}
        self.rope_parameters = rope_parameters or {
            "full_attention": {"rope_theta": 10000.0},
            "sliding_attention": {"rope_theta": 10000.0}}
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.num_experts = num_experts            # the count the router scores
        self.num_experts_per_tok = num_experts_per_tok
        self.held_experts = held_experts          # ids held here; None: all
        self.absent_experts = absent_experts
        self.moe_routed_scaling_factor = moe_routed_scaling_factor
        self.gating = gating
        self.norm_eps = norm_eps
        # rematerialise each block in the backward pass (fleet.utils.recompute)
        self.recompute = recompute


class LagunaAttention(nn.Layer):
    def __init__(self, cfg, kind, num_heads):
        super().__init__()
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"layer type {kind!r}")
        h, d = cfg.hidden_size, cfg.head_dim
        self.num_heads, self.num_kv_heads, self.head_dim = num_heads, cfg.num_key_value_heads, d
        self.window = cfg.sliding_window if kind == "sliding_attention" else None
        rope = cfg.rope_parameters[kind]
        self.rope_theta = float(rope["rope_theta"])
        self.rotary_dim = int(d * rope.get("partial_rotary_factor", 1))
        self.rope_scaling = rope if rope.get("rope_type", "default") != "default" else None
        w = I.Normal(0.0, INITIALIZER_RANGE)

        def linear(n_in, n_out):
            return nn.Linear(n_in, n_out, weight_attr=w, bias_attr=False)
        self.q_proj = linear(h, num_heads * d)
        self.k_proj = linear(h, self.num_kv_heads * d)
        self.v_proj = linear(h, self.num_kv_heads * d)
        self.g_proj = linear(h, num_heads) if cfg.gating else None
        self.o_proj = linear(num_heads * d, h)

    def forward(self, x):
        """A block runs the three parts itself, the core between its
        rematerialised regions."""
        q, k, v, *gate = self.operands(x)
        return self.project(self.core(q, k, v), *gate)

    def operands(self, x):
        """(q, k, v) from the block's normed input, what the attention core
        reads, and with `gating` the heads' gates after them, (batch, seq,
        heads) float32."""
        b, s, _ = x.shape
        q = M.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = M.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        q, k = F.rotary_position_embedding(
            q, k, theta=self.rope_theta, rope_scaling=self.rope_scaling,
            rotary_dim=self.rotary_dim)
        if self.g_proj is None:
            return q, k, v
        return q, k, v, apply(
            lambda n, w: jax.nn.sigmoid(jnp.matmul(
                n, w, preferred_element_type=jnp.float32)),
            x, self.g_proj.weight, name="attn_gate")

    def core(self, q, k, v):
        """The heads' outputs (batch, seq, heads, head_dim): causal attention,
        over the band where the layer has a window, which a rematerialised
        block keeps on the tape (`LagunaBlock.forward`)."""
        return F.scaled_dot_product_attention(
            q, k, v, is_causal=True, window=self.window, training=self.training)

    def project(self, out, gate=None):
        b, s = out.shape[:2]
        if gate is not None:
            out = apply(lambda a, g: (a * g[..., None]).astype(a.dtype), out, gate,
                        name="attn_gate")
        return self.o_proj(M.reshape(out, [b, s, self.num_heads * self.head_dim]))


class LagunaBlock(nn.Layer):
    def __init__(self, cfg, kind, num_heads, ff):
        super().__init__()
        h = cfg.hidden_size
        w = I.Normal(0.0, INITIALIZER_RANGE)
        self.input_layernorm = nn.RMSNorm(h, cfg.norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(h, cfg.norm_eps)
        self.self_attn = LagunaAttention(cfg, kind, num_heads)
        if ff not in ("dense", "sparse"):
            raise ValueError(f"feed-forward type {ff!r}")
        self.is_dense = ff == "dense"
        if self.is_dense:
            self.mlp = nn.SwiGLUFFN(h, cfg.intermediate_size, weight_attr=w)
        else:
            self.mlp = DroplessMoELayer(
                h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held_experts=cfg.held_experts,
                routed_scaling_factor=cfg.moe_routed_scaling_factor,
                weight_attr=w, shared_width=cfg.shared_expert_intermediate_size,
                absent=cfg.absent_experts)

    def forward(self, x, rematerialise=False):
        """(y, load): `load` is the expert layer's rows per held expert, None
        under a dense feed-forward; the model adds it to the layer's counters
        outside any rematerialised region.

        With `rematerialise` the block is two regions of
        `fleet.utils.recompute` round the attention core, and the core runs
        once, on the tape, the plain pair's or the banded one's alike: its
        rerun would be the flash forward for results (the heads' outputs, the
        logsumexp) the pair's own backward rule keeps (docs/kernels.md, "What
        a rematerialised block keeps"). The heads' gates leave the first
        region with q, k and v."""
        if rematerialise:
            from ...distributed.fleet.utils import recompute as region
        else:
            def region(function, *args):
                return function(*args)
        q, k, v, *gate = region(
            lambda v: self.self_attn.operands(self.input_layernorm(v)), x)
        return region(self._after_core, x, self.self_attn.core(q, k, v), *gate)

    def _after_core(self, x, out, *gate):
        x = x + self.self_attn.project(out, *gate)
        a = self.post_attention_layernorm(x)
        if self.is_dense:
            return x + self.mlp(a), None
        out, load = self.mlp(a)
        return x + out, load


class LagunaModel(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        cfg = config or LagunaConfig(**kwargs)
        self.config = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE))
        held = range(cfg.first_layer, cfg.first_layer + cfg.num_layers)
        self.layers = nn.LayerList([
            LagunaBlock(cfg, cfg.layer_types[i], cfg.num_attention_heads_per_layer[i],
                        cfg.mlp_layer_types[i]) for i in held])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        remat = self.config.recompute and self.training
        for block in self.layers:
            x, load = block(x, remat)
            if load is not None:
                block.mlp.record_load(load)
        return self.norm(x)


class LagunaForCausalLM(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        self.model = LagunaModel(config, **kwargs)
        self.config = self.model.config
        self.lm_head = nn.Linear(
            self.config.hidden_size, self.config.vocab_size,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE), bias_attr=False)

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.model(input_ids))
        if labels is None:
            return logits
        return F.cross_entropy(M.reshape(logits, [-1, self.config.vocab_size]),
                               M.reshape(labels, [-1]))
