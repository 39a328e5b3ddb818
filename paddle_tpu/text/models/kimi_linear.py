"""Kimi Linear decoder (Moonshot AI; `model_type` kimi_linear; arXiv 2510.26692).

A hybrid of two token mixers over a sparse feed-forward: block
h = x + Mixer(N1(x)), y = h + FF(N2(h)), N an RMS norm; Mixer is
`nn.KimiDeltaAttention` (a gated delta rule with a decay per channel, linear
in the sequence) or `nn.MultiHeadLatentAttention` (latent attention without
rotation, query/key heads wider than the value heads) by `layer_types`,
three of the first to one of the second as published; FF is a dense
`nn.SwiGLUFFN` in the first `first_k_dense_replace` layers held and a
`DroplessMoELayer` with one shared expert after (sigmoid scores, the top k of
score + bias, renormalised, times `routed_scaling_factor`). No positions
anywhere, no bias but the decay's `dt_bias`; a last RMS norm; the head is
its own matrix, not the embedding's.

A model may hold a share of a deployment: `held_experts` are the routed
experts of each layer that live here (the router still scores all
`num_experts`; the shared expert is whole everywhere), and `vocab_size` is
the held slice of the vocabulary. On one chip the expert layer runs without an
exchange and gives its own experts' part of the result (incubate/moe.py).
"""
from __future__ import annotations

from ... import nn
from ...incubate.moe import DroplessMoELayer
from ...nn import functional as F
from ...nn import initializer as I
from ...tensor import manipulation as M

__all__ = ["KimiLinearConfig", "KimiLinearModel", "KimiLinearForCausalLM"]

INITIALIZER_RANGE = 0.02


class KimiLinearConfig:
    def __init__(self, vocab_size=163840, hidden_size=2304, num_layers=4,
                 layer_types=None, first_k_dense_replace=0,
                 num_attention_heads=32, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 linear_num_heads=32, linear_head_dim=128, short_conv_kernel=4,
                 gate_rank=None, intermediate_size=9216,
                 moe_intermediate_size=1024, num_experts=256,
                 num_experts_per_token=8, num_shared_experts=1,
                 held_experts=None, routed_scaling_factor=2.446,
                 norm_eps=1e-5, recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        # one of "kda", "full_attention" per layer held
        self.layer_types = list(layer_types) if layer_types is not None else [
            "full_attention" if i % 4 == 3 else "kda" for i in range(num_layers)]
        if len(self.layer_types) != num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{num_layers} layers")
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.linear_num_heads = linear_num_heads
        self.linear_head_dim = linear_head_dim
        self.short_conv_kernel = short_conv_kernel
        self.gate_rank = gate_rank                # None: linear_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts            # the count the router scores
        self.num_experts_per_token = num_experts_per_token
        self.num_shared_experts = num_shared_experts
        self.held_experts = held_experts          # ids held here; None: all
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_eps = norm_eps
        # rematerialise each block in the backward pass (fleet.utils.recompute)
        self.recompute = recompute


class KimiLinearBlock(nn.Layer):
    def __init__(self, cfg, layer_type, dense):
        super().__init__()
        h = cfg.hidden_size
        w = I.Normal(0.0, INITIALIZER_RANGE)
        self.input_layernorm = nn.RMSNorm(h, cfg.norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(h, cfg.norm_eps)
        if layer_type == "kda":
            self.self_attn = nn.KimiDeltaAttention(
                h, cfg.linear_num_heads, cfg.linear_head_dim,
                cfg.short_conv_kernel, cfg.gate_rank, cfg.norm_eps,
                weight_attr=w)
        elif layer_type == "full_attention":
            self.self_attn = nn.MultiHeadLatentAttention(
                h, cfg.num_attention_heads, cfg.kv_lora_rank,
                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                cfg.norm_eps, weight_attr=w)
        else:
            raise ValueError(f"layer type {layer_type!r}")
        self.is_kda = layer_type == "kda"
        self.is_dense = dense
        if dense:
            self.mlp = nn.SwiGLUFFN(h, cfg.intermediate_size, weight_attr=w)
        else:
            self.mlp = DroplessMoELayer(
                h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_token, held_experts=cfg.held_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                weight_attr=w,
                shared_width=cfg.num_shared_experts * cfg.moe_intermediate_size
                or None)

    def forward(self, x, rematerialise=False):
        """(y, load): `load` is the expert layer's rows per held expert,
        None under a dense feed-forward; the model adds it to the layer's
        counters outside any rematerialised region.

        With `rematerialise` the block is two regions of
        `fleet.utils.recompute` round the mixer's core, and the core runs
        once, on the tape: its rerun would be the delta rule's forward loops
        (the flash forward in a latent layer) for results the op's own
        backward rule keeps at 0.67 GB a layer (0.34 GB) at 2 x 4096 tokens
        (docs/kernels.md, "What a rematerialised block keeps")."""
        if rematerialise:
            from ...distributed.fleet.utils import recompute as region
        else:
            def region(function, *args):
                return function(*args)
        operands = region(
            lambda v: self.self_attn.operands(self.input_layernorm(v)), x)
        # a KDA layer's last operand is `project`'s, not the core's
        operands, carried = ((operands[:-1], operands[-1:]) if self.is_kda
                             else (operands, ()))
        out = self.self_attn.core(*operands)
        return region(self._after_core, x, out, *carried)

    def _after_core(self, x, out, *carried):
        x = x + self.self_attn.project(out, *carried)
        a = self.post_attention_layernorm(x)
        if self.is_dense:
            return x + self.mlp(a), None
        out, load = self.mlp(a)
        return x + out, load


class KimiLinearModel(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        cfg = config or KimiLinearConfig(**kwargs)
        self.config = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE))
        self.layers = nn.LayerList([
            KimiLinearBlock(cfg, kind, dense=i < cfg.first_k_dense_replace)
            for i, kind in enumerate(cfg.layer_types)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        remat = self.config.recompute and self.training
        for block in self.layers:
            x, load = block(x, remat)
            if load is not None:
                block.mlp.record_load(load)
        return self.norm(x)


class KimiLinearForCausalLM(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        self.model = KimiLinearModel(config, **kwargs)
        self.config = self.model.config
        self.lm_head = nn.Linear(
            self.config.hidden_size, self.config.vocab_size,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE), bias_attr=False)

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.model(input_ids))
        if labels is not None:
            return F.cross_entropy(
                M.reshape(logits, [-1, self.config.vocab_size]),
                M.reshape(labels, [-1]))
        return logits
