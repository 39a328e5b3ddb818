"""DeepSeek-V2 decoder (DeepSeek-AI; `model_type` deepseek_v2; arXiv
2405.04434), as DeepSeek-V2-Lite configures it.

Pre-norm blocks h = x + MLA(N1(x)), y = h + FF(N2(h)), N an RMS norm; MLA is
`nn.MultiHeadLatentAttention` with its decoupled rotary part (the last
`qk_rope_head_dim` entries of each query head and the one key part the
heads share are turned, under YaRN where `rope_scaling` says so; `q_lora_rank`
null: the queries come from one matrix); FF is a dense `nn.SwiGLUFFN` in the
published layers below `first_k_dense_replace` and after them a
`DroplessMoELayer`: a softmax router over all `n_routed_experts`, the top
`num_experts_per_tok` by plain top-k (`topk_method` greedy, one group), their
scores as weights *without* renormalisation (`norm_topk_prob` false) times
`routed_scaling_factor`, and `n_shared_experts` shared experts as one SwiGLU
of their summed width. No bias anywhere; a last RMS norm; the head is its
own matrix. Positions are 0, 1, 2, ... in every row.

Training adds, an expert layer, the sequence-wise balance loss (`seq_aux`;
`DroplessMoELayer`, `balance_alpha` = `aux_loss_alpha`): with labels the
model returns the language-model loss and the layers' balance losses added,
which is what a step differentiates. The source adds that term's gradient and
not its value; here the value is added too.

A model may hold a share of a deployment: `first_layer` and `num_layers` say
which published layers are here (layer i held is published layer
`first_layer` + i, dense where that is below `first_k_dense_replace`),
`held_experts` the routed experts of each layer that live here (the router
still scores all `n_routed_experts`; the shared experts are whole
everywhere; `absent_experts` as `DroplessMoELayer`'s `absent`), and
`vocab_size` the held slice of the vocabulary.
"""
from __future__ import annotations

from ... import nn
from ...incubate.moe import DroplessMoELayer
from ...nn import functional as F
from ...nn import initializer as I
from ...tensor import manipulation as M

__all__ = ["DeepseekV2Config", "DeepseekV2Model", "DeepseekV2ForCausalLM"]

INITIALIZER_RANGE = 0.02


class DeepseekV2Config:
    def __init__(self, vocab_size=102400, hidden_size=2048, num_layers=27,
                 first_layer=0, first_k_dense_replace=1,
                 num_attention_heads=16, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_theta=10000.0, rope_scaling=None, intermediate_size=10944,
                 moe_intermediate_size=1408, n_routed_experts=64,
                 num_experts_per_tok=6, n_shared_experts=2, held_experts=None,
                 absent_experts="drop", norm_topk_prob=False,
                 routed_scaling_factor=1.0, aux_loss_alpha=0.001,
                 norm_eps=1e-6, recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers              # layers held here
        self.first_layer = first_layer            # the published index of the first
        self.first_k_dense_replace = first_k_dense_replace    # of the published layers
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling          # None, or YaRN's dict
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts  # the count the router scores
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.held_experts = held_experts          # ids held here; None: all
        self.absent_experts = absent_experts
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        # None as 0.0: the blocks unpack the expert layer's four values
        self.aux_loss_alpha = 0.0 if aux_loss_alpha is None else aux_loss_alpha
        self.norm_eps = norm_eps
        # rematerialise each block in the backward pass (fleet.utils.recompute)
        self.recompute = recompute


class DeepseekV2Block(nn.Layer):
    def __init__(self, cfg, dense):
        super().__init__()
        h = cfg.hidden_size
        w = I.Normal(0.0, INITIALIZER_RANGE)
        self.input_layernorm = nn.RMSNorm(h, cfg.norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(h, cfg.norm_eps)
        self.self_attn = nn.MultiHeadLatentAttention(
            h, cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.norm_eps, weight_attr=w,
            rope={"theta": cfg.rope_theta, "rope_scaling": cfg.rope_scaling})
        self.is_dense = dense
        if dense:
            self.mlp = nn.SwiGLUFFN(h, cfg.intermediate_size, weight_attr=w)
        else:
            self.mlp = DroplessMoELayer(
                h, cfg.moe_intermediate_size, cfg.n_routed_experts,
                cfg.num_experts_per_tok, held_experts=cfg.held_experts,
                routed_scaling_factor=cfg.routed_scaling_factor, weight_attr=w,
                shared_width=cfg.n_shared_experts * cfg.moe_intermediate_size
                or None, score="softmax", absent=cfg.absent_experts,
                renormalize=cfg.norm_topk_prob,
                balance_alpha=cfg.aux_loss_alpha)

    def forward(self, x, rematerialise=False):
        """(y, load, balance loss, picks): the last three are the expert
        layer's (`DroplessMoELayer.forward`), None under a dense
        feed-forward; the model records and adds them outside any
        rematerialised region.

        With `rematerialise` the block is two regions of
        `fleet.utils.recompute` round the attention core, and the core runs
        once, on the tape: its rerun would be the flash forward for results
        (the heads' outputs, the logsumexp) the pair's own backward rule
        keeps at 168 MB a layer at 8192 positions (docs/kernels.md, "What a
        rematerialised block keeps")."""
        if rematerialise:
            from ...distributed.fleet.utils import recompute as region
        else:
            def region(function, *args):
                return function(*args)
        operands = region(
            lambda v: self.self_attn.operands(self.input_layernorm(v)), x)
        return region(self._after_core, x, self.self_attn.core(*operands))

    def _after_core(self, x, out):
        x = x + self.self_attn.project(out)
        a = self.post_attention_layernorm(x)
        if self.is_dense:
            return x + self.mlp(a), None, None, None
        out, load, balance, picks = self.mlp(a)
        return x + out, load, balance, picks


class DeepseekV2Model(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        cfg = config or DeepseekV2Config(**kwargs)
        self.config = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE))
        self.layers = nn.LayerList([
            DeepseekV2Block(cfg, dense=cfg.first_layer + i < cfg.first_k_dense_replace)
            for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps)

    def forward(self, input_ids):
        """(hidden states, the sum of the expert layers' balance losses:
        None where no layer held has experts)."""
        x = self.embed_tokens(input_ids)
        remat = self.config.recompute and self.training
        balance = None
        for block in self.layers:
            x, load, layer_loss, picks = block(x, remat)
            if load is not None:
                block.mlp.record_load(load, layer_loss, picks)
                balance = layer_loss if balance is None else balance + layer_loss
        return self.norm(x), balance


class DeepseekV2ForCausalLM(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        self.model = DeepseekV2Model(config, **kwargs)
        self.config = self.model.config
        self.lm_head = nn.Linear(
            self.config.hidden_size, self.config.vocab_size,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE), bias_attr=False)

    def forward(self, input_ids, labels=None):
        """Without labels (logits, balance loss); with them (loss,
        language-model loss, balance loss), loss their sum: what a training
        step differentiates."""
        h, balance = self.model(input_ids)
        logits = self.lm_head(h)
        if labels is None:
            return logits, balance
        lm_loss = F.cross_entropy(
            M.reshape(logits, [-1, self.config.vocab_size]),
            M.reshape(labels, [-1]))
        if balance is None:
            return lm_loss.astype("float32"), lm_loss, None
        return lm_loss.astype("float32") + balance, lm_loss, balance
