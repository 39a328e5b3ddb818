"""Keye-VL-2.0's language model (Kwai-Keye; `model_type` KeyeVL2): a
mixture-of-experts decoder whose attention reads, for each query, the keys a
learned index picks (DeepSeek Sparse Attention).

Every layer is the same block, h = x + Attn(N1(x)), y = h + Experts(N2(h)),
N an RMS norm. Attn is grouped-query attention (per-head RMS norms on q and
k, rotary positions in `mrope_section` sections from three position streams)
over the set `F.sparse_attention_index` gives each query: the `index_topk`
causal keys with the largest index score I[t, s] = sum_j w[t, j] relu(qI[t,
j] . kI[s]), every causal key for the first `index_topk` positions. The
indexer (`KeyeVL2Indexer`: `index_heads` query heads of `index_head_dim`
against one key head, a LayerNorm on the key, rotary positions over the whole
head, a weight a head scaled by heads^-1/2 d^-1/2) reads the block's normed
input with its gradient stopped and learns from its own loss alone,
`F.sparse_attention_index_loss`: KL from the main heads' mean probabilities
over the set to the softmax of I over the set. Experts is a
`DroplessMoELayer` with softmax scores renormalised over the picked, no
shared expert. No bias anywhere; a last RMS norm; an untied head. The vision
tower is not part of this model: ids are text, and `position_ids` default
to one stream three times.

The training loss is the language-model loss plus the sum of the layers'
index losses; every leaf but the indexer's takes its gradient from the
first, the indexer's from the second (the stops are inside the ops and the
indexer, not in the caller's step).

A model may hold a share of a deployment: `held_experts` are the experts of
each layer that live here (the router still scores all `num_experts`),
`vocab_size` the held slice of the vocabulary, `num_layers` the layers held
from published layer `first_layer` on (the layers are identical, so
`first_layer` only names them). With `absent_experts="stand_in"` a held
expert computes each expert that is not held (`DroplessMoELayer`), so the
expert layer's rows are tokens x `num_experts_per_tok` whatever the router
learns: a rank's load in a deployment, with the weights this rank has.
"""
from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp

from ... import nn
from ...core.dispatch import apply
from ...core.tensor import Tensor
from ...incubate.moe import DroplessMoELayer
from ...nn import functional as F
from ...nn import initializer as I
from ...profiler import metrics as _metrics
from ...tensor import manipulation as M

__all__ = ["KeyeVL2Config", "KeyeVL2Model", "KeyeVL2ForCausalLM"]

INITIALIZER_RANGE = 0.02


class KeyeVL2Config:
    def __init__(self, vocab_size=151936, hidden_size=2048, num_layers=48,
                 first_layer=0, num_attention_heads=32, num_key_value_heads=4,
                 head_dim=128, moe_intermediate_size=768, num_experts=128,
                 num_experts_per_tok=8, held_experts=None, rope_theta=1e7,
                 mrope_section=(16, 24, 24), index_heads=16, index_head_dim=64,
                 index_topk=2048, norm_eps=1e-6, recompute=False,
                 absent_experts="drop"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.first_layer = first_layer            # published index of layer 0 here
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts            # the count the router scores
        self.num_experts_per_tok = num_experts_per_tok
        self.held_experts = held_experts          # ids held here; None: all
        # "drop": an expert not held adds nothing; "stand_in": a held one
        # computes it (DroplessMoELayer's `absent`)
        self.absent_experts = absent_experts
        self.rope_theta = rope_theta
        self.mrope_section = tuple(mrope_section)
        if sum(self.mrope_section) * 2 != head_dim:
            raise ValueError(f"mrope_section {self.mrope_section} does not "
                             f"cover a head of {head_dim}")
        self.index_heads = index_heads
        self.index_head_dim = index_head_dim
        self.index_topk = index_topk
        self.norm_eps = norm_eps
        # rematerialise each block in the backward pass (fleet.utils.recompute)
        self.recompute = recompute

    @property
    def index_sections(self):
        """The indexer's head is `index_head_dim` wide: its frequency pairs
        are split over the streams in the main head's proportions."""
        pairs, whole = self.index_head_dim // 2, self.head_dim // 2
        parts = [n * pairs // whole for n in self.mrope_section]
        parts[-1] += pairs - sum(parts)
        return tuple(parts)


# every live indexer, for the registry's pull-style readings below
_INDEXERS = weakref.WeakSet()


def _indexer_totals(name):
    return sum(float(getattr(layer, name)._val) for layer in _INDEXERS)


# kept on the device and fetched only when the registry is asked, as the
# expert layer's counters are (incubate/moe.py)
_COUNTERS = {"dsa.selected_pairs_total": "pairs_total",
             "dsa.tiles_skipped_total": "tiles_skipped_total",
             "dsa.queries_total": "queries_total",
             "dsa.calls_total": "calls_total"}
for _name, _buffer in _COUNTERS.items():
    _metrics.get_registry().register_counter_fn(
        _name, lambda _buffer=_buffer: _indexer_totals(_buffer))


class KeyeVL2Indexer(nn.Layer):
    """(q_index (b, s, heads, d), k_index (b, s, d), weights (b, s, heads))
    from the block's normed input, whose gradient stops here: the indexer's
    leaves learn from the index loss alone."""

    def __init__(self, cfg):
        super().__init__()
        h, self.heads, self.dim = cfg.hidden_size, cfg.index_heads, cfg.index_head_dim
        w = I.Normal(0.0, INITIALIZER_RANGE)
        self.q_proj = nn.Linear(h, self.heads * self.dim, weight_attr=w, bias_attr=False)
        self.k_proj = nn.Linear(h, self.dim, weight_attr=w, bias_attr=False)
        self.weights_proj = nn.Linear(h, self.heads, weight_attr=w, bias_attr=False)
        self.k_norm = nn.LayerNorm(self.dim, epsilon=cfg.norm_eps)
        self.rope_theta, self.sections = cfg.rope_theta, cfg.index_sections
        for name in _COUNTERS.values():
            self.register_buffer(name, Tensor(jnp.zeros((), jnp.float32)),
                                 persistable=False)
        _INDEXERS.add(self)

    def forward(self, x, position_ids=None):
        b, s, _ = x.shape
        scale = self.heads ** -0.5 * self.dim ** -0.5

        def project(v, wq, wk, ww):
            v = jax.lax.stop_gradient(v)
            return (jnp.matmul(v, wq), jnp.matmul(v, wk),
                    (jnp.matmul(v, ww, preferred_element_type=jnp.float32)
                     * scale).astype(v.dtype))
        q, k, weights = apply(project, x, self.q_proj.weight, self.k_proj.weight,
                              self.weights_proj.weight, name="dsa_index")
        q = M.reshape(q, [b, s, self.heads, self.dim])
        k = M.reshape(self.k_norm(k), [b, s, 1, self.dim])
        q, k = F.rotary_position_embedding(
            q, k, theta=self.rope_theta, position_ids=position_ids,
            sections=None if position_ids is None else self.sections)
        return q, M.reshape(k, [b, s, self.dim]), weights

    def record(self, stats):
        """Add one call's (pairs selected, tiles skipped, queries) and the
        call itself to the device counters; called outside any rematerialised
        region, where state writes are dropped."""
        from ...core import autograd
        totals = [getattr(self, name) for name in _COUNTERS.values()]
        with autograd.no_grad():
            new = apply(lambda p, t, q, c, st: (p + st[0], t + st[1], q + st[2], c + 1.0),
                        *totals, stats, name="dsa_index")
        for t, v in zip(totals, new):
            t._value = v._val


class KeyeVL2Attention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.rope_theta, self.sections = cfg.rope_theta, cfg.mrope_section
        self.topk = cfg.index_topk
        w = I.Normal(0.0, INITIALIZER_RANGE)
        q, kv = self.num_heads * self.head_dim, self.num_kv_heads * self.head_dim
        self.q_proj = nn.Linear(h, q, weight_attr=w, bias_attr=False)
        self.k_proj = nn.Linear(h, kv, weight_attr=w, bias_attr=False)
        self.v_proj = nn.Linear(h, kv, weight_attr=w, bias_attr=False)
        self.o_proj = nn.Linear(q, h, weight_attr=w, bias_attr=False)
        self.q_norm = nn.RMSNorm(self.head_dim, cfg.norm_eps)
        self.k_norm = nn.RMSNorm(self.head_dim, cfg.norm_eps)
        self.indexer = KeyeVL2Indexer(cfg)

    def forward(self, x, position_ids=None):
        """(out, the layer's index loss, the index's stats). A block runs the
        three parts itself, the core between its rematerialised regions."""
        out, index_loss, stats = self.core(*self.operands(x, position_ids))
        return self.project(out), index_loss, stats

    def operands(self, x, position_ids=None):
        """(q, k, v, q_index, k_index, weights) from the block's normed
        input: what the sparse-attention core reads."""
        b, s, _ = x.shape
        q = M.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = M.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = M.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        q, k = F.rotary_position_embedding(
            self.q_norm(q), self.k_norm(k), theta=self.rope_theta,
            position_ids=position_ids,
            sections=None if position_ids is None else self.sections)
        return (q, k, v, *self.indexer(x, position_ids))

    def core(self, q, k, v, q_index, k_index, weights):
        """(the heads' outputs (b, s, heads, d), the layer's index loss, the
        index's stats): the index's sets, the attention over them and the
        index loss, which a rematerialised block keeps on the tape
        (`KeyeVL2Block.forward`)."""
        key_set, stats = F.sparse_attention_index(q_index, k_index, weights,
                                                  self.topk)
        out, lse = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, training=self.training, key_set=key_set,
            return_lse=True)
        index_loss = F.sparse_attention_index_loss(q_index, k_index, weights,
                                                   key_set, q, k, lse=lse)
        return out, index_loss, stats

    def project(self, out):
        b, s = out.shape[:2]
        return self.o_proj(M.reshape(out, [b, s, self.num_heads * self.head_dim]))


class KeyeVL2Block(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        h = cfg.hidden_size
        self.input_layernorm = nn.RMSNorm(h, cfg.norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(h, cfg.norm_eps)
        self.self_attn = KeyeVL2Attention(cfg)
        self.mlp = DroplessMoELayer(
            h, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, held_experts=cfg.held_experts,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE), score="softmax",
            absent=cfg.absent_experts)

    def forward(self, x, position_ids=None, rematerialise=False):
        """(y, the expert layer's load, the index loss, the index's stats):
        the model adds the last three up outside any rematerialised region.

        With `rematerialise` the block is two regions of
        `fleet.utils.recompute` round the sparse-attention core, and the core
        runs once, on the tape: its rerun would be the flash forward, the
        sets kernel and a walk of every pair for the loss alone, for results
        (the heads' outputs, the logsumexp, the sets) of a quarter of a
        gigabyte a layer at 8192 positions (docs/kernels.md)."""
        if rematerialise:
            from ...distributed.fleet.utils import recompute as region
        else:
            def region(function, *args):
                return function(*args)
        # the positions ride in the closure: state the region reads
        operands = region(lambda v: self.self_attn.operands(
            self.input_layernorm(v), position_ids), x)
        out, index_loss, stats = self.self_attn.core(*operands)
        y, load = region(self._after_core, x, out)
        return y, load, index_loss, stats

    def _after_core(self, x, out):
        x = x + self.self_attn.project(out)
        out, load = self.mlp(self.post_attention_layernorm(x))
        return x + out, load


class KeyeVL2Model(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        cfg = config or KeyeVL2Config(**kwargs)
        self.config = cfg
        self.embed_tokens = nn.Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE))
        self.layers = nn.LayerList([KeyeVL2Block(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps)

    def forward(self, input_ids, position_ids=None):
        """(hidden states, the sum of the layers' index losses). `position_ids`
        (3, batch, seq): the temporal, height and width streams; None is
        0, 1, 2, ... in all three."""
        x = self.embed_tokens(input_ids)
        remat = self.config.recompute and self.training
        index_loss = None
        for block in self.layers:
            x, load, layer_loss, stats = block(x, position_ids, remat)
            block.mlp.record_load(load)
            block.self_attn.indexer.record(stats)
            index_loss = layer_loss if index_loss is None else index_loss + layer_loss
        return self.norm(x), index_loss


class KeyeVL2ForCausalLM(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        self.model = KeyeVL2Model(config, **kwargs)
        self.config = self.model.config
        self.lm_head = nn.Linear(
            self.config.hidden_size, self.config.vocab_size,
            weight_attr=I.Normal(0.0, INITIALIZER_RANGE), bias_attr=False)

    def forward(self, input_ids, labels=None, position_ids=None):
        """Without labels (logits, index loss); with them (loss, language-model
        loss, index loss), loss their sum: what a training step
        differentiates."""
        h, index_loss = self.model(input_ids, position_ids)
        logits = self.lm_head(h)
        if labels is None:
            return logits, index_loss
        lm_loss = F.cross_entropy(
            M.reshape(logits, [-1, self.config.vocab_size]),
            M.reshape(labels, [-1]))
        return lm_loss.astype("float32") + index_loss, lm_loss, index_loss
