"""BERT/ERNIE-style encoder (BASELINE config 3: ERNIE-base fine-tune).

Built on nn.TransformerEncoder (reference nn/layer/transformer.py parity) —
the same assembly PaddleNLP performs out-of-tree for ERNIE.
"""
from __future__ import annotations

from ... import nn
from ...core.tensor import Tensor
from ...nn import functional as F
from ...tensor import manipulation as M

__all__ = ["BertModel", "BertForSequenceClassification", "BertConfig"]


def _reference_init(root, std):
    """PaddleNLP BERT init scheme (transformers/bert/modeling.py
    init_weights): every Linear/Embedding weight ~ N(0, initializer_range),
    LayerNorm scales/biases untouched. The framework default (N(0,1)
    embeddings, Xavier linears — reference fluid defaults) leaves BERT-base
    unable to escape the chance plateau at fine-tune lr: measured on the
    r5 bench probe, 512 steps at lr=1e-4 sat at ln(2) without this, and the
    GPT lane needed the same fix in r4 (gpt.py INITIALIZER_RANGE note)."""
    from ...nn import initializer as I
    for layer in root.sublayers(include_self=True):
        if isinstance(layer, (nn.Linear, nn.Embedding)):
            w = layer.weight
            w.set_value(I.Normal(0.0, std)(w.shape, w.dtype))


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072, max_position=512,
                 type_vocab_size=2, dropout=0.1, initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.type_vocab_size = type_vocab_size
        self.dropout = dropout
        self.initializer_range = initializer_range

    @classmethod
    def base(cls):
        return cls()


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embeddings = nn.Embedding(cfg.max_position,
                                                cfg.hidden_size)
        self.token_type_embeddings = nn.Embedding(cfg.type_vocab_size,
                                                  cfg.hidden_size)
        self.layer_norm = nn.LayerNorm(cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        import jax.numpy as jnp
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
        if token_type_ids is None:
            token_type_ids = Tensor(jnp.zeros((b, s), dtype=jnp.int32))
        x = (self.word_embeddings(input_ids)
             + self.position_embeddings(position_ids)
             + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(x))


class BertModel(nn.Layer):
    def __init__(self, config=None, **kwargs):
        super().__init__()
        cfg = config or BertConfig(**kwargs)
        self.config = cfg
        self.embeddings = BertEmbeddings(cfg)
        enc_layer = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.dropout, activation="gelu")
        self.encoder = nn.TransformerEncoder(enc_layer, cfg.num_layers)
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        _reference_init(self, cfg.initializer_range)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, token_type_ids)
        if attention_mask is not None:
            # (b, s) 1/0 mask -> additive (b, 1, 1, s)
            import jax.numpy as jnp
            from ...core.dispatch import unwrap
            m = unwrap(attention_mask)
            add = jnp.where(m[:, None, None, :] > 0, 0.0, -1e30)
            attention_mask = Tensor(add.astype("float32"))
        seq = self.encoder(x, attention_mask)
        pooled = F.tanh(self.pooler(seq[:, 0]))
        return seq, pooled


class BertForSequenceClassification(nn.Layer):
    def __init__(self, config=None, num_classes=2, **kwargs):
        super().__init__()
        self.bert = BertModel(config, **kwargs)
        cfg = self.bert.config
        self.dropout = nn.Dropout(cfg.dropout)
        self.classifier = nn.Linear(cfg.hidden_size, num_classes)
        _reference_init(self.classifier, cfg.initializer_range)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                labels=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        logits = self.classifier(self.dropout(pooled))
        if labels is not None:
            # no upcast here: F.cross_entropy accumulates in float32 and
            # returns a float32 loss whatever the logits' dtype (a bf16 loss
            # quantizes in ~0.004 steps, too coarse for loss-curve evidence),
            # and keeps no float32 copy of the logits for its backward
            return F.cross_entropy(logits, labels)
        return logits
