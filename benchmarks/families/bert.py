"""BERT family: the program's model, the reference's names for its leaves,
the seeded parity-label stream, and the work a token requires."""
import numpy as np

from benchmarks import flops
from benchmarks.reference import bert as reference  # noqa: F401  (read by run.py)


def program_names(cfg):
    emb = "bert.embeddings."
    names = {"word": emb + "word_embeddings.weight",
             "pos": emb + "position_embeddings.weight",
             "type": emb + "token_type_embeddings.weight",
             "emb_ln_g": emb + "layer_norm.weight",
             "emb_ln_b": emb + "layer_norm.bias",
             "pool_w": "bert.pooler.weight", "pool_b": "bert.pooler.bias",
             "cls_w": "classifier.weight", "cls_b": "classifier.bias"}
    for i in range(cfg["num_layers"]):
        layer = f"bert.encoder.layers.{i}."
        for ref, prog in (("q", "self_attn.q_proj"), ("k", "self_attn.k_proj"),
                          ("v", "self_attn.v_proj"), ("o", "self_attn.out_proj"),
                          ("fc1", "linear1"), ("fc2", "linear2")):
            names[f"l{i}.{ref}_w"] = layer + prog + ".weight"
            names[f"l{i}.{ref}_b"] = layer + prog + ".bias"
        for ref, prog in (("ln1", "norm1"), ("ln2", "norm2")):
            names[f"l{i}.{ref}_g"] = layer + prog + ".weight"
            names[f"l{i}.{ref}_b"] = layer + prog + ".bias"
    return names


def build_model(cfg, tensor_parallel=False):
    from paddle_tpu.text.models.bert import (BertConfig,
                                             BertForSequenceClassification)
    if tensor_parallel:
        raise ValueError("the program's BERT has no tensor-parallel layers")
    bc = BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"], dropout=cfg["dropout"],
        initializer_range=cfg["initializer_range"])
    return BertForSequenceClassification(bc, num_classes=cfg["num_labels"])


def loss_of(model, x, y):
    return model(x, labels=y)


class Stream:
    """bench.py's parity-label stream: tokens uniform over the vocabulary,
    a uniform binary label, and positions 0..7 carrying 2*r + label for a
    uniform r in 0..7, so the label is linearly readable from eight token
    embeddings and the loss can fall only if the optimizer learns them.
    Same shapes whatever the seed."""

    def __init__(self, cfg, job, seed):
        self.batch, self.seq = job["batch"], job["seq"]
        self.vocab = cfg["vocab_size"]
        self.rng = np.random.default_rng(seed)

    def next(self):
        ids = self.rng.integers(0, self.vocab, (self.batch, self.seq))
        labels = self.rng.integers(0, 2, self.batch)
        ids[:, :8] = 2 * self.rng.integers(0, 8, (self.batch, 8)) + labels[:, None]
        return ids.astype(np.int64), labels.astype(np.int64)


def tokens_per_step(job):
    return job["batch"] * job["seq"]


def matmul_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = [(h, h)] * 4 + [(h, f), (f, h)]
    # pooler and classifier see one position of each sequence
    return per_layer * cfg["num_layers"], [(h, h), (h, cfg["num_labels"])]


def flops_per_token(cfg, job):
    per_token, per_sequence = matmul_shapes(cfg)
    return flops.train_flops_per_token(
        per_token, per_sequence, job["seq"], cfg["num_layers"],
        cfg["hidden_size"], causal=False)
