"""GPT family: the program's model and optimizer, the reference's names for
its leaves, the learnable data stream, and the work a token requires."""
import numpy as np

from benchmarks import flops
from benchmarks.reference import gpt as reference  # noqa: F401  (read by run.py)

SUB_VOCAB = 512   # the stream's tokens: x[t+1] = perm[x[t]] over these


def program_names(cfg):
    """{reference leaf: key in the program's state_dict}."""
    names = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
             "lnf_g": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}
    for i in range(cfg["num_layers"]):
        for ref, prog in (("ln1_g", "ln1.weight"), ("ln1_b", "ln1.bias"),
                          ("qkv_w", "attn.qkv.weight"), ("qkv_b", "attn.qkv.bias"),
                          ("proj_w", "attn.out_proj.weight"),
                          ("proj_b", "attn.out_proj.bias"),
                          ("ln2_g", "ln2.weight"), ("ln2_b", "ln2.bias"),
                          ("fc1_w", "mlp.fc1.weight"), ("fc1_b", "mlp.fc1.bias"),
                          ("fc2_w", "mlp.fc2.weight"), ("fc2_b", "mlp.fc2.bias")):
            names[f"h{i}.{ref}"] = f"gpt.h.{i}.{prog}"
    return names


def build_model(cfg, tensor_parallel=False):
    from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        intermediate_size=cfg["intermediate_size"], dropout=cfg["dropout"],
        tensor_parallel=tensor_parallel))


def loss_of(model, x, y):
    """The training loss as a user's step writes it."""
    return model(x, labels=y)


class Stream:
    """bench.py's permutation stream with one repair: a row starts at a
    seeded token and follows a seeded permutation of a 512-token
    sub-vocabulary, so next-token cross-entropy has structure to learn while
    softmax and embedding keep the whole vocabulary. bench.py draws any
    permutation, and a row that starts in a short cycle repeats a few
    tokens hundreds of times (seed 11: one token 1024 times), which changes
    the work with the seed. Here the permutation is one cycle of all 512
    (Sattolo's algorithm), so every row of every seed holds each token
    twice, from another starting point."""

    def __init__(self, cfg, job, seed):
        self.batch, self.seq = job["batch"], job["seq"]
        self.rng = np.random.default_rng(seed)
        perm = np.arange(SUB_VOCAB)
        for i in range(SUB_VOCAB - 1, 0, -1):
            j = int(self.rng.integers(0, i))
            perm[i], perm[j] = perm[j], perm[i]
        # orbit[t, v] = perm applied t times to v
        self.orbit = np.empty((self.seq + 1, SUB_VOCAB), np.int32)
        self.orbit[0] = np.arange(SUB_VOCAB)
        for t in range(self.seq):
            self.orbit[t + 1] = perm[self.orbit[t]]

    def next(self):
        ids = self.orbit[:, self.rng.integers(0, SUB_VOCAB, self.batch)].T
        return np.ascontiguousarray(ids[:, :-1]), np.ascontiguousarray(ids[:, 1:])


def tokens_per_step(job):
    return job["batch"] * job["seq"]


def matmul_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = [(h, 3 * h), (h, h), (h, f), (f, h)]
    # the tied output head is the one matmul the token embedding does
    return per_layer * cfg["num_layers"] + [(h, cfg["vocab_size"])], []


def flops_per_token(cfg, job):
    per_token, per_sequence = matmul_shapes(cfg)
    return flops.train_flops_per_token(
        per_token, per_sequence, job["seq"], cfg["num_layers"],
        cfg["hidden_size"], causal=True)
