"""LFM2 mixture-of-experts family: the program's model, the reference's names
for its leaves, the learnable data stream over the held slice of the
vocabulary, and the work a token requires of this chip's share."""
import importlib.util

from benchmarks import flops
from benchmarks.families import gpt
from benchmarks.reference import lfm2_moe as reference  # noqa: F401  (read by run.py)

# a checkout from before the model (the parent of the PR that added it, with
# these benchmark files laid over it) stops here, at once and before the
# reference's minutes on the chip
if importlib.util.find_spec("paddle_tpu.text.models.lfm2") is None:
    raise SystemExit("benchmarks/families/lfm2_moe.py: this checkout's paddle_tpu has "
                     "no text/models/lfm2.py; nothing was run")

layer_kinds = reference.layer_kinds
# cell 1's construction: rows follow a seeded one-cycle permutation of a
# 512-token sub-vocabulary, which lies inside the held slice (ids 0-511)
Stream = gpt.Stream
tokens_per_step = gpt.tokens_per_step


def program_names(cfg):
    """{reference leaf: key in the program's state_dict}."""
    names = {"wte": "model.embed_tokens.weight",
             "norm_g": "model.embedding_norm.weight"}
    per_kind = {
        "conv": (("conv_in_w", "conv.in_proj.weight"), ("conv_k", "conv.weight"),
                 ("conv_out_w", "conv.out_proj.weight")),
        "full_attention": (
            ("q_w", "self_attn.q_proj.weight"), ("k_w", "self_attn.k_proj.weight"),
            ("v_w", "self_attn.v_proj.weight"), ("o_w", "self_attn.out_proj.weight"),
            ("q_norm_g", "self_attn.q_norm.weight"),
            ("k_norm_g", "self_attn.k_norm.weight")),
        "dense": (("w1", "feed_forward.w1.weight"), ("w3", "feed_forward.w3.weight"),
                  ("w2", "feed_forward.w2.weight")),
        "experts": (("gate_w", "feed_forward.gate.weight"),
                    ("expert_bias", "feed_forward.expert_bias"),
                    ("e_w1", "feed_forward.w1"), ("e_w3", "feed_forward.w3"),
                    ("e_w2", "feed_forward.w2")),
    }
    for i, (op, ff) in enumerate(layer_kinds(cfg)):
        pairs = ((("op_norm_g", "operator_norm.weight"),
                  ("ff_norm_g", "ffn_norm.weight")) + per_kind[op] + per_kind[ff])
        for ref, prog in pairs:
            names[f"l{i}.{ref}"] = f"model.layers.{i}.{prog}"
    return names


def build_model(cfg, tensor_parallel=False):
    from paddle_tpu.text.models.lfm2 import LFM2Config, LFM2ForCausalLM
    if tensor_parallel:
        raise NotImplementedError("the LFM2 model has no tensor-parallel layers")
    return LFM2ForCausalLM(LFM2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"],
        layer_types=[op for op, _ in layer_kinds(cfg)],
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=cfg["held_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        conv_kernel=cfg["conv_L_cache"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm_eps=cfg["norm_eps"], recompute=cfg["recompute"]))


def loss_of(model, x, y):
    """The training loss as a user's step writes it."""
    return model(x, labels=y)


def matmul_shapes(cfg):
    """(in, out) of every weight matrix a token is multiplied by on this
    chip. An expert's three matrices count by the share of tokens expected
    to reach it under uniform routing, experts_per_tok / published experts:
    fractional rows, so that 8 held experts of 64 at 4 a token weigh half an
    expert a token a layer (the counters say how far a run was from it)."""
    h = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    share = (len(cfg["held_experts"]) * cfg["num_experts_per_tok"]
             / cfg["published"]["num_experts"])
    shapes = [(h, cfg["vocab_size"])]           # the tied head, once
    for op, ff in layer_kinds(cfg):
        shapes += [(h, 3 * h), (h, h)] if op == "conv" else \
            [(h, h), (h, kv), (h, kv), (h, h)]
        shapes += [(h, f)] * 2 + [(f, h)] if ff == "dense" else \
            [(h, cfg["published"]["num_experts"]),
             (share * h, fe), (share * h, fe), (share * fe, h)]
    return shapes, []


def flops_per_token(cfg, job):
    per_token, per_sequence = matmul_shapes(cfg)
    attention_layers = sum(op == "full_attention" for op, _ in layer_kinds(cfg))
    return flops.train_flops_per_token(
        per_token, per_sequence, job["seq"], attention_layers,
        cfg["hidden_size"], causal=True)
