"""Kimi Linear family: the program's model, the reference's names for its
leaves, the learnable data stream over the held slice of the vocabulary, and
the work a token requires of this chip's share."""
import importlib.util

from benchmarks import flops
from benchmarks.families import gpt
from benchmarks.reference import kimi_linear as reference  # noqa: F401  (read by run.py)

# a checkout from before the model (the parent of the PR that added it, with
# these benchmark files laid over it) stops here, at once and before the
# reference's minutes on the chip
if importlib.util.find_spec("paddle_tpu.text.models.kimi_linear") is None:
    raise SystemExit("benchmarks/families/kimi_linear.py: this checkout's paddle_tpu "
                     "has no text/models/kimi_linear.py; nothing was run")

layer_kinds = reference.layer_kinds
# cell 1's construction: rows follow a seeded one-cycle permutation of a
# 512-token sub-vocabulary, which lies inside the held slice (ids 0-511)
Stream = gpt.Stream
tokens_per_step = gpt.tokens_per_step

# FLOPs a head a token of the KDA state's forward work: k^T S, the rank-one
# update and the read-out, 2 * d_k * d_v each (the decay is elementwise)
KDA_STATE_PRODUCTS = 3


def program_names(cfg):
    """{reference leaf: key in the program's state_dict}."""
    names = {"wte": "model.embed_tokens.weight", "norm_g": "model.norm.weight",
             "head_w": "lm_head.weight"}
    per_kind = {
        "kda": tuple(
            pair for n in "qkv" for pair in ((f"{n}_w", f"self_attn.{n}_proj.weight"),
                                             (f"{n}_conv", f"self_attn.{n}_conv1d"))
        ) + (("f_a_w", "self_attn.f_a_proj.weight"), ("f_b_w", "self_attn.f_b_proj.weight"),
             ("a_log", "self_attn.A_log"), ("dt_bias", "self_attn.dt_bias"),
             ("b_w", "self_attn.b_proj.weight"),
             ("g_a_w", "self_attn.g_a_proj.weight"), ("g_b_w", "self_attn.g_b_proj.weight"),
             ("o_norm_g", "self_attn.o_norm.weight"), ("o_w", "self_attn.o_proj.weight")),
        "full_attention": (
            ("q_w", "self_attn.q_proj.weight"), ("kv_a_w", "self_attn.kv_a_proj.weight"),
            ("kv_a_norm_g", "self_attn.kv_a_norm.weight"),
            ("kv_b_w", "self_attn.kv_b_proj.weight"), ("o_w", "self_attn.o_proj.weight")),
        "dense": (("w1", "mlp.w1.weight"), ("w3", "mlp.w3.weight"), ("w2", "mlp.w2.weight")),
        "experts": (("gate_w", "mlp.gate.weight"), ("expert_bias", "mlp.expert_bias"),
                    ("e_w1", "mlp.w1"), ("e_w3", "mlp.w3"), ("e_w2", "mlp.w2"),
                    ("s_w1", "mlp.shared.w1.weight"), ("s_w3", "mlp.shared.w3.weight"),
                    ("s_w2", "mlp.shared.w2.weight")),
    }
    for i, (op, ff) in enumerate(layer_kinds(cfg)):
        pairs = ((("op_norm_g", "input_layernorm.weight"),
                  ("ff_norm_g", "post_attention_layernorm.weight"))
                 + per_kind[op] + per_kind[ff])
        for ref, prog in pairs:
            names[f"l{i}.{ref}"] = f"model.layers.{i}.{prog}"
    return names


def build_model(cfg, tensor_parallel=False):
    from paddle_tpu.text.models.kimi_linear import (KimiLinearConfig,
                                                    KimiLinearForCausalLM)
    if tensor_parallel:
        raise NotImplementedError("the Kimi Linear model has no tensor-parallel layers")
    lin = cfg["linear_attn_config"]
    return KimiLinearForCausalLM(KimiLinearConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"],
        layer_types=[op for op, _ in layer_kinds(cfg)],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        linear_num_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        short_conv_kernel=lin["short_conv_kernel_size"],
        gate_rank=cfg["gate_rank"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"],
        num_experts_per_token=cfg["num_experts_per_token"],
        num_shared_experts=cfg["num_shared_experts"],
        held_experts=cfg["held_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_eps=cfg["rms_norm_eps"], recompute=cfg["recompute"]))


def loss_of(model, x, y):
    """The training loss as a user's step writes it."""
    return model(x, labels=y)


def matmul_shapes(cfg):
    """(in, out) of every weight matrix a token is multiplied by on this
    chip. A routed expert's three matrices count by the share of tokens
    expected to reach it under uniform routing, experts per token / published
    experts: 8 held of 256 at 8 a token weigh a quarter of an expert a token
    a layer; the shared expert and the head count once, the embedding's
    gather not at all."""
    h = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    width, rank = lin["num_heads"] * lin["head_dim"], cfg["gate_rank"]
    heads = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    latent = cfg["kv_lora_rank"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["num_shared_experts"]
    share = (len(cfg["held_experts"]) * cfg["num_experts_per_token"]
             / cfg["published"]["num_experts"])
    shapes = [(h, cfg["vocab_size"])]
    for op, ff in layer_kinds(cfg):
        if op == "kda":
            shapes += [(h, width)] * 3 + [(width, h), (h, lin["num_heads"])]
            shapes += [(h, rank), (rank, width)] * 2
        else:
            shapes += [(h, heads * qk), (h, latent + cfg["qk_rope_head_dim"]),
                       (latent, heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
                       (heads * cfg["v_head_dim"], h)]
        shapes += [(h, f)] * 2 + [(f, h)] if ff == "dense" else \
            [(h, cfg["published"]["num_experts"]),
             (h, fs), (h, fs), (fs, h),
             (share * h, fe), (share * h, fe), (share * fe, h)]
    return shapes, []


def flops_per_token(cfg, job):
    """benchmarks/flops.py with what it can express (6 x the matmul weights;
    the latent attention's causal products as attention over a width of
    heads x (d_qk + d_v) / 2) and, added here, the KDA state's required work:
    KDA_STATE_PRODUCTS x 2 x d_k x d_v a head a token forward, three times
    that in training. Recomputation is not counted."""
    per_token, per_sequence = matmul_shapes(cfg)
    kinds = [op for op, _ in layer_kinds(cfg)]
    heads = cfg["num_attention_heads"]
    attention_width = heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                               + cfg["v_head_dim"]) / 2
    lin = cfg["linear_attn_config"]
    state = 3.0 * KDA_STATE_PRODUCTS * 2 * lin["head_dim"] ** 2 * lin["num_heads"]
    return flops.train_flops_per_token(
        per_token, per_sequence, job["seq"], kinds.count("full_attention"),
        attention_width, causal=True) + kinds.count("kda") * state
