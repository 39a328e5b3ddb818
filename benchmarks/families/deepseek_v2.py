"""DeepSeek-V2 family (DeepSeek-V2-Lite's configuration): the program's
model, the reference's names for its leaves, the learnable data stream over
the held slice of the vocabulary, and the work a token requires of this
chip's share."""
import importlib.util

from benchmarks import flops
from benchmarks.families import gpt
from benchmarks.reference import deepseek_v2 as reference  # noqa: F401  (read by run.py)

# a checkout from before the model (the parent of the PR that added it, with
# these benchmark files laid over it) stops here, at once and before the
# reference's minutes on the chip
if importlib.util.find_spec("paddle_tpu.text.models.deepseek_v2") is None:
    raise SystemExit("benchmarks/families/deepseek_v2.py: this checkout's paddle_tpu "
                     "has no text/models/deepseek_v2.py; nothing was run")

layer_kinds = reference.layer_kinds
# cell 1's construction: rows follow a seeded one-cycle permutation of a
# 512-token sub-vocabulary, which lies inside the held slice (ids 0-511)
Stream = gpt.Stream
tokens_per_step = gpt.tokens_per_step


def program_names(cfg):
    """{reference leaf: key in the program's state_dict}."""
    names = {"wte": "model.embed_tokens.weight", "norm_g": "model.norm.weight",
             "head_w": "lm_head.weight"}
    mixer = (("op_norm_g", "input_layernorm.weight"),
             ("ff_norm_g", "post_attention_layernorm.weight"),
             ("q_w", "self_attn.q_proj.weight"), ("kv_a_w", "self_attn.kv_a_proj.weight"),
             ("kv_a_norm_g", "self_attn.kv_a_norm.weight"),
             ("kv_b_w", "self_attn.kv_b_proj.weight"), ("o_w", "self_attn.o_proj.weight"))
    per_kind = {
        "dense": (("w1", "mlp.w1.weight"), ("w3", "mlp.w3.weight"), ("w2", "mlp.w2.weight")),
        "experts": (("gate_w", "mlp.gate.weight"), ("expert_bias", "mlp.expert_bias"),
                    ("e_w1", "mlp.w1"), ("e_w3", "mlp.w3"), ("e_w2", "mlp.w2"),
                    ("s_w1", "mlp.shared.w1.weight"), ("s_w3", "mlp.shared.w3.weight"),
                    ("s_w2", "mlp.shared.w2.weight")),
    }
    for i, ff in enumerate(layer_kinds(cfg)):
        for ref, prog in mixer + per_kind[ff]:
            names[f"l{i}.{ref}"] = f"model.layers.{i}.{prog}"
    return names


def build_model(cfg, tensor_parallel=False):
    from paddle_tpu.text.models.deepseek_v2 import (DeepseekV2Config,
                                                    DeepseekV2ForCausalLM)
    if tensor_parallel:
        raise NotImplementedError("the DeepSeek-V2 model has no tensor-parallel layers")
    return DeepseekV2ForCausalLM(DeepseekV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], first_layer=cfg["first_layer"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), rope_scaling=cfg["rope_scaling"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["n_routed_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        held_experts=cfg["held_experts"],
        absent_experts=cfg.get("absent_experts", "drop"),
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        aux_loss_alpha=cfg["aux_loss_alpha"], norm_eps=cfg["rms_norm_eps"],
        recompute=cfg["recompute"]))


def loss_of(model, x, y):
    """The training loss as a user's step writes it: the language-model loss
    and the balance losses added, the first of what the model returns."""
    return model(x, labels=y)[0]


def matmul_shapes(cfg):
    """(in, out) of every weight matrix a token is multiplied by on this
    chip. A routed expert's three matrices count by the picks of a token
    that are computed here: every one of its `num_experts_per_tok` where a
    held expert stands in for each absent one (`absent_experts`), else the
    share expected under uniform routing, experts per token x held /
    published. The shared experts (one SwiGLU of their summed width) and the
    head count once, the embedding's gather not at all."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    latent = cfg["kv_lora_rank"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    share = cfg["num_experts_per_tok"]
    if cfg.get("absent_experts", "drop") == "drop":
        share *= len(cfg["held_experts"]) / cfg["n_routed_experts_published"]
    shapes = [(h, cfg["vocab_size"])]
    for ff in layer_kinds(cfg):
        shapes += [(h, heads * (nope + rope)), (h, latent + rope),
                   (latent, heads * (nope + dv)), (heads * dv, h)]
        shapes += [(h, f)] * 2 + [(f, h)] if ff == "dense" else \
            [(h, cfg["n_routed_experts_published"]),
             (h, fs), (h, fs), (fs, h),
             (share * h, fe), (share * h, fe), (share * fe, h)]
    return shapes, []


def flops_per_token(cfg, job):
    """benchmarks/flops.py: 6 x the matmul weights, and every layer's latent
    attention as causal attention over a width of heads x (d_qk + d_v) / 2
    (the scores at 192, the values at 128). The rotations, the router's
    softmax and the balance loss are no matrix products. Recomputation is
    not counted."""
    per_token, per_sequence = matmul_shapes(cfg)
    attention_width = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]) / 2
    return flops.train_flops_per_token(
        per_token, per_sequence, job["seq"], cfg["num_layers"], attention_width,
        causal=True)
