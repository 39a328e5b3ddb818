"""Laguna family (Laguna-XS.2's configuration): the program's model, the
reference's names for its leaves, the learnable data stream over the held
slice of the vocabulary, and the work a token requires of this chip's
share."""
import importlib.util

from benchmarks import flops, kernel_costs_laguna
from benchmarks.families import gpt
from benchmarks.reference import laguna as reference  # noqa: F401  (read by run.py)

# a checkout from before the model (the parent of the PR that added it, with
# these benchmark files laid over it) stops here, at once and before the
# reference's minutes on the chip
if importlib.util.find_spec("paddle_tpu.text.models.laguna") is None:
    raise SystemExit("benchmarks/families/laguna.py: this checkout's paddle_tpu "
                     "has no text/models/laguna.py; nothing was run")

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
             ("q_w", "self_attn.q_proj.weight"), ("k_w", "self_attn.k_proj.weight"),
             ("v_w", "self_attn.v_proj.weight"), ("g_w", "self_attn.g_proj.weight"),
             ("o_w", "self_attn.o_proj.weight"))
    per_kind = {
        "dense": (("w1", "mlp.w1.weight"), ("w3", "mlp.w3.weight"), ("w2", "mlp.w2.weight")),
        "sparse": (("gate_w", "mlp.gate.weight"), ("expert_bias", "mlp.expert_bias"),
                   ("e_w1", "mlp.w1"), ("e_w3", "mlp.w3"), ("e_w2", "mlp.w2"),
                   ("s_w1", "mlp.shared.w1.weight"), ("s_w3", "mlp.shared.w3.weight"),
                   ("s_w2", "mlp.shared.w2.weight")),
    }
    for i, (_, _, ff) in enumerate(layer_kinds(cfg)):
        for ref, prog in mixer + per_kind[ff]:
            names[f"l{i}.{ref}"] = f"model.layers.{i}.{prog}"
    return names


def build_model(cfg, tensor_parallel=False):
    from paddle_tpu.text.models.laguna import LagunaConfig, LagunaForCausalLM
    if tensor_parallel:
        raise NotImplementedError("the Laguna model has no tensor-parallel layers")
    return LagunaForCausalLM(LagunaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], first_layer=cfg["first_layer"],
        layer_types=cfg["layer_types"],
        num_attention_heads_per_layer=cfg["num_attention_heads_per_layer"],
        mlp_layer_types=cfg["mlp_layer_types"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"], rope_parameters=cfg["rope_parameters"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["shared_expert_intermediate_size"],
        num_experts=cfg["num_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=cfg["held_experts"],
        absent_experts=cfg.get("absent_experts", "drop"),
        moe_routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        gating=cfg["gating"], norm_eps=cfg["rms_norm_eps"],
        recompute=cfg["recompute"]))


def loss_of(model, x, y):
    """The training loss as a user's step writes it."""
    return model(x, labels=y)


def matmul_shapes(cfg):
    """(in, out) of every weight matrix a token is multiplied by on this
    chip. A routed expert's three matrices count by the picks of a token
    that are computed here: every one of its `num_experts_per_tok` where a
    held expert stands in for each absent one (`absent_experts`), else the
    share expected under uniform routing, experts per token x held /
    published. The shared expert, the heads' gate and the head count once,
    the embedding's gather not at all."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * d
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    share = cfg["num_experts_per_tok"]
    if cfg.get("absent_experts", "drop") == "drop":
        share *= len(cfg["held_experts"]) / cfg["num_experts_published"]
    shapes = [(h, cfg["vocab_size"])]
    for _, heads, ff in layer_kinds(cfg):
        shapes += [(h, heads * d), (h, kv), (h, kv), (h, heads), (heads * d, h)]
        shapes += [(h, f)] * 2 + [(f, h)] if ff == "dense" else \
            [(h, cfg["num_experts_published"]),
             (h, fs), (h, fs), (fs, h),
             (share * h, fe), (share * h, fe), (share * fe, h)]
    return shapes, []


def flops_per_token(cfg, job):
    """benchmarks/flops.py's count with this model's attention in the place
    of its causal term: 6 x the matmul weights, and every layer's two
    attention products at the pairs its mask keeps, forward and twice that
    backward (kernel_costs_laguna.attention_train_flops): the band's pairs in
    a window layer, never the triangle's, so `mfu_pct` credits no pair
    outside the band. The rotations and the sigmoids are no matrix products.
    Recomputation is not counted."""
    per_token, _ = matmul_shapes(cfg)
    return 6.0 * flops.matmul_weights(per_token) \
        + kernel_costs_laguna.attention_train_flops(cfg, job["seq"]) / job["seq"]
