"""Keye-VL-2.0 family (the language model): the program's model, the
reference's names for its leaves, the learnable data stream over the held
slice of the vocabulary, and the work a token requires of this chip's share."""
import importlib.util

from benchmarks import flops, kernel_costs_keye
from benchmarks.families import gpt
from benchmarks.reference import keye_vl2 as reference  # noqa: F401  (read by run.py)

# a checkout from before the model (the parent of the PR that added it, with
# these benchmark files laid over it) stops here, at once and before the
# reference's minutes on the chip
if importlib.util.find_spec("paddle_tpu.text.models.keye_vl2") is None:
    raise SystemExit("benchmarks/families/keye_vl2.py: this checkout's paddle_tpu "
                     "has no text/models/keye_vl2.py; nothing was run")

# cell 1's construction: rows follow a seeded one-cycle permutation of a
# 512-token sub-vocabulary, which lies inside the held slice (ids 0-511)
Stream = gpt.Stream
tokens_per_step = gpt.tokens_per_step


def program_names(cfg):
    """{reference leaf: key in the program's state_dict}."""
    names = {"wte": "model.embed_tokens.weight", "norm_g": "model.norm.weight",
             "head_w": "lm_head.weight"}
    pairs = (("op_norm_g", "input_layernorm.weight"),
             ("ff_norm_g", "post_attention_layernorm.weight"),
             ("q_w", "self_attn.q_proj.weight"), ("k_w", "self_attn.k_proj.weight"),
             ("v_w", "self_attn.v_proj.weight"), ("o_w", "self_attn.o_proj.weight"),
             ("q_norm_g", "self_attn.q_norm.weight"),
             ("k_norm_g", "self_attn.k_norm.weight"),
             ("index_q_w", "self_attn.indexer.q_proj.weight"),
             ("index_k_w", "self_attn.indexer.k_proj.weight"),
             ("index_w_w", "self_attn.indexer.weights_proj.weight"),
             ("index_k_norm_g", "self_attn.indexer.k_norm.weight"),
             ("index_k_norm_b", "self_attn.indexer.k_norm.bias"),
             ("gate_w", "mlp.gate.weight"), ("expert_bias", "mlp.expert_bias"),
             ("e_w1", "mlp.w1"), ("e_w3", "mlp.w3"), ("e_w2", "mlp.w2"))
    for i in range(cfg["num_layers"]):
        for ref, prog in pairs:
            names[f"l{i}.{ref}"] = f"model.layers.{i}.{prog}"
    return names


def build_model(cfg, tensor_parallel=False):
    from paddle_tpu.text.models.keye_vl2 import KeyeVL2Config, KeyeVL2ForCausalLM
    if tensor_parallel:
        raise NotImplementedError("the Keye-VL-2.0 model has no tensor-parallel layers")
    sa = cfg["sa_config"]
    return KeyeVL2ForCausalLM(KeyeVL2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], first_layer=cfg["first_layer"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=cfg["held_experts"], rope_theta=float(cfg["rope_theta"]),
        mrope_section=cfg["rope_scaling"]["mrope_section"],
        index_heads=sa["indexer_num_heads"], index_head_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"], norm_eps=cfg["rms_norm_eps"],
        recompute=cfg["recompute"],
        absent_experts=cfg.get("absent_experts", "drop")))


def loss_of(model, x, y):
    """The training loss as a user's step writes it: the language-model loss
    and the index losses added, the first of what the model returns."""
    return model(x, labels=y)[0]


def matmul_shapes(cfg):
    """(in, out) of every weight matrix a token is multiplied by on this
    chip. A routed expert's three matrices count by the picks of a token
    that are computed here: every one of its `num_experts_per_tok` where a
    held expert stands in for each absent one (`absent_experts`), else the
    share expected under uniform routing, experts per token x held /
    published (16 held of 128 at 8 a token: one expert a token a layer).
    The head counts once, the embedding's gather not at all."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    sa, fe = cfg["sa_config"], cfg["moe_intermediate_size"]
    share = cfg["num_experts_per_tok"]
    if cfg.get("absent_experts", "drop") == "drop":
        share *= len(cfg["held_experts"]) / cfg["num_experts_published"]
    layer = [(h, q), (h, kv), (h, kv), (q, h),
             (h, sa["indexer_num_heads"] * sa["indexer_head_dim"]),
             (h, sa["indexer_head_dim"]), (h, sa["indexer_num_heads"]),
             (h, cfg["num_experts_published"]),
             (share * h, fe), (share * h, fe), (share * fe, h)]
    return [(h, cfg["vocab_size"])] + layer * cfg["num_layers"], []


def flops_per_token(cfg, job):
    """6 x the matmul weights (benchmarks/flops.py), and a layer's attention
    as its required work (benchmarks/kernel_costs_keye.py): the main products
    at the pairs of the set, sum over t of min(t + 1, topk), never the dense
    triangle; the index's products at every causal pair; the index loss's
    second pass over the main scores once; backward twice forward.
    Recomputation is not counted."""
    per_token, _ = matmul_shapes(cfg)
    sa = cfg["sa_config"]
    attention = kernel_costs_keye.layer_train_flops(
        job["seq"], cfg["num_attention_heads"], cfg["head_dim"],
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    return (6.0 * flops.matmul_weights(per_token)
            + cfg["num_layers"] * attention / job["seq"])
