"""deepseek-v2-lite [moe]: 27L d_model=2048, MLA (16 heads, kv latent 512,
no q LoRA, q/k heads 128 + 64 rotated, v heads 128), YaRN (factor 40),
layer 0 dense (SwiGLU 10944), layers 1-26 MoE: 64 routed experts of 1408,
top-6 softmax without renormalisation, 2 shared experts, sequence-wise
balance loss; vocab=102400. [hf:deepseek-ai/DeepSeek-V2-Lite]

Kept out of ``registry.ARCHS``: it trains through ``LM`` +
``train.step`` + ``AsyncCheckpointManager`` (HProt), and has no decode or
dry-run path. RoPE layout: the program rotates the 64 rotated channels of
q and k as two halves (``layers.rope``); the published model rotates
interleaved pairs after de-interleaving them, which equals the half-split
rotation up to a fixed permutation of those weight columns.
"""
from ..models.config import ModelConfig


def from_hf(hf: dict, **overrides) -> ModelConfig:
    """A ``ModelConfig`` from a DeepSeek-V2 ``config.json`` dictionary.
    A chip's share of an expert-parallel layer states the router's width
    as ``router_experts``, and ``n_routed_experts`` counts the experts held
    here, from ``expert_offset``; training knobs go in ``overrides``."""
    y = hf["rope_scaling"]
    if y.get("type") != "yarn" or hf.get("q_lora_rank") is not None:
        raise ValueError("the program runs YaRN RoPE and MLA without a q LoRA")
    if hf["scoring_func"] != "softmax" or hf["topk_method"] != "greedy" \
            or hf["moe_layer_freq"] != 1 or hf["attention_bias"]:
        raise ValueError("the program routes by a greedy softmax top-k in "
                         "every layer past the dense ones, no attention bias")
    kw = dict(
        name=hf.get("name", "deepseek-v2-lite"), family="moe",
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        mlp_act="swiglu", norm="rmsnorm",
        n_experts=hf.get("router_experts", hf["n_routed_experts"]),
        n_experts_held=hf["n_routed_experts"],
        expert_offset=hf.get("expert_offset", 0),
        top_k=hf["num_experts_per_tok"],
        moe_dispatch="dropless", moe_d_ff=hf["moe_intermediate_size"],
        n_shared_experts=hf["n_shared_experts"],
        first_k_dense=hf["first_k_dense_replace"],
        norm_topk_prob=hf["norm_topk_prob"],
        routed_scaling=float(hf["routed_scaling_factor"]),
        aux_loss="seq" if hf["seq_aux"] else "switch",
        aux_loss_alpha=float(hf.get("aux_loss_alpha", 0.001)),
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], rope_theta=float(hf["rope_theta"]),
        yarn_factor=float(y["factor"]),
        yarn_original_max_pos=y["original_max_position_embeddings"],
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]),
        yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]),
        tie_embeddings=hf["tie_word_embeddings"])
    kw.update(overrides)
    return ModelConfig(**kw)


HF = {
    "num_hidden_layers": 27, "hidden_size": 2048, "num_attention_heads": 16,
    "num_key_value_heads": 16, "intermediate_size": 10944,
    "vocab_size": 102400, "n_routed_experts": 64, "num_experts_per_tok": 6,
    "moe_intermediate_size": 1408, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "norm_topk_prob": False,
    "routed_scaling_factor": 1.0, "seq_aux": True, "aux_loss_alpha": 0.001,
    "kv_lora_rank": 512, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "scoring_func": "softmax", "topk_method": "greedy", "moe_layer_freq": 1,
    "attention_bias": False, "tie_word_embeddings": False,
}

CONFIG = from_hf(HF)

# small widths, same structure: a dense layer, then MoE layers with 8
# routed experts (top-3), one shared expert, MLA and YaRN
SMOKE_HF = {**HF, "num_hidden_layers": 3, "hidden_size": 64,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "intermediate_size": 128, "vocab_size": 256,
            "n_routed_experts": 8, "num_experts_per_tok": 3,
            "moe_intermediate_size": 32, "n_shared_experts": 1,
            "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16,
            "rope_scaling": {**HF["rope_scaling"],
                             "original_max_position_embeddings": 16}}
SMOKE = from_hf(SMOKE_HF, name="deepseek-v2-lite-smoke", remat="none",
                compute_dtype="float32")
