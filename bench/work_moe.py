"""Matmul FLOPs and bytes of a DeepSeek-V2 MoE train step.

Counts the work the algorithm needs, from the configuration and the
step's counted expert assignments, not what the program implements:

* per token, forward: MLA's four projections and its two causal score
  products over the ``(S + 1) / 2`` keys a query sees on average, the
  dense layers' SwiGLU, each MoE layer's router and shared experts, and
  the LM head; each held expert assignment adds one SwiGLU of the expert
  width;
* a train step costs three times the forward (the backward pass twice);
  the recomputation of remat is not counted.
"""
from __future__ import annotations

#: HLO names of the grouped-matmul kernels in a TPU trace, before their
#: ``.N`` suffix: megablox ``gmm`` (forward, and the backward's input
#: gradient) and ``tgmm`` (the backward's weight gradient)
EXPERT_OPS = ("gmm", "tgmm")

#: bytes of a bfloat16 operand
BF16 = 2


def is_expert_op(op_name: str) -> bool:
    base = op_name.rsplit(".", 1)[0] if op_name[-1:].isdigit() else op_name
    return base in EXPERT_OPS


def dense_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward matmul FLOPs per token, experts held out."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rd, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    proj = d * h * (nope + rd) + d * (r + rd) + r * h * (nope + vd) \
        + h * vd * d
    scores = h * (nope + rd + vd) * (seq_len + 1) / 2
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    dense_mlp = 3 * d * cfg["intermediate_size"]
    shared = 3 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    router = d * cfg["router_experts"]
    macs = cfg["num_hidden_layers"] * (proj + scores) \
        + n_dense * dense_mlp + n_moe * (shared + router) \
        + d * cfg["vocab_size"]
    return 3.0 * 2.0 * macs


def expert_train_flops(cfg: dict, assignments: float) -> float:
    """Forward + backward FLOPs of ``assignments`` held-expert token
    assignments (summed over the MoE layers): one SwiGLU each."""
    return 3.0 * 2.0 * 3 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"] * assignments


def expert_train_bytes(cfg: dict, assignments: float) -> float:
    """HBM bytes the grouped matmuls of a step need at least: each pass
    (forward, input gradient, weight gradient) reads the assigned rows and
    the held experts' weights once and writes its result once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    weights = n_moe * cfg["n_routed_experts"] * 3 * d * f
    rows = assignments * 3 * (d + f)
    return 3.0 * BF16 * (weights + rows)
