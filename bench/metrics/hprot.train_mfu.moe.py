"""The whole MoE train step's share of the chip's bf16 peak, in percent:
the matmul FLOPs per token of the configuration
(``work_moe.dense_train_flops_per_token``) plus the window's counted
held-expert assignments per step (``work_moe.expert_train_flops``) over
the step's tokens, times the window's tokens per second."""
import work_moe


def read(ctx):
    rate = ctx.e2e.get("train_tokens_per_s")
    a = ctx.counts.get("moe_assignments_held")
    if not rate or a is None:
        return None
    cfg, seq = ctx.counts["config"], ctx.counts["seq_len"]
    tokens = seq * ctx.counts["global_batch"]
    flops = work_moe.dense_train_flops_per_token(cfg, seq) \
        + work_moe.expert_train_flops(cfg, a) / tokens
    return 100.0 * flops * rate / ctx.peaks["flops_per_s"]
