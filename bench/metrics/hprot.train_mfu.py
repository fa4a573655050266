"""The whole train step's share of the chip's bf16 peak, in percent:
forward + backward matmul FLOPs per token of the configuration
(``work.lm_train_flops_per_token``: LM head in, embedding lookup and
recomputation out) times the window's tokens per second."""
import work


def read(ctx):
    rate = ctx.e2e.get("train_tokens_per_s")
    if not rate:
        return None
    flops = work.lm_train_flops_per_token(ctx.counts["config"],
                                          ctx.counts["seq_len"])
    return 100.0 * flops * rate / ctx.peaks["flops_per_s"]
