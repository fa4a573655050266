"""Device seconds a train step spends in the MoE grouped matmuls
(megablox ``gmm`` and ``tgmm`` calls, forward, recomputation and
backward), from the profiler trace, per window step."""
import work_moe


def read(ctx):
    secs = sum(t for name, (t, _) in ctx.trace["ops"].items()
               if work_moe.is_expert_op(name))
    n = ctx.counts.get("steps")
    return secs / n if secs > 0 and n else None
