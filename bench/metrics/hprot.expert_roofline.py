"""Share of the roofline of the MoE grouped matmuls, in percent: the
least time the held assignments' forward and backward expert work needs
(``work_moe.expert_train_flops`` and ``expert_train_bytes`` per step,
``work.least_time`` on the device's peaks) over their device time per
step. Remat's recomputed forward is in the time, not in the work."""
import work
import work_moe


def read(ctx):
    secs = sum(t for name, (t, _) in ctx.trace["ops"].items()
               if work_moe.is_expert_op(name))
    n, a = ctx.counts.get("steps"), ctx.counts.get("moe_assignments_held")
    if not (secs > 0 and n and a):
        return None
    cfg = ctx.counts["config"]
    least = work.least_time(work_moe.expert_train_flops(cfg, a),
                            work_moe.expert_train_bytes(cfg, a),
                            ctx.peaks)[0]
    return 100.0 * least / (secs / n)
