"""Host seconds of the device runner per snapshot: each ``reduce`` span
less the ``device.transfer`` spans inside it (where the kernels run and
their outputs are pulled)."""
from ref import intervals


def read(ctx):
    transfers = ctx.span_intervals("device.transfer")
    total = sum(intervals.self_time(sp, transfers)
                for sp in ctx.span_intervals("reduce"))
    n = ctx.counts["snapshots"]
    return total / n if total > 0 and n else None
