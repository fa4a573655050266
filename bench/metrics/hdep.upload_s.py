"""Wall seconds per snapshot of the staging upload: the union of the
``stage.upload`` spans (the device area's float64 to float32 cast, the
host-to-device copy and its ``block_until_ready``), apart from the wait
for a free ring slot (``stage.wait``)."""
from ref import intervals


def read(ctx):
    total = intervals.union(ctx.span_intervals("stage.upload"))
    n = ctx.counts["snapshots"]
    return total / n if total > 0 and n else None
