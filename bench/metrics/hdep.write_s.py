"""Wall seconds per snapshot in which the Hercule write path was busy:
the union of the ``write`` and ``manifest.commit`` spans."""
from ref import intervals


def read(ctx):
    total = intervals.union(ctx.span_intervals("write")
                            + ctx.span_intervals("manifest.commit"))
    n = ctx.counts["snapshots"]
    return total / n if total > 0 and n else None
