"""Wall seconds in which at least one writer lane was writing the save's
shards: the union of the ``ckpt.write`` spans across lanes."""
from ref import intervals


def read(ctx):
    spans = ctx.span_intervals("ckpt.write")
    return intervals.union(spans) if spans else None
