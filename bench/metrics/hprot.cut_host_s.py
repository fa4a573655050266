"""Wall seconds of the save's cut spent pulling the leaves that found no
room on the device to the host, inside the stall: the union of the
``ckpt.cut.host`` spans."""
from ref import intervals


def read(ctx):
    spans = ctx.span_intervals("ckpt.cut.host")
    return intervals.union(spans) if spans else None
