"""Wall seconds of the save's device-to-host pull: the union of the
``ckpt.pull`` spans inside the gather's ``ckpt.stage`` spans."""
from ref import intervals


def read(ctx):
    spans = ctx.span_intervals("ckpt.pull")
    return intervals.union(spans) if spans else None
