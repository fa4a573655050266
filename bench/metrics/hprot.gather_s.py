"""Wall seconds of the save's host gather: the union of its
``ckpt.stage`` spans (device-to-host pull, encode and CRC32 of each
tensor)."""
from ref import intervals


def read(ctx):
    spans = ctx.span_intervals("ckpt.stage")
    return intervals.union(spans) if spans else None
