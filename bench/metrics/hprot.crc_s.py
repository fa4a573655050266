"""Wall seconds of the save's CRC32 stamps: the union of the
``ckpt.crc`` spans inside the gather's ``ckpt.stage`` spans."""
from ref import intervals


def read(ctx):
    spans = ctx.span_intervals("ckpt.crc")
    return intervals.union(spans) if spans else None
