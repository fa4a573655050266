"""Seconds the train thread stalled in the save: the ``ckpt.snapshot``
span (the device-side copy of the whole state)."""


def read(ctx):
    spans = ctx.span_intervals("ckpt.snapshot")
    return sum(b - a for a, b in spans) if spans else None
