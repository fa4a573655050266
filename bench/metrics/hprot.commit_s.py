"""Seconds of the save's commit: the ``ckpt.commit`` span (fsync of the
referenced files, then the manifest's atomic replace)."""


def read(ctx):
    spans = ctx.span_intervals("ckpt.commit")
    return sum(b - a for a, b in spans) if spans else None
