"""Device seconds of the raster kernels' calls per snapshot, from the
profiler trace (every op whose name ``work.kernel_of`` recognises)."""
import work


def read(ctx):
    secs = sum(t for name, (t, _) in ctx.trace["ops"].items()
               if work.kernel_of(name))
    n = ctx.counts["snapshots"]
    return secs / n if secs > 0 and n else None
