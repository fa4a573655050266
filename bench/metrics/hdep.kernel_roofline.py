"""Share of the roofline over every raster-kernel call in the traced
window: the least time the algorithm's work needs (``work.raster_work``
per call, ``work.least_time`` on the device's peaks) over the kernels'
device time, in percent."""
import work


def read(ctx):
    least = secs = 0.0
    for name, (t, calls) in ctx.trace["ops"].items():
        kind = work.kernel_of(name)
        if kind is None or kind not in ctx.counts["kernel_work"]:
            continue
        ops, nbytes = ctx.counts["kernel_work"][kind]
        least += calls * work.least_time(ops, nbytes, ctx.peaks)[0]
        secs += t
    return 100.0 * least / secs if secs > 0 else None
