"""Device idle seconds per snapshot whose innermost open program span
is one of the in-transit lane's (``spanidle.LANE_SPANS``: the reduce, its
device dispatch and pull, the Hercule write, the manifest commit, a
compile): the chip waiting on the lane's host work."""
from ref import spanidle


def read(ctx):
    secs = spanidle.idle_under(spanidle.LANE_SPANS)
    n = ctx.counts["snapshots"]
    return secs / n if secs is not None and n else None
