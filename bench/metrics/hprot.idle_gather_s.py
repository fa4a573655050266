"""Device idle seconds in the window whose innermost open program span
is a part of the save's gather (``spanidle.GATHER_SPANS``: pull, encode,
CRC32, enqueue): the chip waiting while the gather holds the host."""
from ref import spanidle


def read(ctx):
    return spanidle.idle_under(spanidle.GATHER_SPANS)
