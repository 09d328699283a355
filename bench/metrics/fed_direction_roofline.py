"""Local update layer (``kernels/fed_direction``): the least time the
bytes the direction rows must move take at the chip's HBM peak, over the
device time of every operation inside ``fed_direction_flat`` (the kernel
and the copies around it).  Moves ``rounds_per_s``."""
from bench import trace_reduce

SCOPES = ("fed_direction_flat",)


def read(ctx):
    t = trace_reduce.layer_s(ctx.trace, SCOPES)
    if not t:
        return None
    least = ctx.work["direction_bytes"] / ctx.chips / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
