"""Server fold layer (``kernels/server_update``): the least time the
bytes the fold must move (the cohort's changes read, x and the momentum
read and written) take at the chip's HBM peak, over the device time of
every operation inside ``server_update_flat`` or ``dequant_update_flat``.
Moves ``rounds_per_s``."""
from bench import trace_reduce

SCOPES = ("server_update_flat", "dequant_update_flat")


def read(ctx):
    t = trace_reduce.layer_s(ctx.trace, SCOPES)
    if not t:
        return None
    least = ctx.work["fold_bytes"] / ctx.chips / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
