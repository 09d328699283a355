"""Share of the traced window in which no operation ran on the chip,
averaged over the chips (device layer; moves ``rounds_per_s``)."""
from bench import trace_reduce


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(ctx.trace) / ctx.trace.window_s)
