"""Cohort mesh layer (``shard_map`` over ``("clients",)``): device time of
the collectives (all_to_all, all_gather and their waits) that held a
chip's core, during which nothing else ran there, per round, averaged
over the chips.  Moves ``rounds_per_s``."""
from bench import trace_reduce


def read(ctx):
    t = trace_reduce.self_s(ctx.trace, trace_reduce.is_collective)
    if t is None:
        return None
    return 1e3 * t / ctx.rounds
