"""Expert dispatch layer (``models/layers._dropless_local``): device self
time of the operations whose ``op_name`` holds ``moe.dispatch`` (the
router, its top-k, the sort of the (token, choice) rows by expert, their
gather and the gated scatter back, forward and backward), per round,
averaged over the chips.  Moves ``rounds_per_s``."""
from bench import trace_reduce

PROGRAM_SCOPE = "moe.dispatch"


def read(ctx):
    t = trace_reduce.self_s(ctx.trace, lambda op: bool(op.stacks) and PROGRAM_SCOPE in op.stacks[0])
    return None if t is None else 1e3 * t / ctx.rounds
