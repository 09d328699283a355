"""Expert layer (``models/layers._dropless_local``): device self time of
the held experts' SwiGLU per round, averaged over the chips: the
operations whose ``op_name`` holds ``moe.experts`` (the SiLU product and
the work around the grouped matmuls, forward and backward) and the
grouped matmuls themselves.  The TPU compiler rewrites each
``ragged_dot`` into Mosaic kernels named ``ragged-dot-*`` and gives them
that name as their ``op_name``, so the scope never reaches them; they are
found by that name, and nothing else in the round program makes one.
Moves ``rounds_per_s``."""
from bench import trace_reduce

PROGRAM_SCOPE = "moe.experts"
KERNEL_PREFIX = "ragged-dot"


def _experts(op) -> bool:
    stack = op.stacks[0] if op.stacks else ""
    return PROGRAM_SCOPE in stack or (bool(op.stacks) and op.name.startswith(KERNEL_PREFIX))


def read(ctx):
    t = trace_reduce.self_s(ctx.trace, _experts)
    return None if t is None else 1e3 * t / ctx.rounds
