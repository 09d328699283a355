"""The round program's own name scopes, and which one an operation counts to.

The program places ``jax.named_scope`` at three layer boundaries; the
scope reaches each operation's ``op_name`` (``Op.stacks[0]``), also through
autodiff (``transpose(jvp(fedcm.plane_view))``).  The innermost wins, so
the three are a partition: an operation counts to the first of ``ORDER``
that its own ``op_name`` holds, and to none where it holds none.  A fusion
counts by its own ``op_name``, as ``trace_reduce.layer_s`` counts it.
"""
from __future__ import annotations

from typing import Optional

from bench import trace_reduce

PLANE_VIEW = "fedcm.plane_view"  # FlatSpec.ravel / unravel, and the gradient's pads back
LOCAL_STEPS = "fedcm.local_steps"  # each client's loss, gradient and finalize
FOLD = "fedcm.fold"  # the round close and metric norms, with the cohort mesh's exchange
ORDER = (PLANE_VIEW, LOCAL_STEPS, FOLD)


def scope_of(op: trace_reduce.Op) -> Optional[str]:
    stack = op.stacks[0] if op.stacks else ""
    return next((s for s in ORDER if s in stack), None)


def ms_per_round(ctx, scope: str) -> Optional[float]:
    """Device self time of the scope's operations, in ms per round,
    averaged over the chips; None where none ran."""
    t = trace_reduce.self_s(ctx.trace, lambda op: scope_of(op) == scope)
    return None if t is None else 1e3 * t / ctx.rounds


def coverage(trace: trace_reduce.Trace) -> dict:
    """Seconds per chip of the round program's operations (those with a
    name stack), by the scope they count to, with the local update's
    ``jit(fed_direction_flat)`` beside them and ``share`` the part of the
    whole that these four hold."""
    per = {s: 0 for s in ORDER}
    direction = total = 0
    for ops in trace.ops.values():
        for o in ops:
            if not o.stacks:
                continue
            total += o.self_ns
            s = scope_of(o)
            if s:
                per[s] += o.self_ns
            elif "jit(fed_direction_flat)" in o.stacks[0]:
                direction += o.self_ns
    n = max(1, len(trace.ops))
    out = {s: v * 1e-9 / n for s, v in per.items()}
    out.update(direction=direction * 1e-9 / n, round_program=total * 1e-9 / n,
               share=(sum(per.values()) + direction) / total if total else None)
    return out
