"""Client local steps layer: device self time of the operations in the
program's ``fedcm.local_steps`` scope (each client's forward and backward
pass, with the weight decay, and its finalize ``x_K - x_t``) and outside
``fedcm.plane_view``, per round, averaged over the chips.  On TPU the
gradient's accumulation into the plane fuses into the weight-decay add and
counts here.  Moves ``rounds_per_s``."""
from bench import scopes

PROGRAM_SCOPE = scopes.LOCAL_STEPS


def read(ctx):
    return scopes.ms_per_round(ctx, PROGRAM_SCOPE)
