"""Plane view layer: device self time of the operations in the program's
``fedcm.plane_view`` scope (every conversion between the flat parameter
plane and its leaf views, and the gradient's pads back into the plane),
per round, averaged over the chips.  The gradient's accumulation into the
plane is not here where XLA fuses it into the weight-decay add, as it does
on TPU: that fusion counts to ``local_steps_ms``.  Moves ``rounds_per_s``."""
from bench import scopes

PROGRAM_SCOPE = scopes.PLANE_VIEW


def read(ctx):
    return scopes.ms_per_round(ctx, PROGRAM_SCOPE)
