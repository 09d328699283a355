"""Server fold layer: device self time of the operations in the program's
``fedcm.fold`` scope (the round close: the fold kernel and the copies
around it, the quorum select, the round's metric norms and, on the cohort
mesh, the exchange and the column work that carries an ``op_name``) and
outside the two other scopes, per round, averaged over the chips.  Moves
``rounds_per_s``."""
from bench import scopes

PROGRAM_SCOPE = scopes.FOLD


def read(ctx):
    return scopes.ms_per_round(ctx, PROGRAM_SCOPE)
