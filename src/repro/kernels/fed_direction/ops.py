"""jit'd dispatch from an ``AlgorithmSpec`` to the generalized direction kernel.

``flat_direction_step`` is the flat engine's fused local step: given the
flat plane buffers it resolves the spec's declarative ``DirectionRow``
(``repro.core.registry``) into the (η_l, c_g, c_x, c_aux...) SMEM
coefficient vector and launches ONE kernel pass — no per-step
concatenate/split, the buffers already ARE flat, and no per-algorithm
branching: the row's named streams (``"momentum"``, ``"client_state"``)
map onto the kernel's auxiliary operands, and a nonzero proximal
coefficient ``c_x`` on ``(x − x_t)`` is distributed onto the kernel's
``c_x·x`` slot plus an ``−c_x·x_t`` auxiliary (a tolerance-level
reassociation covered by the feddyn sweep test).

Statically-zero coefficients drop their stream entirely — FedCM at α = 1
launches the same zero-aux kernel as FedAvg.  Specs with an escape-hatch
``direction_fn`` (non-affine directions) bypass the kernel: the callable
is array-polymorphic and runs on the flat buffers directly.

shard_map compatibility (cohort-parallel engine): this launch runs
INSIDE ``shard_map`` over the ``"clients"`` mesh axis, vmapped over each
device's local clients.  Every operand is either per-client ``(P,)``
(x, g, the client-state row) or replicated ``(P,)`` broadcast state
(x_t, Δ_t) — the full plane, never a shard — so the launch shapes are
IDENTICAL at every shard width and the kernel needs no grid-stability
floor (unlike ``server_update``, which launches on plane-column chunks);
no collective ever enters the local-step loop.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro import kernels
from repro.kernels.fed_direction.kernel import DEFAULT_BLOCK, fed_direction_flat
from repro.kernels.server_update.ops import TILE


def _coefs(eta_l, c_g, c_x, *c_aux):
    return jnp.stack(
        [jnp.asarray(c, jnp.float32) for c in (eta_l, c_g, c_x, *c_aux)]
    )


def flat_direction_step(algo, cfg, x, g, m, cst, x0, eta_l):
    """One fused local step x ← x − η_l·v on flat (P,) buffers.

    ``algo`` is an ``AlgorithmSpec`` or a registered name.  ``m`` is the
    broadcast buffer (Δ_t for fedcm/mimelite, c for scaffold), ``cst`` the
    per-client state plane (c_i / λ_i, or None), ``x0`` the round anchor
    x_t — the spec's row picks the streams it consumes by name.
    """
    # deferred import: repro.core.engine imports this module at package
    # init, so a module-level registry import would be circular
    from repro.core.registry import _dir_coef, get_algorithm

    spec = get_algorithm(algo) if isinstance(algo, str) else algo
    if spec.direction_row is None:
        # escape hatch: non-affine direction, pure jnp on the flat buffers
        v = spec.direction(cfg, m, cst, x, x0, g)
        return (x - eta_l * v).astype(x.dtype)
    row = spec.direction_row
    c_g = _dir_coef(row.c_g, cfg)
    c_x = _dir_coef(row.c_x, cfg)
    streams = {"momentum": m, "client_state": cst}
    auxes, aux_coefs = [], []
    for stream, c in row.aux:
        c = _dir_coef(c, cfg)
        if c != 0.0:  # static zero: the stream never reaches the kernel
            auxes.append(streams[stream])
            aux_coefs.append(c)
    if c_x != 0.0:
        # distribute c_x·(x − x_t) onto the kernel's c_x·x slot + a −c_x·x_t aux
        auxes.append(x0)
        aux_coefs.append(-c_x)
    coefs = _coefs(eta_l, c_g, c_x, *aux_coefs)
    return fed_direction_flat(x, g, tuple(auxes), coefs, block_elems=_block(x.shape[-1]),
                              interpret=kernels.interpret_mode())


def _block(n: int) -> int:
    """The default block, or for a plane shorter than it, one block of the
    plane rounded up to whole tiles: the engine aligns short planes to
    ``TILE`` (``repro.kernels.plane_alignment``), so they launch unpadded."""
    return DEFAULT_BLOCK if n >= DEFAULT_BLOCK else -(-n // TILE) * TILE
