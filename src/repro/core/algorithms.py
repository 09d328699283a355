"""Builtin federated algorithms as registry specs (paper + baselines + family).

Algorithm 2 of the paper (FedCM) and every baseline it compares against —
FedAvg [McMahan+17], FedAdam [Reddi+20], SCAFFOLD [Karimireddy+20b],
FedDyn [Acar+21], MimeLite [Karimireddy+20a] — plus the wider
momentum-corrected family the registry makes cheap to add: FedAvgM
[Hsu+19] (server heavy-ball), FedAdagrad / FedYogi [Reddi+20] (adaptive
server optimizers), FedACG-style Nesterov server acceleration
[Kim+22, arXiv:2201.03172], and FedProx [Li+20] (the ``c_x``-only
proximal row).  Every algorithm is an ``AlgorithmSpec``
(``repro.core.registry``): a client-direction coefficient row, server-fold
coefficient rows (+ optional pure post-step), and state-plane flags — the
engine contains zero per-algorithm branches.

The *paper-faithful* convention (appendix C.2) is used throughout: the
pseudo-gradient is ``Δ_{t+1} = −(1/(η_l·K)) · mean_i(x_{i,K} − x_t)`` and
the server step on it is ``η_g·η_l·K``, so ``η_g = 1`` corresponds to
plain client-model averaging.  The adaptive server methods (FedAdam /
FedAdagrad / FedYogi) apply their update to the pseudo-gradient with an
absolute server lr (η_g = 0.1 in the paper).

Statelessness matters: FedCM/FedAvg/FedAdam/MimeLite/FedAvgM/FedACG keep
NO per-client state; SCAFFOLD and FedDyn keep per-client control variates,
which is exactly what the paper blames for their degradation at 2%
participation — the engine stores them stacked ``(N, …)`` and leaves
non-participants stale, reproducing that failure mode honestly.  At fleet
scale (``cfg.population_store="host"``) the same planes live out-of-core
in ``repro.data.population.HostPopulationStore`` instead —
``client_state_init`` returns None and the engine gathers/scatters
``(C, P)`` cohort rows per round, bitwise-matching the resident plane.

Flat fast path: every spec interpreter is *array-polymorphic* — a bare jax
array is a single-leaf pytree, so ``spec.direction``/``spec.server_update``
run unchanged on the flat ``(P,)`` parameter plane (``repro.core.flat``).
The flat-only additions are ``FlatClientOutputs`` (optional planes:
algorithms without client state / full-batch grads carry ``None`` instead
of a materialized ``(C, P)`` zeros plane) and ``sparse_client_finalize``
which produces them with the same op order as the tree finalizer, so the
two paths stay bitwise-comparable (tests/test_flat.py holds them to it).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.core.registry import (  # noqa: F401  (re-exported public API)
    Algorithm,
    AlgorithmSpec,
    ClientOutputs,
    DirectionRow,
    FoldPass,
    ServerState,
    client_state_init,
    get_algorithm,
    list_algorithms,
    register_algorithm,
    server_init,
)
from repro.utils.trees import tree_axpy, tree_scale, tree_sub


class _AlgorithmsView:
    """Read-only dict-like view of the registry (back-compat for the old
    module-level ``ALGORITHMS`` dict)."""

    def __getitem__(self, name: str) -> AlgorithmSpec:
        return get_algorithm(name)

    def __contains__(self, name: str) -> bool:
        return name in list_algorithms()

    def __iter__(self):
        return iter(list_algorithms())

    def __len__(self) -> int:
        return len(list_algorithms())

    def keys(self):
        return list_algorithms()

    def items(self):
        return [(n, get_algorithm(n)) for n in list_algorithms()]


ALGORITHMS = _AlgorithmsView()


# ----------------------------------------------------------------------
# shared coefficient / post-step pieces
# ----------------------------------------------------------------------


def _eta_g_eff(cfg: FedConfig, eta_l) -> jax.Array:
    # appendix C.2: η_g is reported in "averaging" units; effective server
    # step on Δ_{t+1} is η_g·η_l·K, i.e. x ← x + η_g·mean(Δ_i).
    return cfg.eta_g * eta_l * cfg.local_steps


def _c_pseudo_grad(cfg, eta_l, n_active):
    """Fold coefficient turning mean(Δ_i) into Δ_{t+1} (Algorithm 1/2
    line 13): ``m ← −mean/(η_l·K)``."""
    return -1.0 / (eta_l * cfg.local_steps)


def _c_alpha_pseudo_grad(cfg, eta_l, n_active):
    """EMA coupling of the adaptive methods: ``m ← (1−α)·m + α·Δ_{t+1}``."""
    return -cfg.alpha / (eta_l * cfg.local_steps)


def _c_eta_g(cfg, eta_l, n_active):
    return cfg.eta_g


def _c_participation_frac(cfg, eta_l, n_active):
    """SCAFFOLD server control variate: ``c ← c + (|S|/N)·mean(Δc_i)``."""
    return n_active / cfg.num_clients


def _c_feddyn_h(cfg, eta_l, n_active):
    """FedDyn: ``h ← h − α_dyn·(|S|/N)·mean(Δ_i)``."""
    return -cfg.feddyn_alpha * (n_active / cfg.num_clients)


def _pseudo_grad(mean_delta, eta_l, K):
    """Δ_{t+1} = −(1/(η_l·K))·mean_i(Δ_i) — Algorithm 1/2 line 13."""
    return tree_scale(mean_delta, -1.0 / (eta_l * K))


# --- per-client state updates (round close; see registry.state_update_fn)


def _scaffold_state_update(cfg, x0, xK, c_i, c, delta, eta_l):
    # option II: c_i⁺ = c_i − c + (x_t − x_{i,K}) / (K·η_l)
    K = cfg.local_steps
    c_new = jax.tree_util.tree_map(
        lambda ci, cg, d: ci - cg - d / (K * eta_l), c_i, c, delta
    )
    return tree_sub(c_new, c_i)


def _feddyn_state_update(cfg, x0, xK, lam_i, m, delta, eta_l):
    # λ_i ← λ_i − α_dyn·(θ_i − x_t)
    return tree_scale(delta, -cfg.feddyn_alpha)


# --- pure server post-steps (the part a streaming fold pass can't express)


def _feddyn_post(cfg, x, srv, dmean, n_active, eta_l):
    # fold already did  h ← h − α_dyn·(|S|/N)·mean  and  x ← x + mean
    # (the mean of client models); the dual shift is x ← x − h/α_dyn.
    return tree_axpy(-1.0 / cfg.feddyn_alpha, srv.momentum, x), srv


def _fedadam_post(cfg, x, srv, dmean, n_active, eta_l):
    # Reddi+20 server Adam: fold already did m ← (1−α)m + α·Δ_{t+1};
    # here the second moment EMA + preconditioned absolute-lr step.
    pg = _pseudo_grad(dmean, eta_l, cfg.local_steps)
    v = jax.tree_util.tree_map(
        lambda vi, gi: cfg.adam_beta2 * vi + (1.0 - cfg.adam_beta2) * jnp.square(gi),
        srv.second_moment, pg,
    )
    x = jax.tree_util.tree_map(
        lambda p, mi, vi: p - cfg.eta_g * mi / (jnp.sqrt(vi) + cfg.adam_tau),
        x, srv.momentum, v,
    )
    return x, srv._replace(second_moment=v)


def _fedadagrad_post(cfg, x, srv, dmean, n_active, eta_l):
    # Reddi+20 FedAdagrad: v accumulates (no decay) — v ← v + Δ²_{t+1}.
    pg = _pseudo_grad(dmean, eta_l, cfg.local_steps)
    v = jax.tree_util.tree_map(
        lambda vi, gi: vi + jnp.square(gi), srv.second_moment, pg
    )
    x = jax.tree_util.tree_map(
        lambda p, mi, vi: p - cfg.eta_g * mi / (jnp.sqrt(vi) + cfg.adam_tau),
        x, srv.momentum, v,
    )
    return x, srv._replace(second_moment=v)


def _fedyogi_post(cfg, x, srv, dmean, n_active, eta_l):
    # Reddi+20 FedYogi: sign-controlled second moment —
    # v ← v − (1−β2)·sign(v − Δ²)·Δ².
    pg = _pseudo_grad(dmean, eta_l, cfg.local_steps)
    v = jax.tree_util.tree_map(
        lambda vi, gi: vi - (1.0 - cfg.adam_beta2)
        * jnp.sign(vi - jnp.square(gi)) * jnp.square(gi),
        srv.second_moment, pg,
    )
    x = jax.tree_util.tree_map(
        lambda p, mi, vi: p - cfg.eta_g * mi / (jnp.sqrt(vi) + cfg.adam_tau),
        x, srv.momentum, v,
    )
    return x, srv._replace(second_moment=v)


def _fedavgm_post(cfg, x, srv, dmean, n_active, eta_l):
    # heavy-ball server step along the post-fold momentum:
    # x ← x − η_g·η_l·K·m'  (α=1 degenerates to FedAvg exactly).
    return tree_axpy(-_eta_g_eff(cfg, eta_l), srv.momentum, x), srv


def _fedacg_post(cfg, x, srv, dmean, n_active, eta_l):
    # Nesterov/FedACG-style lookahead: step along pg + λ·m' (the momentum
    # the NEXT round will broadcast), not the stale m.
    lam = cfg.acg_lambda
    pg = _pseudo_grad(dmean, eta_l, cfg.local_steps)
    step = jax.tree_util.tree_map(lambda mi, gi: gi + lam * mi, srv.momentum, pg)
    return tree_axpy(-_eta_g_eff(cfg, eta_l), step, x), srv


# ----------------------------------------------------------------------
# the builtin specs — pure data (see repro.core.registry)
# ----------------------------------------------------------------------

register_algorithm(AlgorithmSpec(
    name="fedavg",
    direction_row=DirectionRow(),  # v = g
    # m' := Δ_{t+1} (kept for metrics/inspection);  x' = x + η_g·mean
    fold=(FoldPass("delta", c_mm=0.0, c_md=_c_pseudo_grad, c_xd=_c_eta_g),),
))

register_algorithm(AlgorithmSpec(
    name="fedcm",
    # Algorithm 2, line 8: v = α·g + (1−α)·Δ_t
    direction_row=DirectionRow(
        c_g=lambda cfg: cfg.alpha,
        aux=(("momentum", lambda cfg: 1.0 - cfg.alpha),),
    ),
    # lines 13–14: Δ_{t+1} IS the new momentum (Lemma 4.1: it equals
    # α·Δ̃_t + (1−α)·Δ_t because clients descend along v, not g).
    fold=(FoldPass("delta", c_mm=0.0, c_md=_c_pseudo_grad, c_xd=_c_eta_g),),
    needs_momentum_broadcast=True,
    momentum_store="momentum_dtype",
))

register_algorithm(AlgorithmSpec(
    name="fedadam",
    direction_row=DirectionRow(),  # clients run plain SGD
    # m ← (1−α)·m + α·Δ_{t+1}; the v EMA + preconditioned step is the post
    fold=(FoldPass("delta", c_mm=lambda cfg, e, n: 1.0 - cfg.alpha,
                   c_md=_c_alpha_pseudo_grad, c_xd=0.0),),
    server_post_fn=_fedadam_post,
    needs_second_moment=True,
))

register_algorithm(AlgorithmSpec(
    name="scaffold",
    # option: v = g − c_i + c  (the server's c rides the momentum broadcast)
    direction_row=DirectionRow(
        aux=(("client_state", -1.0), ("momentum", 1.0)),
    ),
    state_update_fn=_scaffold_state_update,
    # params pass over Δ, then the c-EMA pass over Δc
    fold=(FoldPass("delta", c_mm=1.0, c_md=0.0, c_xd=_c_eta_g),
          FoldPass("state_delta", c_mm=1.0, c_md=_c_participation_frac, c_xd=0.0)),
    needs_client_state=True,
    needs_momentum_broadcast=True,
    client_state_uplink=True,  # Δc_i goes up; c comes down with the broadcast
))

register_algorithm(AlgorithmSpec(
    name="feddyn",
    # local objective f_i(x) − ⟨λ_i, x⟩ + (α_dyn/2)‖x − x_t‖²
    direction_row=DirectionRow(
        c_x=lambda cfg: cfg.feddyn_alpha,
        aux=(("client_state", -1.0),),
    ),
    state_update_fn=_feddyn_state_update,
    # h ← h − α_dyn·(|S|/N)·mean;  x ← (x + mean) − h/α_dyn (post)
    fold=(FoldPass("delta", c_mm=1.0, c_md=_c_feddyn_h, c_xd=1.0),),
    server_post_fn=_feddyn_post,
    needs_client_state=True,
    # λ_i never leaves the client — no uplink charge for the state plane
))

register_algorithm(AlgorithmSpec(
    name="mimelite",
    # MimeLite w/ momentum-SGD statistics: d = (1−β)·g + β·m, β = 1−α —
    # identical functional form to FedCM; the difference is how m is
    # UPDATED (full-batch grads at x_t: the ``extra`` fold pass below).
    direction_row=DirectionRow(
        c_g=lambda cfg: cfg.alpha,
        aux=(("momentum", lambda cfg: 1.0 - cfg.alpha),),
    ),
    fold=(FoldPass("delta", c_mm=1.0, c_md=0.0, c_xd=_c_eta_g),
          FoldPass("extra", c_mm=lambda cfg, e, n: 1.0 - cfg.alpha,
                   c_md=lambda cfg, e, n: cfg.alpha, c_xd=0.0)),
    needs_momentum_broadcast=True,
    needs_full_grad=True,
))

# --- the family beyond the paper: pure spec definitions -----------------

register_algorithm(AlgorithmSpec(
    name="fedavgm",
    direction_row=DirectionRow(),  # clients run plain SGD
    # Hsu+19 server heavy-ball on the pseudo-gradient, β = 1−α:
    # m' = (1−α)·m + Δ_{t+1};  x ← x − η_g·η_l·K·m'  (α=1 ⇒ FedAvg)
    fold=(FoldPass("delta", c_mm=lambda cfg, e, n: 1.0 - cfg.alpha,
                   c_md=_c_pseudo_grad, c_xd=0.0),),
    server_post_fn=_fedavgm_post,
))

register_algorithm(AlgorithmSpec(
    name="fedadagrad",
    direction_row=DirectionRow(),
    fold=(FoldPass("delta", c_mm=lambda cfg, e, n: 1.0 - cfg.alpha,
                   c_md=_c_alpha_pseudo_grad, c_xd=0.0),),
    server_post_fn=_fedadagrad_post,
    needs_second_moment=True,
))

register_algorithm(AlgorithmSpec(
    name="fedyogi",
    direction_row=DirectionRow(),
    fold=(FoldPass("delta", c_mm=lambda cfg, e, n: 1.0 - cfg.alpha,
                   c_md=_c_alpha_pseudo_grad, c_xd=0.0),),
    server_post_fn=_fedyogi_post,
    needs_second_moment=True,
))

register_algorithm(AlgorithmSpec(
    name="fedprox",
    # Li+20 (MLSys): local objective f_i(x) + (μ/2)‖x − x_t‖² — the
    # proximal gradient is the pure c_x row v = g + μ·(x − x_t).  No
    # client state, no extra uplink: stateless like FedAvg (and μ=0 IS
    # FedAvg), which is exactly why it stays data-only under every
    # execution path, cohort sharding included.
    direction_row=DirectionRow(c_x=lambda cfg: cfg.fedprox_mu),
    fold=(FoldPass("delta", c_mm=0.0, c_md=_c_pseudo_grad, c_xd=_c_eta_g),),
))

register_algorithm(AlgorithmSpec(
    name="fedacg",
    direction_row=DirectionRow(),
    # Kim+22-style accelerated server momentum:
    # m' = λ·m + Δ_{t+1};  x ← x − η_g·η_l·K·(Δ_{t+1} + λ·m')  (lookahead)
    fold=(FoldPass("delta", c_mm=lambda cfg, e, n: cfg.acg_lambda,
                   c_md=_c_pseudo_grad, c_xd=0.0),),
    server_post_fn=_fedacg_post,
))


# ----------------------------------------------------------------------
# flat-plane fast path
# ----------------------------------------------------------------------


class FlatClientOutputs(NamedTuple):
    """Per-client uplink on the flat plane.  Unused planes are ``None`` —
    the tree path materializes (and aggregates) zeros trees for them, which
    for a stateless algorithm is two full (C, P) writes + reductions of
    nothing; skipping them is part of the flat engine's win."""

    delta: Any  # (P,) x_{i,K} − x_t
    state_delta: Optional[Any]  # (P,) SCAFFOLD Δc_i / FedDyn Δλ_i, or None
    extra: Optional[Any]  # (P,) MimeLite full-batch grad, or None


def sparse_client_finalize(
    algo: AlgorithmSpec, cfg: FedConfig, x0, xK, cst, m, eta_l, full_grad
) -> FlatClientOutputs:
    """``algo.client_finalize`` minus the zeros trees it materializes:
    unused planes come back ``None``.  Array-polymorphic — the flat
    engine feeds it bare ``(P,)`` buffers (single-leaf pytrees).  Op
    order deliberately mirrors the tree finalizer exactly (same
    ``state_update_fn``), so flat and tree trajectories agree bitwise, not
    just to tolerance."""
    delta = tree_sub(xK, x0)
    state_delta = None
    if algo.needs_client_state and algo.state_update_fn is not None:
        state_delta = algo.state_update_fn(cfg, x0, xK, cst, m, delta, eta_l)
    extra = full_grad if algo.needs_full_grad else None
    return FlatClientOutputs(delta, state_delta, extra)
