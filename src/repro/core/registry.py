"""Declarative algorithm registry: one ``AlgorithmSpec`` drives every path.

FedCM is one point in a family of momentum-corrected local-update methods —
FedACG's accelerated server momentum (Kim et al., arXiv:2201.03172) and
generalized heavy-ball methods (Zaccone et al., arXiv:2311.18578) are the
same affine-blend shape with different coefficients.  This module makes
that family structure the API: an algorithm is DATA — three declarative
pieces the engine, the flat plane, the fused kernels, and the async ring
all consume without ever branching on an algorithm name.

An ``AlgorithmSpec`` declares:

(a) **client direction** — an affine coefficient row (``DirectionRow``)
    consumed directly by the ``fed_direction`` kernel::

        v = c_g·g + c_x·(x − x_t) + Σ_s c_s·stream_s

    where the named streams are ``"momentum"`` (the broadcast buffer Δ_t /
    c) and ``"client_state"`` (this client's c_i / λ_i).  Coefficients are
    floats or ``cfg -> float`` callables, resolved at trace time — static
    zeros are dropped, so unused streams cost nothing on either path.  An
    escape-hatch ``direction_fn(cfg, m, cst, x, x0, g) -> v`` replaces the
    row for non-affine directions (array-polymorphic: it runs on leaf
    trees AND flat ``(P,)`` buffers).

(b) **server fold** — a tuple of ``FoldPass`` coefficient rows, each one a
    ``server_update``-kernel SMEM row over one uplink plane::

        mean = Σ_c wn_c · plane_c        (masked cohort mean)
        m'   = c_mm·m + c_md·(γ·mean)    (momentum EMA / pseudo-grad store)
        x'   = x + c_xd·(γ·mean)         (server param step)

    (γ is the async staleness discount; 1.0 on the sync path) plus an
    optional pure ``server_post_fn(cfg, x, server, dmean, n_active,
    eta_l) -> (x, server)`` for the part a streaming pass cannot express
    (FedAdam's preconditioner, FedDyn's ``−h/α`` shift, FedACG's Nesterov
    lookahead).  Coefficients are floats or ``(cfg, eta_l, n_active) ->
    scalar`` callables — η_l decays per round and |S| is traced, so they
    resolve inside the jitted program.  A full escape hatch ``server_fn``
    (legacy ``server_update`` signature) replaces fold + post entirely;
    on the flat plane such algorithms reduce their ``(C, P)`` planes in
    jnp instead of the fold kernel.

(c) **state planes** — ``needs_client_state`` / ``needs_momentum_broadcast``
    / ``needs_full_grad`` / ``needs_second_moment`` flags from which
    ``FedState`` allocation (stacked ``(N, …)`` control variates, the
    second-moment plane, the f32 master cache) and uplink payload shapes
    are derived; ``client_state_uplink`` marks whether the per-client
    state delta rides the wire (SCAFFOLD's Δc_i does, FedDyn's λ_i never
    leaves the client).

Registering a new algorithm is therefore a pure data definition::

    @register_algorithm
    def _fedavgm():
        return AlgorithmSpec(
            name="fedavgm",
            direction_row=DirectionRow(),            # plain local SGD
            fold=(FoldPass("delta",
                           c_mm=lambda cfg: 1.0 - cfg.alpha,
                           c_md=_c_pseudo_grad, c_xd=0.0),),
            server_post_fn=_post_momentum_step,
        )

and it immediately runs on the tree path, the flat plane, the fused
Pallas kernels, and the async pipelined ring — plus the registry-
parametrized cross-path equivalence tests (tests/test_registry.py) pick
it up automatically.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.utils.trees import tree_sub, tree_zeros_like

# coefficient forms: a static python float, or a callable resolved at
# trace time — cfg-only for direction rows, (cfg, eta_l, n_active) for
# fold rows (η_l decays per round, |S| is traced under bernoulli
# participation)
DirCoef = Union[float, Callable[[Any], float]]
FoldCoef = Union[float, Callable[[Any, Any, Any], Any]]

#: stream names a DirectionRow may reference
DIRECTION_STREAMS = ("momentum", "client_state")
#: uplink plane names a FoldPass may reference
FOLD_PLANES = ("delta", "state_delta", "extra")


class DirectionRow(NamedTuple):
    """Affine client-direction coefficients (see module docstring (a))."""

    c_g: DirCoef = 1.0  # on the minibatch gradient g
    c_x: DirCoef = 0.0  # on the proximal drift (x − x_t)
    aux: Tuple[Tuple[str, DirCoef], ...] = ()  # (stream name, coefficient)


class FoldPass(NamedTuple):
    """One ``server_update`` SMEM coefficient row over one uplink plane.

    Statically-zero coefficients are structural: ``c_xd == 0.0`` means the
    pass leaves params untouched, ``c_md == 0.0 and c_mm == 1.0`` means it
    leaves the momentum buffer untouched — both paths (jnp interpreter and
    fused kernel) skip the corresponding write, so e.g. SCAFFOLD's params
    pass never re-rounds the momentum plane."""

    plane: str  # "delta" | "state_delta" | "extra"
    c_mm: FoldCoef = 1.0  # momentum carry-over
    c_md: FoldCoef = 0.0  # momentum ← mean coupling
    c_xd: FoldCoef = 0.0  # param step on the mean


class ServerState(NamedTuple):
    """Server-side state shared by all algorithms.

    ``second_moment`` is ``None`` unless the spec sets
    ``needs_second_moment`` — stateless-in-v algorithms never allocate
    (or checkpoint, or donate) the extra params-sized plane."""

    momentum: Any  # FedCM Δ_t / FedAdam m / MimeLite m / FedDyn h / SCAFFOLD c
    second_moment: Any  # FedAdam/FedAdagrad/FedYogi v, or None
    round: jax.Array  # int32 round counter t


class ClientOutputs(NamedTuple):
    delta: Any  # x_{i,K} − x_t  (the uplink payload of every algorithm)
    state_delta: Any  # per-client state update (SCAFFOLD Δc_i, FedDyn Δλ_i) or zeros
    extra: Any  # extra uplink pytree (MimeLite full-batch grad) or zeros


def _dir_coef(c: DirCoef, cfg) -> float:
    # a Python float by contract, resolved from static config at trace
    # time; the static-zero tests (``c != 0.0``) refuse a traced one
    return c(cfg) if callable(c) else c


def _fold_coef(c: FoldCoef, cfg, eta_l, n_active):
    return c(cfg, eta_l, n_active) if callable(c) else c


def _is_static_zero(c) -> bool:
    return isinstance(c, (int, float)) and float(c) == 0.0  # repro: noqa REP003 -- isinstance-guarded Python scalar, static at trace time


def _is_static_one(c) -> bool:
    return isinstance(c, (int, float)) and float(c) == 1.0  # repro: noqa REP003 -- isinstance-guarded Python scalar, static at trace time


class AlgorithmSpec(NamedTuple):
    """One federated algorithm as data (see module docstring).

    The methods (``direction`` / ``client_finalize`` / ``server_update``)
    are the generic interpreters of the declarative fields — they are
    array-polymorphic (a bare ``(P,)`` buffer is a single-leaf pytree), so
    the tree path and the flat plane share them verbatim.  The flat
    plane's kernels consume the SAME rows through
    ``kernels/fed_direction/ops.flat_direction_step`` and
    ``kernels/server_update/ops.fused_fold``.
    """

    name: str
    # --- (a) client direction ---
    direction_row: Optional[DirectionRow] = DirectionRow()
    direction_fn: Optional[Callable] = None  # (cfg, m, cst, x, x0, g) -> v
    # round-close per-client state update, or None (stateless):
    #   (cfg, x0, xK, cst, m, delta, eta_l) -> state_delta
    state_update_fn: Optional[Callable] = None
    # --- (b) server fold ---
    fold: Tuple[FoldPass, ...] = (FoldPass("delta"),)
    # (cfg, x, server, dmean, n_active, eta_l) -> (x, server)
    server_post_fn: Optional[Callable] = None
    # full escape hatch, legacy signature (cfg, params, st, mean_delta,
    # mean_sd, mean_extra, n_active, eta_l) -> (params, ServerState)
    server_fn: Optional[Callable] = None
    # --- (c) state-plane requirements ---
    needs_client_state: bool = False
    needs_momentum_broadcast: bool = False
    needs_full_grad: bool = False
    needs_second_moment: bool = False
    client_state_uplink: bool = False  # does Δstate ride the uplink (payload)
    # stored-momentum dtype policy: "float32", or "momentum_dtype" to honor
    # cfg.momentum_dtype (FedCM's broadcastable Δ_t)
    momentum_store: str = "float32"
    # --- (d) uplink compression (repro.core.compress) ---
    # spec-declared default uplink compression kind ("int8"/"bf16"/"topk",
    # None = uncompressed).  cfg.compression overrides it; the engine
    # resolves ``effective = cfg.compression or spec default``.
    uplink_compression: Optional[str] = None
    # top-k sparsification carries error-feedback residuals as a NEW
    # per-client state stream (resident (N, P) plane / host-store rows,
    # checkpointed with the run).  A spec that declares lossy
    # sparsification must also declare the residual stream — validation
    # refuses "topk" without it (sparsifying with no residual silently
    # biases every uplink; see core/compress.py).
    needs_residual: bool = False

    # ------------------------------------------------------------------
    # derived uplink / ring layout (cohort-parallel engine consumes these)
    # ------------------------------------------------------------------
    @property
    def uplink_planes(self) -> Tuple[str, ...]:
        """Names of the cohort-stacked uplink planes this spec produces —
        the ``(C, P)`` buffers that ride the async ring (``CohortUplink``)
        and, under cohort sharding, the planes whose leading axis is
        partitioned over the ``"clients"`` mesh axis.  Derived purely from
        the state-plane flags: ``delta`` always, ``state_delta`` iff the
        spec keeps per-client state, ``extra`` iff it uplinks a full-batch
        gradient.  Ring and shard_map in/out specs are built from this —
        never from algorithm names."""
        names = ["delta"]
        if self.needs_client_state and self.state_update_fn is not None:
            names.append("state_delta")
        if self.needs_full_grad:
            names.append("extra")
        return tuple(names)

    @property
    def wire_uplink_planes(self) -> Tuple[str, ...]:
        """Uplink planes that actually cross the client→server WIRE —
        §4.2's payload accounting as data.  ``uplink_planes`` minus the
        state planes that stay client-local: feddyn's λ_i rides the ring
        as a ``state_delta`` plane but never leaves the client in the real
        system (``client_state_uplink=False``), so it costs no uplink
        bytes.  The engine's payload metrics and ``fed_train --list-algos``
        both derive bytes/round from this."""
        names = ["delta"]
        if self.needs_client_state and self.client_state_uplink:
            names.append("state_delta")
        if self.needs_full_grad:
            names.append("extra")
        return tuple(names)

    @property
    def fold_planes(self) -> Tuple[str, ...]:
        """Uplink planes the ROUND CLOSE consumes (in first-use order).
        For declarative folds these are the planes named by the
        ``FoldPass`` rows — the set the scattered (reduce-scatter) fold
        must transpose; a ``server_fn`` escape hatch consumes the masked
        mean of every uplink plane."""
        if self.server_fn is not None:
            return self.uplink_planes
        return tuple(dict.fromkeys(p.plane for p in self.fold))

    # ------------------------------------------------------------------
    # generic interpreters (array-polymorphic: trees OR flat planes)
    # ------------------------------------------------------------------
    def direction(self, cfg, m, cst, x, x0, g):
        """Per-local-step direction v from the affine row (or escape hatch)."""
        if self.direction_fn is not None:
            return self.direction_fn(cfg, m, cst, x, x0, g)
        row = self.direction_row
        c_g = _dir_coef(row.c_g, cfg)
        c_x = _dir_coef(row.c_x, cfg)
        streams = {"momentum": m, "client_state": cst}
        aux = [(streams[s], _dir_coef(c, cfg)) for s, c in row.aux]
        aux = [(t, c) for t, c in aux if c != 0.0]  # static-zero streams drop
        trees = [g] + ([x, x0] if c_x != 0.0 else []) + [t for t, _ in aux]
        coefs = [c for _, c in aux]

        def leaf(g_l, *rest):
            v = c_g * g_l
            if c_x != 0.0:
                v = v + c_x * (rest[0] - rest[1])
                rest = rest[2:]
            for c_s, s_l in zip(coefs, rest):
                v = v + c_s * s_l
            return v

        return jax.tree_util.tree_map(leaf, *trees)

    def client_finalize(self, cfg, x0, xK, cst, m, eta_l, full_grad) -> ClientOutputs:
        """Round-close uplink on the TREE path: unused planes materialize
        as zeros (the tree path aggregates them — part of why the flat
        path wins; see ``sparse_client_finalize`` in core.algorithms)."""
        delta = tree_sub(xK, x0)
        if self.state_update_fn is not None:
            sd = self.state_update_fn(cfg, x0, xK, cst, m, delta, eta_l)
        else:
            sd = tree_zeros_like(x0)
        extra = full_grad if self.needs_full_grad else tree_zeros_like(x0)
        return ClientOutputs(delta, sd, extra)

    def server_update(self, cfg, params, st, mean_delta, mean_sd, mean_extra,
                      n_active, eta_l, discount=1.0):
        """Round-close from the aggregated means: interpret the fold rows
        (plus post-step), or defer to the ``server_fn`` escape hatch.
        ``discount`` is the async staleness weight γ (static 1.0 on the
        sync path — skipped, so sync stays bitwise)."""
        if self.server_fn is not None:
            if not _is_static_one(discount):
                scale = lambda t: None if t is None else jax.tree_util.tree_map(
                    lambda a: discount * a, t)
                mean_delta, mean_sd, mean_extra = (
                    scale(mean_delta), scale(mean_sd), scale(mean_extra))
            return self.server_fn(cfg, params, st, mean_delta, mean_sd,
                                  mean_extra, n_active, eta_l)
        planes = {"delta": mean_delta, "state_delta": mean_sd, "extra": mean_extra}
        x, m = params, st.momentum
        dmean_delta = None
        for p in self.fold:
            mean = planes[p.plane]
            dmean = mean if _is_static_one(discount) else jax.tree_util.tree_map(
                lambda a: discount * a, mean)
            if p.plane == "delta":
                dmean_delta = dmean
            c_mm = _fold_coef(p.c_mm, cfg, eta_l, n_active)
            c_md = _fold_coef(p.c_md, cfg, eta_l, n_active)
            c_xd = _fold_coef(p.c_xd, cfg, eta_l, n_active)
            if not (_is_static_zero(p.c_md) and _is_static_one(p.c_mm)):
                if _is_static_zero(p.c_mm):
                    m = jax.tree_util.tree_map(lambda d: c_md * d, dmean)
                else:
                    m = jax.tree_util.tree_map(
                        lambda mi, d: c_mm * mi + c_md * d, m, dmean)
            if not _is_static_zero(p.c_xd):
                x = jax.tree_util.tree_map(lambda xi, d: xi + c_xd * d, x, dmean)
        if self.momentum_store == "momentum_dtype":
            mdt = jnp.dtype(getattr(cfg, "momentum_dtype", "float32"))
            m = jax.tree_util.tree_map(lambda a: a.astype(mdt), m)
        new_st = st._replace(momentum=m, round=st.round + 1)
        if self.server_post_fn is not None:
            x, new_st = self.server_post_fn(cfg, x, new_st, dmean_delta,
                                            n_active, eta_l)
        return x, new_st


#: back-compat alias — PR-2/3 code and tests name the spec ``Algorithm``
Algorithm = AlgorithmSpec


# ----------------------------------------------------------------------
# the registry
# ----------------------------------------------------------------------

_REGISTRY: Dict[str, AlgorithmSpec] = {}


def _validate(spec: AlgorithmSpec) -> None:
    if not spec.name or not isinstance(spec.name, str):
        raise ValueError(f"AlgorithmSpec needs a non-empty string name, got {spec.name!r}")
    if spec.momentum_store not in ("float32", "momentum_dtype"):
        raise ValueError(
            f"{spec.name}: momentum_store must be 'float32' or 'momentum_dtype'"
        )
    if (spec.direction_row is None) == (spec.direction_fn is None):
        raise ValueError(
            f"{spec.name}: exactly one of direction_row / direction_fn required"
        )
    if spec.direction_row is not None:
        for stream, _ in spec.direction_row.aux:
            if stream not in DIRECTION_STREAMS:
                raise ValueError(
                    f"{spec.name}: unknown direction stream {stream!r}; "
                    f"known: {DIRECTION_STREAMS}"
                )
            if stream == "client_state" and not spec.needs_client_state:
                raise ValueError(
                    f"{spec.name}: direction consumes 'client_state' but "
                    f"needs_client_state is False — no plane would be allocated"
                )
            if stream == "momentum" and not spec.needs_momentum_broadcast:
                raise ValueError(
                    f"{spec.name}: direction consumes 'momentum' but "
                    f"needs_momentum_broadcast is False — payload accounting "
                    f"would undercharge the downlink"
                )
    if spec.needs_client_state and spec.state_update_fn is None:
        raise ValueError(
            f"{spec.name}: needs_client_state requires state_update_fn "
            f"(how does the per-client plane evolve?)"
        )
    if spec.client_state_uplink and not spec.needs_client_state:
        raise ValueError(f"{spec.name}: client_state_uplink without client state")
    if spec.uplink_compression not in (None, "int8", "bf16", "topk"):
        raise ValueError(
            f"{spec.name}: unknown uplink_compression "
            f"{spec.uplink_compression!r}; known: int8 | bf16 | topk"
        )
    if spec.uplink_compression == "topk" and not spec.needs_residual:
        raise ValueError(
            f"{spec.name}: uplink_compression='topk' without needs_residual "
            f"— lossy sparsification needs the error-feedback residual "
            f"stream or every uplink is silently biased (repro.core.compress)"
        )
    if spec.needs_residual and spec.uplink_compression != "topk":
        raise ValueError(
            f"{spec.name}: needs_residual declared but uplink_compression is "
            f"{spec.uplink_compression!r} — only 'topk' carries residuals"
        )
    if spec.server_fn is not None:
        if spec.server_post_fn is not None:
            raise ValueError(f"{spec.name}: server_fn replaces fold+post — drop server_post_fn")
    else:
        if not spec.fold:
            raise ValueError(f"{spec.name}: empty fold and no server_fn escape hatch")
        for p in spec.fold:
            if p.plane not in FOLD_PLANES:
                raise ValueError(
                    f"{spec.name}: unknown fold plane {p.plane!r}; known: {FOLD_PLANES}"
                )
            if p.plane == "state_delta" and not spec.needs_client_state:
                raise ValueError(f"{spec.name}: fold over state_delta without client state")
            if p.plane == "extra" and not spec.needs_full_grad:
                raise ValueError(f"{spec.name}: fold over extra without needs_full_grad")
        if not any(p.plane == "delta" for p in spec.fold):
            raise ValueError(
                f"{spec.name}: fold needs a pass over 'delta' (metrics and "
                f"post-steps consume the cohort mean)"
            )
        def identity(p):
            return (_is_static_zero(p.c_xd) and _is_static_zero(p.c_md)
                    and _is_static_one(p.c_mm))
        if spec.server_post_fn is None and all(identity(p) for p in spec.fold):
            raise ValueError(
                f"{spec.name}: every fold pass is the identity "
                f"(c_mm=1, c_md=0, c_xd=0) and there is no server_post_fn — "
                f"the server would never move; give a pass real "
                f"coefficients, or add server_post_fn / server_fn"
            )


def register_algorithm(spec_or_builder=None, *, override: bool = False):
    """Register an ``AlgorithmSpec``.  Three forms::

        register_algorithm(spec)                  # direct
        @register_algorithm                       # decorator on a zero-arg
        def _myalgo(): return AlgorithmSpec(...)  #   builder function
        register_algorithm(spec, override=True)   # replace an existing name

    Validates the spec (stream/plane names, state-flag consistency) and
    returns it.  Duplicate names raise unless ``override=True``.
    """
    if spec_or_builder is None:  # @register_algorithm(override=True)
        return lambda sb: register_algorithm(sb, override=override)
    spec = spec_or_builder() if callable(spec_or_builder) else spec_or_builder
    if not isinstance(spec, AlgorithmSpec):
        raise TypeError(f"expected AlgorithmSpec, got {type(spec).__name__}")
    _validate(spec)
    if spec.name in _REGISTRY and not override:
        raise ValueError(
            f"algorithm {spec.name!r} already registered "
            f"(pass override=True to replace)"
        )
    _REGISTRY[spec.name] = spec
    return spec


def unregister_algorithm(name: str) -> None:
    """Remove a registered algorithm (tests / interactive use)."""
    _REGISTRY.pop(name, None)


def get_algorithm(name: str) -> AlgorithmSpec:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown federated algorithm {name!r}; registered: "
            f"{sorted(_REGISTRY)} (see repro.core.registry.register_algorithm)"
        )
    return _REGISTRY[name]


def list_algorithms() -> Tuple[str, ...]:
    """Registered algorithm names, sorted."""
    return tuple(sorted(_REGISTRY))


# ----------------------------------------------------------------------
# state-plane allocation (derived from spec flags)
# ----------------------------------------------------------------------


def server_init(params, momentum_dtype="float32",
                needs_second_moment: bool = True) -> ServerState:
    """Allocate the server planes a spec requires.  The momentum plane is
    universal (it doubles as SCAFFOLD's c and FedDyn's h); the second
    moment only exists for ``needs_second_moment`` specs."""
    mdt = jnp.dtype(momentum_dtype)
    z = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, mdt), params)
    sm = tree_zeros_like(params) if needs_second_moment else None
    return ServerState(momentum=z, second_moment=sm, round=jnp.int32(0))


def client_state_init(params, cfg):
    """Stacked ``(N, …)`` per-client control variates — allocated iff the
    registered spec sets ``needs_client_state`` (new stateful algorithms
    get their planes automatically; nothing is keyed on names).

    Under an out-of-core population store (``cfg.population_store`` other
    than "resident") the per-client planes live in host memory
    (``repro.data.population``) — no ``(N, …)`` device array exists, so
    this returns None and the engine attaches the store at ``init()``."""
    if not get_algorithm(cfg.algo).needs_client_state:
        return None
    if getattr(cfg, "population_store", "resident") != "resident":
        return None
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((cfg.num_clients, *p.shape), p.dtype), params
    )


# ----------------------------------------------------------------------
# routing description (kernels/README.md table + fed_train --list-algos)
# ----------------------------------------------------------------------


def describe_algorithm(spec: AlgorithmSpec) -> Dict[str, str]:
    """Human-readable routing summary of one spec (pure function of the
    registry — the README table and ``--list-algos`` both render it)."""
    if spec.direction_fn is not None:
        direction = "custom jnp (`direction_fn`)"
    else:
        row = spec.direction_row
        terms = ["g"]
        if not _is_static_zero(row.c_x):
            terms.append("(x−x₀)")
        terms += [s for s, _ in row.aux]
        direction = f"`fed_direction` affine: {' + '.join(terms)}"
    if spec.server_fn is not None:
        server = "custom jnp (`server_fn`)"
    else:
        server = f"`server_update` ×{len(spec.fold)}"
        if spec.server_post_fn is not None:
            server += " + post"
    planes = [
        flag for flag, on in (
            ("client_state", spec.needs_client_state),
            ("momentum_bcast", spec.needs_momentum_broadcast),
            ("full_grad", spec.needs_full_grad),
            ("second_moment", spec.needs_second_moment),
        ) if on
    ] or ["—"]
    wire = spec.wire_uplink_planes
    comp = spec.uplink_compression or "f32"
    if spec.needs_residual:
        comp += " + residual"
    return {
        "algorithm": spec.name,
        "local step": direction,
        "server fold": server,
        "state planes": ", ".join(planes),
        # §4.2 payload accounting: planes that cross the client→server wire
        "uplink": f"{len(wire)}×P ({'+'.join(wire)})",
        # spec-declared default wire format (cfg.compression overrides)
        "wire": comp,
    }


def routing_table_md() -> str:
    """The per-algorithm routing table as markdown, generated FROM the
    registry (tests/test_registry.py asserts kernels/README.md embeds this
    verbatim — regenerate with ``python -m repro.core.registry --write``)."""
    rows = [describe_algorithm(get_algorithm(n)) for n in list_algorithms()]
    cols = ["algorithm", "local step", "server fold", "state planes",
            "uplink", "wire"]
    widths = {c: max(len(c), *(len(r[c]) for r in rows)) for c in cols}
    fmt = lambda r: "| " + " | ".join(r[c].ljust(widths[c]) for c in cols) + " |"
    head = fmt({c: c for c in cols})
    sep = "|" + "|".join("-" * (widths[c] + 2) for c in cols) + "|"
    return "\n".join([head, sep] + [fmt(r) for r in rows])


README_BEGIN = "<!-- registry-routing:begin (generated by repro.core.registry) -->"
README_END = "<!-- registry-routing:end -->"


def sync_readme(write: bool = False) -> bool:
    """True if kernels/README.md embeds the current routing table; with
    ``write=True`` regenerate the block between the markers in place."""
    from pathlib import Path

    import repro.core.algorithms  # noqa: F401  (builtin specs register on import)

    readme = Path(__file__).resolve().parents[1] / "kernels" / "README.md"
    text = readme.read_text()
    block = f"{README_BEGIN}\n{routing_table_md()}\n{README_END}"
    if README_BEGIN not in text or README_END not in text:
        if not write:
            return False
        raise RuntimeError(f"{readme}: routing-table markers missing")
    start = text.index(README_BEGIN)
    end = text.index(README_END) + len(README_END)
    if text[start:end] == block:
        return True
    if write:
        readme.write_text(text[:start] + block + text[end:])
        return True
    return False


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="print (or sync into kernels/README.md) the registry routing table"
    )
    ap.add_argument("--write", action="store_true",
                    help="rewrite the README block between the markers")
    args = ap.parse_args(argv)
    # under ``python -m`` this file executes as __main__, a SEPARATE module
    # instance with its own empty _REGISTRY — delegate to the canonical
    # import (which repro.core.algorithms populates)
    from repro.core import registry as canonical

    print(canonical.routing_table_md())
    if args.write:
        canonical.sync_readme(write=True)
        print("\n(README block synced)")
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
