"""Algorithm registry (repro.core.registry): the AlgorithmSpec API.

* registry contents + lookup errors name the registered set
* register_algorithm validation rejects inconsistent specs (unknown
  streams/planes, state flags without the machinery they promise)
* a custom spec registered at runtime — including full escape hatches
  (direction_fn + server_fn) — runs on every engine path with zero
  engine changes, and its state planes are allocated from its flags
* the new pure-spec algorithms (fedavgm / fedadagrad / fedyogi / fedacg)
  have the semantics their papers define (hand-checked single-round math
  + convergence), and fedavgm degenerates to fedavg at α = 1 exactly
* the kernels/README.md routing table is GENERATED from the registry —
  the test holds the file and `routing_table_md()` to byte agreement
"""
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.core import (
    AlgorithmSpec,
    DirectionRow,
    FederatedEngine,
    FoldPass,
    describe_algorithm,
    get_algorithm,
    list_algorithms,
    register_algorithm,
    unregister_algorithm,
)
from repro.data import FederatedData, make_synthetic_classification
from repro.models.small import classification_loss, mlp_classifier

BUILTINS = ("fedavg", "fedcm", "fedadam", "scaffold", "feddyn", "mimelite",
            "fedavgm", "fedadagrad", "fedyogi", "fedacg")


# ----------------------------------------------------------------------
# registry mechanics
# ----------------------------------------------------------------------


def test_builtins_registered():
    names = list_algorithms()
    for n in BUILTINS:
        assert n in names, n
    assert names == tuple(sorted(names))


def test_get_algorithm_unknown_names_registry():
    with pytest.raises(KeyError, match="fedcm"):
        get_algorithm("sgd")


def test_duplicate_registration_rejected_unless_override():
    spec = get_algorithm("fedavg")
    with pytest.raises(ValueError, match="already registered"):
        register_algorithm(spec)
    assert register_algorithm(spec, override=True) is spec  # idempotent replace


@pytest.mark.parametrize("bad,match", [
    (dict(name="x", direction_row=DirectionRow(aux=(("nope", 1.0),))),
     "unknown direction stream"),
    (dict(name="x", direction_row=DirectionRow(aux=(("client_state", 1.0),))),
     "needs_client_state"),
    (dict(name="x", direction_row=DirectionRow(aux=(("momentum", 1.0),))),
     "needs_momentum_broadcast"),
    (dict(name="x", needs_client_state=True), "state_update_fn"),
    (dict(name="x", client_state_uplink=True), "without client state"),
    (dict(name="x", fold=(FoldPass("nope"),)), "unknown fold plane"),
    (dict(name="x", fold=(FoldPass("state_delta"),)), "without client state"),
    (dict(name="x", fold=(FoldPass("extra"),)), "without needs_full_grad"),
    (dict(name="x", fold=()), "escape hatch"),
    # a bare spec's default fold is the identity — the server would never
    # move; registration must refuse rather than silently freeze training
    (dict(name="x"), "never move"),
    (dict(name="x", fold=(FoldPass("delta", c_mm=1.0, c_md=0.0, c_xd=0.0),)),
     "never move"),
    (dict(name="x", direction_fn=lambda *a: a), "exactly one of"),
    (dict(name="x", momentum_store="bf16"), "momentum_store"),
])
def test_spec_validation(bad, match):
    with pytest.raises(ValueError, match=match):
        register_algorithm(AlgorithmSpec(**bad))


def test_state_plane_flags_drive_allocation():
    """FedState allocation is derived from the spec flags: stateless specs
    carry NO second-moment / client-state planes at all."""
    x, y, *_ = make_synthetic_classification(n_classes=4, dim=8, n_train=200, n_test=8)
    model = mlp_classifier((8, 8, 4))
    for algo, wants_v, wants_cst in [("fedcm", False, False),
                                     ("fedadagrad", True, False),
                                     ("scaffold", False, True)]:
        cfg = FedConfig(algo=algo, num_clients=4, cohort_size=2, local_steps=1)
        eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
        st = eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
        assert (st.server.second_moment is not None) == wants_v, algo
        assert (st.client_states is not None) == wants_cst, algo


# ----------------------------------------------------------------------
# custom registration: new algorithms are data, the engine never changes
# ----------------------------------------------------------------------


def _toy_setup(algo, **kw):
    x, y, *_ = make_synthetic_classification(n_classes=4, dim=8, n_train=800, n_test=8)
    model = mlp_classifier((8, 16, 4))
    base = dict(algo=algo, num_clients=10, cohort_size=3, local_steps=2,
                participation="fixed")
    base.update(kw)
    cfg = FedConfig(**base)
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
    data = FederatedData(x, y, cfg.num_clients, seed=0)
    return cfg, eng, data, model


def _fresh(eng, model):
    return eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))


def _close(a, b, atol=1e-5, rtol=1e-5):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=rtol, atol=atol)


def test_custom_affine_spec_runs_every_path():
    """A brand-new affine spec (declared as pure data) passes the same
    flat-vs-tree / async-bitwise contracts as the builtins."""
    register_algorithm(AlgorithmSpec(
        name="_test_damped",
        # damped SGD with a proximal pull toward the round anchor
        direction_row=DirectionRow(c_g=0.7, c_x=0.05),
        fold=(FoldPass("delta", c_mm=0.0,
                       c_md=lambda cfg, e, n: -1.0 / (e * cfg.local_steps),
                       c_xd=lambda cfg, e, n: cfg.eta_g),),
    ), override=True)
    try:
        cfg, eng, data, model = _toy_setup("_test_damped")
        eng_tree = FederatedEngine(replace(cfg, use_flat_plane=False),
                                   eng.loss_fn, batch_size=8)
        s_f, _ = eng.run_rounds(_fresh(eng, model), data, 3)
        s_t, _ = eng_tree.run_rounds(_fresh(eng_tree, model), data, 3)
        s_a, _ = eng.run_rounds_async(_fresh(eng, model), data, 3,
                                      pipeline_depth=1, staleness=0)
        _close(s_f.params, s_t.params)
        for a, b in zip(jax.tree_util.tree_leaves(s_f.params),
                        jax.tree_util.tree_leaves(s_a.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        unregister_algorithm("_test_damped")


def test_custom_escape_hatch_spec_runs_both_paths():
    """Full escape hatches (non-affine direction_fn + server_fn) still ride
    every engine path; on the flat plane the server takes the jnp
    reduction of the (C, P) planes instead of the fold kernel."""
    def sign_dir(cfg, m, cst, x, x0, g):
        return jax.tree_util.tree_map(jnp.sign, g)

    def avg_server(cfg, params, st, mean_delta, mean_sd, mean_extra, n_active, eta_l):
        new = jax.tree_util.tree_map(lambda p, d: p + cfg.eta_g * d,
                                     params, mean_delta)
        return new, st._replace(round=st.round + 1)

    register_algorithm(AlgorithmSpec(
        name="_test_signsgd", direction_row=None, direction_fn=sign_dir,
        fold=(), server_fn=avg_server,
    ), override=True)
    try:
        cfg, eng, data, model = _toy_setup("_test_signsgd")
        eng_tree = FederatedEngine(replace(cfg, use_flat_plane=False),
                                   eng.loss_fn, batch_size=8)
        s_f, m_f = eng.run_rounds(_fresh(eng, model), data, 3)
        s_t, _ = eng_tree.run_rounds(_fresh(eng_tree, model), data, 3)
        _close(s_f.params, s_t.params)
        assert np.all(np.isfinite(np.asarray(m_f.loss)))
    finally:
        unregister_algorithm("_test_signsgd")


# ----------------------------------------------------------------------
# new pure-spec algorithms: semantics
# ----------------------------------------------------------------------


def quad_loss(params, batch):
    c = batch["c"]  # (B, 2) — rows identical per client
    return 0.5 * jnp.mean(jnp.sum((params["x"][None] - c) ** 2, axis=-1))


def _quad_round(algo_name, params, centers, K=1, **cfg_kw):
    base = dict(algo=algo_name, num_clients=4, cohort_size=4, local_steps=K,
                alpha=0.5, eta_l=0.1, eta_g=1.0, weight_decay=0.0,
                eta_l_decay=1.0, participation="fixed")
    base.update(cfg_kw)
    cfg = FedConfig(**base)
    eng = FederatedEngine(cfg, quad_loss, batch_size=2)
    state = eng.init(params, jax.random.PRNGKey(0))
    C = centers.shape[0]
    batches = {"c": jnp.broadcast_to(centers[:, None, None, :], (C, K, 2, 2))}
    new, m = eng.round_step(state, batches, jnp.arange(4), jnp.ones(4, bool))
    return cfg, state, new, m


def test_fedavgm_server_heavy_ball_math():
    """Round 0 (m=0): FedAvgM's step equals FedAvg's; round 1 adds β·m."""
    params = {"x": jnp.array([1.0, -2.0])}
    centers = jnp.array([[0.0, 0.0], [2.0, 2.0], [1.0, 1.0], [-1.0, 3.0]])
    cfg, old, new, _ = _quad_round("fedavgm", params, centers, K=1, alpha=0.5)
    g = np.mean(np.asarray(params["x"])[None] - np.asarray(centers), axis=0)
    # m_1 = (1−α)·0 + pg = pg = g (K=1, plain-SGD clients); x − η_g·η_l·m
    np.testing.assert_allclose(np.asarray(new.params["x"]),
                               np.asarray(params["x"]) - cfg.eta_g * cfg.eta_l * g,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new.server.momentum["x"]), g, rtol=1e-5)


def test_fedavgm_alpha1_is_fedavg():
    """α = 1 kills the momentum carry-over: FedAvgM degenerates to FedAvg
    (to f32 reassociation — FedAvg steps x + η_g·mean in the fold row,
    FedAvgM steps x − η_g·η_l·K·m' in its post, algebraically equal)."""
    cfg, eng, data, model = _toy_setup("fedavgm", alpha=1.0)
    eng_avg = FederatedEngine(replace(cfg, algo="fedavg"), eng.loss_fn, batch_size=8)
    s_m, _ = eng.run_rounds(_fresh(eng, model), data, 4)
    s_a, _ = eng_avg.run_rounds(_fresh(eng_avg, model), data, 4)
    _close(s_m.params, s_a.params, atol=1e-6, rtol=1e-5)


def test_fedadagrad_accumulates_second_moment():
    """v is monotone non-decreasing (no decay) — the Adagrad signature —
    and the step uses the adaptive denominator."""
    params = {"x": jnp.array([10.0, 10.0])}
    centers = jnp.broadcast_to(jnp.zeros(2), (4, 2))
    cfg, old, new, _ = _quad_round("fedadagrad", params, centers, K=1, alpha=0.5)
    g = np.asarray(params["x"])  # ∇ = x − 0
    # m = α·g; v = 0 + g²; step = η_g·α·g/(|g| + τ)
    expect = cfg.eta_g * cfg.alpha * g / (np.abs(g) + cfg.adam_tau)
    np.testing.assert_allclose(
        np.asarray(old.params["x"]) - np.asarray(new.params["x"]), expect, rtol=1e-5
    )
    v1 = np.asarray(new.server.second_moment["x"])
    np.testing.assert_allclose(v1, g**2, rtol=1e-5)
    # second round: v only grows (snapshot before run_rounds donates st)
    _, eng, data, model = _toy_setup("fedadagrad")
    st, _ = eng.run_rounds(_fresh(eng, model), data, 1)
    v_prev = [np.array(l) for l in jax.tree_util.tree_leaves(st.server.second_moment)]
    st2, _ = eng.run_rounds(st, data, 1)
    for a, b in zip(v_prev, jax.tree_util.tree_leaves(st2.server.second_moment)):
        assert np.all(np.asarray(b) >= a - 1e-12)


def test_fedyogi_differs_from_fedadagrad_only_in_v():
    """Same fold row, different v rule: first round from v=0 they agree in
    m but diverge in v (yogi's sign-controlled update)."""
    params = {"x": jnp.array([3.0, -4.0])}
    centers = jnp.array([[0.0, 1.0], [1.0, 0.0], [-1.0, 0.0], [0.0, -1.0]])
    _, _, new_a, _ = _quad_round("fedadagrad", params, centers, K=1)
    _, _, new_y, _ = _quad_round("fedyogi", params, centers, K=1)
    np.testing.assert_allclose(np.asarray(new_a.server.momentum["x"]),
                               np.asarray(new_y.server.momentum["x"]), rtol=1e-6)
    assert not np.allclose(np.asarray(new_a.server.second_moment["x"]),
                           np.asarray(new_y.server.second_moment["x"]))


def test_fedacg_lookahead_step():
    """Round 0 (m=0): m_1 = pg, step = η_g·η_l·K·(pg + λ·m_1) — the
    Nesterov lookahead makes the first step (1+λ)× FedAvg's."""
    params = {"x": jnp.array([1.0, -2.0])}
    centers = jnp.array([[0.0, 0.0], [2.0, 2.0], [1.0, 1.0], [-1.0, 3.0]])
    cfg, old, new, _ = _quad_round("fedacg", params, centers, K=1, acg_lambda=0.5)
    g = np.mean(np.asarray(params["x"])[None] - np.asarray(centers), axis=0)
    expect = cfg.eta_g * cfg.eta_l * (1.0 + cfg.acg_lambda) * g
    np.testing.assert_allclose(
        np.asarray(old.params["x"]) - np.asarray(new.params["x"]), expect, rtol=1e-5
    )


@pytest.mark.parametrize("algo,kw,rounds", [
    ("fedavgm", dict(alpha=0.5), 40),
    # adagrad's denominator only accumulates — give it the paper's
    # absolute server lr and enough rounds for the 1/√T tail
    ("fedadagrad", dict(alpha=0.5, eta_g=1.0), 120),
    ("fedyogi", dict(alpha=0.5, eta_g=0.3), 40),
    ("fedacg", dict(acg_lambda=0.5), 40),
])
def test_new_algorithms_descend_on_convex(algo, kw, rounds):
    centers = jnp.array([[1.0, 0.0], [0.0, 1.0], [-1.0, 0.0], [0.0, -1.0]])
    target = np.mean(np.asarray(centers), axis=0)
    base = dict(algo=algo, num_clients=4, cohort_size=4, local_steps=4,
                eta_l=0.1, eta_g=1.0, weight_decay=0.0, eta_l_decay=1.0,
                participation="fixed")
    base.update(kw)
    cfg = FedConfig(**base)
    eng = FederatedEngine(cfg, quad_loss, batch_size=2)
    state = eng.init({"x": jnp.array([6.0, -6.0])}, jax.random.PRNGKey(0))
    batches = {"c": jnp.broadcast_to(centers[:, None, None, :], (4, 4, 2, 2))}
    ids, mask = jnp.arange(4), jnp.ones(4, bool)
    d0 = float(jnp.linalg.norm(state.params["x"] - jnp.asarray(target)))
    for _ in range(rounds):
        state, _ = eng.round_step(state, batches, ids, mask)
    d1 = float(jnp.linalg.norm(state.params["x"] - jnp.asarray(target)))
    assert d1 < 0.2 * d0, (algo, d0, d1)


def test_new_algorithms_payload_is_fedavg_shaped():
    """The family additions are all server-side: §4.2 accounting must
    charge them exactly FedAvg's wire footprint (derived from the flags)."""
    from repro.utils.trees import tree_bytes

    model = mlp_classifier((8, 16, 4))
    params = model.init(jax.random.PRNGKey(0))
    P = tree_bytes(params)
    for algo in ("fedavgm", "fedadagrad", "fedyogi", "fedacg"):
        cfg = FedConfig(algo=algo)
        eng = FederatedEngine(cfg, classification_loss(model.apply))
        pay = eng.payload_bytes(params)
        assert pay == {"down_per_client": P, "up_per_client": P}, algo


# ----------------------------------------------------------------------
# README routing table is generated from the registry
# ----------------------------------------------------------------------


def test_readme_routing_table_matches_registry():
    """kernels/README.md embeds `routing_table_md()` verbatim between the
    generation markers — regenerate with
    ``PYTHONPATH=src python -m repro.core.registry --write``."""
    from repro.core.registry import sync_readme

    assert sync_readme(write=False), (
        "kernels/README.md routing table is stale — run "
        "`PYTHONPATH=src python -m repro.core.registry --write`"
    )


def test_describe_algorithm_rows():
    d = describe_algorithm(get_algorithm("scaffold"))
    assert d["algorithm"] == "scaffold"
    assert "client_state" in d["local step"]
    assert "×2" in d["server fold"]
    assert "client_state" in d["state planes"]
    d = describe_algorithm(get_algorithm("fedadam"))
    assert "post" in d["server fold"]
    assert "second_moment" in d["state planes"]
