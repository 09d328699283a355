"""Fused multi-round engine (engine.run_rounds / run_rounds_async) tests.

* trajectory equivalence: run_rounds(n) must reproduce the sequential
  run_round × n trajectory (params, server momentum, metrics) to tolerance
  for fedcm + fedavg + scaffold (stateful) — same rng threading, same
  round-step implementation, so the tolerance is tight.
* compile-count: N rounds execute as ONE trace of the scanned program, and
  a second call with the same shapes does not retrace.
* the flat engine (Pallas kernels): matches the tree oracle's unfused
  tree_map arithmetic (ref.py is the kernel's own oracle in test_kernels).
* client_sharding: constraining the cohort axis changes nothing numerically.
* async pipelined engine (run_rounds_async): the degenerate schedule
  (pipeline_depth=1, staleness=0) must be EXACTLY run_rounds — f32
  bitwise — for every algorithm; depth>1
  fills/folds/drains correctly; staleness>0 still converges on a
  heterogeneous quadratic toy problem.
* bf16 master plane: sequential run_round and fused run_rounds share the
  f32 master-plane carry, so their bf16 trajectories stay within
  f32-noise tolerance (the legacy per-boundary re-rounding was a bf16 ulp
  per round — the bound here would catch its return).
"""
from dataclasses import replace
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.trace import assert_trace_budget
from repro.configs.base import FedConfig
from repro.core import FederatedEngine, list_algorithms
from repro.data import FederatedData, make_synthetic_classification
from repro.models.small import classification_loss, mlp_classifier
from repro.utils.trees import tree_cast

N_ROUNDS = 5


def _setup(algo, widths=(8, 16, 4), **kw):
    x, y, *_ = make_synthetic_classification(n_classes=4, dim=8, n_train=800, n_test=8)
    model = mlp_classifier(widths)
    base = dict(algo=algo, num_clients=10, cohort_size=3, local_steps=2,
                participation="fixed")
    base.update(kw)
    cfg = FedConfig(**base)
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
    data = FederatedData(x, y, cfg.num_clients, seed=0)
    return cfg, eng, data, model


def _fresh_state(eng, model):
    return eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))


def _assert_trees_close(a, b, rtol=2e-5, atol=1e-6):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=rtol, atol=atol)


@pytest.mark.parametrize("algo", ["fedcm", "fedavg", "scaffold"])
def test_run_rounds_matches_sequential_trajectory(algo):
    cfg, eng, data, model = _setup(algo)
    st = _fresh_state(eng, model)
    seq_metrics = []
    for _ in range(N_ROUNDS):
        st, m = eng.run_round(st, data)
        seq_metrics.append(m)

    fused_st, fused_m = eng.run_rounds(_fresh_state(eng, model), data, N_ROUNDS)

    _assert_trees_close(st.params, fused_st.params)
    _assert_trees_close(st.server.momentum, fused_st.server.momentum)
    if cfg.algo == "scaffold":
        _assert_trees_close(st.client_states, fused_st.client_states)
    assert int(fused_st.server.round) == N_ROUNDS
    # stacked per-round metrics match the sequential per-round values
    assert fused_m.loss.shape == (N_ROUNDS,)
    np.testing.assert_allclose(
        np.array([float(m.loss) for m in seq_metrics]),
        np.asarray(fused_m.loss), rtol=2e-5,
    )
    np.testing.assert_allclose(
        np.array([float(m.eta_l) for m in seq_metrics]),
        np.asarray(fused_m.eta_l), rtol=1e-6,
    )
    np.testing.assert_array_equal(
        np.array([float(m.n_active) for m in seq_metrics]),
        np.asarray(fused_m.n_active),
    )


def test_run_rounds_is_one_trace_and_caches():
    """The per-path budget itself lives in repro.analysis.trace
    (TRACE_BUDGET): N rounds are ONE trace of the scan, a same-shapes
    call is cached, a new static n_rounds is one new path."""
    _, eng, data, model = _setup("fedcm")
    assert_trace_budget(
        eng, "run_rounds_traces",
        calls=[
            lambda: eng.run_rounds(_fresh_state(eng, model), data, N_ROUNDS),
            lambda: eng.run_rounds(_fresh_state(eng, model), data, N_ROUNDS),
            lambda: eng.run_rounds(_fresh_state(eng, model), data,
                                   N_ROUNDS + 1),
        ],
        expected_paths=[1, 1, 2],
    )


def test_run_rounds_rejects_nonpositive():
    _, eng, data, model = _setup("fedcm")
    with pytest.raises(ValueError):
        eng.run_rounds(_fresh_state(eng, model), data, 0)


# an MLP whose 69,804 parameters pass one fed_direction block (65,536) and
# are no multiple of it: the engine's plane aligns to 131,072
LONG_PLANE = (8, 300, 220, 4)


@pytest.mark.parametrize("algo,widths", [
    *(pytest.param(a, (8, 16, 4), id=a) for a in list_algorithms()),
    *(pytest.param(a, LONG_PLANE, id=f"{a}-long") for a in ("fedcm", "scaffold", "feddyn")),
])
def test_fused_kernel_path_matches_reference(algo, widths):
    """Flat engine + Pallas kernels (fed_direction local steps, fused
    server fold-row passes + pure post-steps) vs the tree oracle — for
    EVERY registered algorithm (the registry parametrizes), and on a
    plane past one direction block that the engine aligns."""
    cfg, engk, data, model = _setup(algo, widths)
    eng = FederatedEngine(replace(cfg, use_flat_plane=False), engk.loss_fn, batch_size=8)
    s_ref, m_ref = eng.run_rounds(_fresh_state(eng, model), data, 3)
    s_k, m_k = engk.run_rounds(_fresh_state(engk, model), data, 3)
    _assert_trees_close(s_ref.params, s_k.params, rtol=1e-5, atol=1e-6)
    _assert_trees_close(s_ref.server.momentum, s_k.server.momentum, rtol=1e-5, atol=1e-6)
    if s_ref.client_states is not None:
        _assert_trees_close(s_ref.client_states, s_k.client_states, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_ref.loss), np.asarray(m_k.loss), rtol=1e-5)


def test_fused_server_kernel_honors_aggregate_dtype():
    """Regression: the fused server kernel must quantize the uplink planes
    with cfg.aggregate_dtype before reducing, like the tree oracle does."""
    cfg, eng, data, model = _setup("fedcm")
    cfg_bf = replace(cfg, aggregate_dtype="bfloat16")
    engs = {
        "tree_bf16": FederatedEngine(replace(cfg_bf, use_flat_plane=False),
                                     eng.loss_fn, batch_size=8),
        "kern_bf16": FederatedEngine(cfg_bf, eng.loss_fn, batch_size=8),
        "kern_f32": eng,
    }
    out = {k: e.run_rounds(_fresh_state(e, model), data, 2)[0] for k, e in engs.items()}
    # bf16 aggregation in the fold kernel tracks the tree oracle's…
    _assert_trees_close(out["kern_bf16"].params, out["tree_bf16"].params,
                        rtol=2e-2, atol=2e-2)
    # …and actually differs from unquantized f32 aggregation
    diff = sum(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree_util.tree_leaves(out["kern_bf16"].params),
                        jax.tree_util.tree_leaves(out["kern_f32"].params))
    )
    assert diff > 0.0


@pytest.mark.parametrize("flat", [True, False], ids=["flat", "tree"])
def test_engine_refuses_the_retired_jnp_path(flat):
    """The flat engine has one path: ``use_fused_kernel=False`` is refused
    at construction, on either representation, instead of running a
    second engine the chip never measures."""
    cfg, eng, _, _ = _setup("fedcm")
    with pytest.raises(ValueError, match="one path"):
        FederatedEngine(replace(cfg, use_fused_kernel=False, use_flat_plane=flat),
                        eng.loss_fn, batch_size=8)


def test_client_sharding_constraint_is_numerically_inert():
    cfg, eng, data, model = _setup("fedcm")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    engs = FederatedEngine(
        cfg, eng.loss_fn, batch_size=8,
        client_sharding=NamedSharding(mesh, P("data")),
    )
    s_ref, _ = eng.run_rounds(_fresh_state(eng, model), data, 3)
    s_sh, _ = engs.run_rounds(_fresh_state(engs, model), data, 3)
    _assert_trees_close(s_ref.params, s_sh.params, rtol=1e-5, atol=1e-7)
    # per-round path honors the constraint too
    st = _fresh_state(engs, model)
    st, m = engs.run_round(st, data)
    assert np.isfinite(float(m.loss))


def test_run_rounds_bernoulli_participation():
    """Masked (bernoulli) cohorts also survive the fused path."""
    cfg, eng, data, model = _setup("fedcm", participation="bernoulli",
                                   num_clients=20, cohort_size=5)
    st, ms = eng.run_rounds(_fresh_state(eng, model), data, 4)
    assert np.all(np.asarray(ms.n_active) >= 1)
    for leaf in jax.tree_util.tree_leaves(st.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


# ----------------------------------------------------------------------
# async pipelined engine (run_rounds_async)
# ----------------------------------------------------------------------


def _assert_state_equal(a, b, check_master=False):
    """f32-exact (bitwise) equality of two FedStates' learned state."""
    pairs = [(a.params, b.params), (a.server.momentum, b.server.momentum),
             (a.client_states, b.client_states)]
    if check_master:
        pairs.append((a.master, b.master))
    for ta, tb in pairs:
        for la, lb in zip(jax.tree_util.tree_leaves(ta), jax.tree_util.tree_leaves(tb)):
            np.testing.assert_array_equal(
                np.asarray(la, np.float32), np.asarray(lb, np.float32)
            )


@pytest.mark.parametrize("algo", list_algorithms())
def test_async_depth1_is_exactly_run_rounds(algo):
    """run_rounds_async(D=1, S=0) IS the sync schedule: EVERY registered
    algorithm's trajectory AND per-round metrics must match run_rounds
    f32-EXACTLY (bitwise) — the ring degenerates to push-then-pop of the
    same slot."""
    cfg, eng, data, model = _setup(algo)
    s_sync, m_sync = eng.run_rounds(_fresh_state(eng, model), data, N_ROUNDS)
    s_async, m_async = eng.run_rounds_async(
        _fresh_state(eng, model), data, N_ROUNDS, pipeline_depth=1, staleness=0
    )
    _assert_state_equal(s_sync, s_async)
    assert int(s_async.server.round) == N_ROUNDS
    for field in ("loss", "n_active", "delta_norm", "momentum_norm",
                  "eta_l", "bytes_down", "bytes_up"):
        np.testing.assert_array_equal(
            np.asarray(getattr(m_sync, field)),
            np.asarray(getattr(m_async, field)), err_msg=field,
        )
    assert np.all(np.asarray(m_async.folded) == 1.0)


@pytest.mark.parametrize("algo", ["fedcm", "scaffold", "fedadam"])
def test_async_depth1_kernel_path_is_exactly_run_rounds(algo):
    """Same degenerate-schedule contract over 3 rounds (the
    staleness-discount SMEM scalar is 1.0 there — must stay exact).
    fedadam covers a spec whose round-close is fold pass + pure post."""
    cfg, eng, data, model = _setup(algo)
    s_sync, _ = eng.run_rounds(_fresh_state(eng, model), data, 3)
    s_async, _ = eng.run_rounds_async(
        _fresh_state(eng, model), data, 3, pipeline_depth=1, staleness=0
    )
    _assert_state_equal(s_sync, s_async)


@pytest.mark.parametrize("algo,shards", [
    ("fedcm", 0), ("scaffold", 0), ("fedcm", 1), ("scaffold", 1),
], ids=["fedcm", "scaffold", "fedcm-shard1", "scaffold-shard1"])
def test_async_pipeline_fill_fold_drain(algo, shards):
    """D>1: the first D−1 rounds launch without folding (pipeline fill),
    every later round folds exactly one cohort, and the drain applies the
    leftover in-flight work (drain=False must differ — work discarded).
    scaffold folds the ring's client-state planes into the (N, P) scatter;
    ``cohort_shard=1`` carries the sharded pass's padded ring entries."""
    cfg, eng, data, model = _setup(algo, cohort_shard=shards)
    D = 3
    st, ms = eng.run_rounds_async(_fresh_state(eng, model), data, 6,
                                  pipeline_depth=D, staleness=0)
    folded = np.asarray(ms.folded)
    np.testing.assert_array_equal(folded, [0, 0, 1, 1, 1, 1])
    assert np.all(np.asarray(ms.delta_norm)[:D - 1] == 0.0)
    assert np.all(np.asarray(ms.delta_norm)[D - 1:] > 0.0)
    assert int(st.server.round) == 6
    st_nodrain, _ = eng.run_rounds_async(_fresh_state(eng, model), data, 6,
                                         pipeline_depth=D, staleness=0,
                                         drain=False)
    diff = sum(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree_util.tree_leaves(st.params),
                               jax.tree_util.tree_leaves(st_nodrain.params)))
    assert diff > 0.0
    for s in (st, st_nodrain):
        for leaf in jax.tree_util.tree_leaves(s.params):
            assert bool(jnp.all(jnp.isfinite(leaf)))


def test_async_shorter_than_warmup_run():
    """n_rounds < D−1: nothing ever folds in-scan — the whole run is
    unrolled pipeline fill and the ring holds every launch; the drain must
    still apply each of them (in launch order)."""
    cfg, eng, data, model = _setup("fedcm")
    st, ms = eng.run_rounds_async(_fresh_state(eng, model), data, 2,
                                  pipeline_depth=4, staleness=0)
    np.testing.assert_array_equal(np.asarray(ms.folded), [0, 0])
    # both launched cohorts were drained: params moved off the init point
    st0 = _fresh_state(eng, model)
    diff = sum(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree_util.tree_leaves(st.params),
                               jax.tree_util.tree_leaves(st0.params)))
    assert diff > 0.0


def test_async_requires_flat_plane_and_validates_args():
    cfg, eng, data, model = _setup("fedcm")
    eng_tree = FederatedEngine(replace(cfg, use_flat_plane=False),
                               eng.loss_fn, batch_size=8)
    with pytest.raises(ValueError, match="use_flat_plane"):
        eng_tree.run_rounds_async(_fresh_state(eng_tree, model), data, 2)
    with pytest.raises(ValueError):
        eng.run_rounds_async(_fresh_state(eng, model), data, 0)
    with pytest.raises(ValueError):
        eng.run_rounds_async(_fresh_state(eng, model), data, 2, pipeline_depth=0)
    with pytest.raises(ValueError):
        eng.run_rounds_async(_fresh_state(eng, model), data, 2, staleness=-1)
    with pytest.raises(ValueError, match="eval_every"):
        eng.run_rounds_async(_fresh_state(eng, model), data, 2, eval_every=1)


def test_async_is_one_trace_and_caches():
    """Async budget pinned through the same repro.analysis.trace checker:
    same statics are cached, a new static depth is one new path."""
    _, eng, data, model = _setup("fedcm")
    assert_trace_budget(
        eng, "run_rounds_async_traces",
        calls=[
            lambda: eng.run_rounds_async(_fresh_state(eng, model), data, 4,
                                         pipeline_depth=2),
            lambda: eng.run_rounds_async(_fresh_state(eng, model), data, 4,
                                         pipeline_depth=2),
            lambda: eng.run_rounds_async(_fresh_state(eng, model), data, 4,
                                         pipeline_depth=4),
        ],
        expected_paths=[1, 1, 2],
    )


def test_async_inscan_eval_cadence():
    """eval_every moves eval inside the scan: accuracies appear exactly on
    cadence, −1.0 sentinels elsewhere, and the on-cadence values agree
    with the host-side make_eval_fn on the same params."""
    from repro.core import make_eval_fn

    cfg, eng, data, model = _setup("fedcm")
    x_te = np.asarray(data.client_x.reshape(-1, data.client_x.shape[-1]))[:64]
    y_te = np.asarray(data.client_y.reshape(-1))[:64]
    st, ms = eng.run_rounds_async(
        _fresh_state(eng, model), data, 6, pipeline_depth=2, eval_every=3,
        eval_data=(x_te, y_te), predict_fn=model.apply, eval_batch_size=16,
    )
    accs = np.asarray(ms.eval_acc)
    on = np.arange(6) % 3 == 2
    assert np.all(accs[~on] == -1.0)
    assert np.all(accs[on] >= 0.0)
    # NOTE: in-scan eval sees the pre-drain params of its round; the final
    # on-cadence eval runs at t=5 BEFORE the drain fold, so compare
    # against the no-drain trajectory's params
    st_nodrain, _ = eng.run_rounds_async(
        _fresh_state(eng, model), data, 6, pipeline_depth=2, drain=False
    )
    host_eval = make_eval_fn(model.apply, batch_size=16)
    np.testing.assert_allclose(
        accs[-1], host_eval(st_nodrain.params, x_te, y_te), rtol=1e-6
    )


def _quadratic_setup(staleness_discount=1.0, **cfg_kw):
    """Heterogeneous quadratic toy: client i holds points around its own
    center c_i; loss(w, batch) = ½·mean‖w − x‖² so the global optimum is
    the mean of all client centers.  Convergence here isolates the round
    machinery from model nonconvexity."""
    rng = np.random.default_rng(0)
    N, n_per, d = 12, 32, 6
    # heterogeneous client centers around a NONZERO global mean — the
    # zeros init must be far from w* so convergence is measurable
    centers = 3.0 + rng.normal(size=(N, 1, d)) * 2.0
    pts = centers + 0.1 * rng.normal(size=(N, n_per, d))
    data = SimpleNamespace(client_x=jnp.asarray(pts, jnp.float32),
                           client_y=jnp.zeros((N, n_per), jnp.int32))

    def quad_loss(params, batch):
        diff = params["w"][None, :] - batch["x"]
        return 0.5 * jnp.mean(jnp.sum(diff * diff, axis=-1))

    base = dict(algo="fedcm", num_clients=N, cohort_size=4, local_steps=4,
                participation="fixed", eta_l=0.2, eta_l_decay=1.0,
                weight_decay=0.0, staleness_discount=staleness_discount)
    base.update(cfg_kw)
    cfg = FedConfig(**base)
    eng = FederatedEngine(cfg, quad_loss, batch_size=8)
    w_star = np.asarray(pts.reshape(-1, d).mean(axis=0))
    state = eng.init({"w": jnp.zeros((d,), jnp.float32)}, jax.random.PRNGKey(3))
    return eng, data, state, w_star


@pytest.mark.parametrize("depth,stale", [(2, 1), (4, 2)])
def test_async_staleness_converges_on_quadratic(depth, stale):
    """Staleness>0 convergence smoke (the paper's robustness claim carried
    to the async schedule): overlapped cohorts descending against stale
    momentum still drive the quadratic toy to its optimum."""
    eng, data, state, w_star = _quadratic_setup(staleness_discount=0.9)
    d0 = float(np.linalg.norm(w_star))  # ‖w_0 − w*‖, w_0 = 0
    state, ms = eng.run_rounds_async(state, data, 80, pipeline_depth=depth,
                                     staleness=stale)
    w = np.asarray(state.params["w"])
    assert np.all(np.isfinite(w))
    d_final = float(np.linalg.norm(w - w_star))
    assert d_final < 0.15 * d0, (d_final, d0)
    # and the loss decayed toward the minibatch-variance floor
    losses = np.asarray(ms.loss)
    assert losses[-1] < 0.25 * losses[0]


# ----------------------------------------------------------------------
# bf16 master plane (run_round vs run_rounds divergence regression)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("algo", ["fedcm", "scaffold"])
def test_bf16_run_round_matches_run_rounds_master_plane(algo):
    """Sequential run_round must continue the SAME f32 master planes the
    run_rounds scan carries (FedState.master), so their bf16 trajectories
    stay within an occasional single-ulp bf16 rounding flip of each other
    (f32-level noise pushed across a rounding boundary; ≤5e-4 here).  The
    legacy behaviour re-rounded the carried state to bf16 at EVERY
    run_round boundary — a ~4e-3 divergence that this bound would catch
    coming back.  scaffold adds the (N, P) master client-state plane."""
    cfg, eng, data, model = _setup(algo)
    p_bf16 = tree_cast(model.init(jax.random.PRNGKey(0)), jnp.bfloat16)

    st = eng.init(p_bf16, jax.random.PRNGKey(1))
    assert st.master is not None  # sub-f32 leaves attach the master planes
    assert (st.master.client_states is not None) == (algo == "scaffold")
    for _ in range(4):
        st, _ = eng.run_round(st, data)
    st_f, _ = eng.run_rounds(eng.init(p_bf16, jax.random.PRNGKey(1)), data, 4)
    assert st_f.master is not None
    for a, b in zip(jax.tree_util.tree_leaves((st.params, st.server.momentum,
                                               st.client_states)),
                    jax.tree_util.tree_leaves((st_f.params, st_f.server.momentum,
                                               st_f.client_states))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0, atol=5e-4)

    # the re-rounding contract can't silently widen: stripping the master
    # (the legacy behaviour) must show the bf16-ulp boundary divergence
    st_legacy = eng.init(p_bf16, jax.random.PRNGKey(1))._replace(master=None)
    for _ in range(4):
        st_legacy, _ = eng.run_round(st_legacy, data)
        st_legacy = st_legacy._replace(master=None)
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
               for a, b in zip(jax.tree_util.tree_leaves(st_legacy.params),
                               jax.tree_util.tree_leaves(st_f.params)))
    assert diff > 5e-4, diff


def test_f32_states_carry_no_master():
    """All-f32 trees must NOT pay for the master planes (the ravel is
    exact; treedef stability keeps the trace cache warm)."""
    cfg, eng, data, model = _setup("fedcm")
    st = eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
    assert st.master is None
    st, _ = eng.run_round(st, data)
    assert st.master is None
    st, _ = eng.run_rounds(st, data, 2)
    assert st.master is None
