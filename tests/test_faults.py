"""Fault-tolerant round execution (PR-7).

Covers the three tentpole layers:

1. fault injection as pure FaultConfig data — seeded, reproducible,
   zero-rate ~ fault=None (tight tolerance; the extra traced quarantine
   ops perturb XLA's scan fusion at f32 noise level, while fault=None
   itself traces NOTHING extra and is held bitwise by the pre-existing
   trajectory suites),
2. graceful degradation — NaN/Inf quarantine equal to the fold that
   excluded the bad client (every registered algorithm), min_quorum
   skip-rounds, empty-cohort no-op (the 0/0 NaN-poisoning regression),
   host-store retry with capped backoff,
3. preemption-safe runs — atomic save_fed_run/load_fed_run snapshots
   continuing the trajectory bitwise, resident and host-store.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core.engine as engine_mod
from repro.configs.base import FaultConfig, FedConfig
from repro.checkpoint import (
    latest_step,
    load_fed_run,
    save_checkpoint,
    save_fed_run,
)
from repro.core import FederatedEngine, get_algorithm, list_algorithms
from repro.core.faults import fault_masks
from repro.data import FederatedData, StreamingClientData, make_synthetic_classification
from repro.data.population import FaultyStore, TransientStoreError
from repro.models.small import classification_loss, mlp_classifier


def _setup(algo, **kw):
    x, y, *_ = make_synthetic_classification(n_classes=4, dim=8, n_train=800, n_test=8)
    model = mlp_classifier((8, 16, 4))
    base = dict(algo=algo, num_clients=10, cohort_size=3, local_steps=2,
                participation="fixed")
    base.update(kw)
    cfg = FedConfig(**base)
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
    data = FederatedData(x, y, cfg.num_clients, seed=0)
    return cfg, eng, data, model


def _fresh_state(eng, model):
    return eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _all_finite(tree):
    return all(bool(jnp.all(jnp.isfinite(l))) for l in _leaves(tree)
               if jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating))


def _assert_trees_equal(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x1, x2 in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))


def _assert_trees_close(a, b, rtol=2e-5, atol=1e-6):
    for la, lb in zip(_leaves(a), _leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# 1. faults as config data
# ---------------------------------------------------------------------------

def test_zero_rate_fault_config_matches_fault_none():
    """All-zero rates inject nothing: same trajectory as fault=None up to
    scan-fusion noise (the quarantine guard's isfinite/where ops perturb
    XLA's reduction fusion inside lax.scan — values, not semantics)."""
    _, eng0, data, model = _setup("fedcm")
    st0, m0 = eng0.run_rounds(_fresh_state(eng0, model), data, 4)
    _, eng1, _, _ = _setup("fedcm", fault=FaultConfig())
    st1, m1 = eng1.run_rounds(_fresh_state(eng1, model), data, 4)
    _assert_trees_close(st0.params, st1.params, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(m0.n_active), np.asarray(m1.n_active))
    assert float(m1.n_dropped.sum()) == 0.0
    assert float(m1.n_quarantined.sum()) == 0.0
    assert float(m1.quorum_skipped.sum()) == 0.0


def test_fault_draws_are_reproducible_and_slot_independent():
    """The fault stream is keyed by (seed, absolute round, client id) —
    the same client gets the same fate regardless of cohort slot."""
    fault = FaultConfig(drop_rate=0.5, corrupt_rate=0.5, seed=3)
    ids = jnp.asarray([4, 7, 1])
    a = fault_masks(fault, 2, ids)
    b = fault_masks(fault, 2, ids)
    np.testing.assert_array_equal(np.asarray(a.drop), np.asarray(b.drop))
    np.testing.assert_array_equal(np.asarray(a.corrupt), np.asarray(b.corrupt))
    # permute the cohort: per-client fates permute with it
    perm = jnp.asarray([1, 7, 4])
    c = fault_masks(fault, 2, perm)
    np.testing.assert_array_equal(np.asarray(a.drop)[[2, 1, 0]], np.asarray(c.drop))
    # a different round or seed redraws
    d = fault_masks(fault, 3, ids)
    e = fault_masks(FaultConfig(drop_rate=0.5, corrupt_rate=0.5, seed=4), 2, ids)
    assert (not np.array_equal(np.asarray(a.drop), np.asarray(d.drop))
            or not np.array_equal(np.asarray(a.corrupt), np.asarray(d.corrupt))
            or not np.array_equal(np.asarray(a.drop), np.asarray(e.drop)))


@pytest.mark.parametrize("flat_plane", [True, False])
def test_lossy_uplink_run_stays_finite(flat_plane):
    """The acceptance scenario: 20% drops + 1% NaN corruption, fedcm —
    the run completes finite on the flat engine and the tree oracle."""
    fault = FaultConfig(drop_rate=0.2, corrupt_rate=0.01, seed=0)
    _, eng, data, model = _setup("fedcm", num_clients=20, cohort_size=8,
                                 participation="bernoulli", fault=fault,
                                 min_quorum=2, use_flat_plane=flat_plane)
    st, ms = eng.run_rounds(_fresh_state(eng, model), data, 8)
    assert _all_finite(st.params)
    assert _all_finite(st.server)
    assert float(ms.n_dropped.sum()) > 0
    assert np.all(np.isfinite(np.asarray(ms.loss)))


# ---------------------------------------------------------------------------
# 2. graceful degradation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", list_algorithms())
def test_quarantine_equals_excluding_the_client(algo, monkeypatch):
    """A NaN-corrupted uplink, quarantined, folds IDENTICALLY to the same
    round with that client dropped outright — for EVERY registered
    algorithm (the registry parametrizes).  Run B reroutes the corrupt
    mask into the drop mask before injection, so the same per-client
    fault stream marks the same clients; equality then says quarantine
    zeroing removed every trace of the poisoned rows from params, server
    planes, and client state."""
    fault = FaultConfig(corrupt_rate=0.5, corrupt_mode="nan", seed=5)
    _, eng_a, data, model = _setup(algo, fault=fault)
    st_a, ms_a = eng_a.run_rounds(_fresh_state(eng_a, model), data, 3)
    assert float(ms_a.n_quarantined.sum()) > 0  # the stream did corrupt
    assert _all_finite(st_a.params) and _all_finite(st_a.server)

    orig = engine_mod.fault_masks

    def rerouted(f, t, ids):
        plan = orig(f, t, ids)
        return plan._replace(drop=plan.corrupt,
                             corrupt=jnp.zeros_like(plan.corrupt))

    monkeypatch.setattr(engine_mod, "fault_masks", rerouted)
    # drop_rate>0 opens the engine's (python-level) drop branch; the
    # rerouted plan then discards the real drop draws — the corrupt
    # stream is keyed independently, so B marks exactly A's clients
    fault_b = FaultConfig(drop_rate=0.5, corrupt_rate=0.5,
                          corrupt_mode="nan", seed=5)
    _, eng_b, _, _ = _setup(algo, fault=fault_b)
    st_b, ms_b = eng_b.run_rounds(_fresh_state(eng_b, model), data, 3)
    np.testing.assert_array_equal(np.asarray(ms_a.n_active),
                                  np.asarray(ms_b.n_active))
    _assert_trees_equal(st_a.params, st_b.params)
    _assert_trees_equal(st_a.server, st_b.server)
    if get_algorithm(algo).needs_client_state:
        _assert_trees_equal(st_a.client_states, st_b.client_states)


@pytest.mark.parametrize("corrupt_mode", ["inf", "noise"])
def test_other_corruption_modes_stay_finite(corrupt_mode):
    fault = FaultConfig(corrupt_rate=0.4, corrupt_mode=corrupt_mode,
                        noise_scale=100.0, seed=1,
                        quarantine_norm_mult=3.0 if corrupt_mode == "noise" else 0.0)
    _, eng, data, model = _setup("fedcm", fault=fault)
    st, ms = eng.run_rounds(_fresh_state(eng, model), data, 4)
    assert _all_finite(st.params)
    if corrupt_mode == "inf":
        assert float(ms.n_quarantined.sum()) > 0


def test_min_quorum_skips_the_fold():
    """min_quorum above the cohort size: every fold skips, params carry
    BITWISE unchanged, and the counter reports it."""
    fault = FaultConfig(drop_rate=0.0, seed=0)
    _, eng, data, model = _setup("fedcm", fault=fault, min_quorum=99)
    st0 = _fresh_state(eng, model)
    p0 = jax.tree_util.tree_map(lambda l: np.asarray(l), st0.params)
    st, ms = eng.run_rounds(st0, data, 3)
    assert np.all(np.asarray(ms.quorum_skipped) == 1.0)
    _assert_trees_equal(p0, st.params)
    _assert_trees_equal(st.server.momentum,
                        jax.tree_util.tree_map(jnp.zeros_like, st.server.momentum))


@pytest.mark.parametrize("flat_plane", [True, False])
def test_empty_cohort_round_is_a_guarded_noop(flat_plane):
    """The empty-cohort hazard: an all-dropped cohort used to masked-mean
    0/0 → NaN params.  With allow_empty_cohort the round must be a finite
    no-op on BOTH the flat engine and the tree oracle."""
    _, eng, data, model = _setup("fedcm", dropout_rate=1.0,
                                 allow_empty_cohort=True,
                                 use_flat_plane=flat_plane)
    st0 = _fresh_state(eng, model)
    p0 = jax.tree_util.tree_map(lambda l: np.asarray(l), st0.params)
    st, ms = eng.run_rounds(st0, data, 2)
    assert np.all(np.asarray(ms.n_active) == 0.0)
    assert _all_finite(st.params)
    _assert_trees_equal(p0, st.params)  # no 0/0 poison, no partial fold


def test_allow_empty_cohort_flag_toggles_the_guard():
    """dropout_rate=1.0: the legacy guard keeps one client per round;
    allow_empty_cohort=True lets the cohort empty entirely."""
    _, eng_legacy, data, model = _setup("fedcm", dropout_rate=1.0)
    _, ms = eng_legacy.run_rounds(_fresh_state(eng_legacy, model), data, 3)
    assert np.all(np.asarray(ms.n_active) == 1.0)
    _, eng_empty, _, _ = _setup("fedcm", dropout_rate=1.0,
                                allow_empty_cohort=True)
    _, ms2 = eng_empty.run_rounds(_fresh_state(eng_empty, model), data, 3)
    assert np.all(np.asarray(ms2.n_active) == 0.0)


def _store_setup(algo, fault, num_clients=64):
    cfg = FedConfig(algo=algo, num_clients=num_clients, cohort_size=8,
                    local_steps=2, population_store="host", fault=fault)
    data = StreamingClientData(num_clients, dim=8, n_classes=4, seed=0)
    model = mlp_classifier((8, 16, 4))
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
    st = eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
    return eng, data, st


def test_store_transient_failures_are_retried():
    """FaultyStore raises TransientStoreError with host-side probability;
    the engine retries with capped backoff and counts the attempts.
    (seed=2: the chaos stream fails within the first rounds — seed=1's
    first 16 draws happen to all pass.)"""
    fault = FaultConfig(store_failure_rate=0.3, store_backoff_base=0.0, seed=2)
    eng, data, st = _store_setup("scaffold", fault)
    assert isinstance(eng.population, FaultyStore)
    st, ms = eng.run_rounds_store(st, data, 5)
    assert _all_finite(st.params)
    assert float(ms.n_retries.sum()) > 0


def test_store_retry_exhaustion_reraises():
    fault = FaultConfig(store_failure_rate=1.0, store_max_retries=2,
                        store_backoff_base=0.0, seed=0)
    eng, data, st = _store_setup("scaffold", fault)
    with pytest.raises(TransientStoreError):
        eng.run_rounds_store(st, data, 1)


def test_retries_never_change_the_math():
    """A run that needed retries is bitwise-equal to one that didn't:
    same config, chaos on vs off, identical trajectories."""
    fault_on = FaultConfig(drop_rate=0.2, store_failure_rate=0.3,
                           store_backoff_base=0.0, seed=2)
    fault_off = FaultConfig(drop_rate=0.2, store_failure_rate=0.0, seed=2)
    eng_a, data, st_a = _store_setup("scaffold", fault_on)
    st_a, ms_a = eng_a.run_rounds_store(st_a, data, 4)
    assert float(ms_a.n_retries.sum()) > 0
    eng_b, _, st_b = _store_setup("scaffold", fault_off)
    st_b, ms_b = eng_b.run_rounds_store(st_b, data, 4)
    assert float(ms_b.n_retries.sum()) == 0.0
    _assert_trees_equal(st_a.params, st_b.params)
    np.testing.assert_array_equal(
        np.asarray(eng_a.population.inner.to_pytree()["rows"]),
        np.asarray(eng_b.population.to_pytree()["rows"]))


# ---------------------------------------------------------------------------
# 3. preemption-safe runs
# ---------------------------------------------------------------------------

def test_save_checkpoint_publishes_atomically(tmp_path):
    """No .tmp residue after publish — the rename is the commit point."""
    save_checkpoint(str(tmp_path), 3, {"w": jnp.ones((4,))})
    names = os.listdir(tmp_path)
    assert "step_3.msgpack" in names
    assert not any(n.endswith(".tmp") for n in names)


def test_save_fed_run_roundtrip_resident(tmp_path):
    _, eng, data, model = _setup("fedcm")
    st, _ = eng.run_rounds(_fresh_state(eng, model), data, 2)
    save_fed_run(str(tmp_path), 2, st, meta={"note": "x"})
    restored, pop, _res, meta = load_fed_run(str(tmp_path), 2, st)
    assert meta["step"] == 2 and meta["note"] == "x" and pop is None
    _assert_trees_equal(st, restored)


def test_kill_and_resume_is_bitwise_resident():
    """6 straight rounds == 3 rounds + snapshot + restore + 3 rounds, on
    the fused scan — the trajectory continues bitwise through the
    checkpoint boundary."""
    import tempfile

    fault = FaultConfig(drop_rate=0.2, seed=1)
    _, eng, data, model = _setup("fedcm", fault=fault)
    st_full, _ = eng.run_rounds(_fresh_state(eng, model), data, 3)
    st_full, _ = eng.run_rounds(st_full, data, 3)

    st_half, _ = eng.run_rounds(_fresh_state(eng, model), data, 3)
    with tempfile.TemporaryDirectory() as d:
        save_fed_run(d, 3, st_half)
        assert latest_step(d) == 3
        st_resumed, pop, _res, _ = load_fed_run(d, None, st_half)
    st_resumed, _ = eng.run_rounds(st_resumed, data, 3)
    _assert_trees_equal(st_full, st_resumed)


def test_kill_and_resume_is_bitwise_host_store(tmp_path):
    """Same through the host population store: the snapshot carries the
    packed rows, the restore rebuilds the store, scaffold's c_i planes
    continue bitwise."""
    fault = FaultConfig(drop_rate=0.1, seed=0)
    eng_a, data, st_a = _store_setup("scaffold", fault)
    st_a, _ = eng_a.run_rounds_store(st_a, data, 4)

    eng_b, _, st_b = _store_setup("scaffold", fault)
    st_b, _ = eng_b.run_rounds_store(st_b, data, 2)
    save_fed_run(str(tmp_path), 2, st_b,
                 population=getattr(eng_b.population, "inner", eng_b.population))
    # a FRESH engine (the resumed process) restores state + store
    eng_c, _, st_c = _store_setup("scaffold", fault)
    st_c, pop, _res, meta = load_fed_run(str(tmp_path), None, st_c,
                                         num_clients=eng_c.cfg.num_clients)
    assert meta["step"] == 2 and pop is not None
    getattr(eng_c.population, "inner", eng_c.population)._rows = pop._rows
    st_c, _ = eng_c.run_rounds_store(st_c, data, 2)
    _assert_trees_equal(st_a.params, st_c.params)
    np.testing.assert_array_equal(
        np.asarray(getattr(eng_a.population, "inner", eng_a.population)
                   .to_pytree()["rows"]),
        np.asarray(getattr(eng_c.population, "inner", eng_c.population)
                   .to_pytree()["rows"]))
