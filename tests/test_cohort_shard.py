"""Cohort-parallel execution engine (shard_map over the client axis).

The contract under test: a ``("clients",)`` mesh changes WHERE the cohort
executes — each device owns C/num_shards clients end-to-end and the
server fold becomes an explicit reduce-scatter/all-gather — and nothing
about WHAT is computed:

* f32-BITWISE equivalence against the unsharded flat+kernel engine,
  parametrized over every registered algorithm, for the sync scan and the
  ``(D=2, S=1)`` async pipelined scan.  Bitwise is by construction: the
  scattered fold transposes clients→plane-columns (``all_to_all``) and
  reduces over the COMPLETE cohort device-locally in the unsharded
  reduction order (a ``psum_scatter`` would pre-reduce per device and
  re-associate), and the server kernel's ≥2-step grid floor keeps its
  loop-body codegen shape-stable across shard widths.
* ragged cohorts (C not a multiple of the device count) pad with
  zero-weight rows AFTER the gathers — rng stream untouched, trailing
  ``+0.0`` fold terms exact, pad ids dropped before the client-state
  scatter (a pad id colliding with a real cohort member would make the
  duplicate-index scatter nondeterministic).

Single-device runs exercise the FULL sharded path on a 1-device mesh
(shard_map, all_to_all, scattered fold all run degenerately), so tier-1
covers the machinery; the multi-device cases skip unless the process was
started with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
multi-device CI job does).
"""
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.core import FederatedEngine, list_algorithms
from repro.core.registry import (
    AlgorithmSpec,
    DirectionRow,
    FoldPass,
    register_algorithm,
    unregister_algorithm,
)
from repro.data import FederatedData, make_synthetic_classification
from repro.launch.mesh import make_cohort_mesh
from repro.models.small import classification_loss, mlp_classifier
from repro.sharding.rules import cohort_axis_size, padded_cohort

N_DEV = len(jax.devices())
multidevice = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs 8 devices: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8",
)

_DATA = {}


def _data(num_clients):
    if num_clients not in _DATA:
        x, y, *_ = make_synthetic_classification(
            n_classes=4, dim=8, n_train=3200, n_test=8
        )
        _DATA[num_clients] = FederatedData(x, y, num_clients, seed=0)
    return _DATA[num_clients]


_MODEL = mlp_classifier((8, 16, 4))
_LOSS = classification_loss(_MODEL.apply)


def _engine(algo, n_shards, cohort=16, participation="fixed", **kw):
    cfg = FedConfig(algo=algo, num_clients=32, cohort_size=cohort,
                    local_steps=2, participation=participation, **kw)
    mesh = make_cohort_mesh(n_shards) if n_shards else None
    eng = FederatedEngine(cfg, _LOSS, batch_size=8, cohort_mesh=mesh)
    state = eng.init(_MODEL.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
    return eng, state


def _assert_tree_bitwise(a, b, what=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=what)


def _assert_state_bitwise(sharded, ref):
    _assert_tree_bitwise(sharded.params, ref.params, "params")
    _assert_tree_bitwise(sharded.server.momentum, ref.server.momentum, "momentum")
    if ref.server.second_moment is not None:
        _assert_tree_bitwise(sharded.server.second_moment,
                             ref.server.second_moment, "second_moment")
    if ref.client_states is not None:
        _assert_tree_bitwise(sharded.client_states, ref.client_states,
                             "client_states")


# ----------------------------------------------------------------------
# construction / validation (single-device)
# ----------------------------------------------------------------------


def test_cohort_mesh_requires_clients_axis():
    from repro.launch.mesh import make_test_mesh

    with pytest.raises(ValueError, match="clients"):
        cohort_axis_size(make_test_mesh())


def test_padded_cohort():
    assert padded_cohort(16, 8) == 16
    assert padded_cohort(10, 8) == 16
    assert padded_cohort(10, 1) == 10


def test_cohort_mesh_rejects_tree_and_jnp_paths():
    mesh = make_cohort_mesh(1)
    with pytest.raises(ValueError, match="flat"):
        FederatedEngine(FedConfig(algo="fedcm", use_flat_plane=False), _LOSS,
                        cohort_mesh=mesh)


def test_cohort_mesh_rejects_client_sharding_combo():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_cohort_mesh(1)
    with pytest.raises(ValueError, match="client_sharding"):
        FederatedEngine(
            FedConfig(algo="fedcm"), _LOSS,
            cohort_mesh=mesh,
            client_sharding=NamedSharding(mesh, P("clients")),
        )


def test_cohort_mesh_too_many_devices_errors():
    with pytest.raises(ValueError, match="devices"):
        make_cohort_mesh(2 * N_DEV)


def test_cfg_cohort_shard_builds_mesh():
    """cohort_shard as pure config data: the engine builds the mesh."""
    eng, state = _engine("fedcm", 0, cohort_shard=1)
    assert eng.cohort_mesh is not None
    assert eng.cohort_mesh.axis_names == ("clients",)
    state, m = eng.run_rounds(state, _data(32), 2)
    assert int(state.server.round) == 2


def test_sharded_state_reuses_the_compiled_round():
    """``init`` places the state on the cohort mesh as the sharded round
    returns it, so chunked calls (``fed_train``'s eval cadence) trace and
    compile the round once, not again for the second chunk's layout."""
    eng, state = _engine("fedcm", 1)
    for _ in range(3):
        state, _ = eng.run_rounds(state, _data(32), 2)
    assert eng.run_rounds_traces == 1


# ----------------------------------------------------------------------
# single-shard mesh ≡ unsharded — runs everywhere, tier-1 included
# ----------------------------------------------------------------------


@pytest.mark.parametrize("algo", ["fedcm", "scaffold", "fedadam"])
def test_single_shard_mesh_is_bitwise_unsharded(algo):
    """A 1-device ("clients",) mesh runs the FULL sharded program —
    shard_map, all_to_all transpose, scattered fold, gathers — and must
    be f32-bitwise the unsharded engine (collectives degenerate)."""
    eng_ref, st_ref = _engine(algo, 0)
    eng_sh, st_sh = _engine(algo, 1)
    ref, m_ref = eng_ref.run_rounds(st_ref, _data(32), 3)
    out, m_sh = eng_sh.run_rounds(st_sh, _data(32), 3)
    _assert_state_bitwise(out, ref)
    np.testing.assert_array_equal(np.asarray(m_sh.loss), np.asarray(m_ref.loss))
    np.testing.assert_array_equal(np.asarray(m_sh.delta_norm),
                                  np.asarray(m_ref.delta_norm))


# ----------------------------------------------------------------------
# multi-device equivalence (the multi-device CI job)
# ----------------------------------------------------------------------


@multidevice
@pytest.mark.parametrize("algo", list_algorithms())
def test_sync_sharded_bitwise_all_algorithms(algo):
    """8-way cohort sharding is f32-bitwise the unsharded sync engine for
    every registered algorithm (state AND per-round metrics)."""
    eng_ref, st_ref = _engine(algo, 0)
    eng_sh, st_sh = _engine(algo, 8)
    ref, m_ref = eng_ref.run_rounds(st_ref, _data(32), 3)
    out, m_sh = eng_sh.run_rounds(st_sh, _data(32), 3)
    _assert_state_bitwise(out, ref)
    np.testing.assert_array_equal(np.asarray(m_sh.loss), np.asarray(m_ref.loss))
    np.testing.assert_array_equal(np.asarray(m_sh.n_active),
                                  np.asarray(m_ref.n_active))
    np.testing.assert_array_equal(np.asarray(m_sh.delta_norm),
                                  np.asarray(m_ref.delta_norm))


@multidevice
@pytest.mark.parametrize("algo", list_algorithms())
def test_async_sharded_bitwise_all_algorithms(algo):
    """(D=2, S=1) overlapping-cohort schedule, 8-way sharded vs unsharded:
    the ring carries client-sharded (C_pad, P) planes and the stale fold
    goes through the scattered kernel — still f32-bitwise."""
    eng_ref, st_ref = _engine(algo, 0)
    eng_sh, st_sh = _engine(algo, 8)
    ref, _ = eng_ref.run_rounds_async(st_ref, _data(32), 4,
                                      pipeline_depth=2, staleness=1)
    out, _ = eng_sh.run_rounds_async(st_sh, _data(32), 4,
                                     pipeline_depth=2, staleness=1)
    _assert_state_bitwise(out, ref)


@multidevice
@pytest.mark.parametrize("algo,participation", [
    ("fedcm", "fixed"), ("fedcm", "bernoulli"), ("scaffold", "fixed"),
])
def test_ragged_cohort_bitwise(algo, participation):
    """C=10 over 8 devices: the cohort pads to 16 with zero-weight rows.
    Padding happens after the gathers (rng stream identical), the fold's
    trailing +0.0 terms are exact, and pad ids never reach the
    client-state scatter — so ragged sharding stays bitwise (scaffold
    pins the scatter; bernoulli pins mask-within-pad composition)."""
    eng_ref, st_ref = _engine(algo, 0, cohort=10, participation=participation)
    eng_sh, st_sh = _engine(algo, 8, cohort=10, participation=participation)
    ref, m_ref = eng_ref.run_rounds(st_ref, _data(32), 3)
    out, m_sh = eng_sh.run_rounds(st_sh, _data(32), 3)
    _assert_state_bitwise(out, ref)
    np.testing.assert_array_equal(np.asarray(m_sh.loss), np.asarray(m_ref.loss))
    np.testing.assert_array_equal(np.asarray(m_sh.n_active),
                                  np.asarray(m_ref.n_active))


@multidevice
def test_ragged_async_drain_bitwise():
    """Ragged + async + drain: the ≤D−1 in-flight padded cohorts fold in
    the epilogue dispatch through the same scattered kernel."""
    eng_ref, st_ref = _engine("scaffold", 0, cohort=10)
    eng_sh, st_sh = _engine("scaffold", 8, cohort=10)
    ref, _ = eng_ref.run_rounds_async(st_ref, _data(32), 5, pipeline_depth=3)
    out, _ = eng_sh.run_rounds_async(st_sh, _data(32), 5, pipeline_depth=3)
    _assert_state_bitwise(out, ref)


@multidevice
def test_runtime_registered_spec_with_server_fn_escape():
    """A custom spec whose round close is a ``server_fn`` escape hatch
    cannot ride the fold kernel; under cohort sharding its uplink means
    come from the scattered reduction (``cohort_mean_scatter``) and the
    escape runs replicated — bitwise vs unsharded."""
    def server_fn(cfg, params, st, mean_delta, mean_sd, mean_extra,
                  n_active, eta_l):
        new_x = jax.tree_util.tree_map(
            lambda x, d: x + cfg.eta_g * d, params, mean_delta)
        return new_x, st._replace(round=st.round + 1)

    spec = AlgorithmSpec(
        name="_test_escape",
        direction_row=DirectionRow(),
        server_fn=server_fn,
    )
    register_algorithm(spec)
    try:
        eng_ref, st_ref = _engine("_test_escape", 0)
        eng_sh, st_sh = _engine("_test_escape", 8)
        ref, _ = eng_ref.run_rounds(st_ref, _data(32), 3)
        out, _ = eng_sh.run_rounds(st_sh, _data(32), 3)
        _assert_state_bitwise(out, ref)
    finally:
        unregister_algorithm("_test_escape")


@multidevice
def test_sharded_run_round_matches_run_rounds():
    """Per-round dispatch and the fused scan agree under sharding (same
    shared _prepare_round/_flat_round_step, shard_map inside both)."""
    eng, st = _engine("fedcm", 8)
    eng2, st2 = _engine("fedcm", 8)
    for _ in range(3):
        st, _ = eng.run_round(st, _data(32))
    fused, _ = eng2.run_rounds(st2, _data(32), 3)
    for a, b in zip(jax.tree_util.tree_leaves(st.params),
                    jax.tree_util.tree_leaves(fused.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
