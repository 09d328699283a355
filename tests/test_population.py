"""Out-of-core population engine tests (store + sampler + streaming data).

The contract under test (ISSUE: million-client population engine):

* **Store-backed = resident, f32-bitwise.**  ``population_store="host"``
  runs the SAME jitted round functions as the resident engine,
  parameterized by host-gathered ``(C, P)`` rows — so at matched cohorts
  the trajectories agree bitwise with the per-round resident oracle
  (``run_round`` × n) on the sync engine and with ``run_rounds_async``
  (Pallas pins the op order).  The async run is also held to tight f32
  tolerance, a bound that holds even where XLA's fusion choices across
  the resident scan boundary reassociate a reduction at the ulp level —
  the same reason the repo holds ``run_rounds`` vs sequential
  ``run_round`` to tolerance rather than bitwise.
* **No (N, ·) device plane** ever exists on the host path; host memory
  scales with TOUCHED clients.
* **Uniform availability is the legacy sampler, verbatim** (same key
  splits, same ``jax.random.choice``/scalar-p bernoulli) — pre-existing
  trajectories can't move.
* **Capacity clips are counted, not silent** (``RoundMetrics.n_clipped``).
* Checkpoint round-trip of a store-backed run via the template-free
  ``repro.checkpoint.ckpt.load_flat``.
"""
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.ckpt import load_checkpoint, load_flat, save_checkpoint
from repro.configs.base import FedConfig
from repro.core import FederatedEngine, cohort_capacity, sample_cohort, sample_cohort_ex
from repro.data import FederatedData, make_synthetic_classification
from repro.data.population import (
    HostPopulationStore,
    StreamingClientData,
    availability_log_weights,
)
from repro.models.small import classification_loss, mlp_classifier
from repro.sharding.rules import fed_state_specs


def _setup(algo, **kw):
    x, y, *_ = make_synthetic_classification(n_classes=4, dim=8, n_train=800, n_test=8)
    model = mlp_classifier((8, 16, 4))
    base = dict(algo=algo, num_clients=10, cohort_size=3, local_steps=2,
                participation="fixed")
    base.update(kw)
    cfg = FedConfig(**base)
    data = FederatedData(x, y, cfg.num_clients, seed=0)
    return cfg, classification_loss(model.apply), data, model


def _fresh(eng, model):
    return eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))


def _assert_bitwise(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _store_rows_vs_resident(eng_host, resident_state):
    """Dense (N, P') view of the host store vs the resident stacked plane,
    both in the engine's plane layout (its zero tail included)."""
    spec = eng_host._flat_spec(resident_state.params)
    rows_ref = np.asarray(spec.ravel(resident_state.client_states, batch_dims=1))
    tree = eng_host.population.to_pytree()
    dense = np.zeros_like(rows_ref)
    dense[np.asarray(tree["ids"])] = np.asarray(tree["rows"])
    np.testing.assert_array_equal(dense, rows_ref)


# ----------------------------------------------------------------------
# store-backed engine vs resident oracle
# ----------------------------------------------------------------------


@pytest.mark.parametrize("algo", ["scaffold", "feddyn"])
def test_store_sync_bitwise_vs_resident(algo):
    cfg, loss_fn, data, model = _setup(algo)
    eng_r = FederatedEngine(cfg, loss_fn, batch_size=8)
    sr = _fresh(eng_r, model)
    losses = []
    for _ in range(5):  # the per-round resident oracle
        sr, m = eng_r.run_round(sr, data)
        losses.append(np.asarray(m.loss))

    eng_h = FederatedEngine(replace(cfg, population_store="host"), loss_fn,
                            batch_size=8)
    sh, mh = eng_h.run_rounds(_fresh(eng_h, model), data, 5)

    assert sh.client_states is None  # no (N, P) device plane, ever
    _assert_bitwise((sr.params, sr.server.momentum),
                    (sh.params, sh.server.momentum))
    np.testing.assert_array_equal(np.stack(losses), np.asarray(mh.loss))
    _store_rows_vs_resident(eng_h, sr)


@pytest.mark.parametrize("algo", ["scaffold", "feddyn"])
def test_store_async_kernel_bitwise_vs_resident(algo):
    cfg, loss_fn, data, model = _setup(algo, pipeline_depth=2, staleness=1)
    eng_r = FederatedEngine(cfg, loss_fn, batch_size=8)
    sr, mr = eng_r.run_rounds_async(_fresh(eng_r, model), data, 6)

    eng_h = FederatedEngine(replace(cfg, population_store="host"), loss_fn,
                            batch_size=8)
    sh, mh = eng_h.run_rounds_async(_fresh(eng_h, model), data, 6)

    assert sh.client_states is None
    _assert_bitwise((sr.params, sr.server.momentum),
                    (sh.params, sh.server.momentum))
    np.testing.assert_array_equal(np.asarray(mr.loss), np.asarray(mh.loss))
    np.testing.assert_array_equal(np.asarray(mr.folded), np.asarray(mh.folded))
    _store_rows_vs_resident(eng_h, sr)


@pytest.mark.parametrize("algo", ["scaffold", "feddyn"])
def test_store_async_matches_resident_tight(algo):
    # the same host-loop schedule as the bitwise test above, held to
    # f32-noise tolerance: the bound that survives XLA reassociating a
    # reduction differently across the resident scan boundary
    cfg, loss_fn, data, model = _setup(algo, pipeline_depth=2, staleness=1)
    eng_r = FederatedEngine(cfg, loss_fn, batch_size=8)
    sr, mr = eng_r.run_rounds_async(_fresh(eng_r, model), data, 6)

    eng_h = FederatedEngine(replace(cfg, population_store="host"), loss_fn,
                            batch_size=8)
    sh, mh = eng_h.run_rounds_async(_fresh(eng_h, model), data, 6)

    for la, lb in zip(
        jax.tree_util.tree_leaves((sr.params, sr.server.momentum)),
        jax.tree_util.tree_leaves((sh.params, sh.server.momentum)),
    ):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mr.loss), np.asarray(mh.loss),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(mr.folded), np.asarray(mh.folded))


def test_store_sharding_specs_drop_client_plane():
    cfg, *_ = _setup("scaffold")
    cfg_h = replace(cfg, population_store="host")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    p_specs = jax.sharding.PartitionSpec()
    assert fed_state_specs(p_specs, cfg, mesh)["client_states"] is not None
    assert fed_state_specs(p_specs, cfg_h, mesh)["client_states"] is None


# ----------------------------------------------------------------------
# checkpoint round-trip (template-free store restore)
# ----------------------------------------------------------------------


def test_store_checkpoint_roundtrip(tmp_path):
    cfg, loss_fn, data, model = _setup("scaffold", population_store="host")
    eng1 = FederatedEngine(cfg, loss_fn, batch_size=8)
    st = _fresh(eng1, model)
    st, _ = eng1.run_rounds(st, data, 3)
    touched_at_save = eng1.population.touched
    ckpt_tree = {"state": st, "store": eng1.population.to_pytree()}
    save_checkpoint(str(tmp_path), 3, ckpt_tree,
                    meta={"touched": touched_at_save})
    st_cont, _ = eng1.run_rounds(st, data, 2)  # the uninterrupted reference

    # cold restore into a fresh engine: params/server/rng via the template
    # path, the run-dependent (M, P) store packing via template-free
    # load_flat (no template can predict M = touched clients)
    eng2 = FederatedEngine(cfg, loss_fn, batch_size=8)
    template = {"state": _fresh(eng2, model),
                "store": {"ids": np.zeros(0, np.int32),
                          "rows": np.zeros((0, 0), np.float32)}}
    flat, meta = load_flat(str(tmp_path))
    assert meta["step"] == 3 and meta["touched"] == touched_at_save
    restored, _ = load_checkpoint(
        str(tmp_path), 3,
        {"state": template["state"],
         "store": {"ids": flat["store/ids"], "rows": flat["store/rows"]}},
    )
    eng2.population = HostPopulationStore.from_pytree(
        restored["store"], cfg.num_clients,
        plane_size=eng1.population.plane_size,
    )
    st2, _ = eng2.run_rounds(restored["state"], data, 2)

    _assert_bitwise((st_cont.params, st_cont.server.momentum, st_cont.rng),
                    (st2.params, st2.server.momentum, st2.rng))
    t1, t2 = eng1.population.to_pytree(), eng2.population.to_pytree()
    _assert_bitwise(t1, t2)


# ----------------------------------------------------------------------
# sampler: clips, legacy-bitwise uniform, availability processes
# ----------------------------------------------------------------------


def test_bernoulli_clip_is_counted_at_small_n():
    # N=40, S=30 at capacity sigma 0 → cap = 30, p = 0.75: the binomial
    # draw exceeds its mean ~42% of rounds.  The pre-store engine silently
    # truncated those rounds (participation bias toward low draws); the
    # sampler now surfaces every overflow in n_clipped.
    cfg = FedConfig(algo="fedcm", num_clients=40, cohort_size=30,
                    participation="bernoulli", bernoulli_capacity_sigma=0.0)
    cap = cohort_capacity(cfg)
    assert cap == 30
    clipped_rounds, key = 0, jax.random.PRNGKey(0)
    for _ in range(200):
        key, k = jax.random.split(key)
        ids, mask, n_clipped = sample_cohort_ex(k, cfg)
        assert ids.shape == (cap,) and mask.shape == (cap,)
        n_clipped = int(n_clipped)
        assert n_clipped >= 0
        if n_clipped > 0:
            clipped_rounds += 1
            assert int(mask.sum()) == cap  # clipped ⇒ mask saturated
    assert 0.25 < clipped_rounds / 200 < 0.65

    ids2, mask2 = sample_cohort(jax.random.PRNGKey(1), cfg)  # 2-tuple wrapper
    assert ids2.shape == (cap,) and mask2.shape == (cap,)


@pytest.mark.parametrize("participation", ["fixed", "bernoulli"])
def test_uniform_availability_is_the_legacy_draw(participation):
    # the exact legacy two-key sampler, reproduced by hand: any drift here
    # moves every pre-existing trajectory in the repo
    cfg = FedConfig(algo="fedcm", num_clients=50, cohort_size=10,
                    participation=participation)
    assert availability_log_weights(cfg) is None
    cap = cohort_capacity(cfg)
    key = jax.random.PRNGKey(7)
    ids, mask, _ = sample_cohort_ex(key, cfg)

    k_perm, k_n = jax.random.split(key)
    ref_ids = jax.random.choice(k_perm, cfg.num_clients, (cap,), replace=False)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids))
    if participation == "fixed":
        assert bool(mask.all())
    else:
        p = cfg.cohort_size / cfg.num_clients
        s = jnp.clip(jnp.sum(jax.random.bernoulli(
            k_n, p, (cfg.num_clients,))).astype(jnp.int32), 1, cap)
        np.testing.assert_array_equal(np.asarray(mask),
                                      np.asarray(jnp.arange(cap) < s))


def test_zipf_availability_biases_low_ids():
    n = 1000
    cfg_u = FedConfig(algo="fedcm", num_clients=n, cohort_size=50,
                      participation="fixed")
    cfg_z = replace(cfg_u, availability="zipf", zipf_exponent=1.5)
    key = jax.random.PRNGKey(0)
    mean_u, mean_z = [], []
    for i in range(20):
        k = jax.random.fold_in(key, i)
        mean_u.append(float(np.mean(np.asarray(sample_cohort_ex(k, cfg_u)[0]))))
        mean_z.append(float(np.mean(np.asarray(sample_cohort_ex(k, cfg_z)[0]))))
    # zipf head (low ids) dominates; uniform sits near N/2
    assert np.mean(mean_z) < 0.5 * np.mean(mean_u)


def test_diurnal_availability_is_time_dependent():
    cfg = FedConfig(algo="fedcm", num_clients=200, cohort_size=20,
                    participation="fixed", availability="diurnal",
                    diurnal_period=10.0, diurnal_amplitude=0.95)
    key = jax.random.PRNGKey(3)
    ids_t0 = np.sort(np.asarray(sample_cohort_ex(key, cfg, t=0)[0]))
    ids_t5 = np.sort(np.asarray(sample_cohort_ex(key, cfg, t=5)[0]))
    # half a period later the sinusoid has rotated phase by π — the same
    # key must select a (mostly) different cohort
    assert not np.array_equal(ids_t0, ids_t5)
    w0 = availability_log_weights(cfg, t=0)
    w5 = availability_log_weights(cfg, t=5)
    assert not np.allclose(np.asarray(w0), np.asarray(w5))


def test_dropout_thins_but_never_empties():
    cfg = FedConfig(algo="fedcm", num_clients=100, cohort_size=16,
                    participation="fixed", dropout_rate=0.5)
    key, active = jax.random.PRNGKey(0), []
    for i in range(50):
        _, mask, _ = sample_cohort_ex(jax.random.fold_in(key, i), cfg)
        n = int(mask.sum())
        assert 1 <= n <= 16
        active.append(n)
    assert np.mean(active) < 12  # ~8 expected at rate 0.5


def test_unknown_availability_raises():
    cfg = FedConfig(algo="fedcm", num_clients=10, cohort_size=3,
                    availability="lunar")
    with pytest.raises(ValueError, match="lunar"):
        availability_log_weights(cfg)


# ----------------------------------------------------------------------
# streaming data + store mechanics
# ----------------------------------------------------------------------


def test_streaming_shards_deterministic_and_shaped():
    task = StreamingClientData(1000, dim=8, n_classes=4, n_per_client=20, seed=0)
    ids = np.array([3, 999, 41], np.int32)
    b1 = task.host_round_batches(ids, seed=7, local_steps=3, batch_size=5)
    b2 = task.host_round_batches(ids, seed=7, local_steps=3, batch_size=5)
    assert b1["x"].shape == (3, 3, 5, 8) and b1["y"].shape == (3, 3, 5)
    _assert_bitwise(b1, b2)  # same (seed, ids) → same block
    b3 = task.host_round_batches(ids, seed=8, local_steps=3, batch_size=5)
    assert not np.array_equal(b1["x"], b3["x"])

    x3, y3 = task.client_dataset(3)
    x999, _ = task.client_dataset(999)
    assert x3.shape == (20, 8) and y3.dtype == np.int32
    assert not np.array_equal(x3, x999)
    full = task.host_full_batches(ids)
    np.testing.assert_array_equal(full["x"][0], x3)
    # label skew: the dominant class cid % n_classes leads the histogram
    assert np.bincount(y3, minlength=4).argmax() == 3 % 4
    xt1, yt1 = task.test_set(100)
    xt2, yt2 = task.test_set(100)
    np.testing.assert_array_equal(xt1, xt2)
    np.testing.assert_array_equal(yt1, yt2)


def test_host_store_gather_scatter_and_packing():
    store = HostPopulationStore(1000, plane_size=4)
    assert store.gather(np.array([5, 900])).tolist() == [[0] * 4, [0] * 4]
    rows = np.arange(8, dtype=np.float32).reshape(2, 4)
    store.scatter(np.array([900, 5]), rows)
    np.testing.assert_array_equal(store.gather(np.array([5])), rows[1:])
    assert store.touched == 2 and store.nbytes == 2 * 4 * 4
    with pytest.raises(ValueError):
        store.scatter(np.array([1]), np.zeros((1, 3), np.float32))
    packed = store.to_pytree()
    assert packed["ids"].tolist() == [5, 900]  # sorted
    again = HostPopulationStore.from_pytree(packed, 1000)
    np.testing.assert_array_equal(again.gather(np.array([5, 900])),
                                  store.gather(np.array([5, 900])))


def test_host_store_requires_init_and_flat_plane():
    cfg, loss_fn, data, model = _setup("scaffold", population_store="host")
    eng = FederatedEngine(cfg, loss_fn, batch_size=8)
    state = _fresh(eng, model)
    eng.population = None  # simulate a hand-built state skipping init()
    with pytest.raises(RuntimeError, match="population store"):
        eng.run_rounds(state, data, 1)
    with pytest.raises(ValueError, match="flat"):
        FederatedEngine(replace(cfg, use_flat_plane=False), loss_fn,
                        batch_size=8)


def test_host_store_streaming_end_to_end_bounded_memory():
    # StreamingClientData + host store: run rounds at N ≫ cohort and check
    # the store only ever holds touched clients (≤ rounds × capacity)
    cfg = FedConfig(algo="scaffold", num_clients=5_000, cohort_size=4,
                    local_steps=2, participation="fixed",
                    population_store="host")
    task = StreamingClientData(cfg.num_clients, dim=8, n_classes=4, seed=0)
    model = mlp_classifier((8, 16, 4))
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
    st = _fresh(eng, model)
    st, ms = eng.run_rounds(st, task, 4)
    assert st.client_states is None
    assert np.all(np.isfinite(np.asarray(ms.loss)))
    assert 0 < eng.population.touched <= 4 * cohort_capacity(cfg)


@pytest.mark.slow
def test_host_store_1e5_smoke():
    # the multidevice CI job's N=1e5 participation smoke: a store-backed
    # zipf run must hold rounds without materializing the
    # population (device OR host)
    cfg = FedConfig(algo="scaffold", num_clients=100_000, cohort_size=20,
                    local_steps=2, participation="bernoulli",
                    availability="zipf", population_store="host")
    task = StreamingClientData(cfg.num_clients, dim=8, n_classes=4, seed=0)
    model = mlp_classifier((8, 16, 4))
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
    st = _fresh(eng, model)
    st, ms = eng.run_rounds(st, task, 3)
    assert np.all(np.isfinite(np.asarray(ms.loss)))
    assert 0 < eng.population.touched < 5_000
