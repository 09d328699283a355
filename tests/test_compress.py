"""Compressed uplink (repro.core.compress + the fused dequant fold).

Covers the tentpole layers:

1. the pure plane transforms — stochastic int8 unbiasedness (incl. the
   clip boundary), bf16 normalization, top-k error-feedback semantics,
   and the wire-bytes accounting the engine bills,
2. the fused dequant kernel against its jnp reference,
3. registry validation — lossy sparsification without a residual stream
   is refused at registration time,
4. the engine end-to-end: compressed runs tolerance-bounded against the
   uncompressed oracle on the sync and async engines, the EF residual
   stream checkpointing (resident + host store) and continuing bitwise
   through a kill/resume, and the double-buffered host-store loop's
   bitwise contract against the synchronous loop.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import CompressionConfig, FedConfig
from repro.checkpoint import load_fed_run, save_fed_run
from repro.core import FederatedEngine, get_algorithm
from repro.core.compress import (
    QPlane,
    TopKPlane,
    as_qplane,
    densify_topk,
    dequantize,
    error_feedback_topk,
    plane_key,
    quantize_int8,
    round_key,
    sparsify_topk,
    topk_k,
    uplink_bytes_per_client,
    validate_compression,
    wire_plane_bytes,
)
from repro.core.registry import _validate
from repro.data import FederatedData, StreamingClientData, make_synthetic_classification
from repro.models.small import classification_loss, mlp_classifier

RNG = np.random.default_rng(0)


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _assert_trees_equal(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x1, x2 in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))


def _assert_trees_close(a, b, rtol=2e-5, atol=1e-6):
    for la, lb in zip(_leaves(a), _leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=atol)


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


# ---------------------------------------------------------------------------
# 1. pure plane transforms
# ---------------------------------------------------------------------------

def test_int8_stochastic_rounding_is_unbiased():
    """E[dequantize(quantize(x))] = x elementwise — the property that lets
    the masked cohort mean stay an unbiased gradient estimate."""
    plane = jnp.asarray(RNG.normal(size=(2, 64)) * 3.0, jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(7), 4096)
    deq = jax.vmap(lambda k: dequantize(quantize_int8(plane, k)))(keys)
    mean = np.asarray(jnp.mean(deq, axis=0))
    scale = np.abs(np.asarray(plane)).max(axis=-1, keepdims=True) / 127.0
    # se of the mean of a U[0,1)-dithered floor ≈ scale/sqrt(12·N); 6σ bound
    np.testing.assert_allclose(mean, np.asarray(plane),
                               atol=float(6 * scale.max() / np.sqrt(12 * 4096)))


def test_int8_clip_boundary_and_zero_rows():
    """±absmax lands exactly on ±127 for every dither draw (the clip never
    biases), and an all-zero row (dropped client) stays exactly zero with
    unit scale."""
    plane = jnp.asarray([[-6.0, 0.0, 6.0], [0.0, 0.0, 0.0]], jnp.float32)
    for s in range(16):
        rep = quantize_int8(plane, jax.random.PRNGKey(s))
        q = np.asarray(rep.q)
        assert q[0, 0] == -127 and q[0, 2] == 127
        np.testing.assert_array_equal(q[1], 0)
        np.testing.assert_array_equal(np.asarray(rep.scale[1]), 1.0)
        deq = np.asarray(dequantize(rep))
        assert deq[0, 0] == pytest.approx(-6.0) and deq[0, 2] == pytest.approx(6.0)


def test_as_qplane_bf16_unit_scale_is_exact():
    plane = jnp.asarray(RNG.normal(size=(3, 32)), jnp.float32)
    rep = as_qplane(plane.astype(jnp.bfloat16))
    assert isinstance(rep, QPlane)
    np.testing.assert_array_equal(np.asarray(rep.scale), 1.0)
    # dequant with unit scale == plain bf16→f32 upcast, bitwise
    np.testing.assert_array_equal(
        np.asarray(dequantize(rep)),
        np.asarray(plane.astype(jnp.bfloat16).astype(jnp.float32)))


def test_topk_roundtrip_and_k_bounds():
    comp = CompressionConfig(kind="topk", topk_frac=0.1)
    assert topk_k(comp, 100) == 10
    assert topk_k(comp, 3) == 1  # floor at 1
    assert topk_k(CompressionConfig(kind="topk", topk_frac=1.0), 7) == 7
    plane = jnp.asarray(RNG.normal(size=(4, 50)), jnp.float32)
    rep = sparsify_topk(plane, 5)
    assert isinstance(rep, TopKPlane)
    dense = np.asarray(densify_topk(rep, 50))
    for c in range(4):
        nz = np.flatnonzero(dense[c])
        assert len(nz) == 5
        # kept entries are exact and are the top-5 magnitudes of the row
        row = np.asarray(plane[c])
        np.testing.assert_array_equal(dense[c][nz], row[nz])
        kept = set(nz)
        top5 = set(np.argsort(-np.abs(row))[:5])
        assert kept == top5


def test_error_feedback_semantics():
    """Active rows: sent + residual' == plane + residual (nothing is ever
    lost, only deferred).  Inactive rows: residual untouched, recon zero
    (they must fold as zeros, not as a stale accumulator)."""
    comp = CompressionConfig(kind="topk", topk_frac=0.2)
    plane = jnp.asarray(RNG.normal(size=(3, 20)), jnp.float32)
    res = jnp.asarray(RNG.normal(size=(3, 20)) * 0.1, jnp.float32)
    active = jnp.asarray([1.0, 0.0, 1.0])
    rep, recon, new_res = error_feedback_topk(comp, plane, res, active, 20)
    recon, new_res = np.asarray(recon), np.asarray(new_res)
    acc = np.asarray(plane) + np.asarray(res)
    for c in (0, 2):  # active: conservation of the accumulated signal
        np.testing.assert_allclose(recon[c] + new_res[c], acc[c],
                                   rtol=1e-6, atol=1e-7)
        assert np.count_nonzero(recon[c]) == topk_k(comp, 20)
    np.testing.assert_array_equal(recon[1], 0.0)  # inactive folds as zero
    np.testing.assert_array_equal(new_res[1], np.asarray(res)[1])


def test_wire_bytes_accounting():
    P = 1000
    assert wire_plane_bytes(None, P, 4 * P) == 4 * P
    assert wire_plane_bytes(CompressionConfig(kind="bf16"), P, 4 * P) == 2 * P
    assert wire_plane_bytes(CompressionConfig(kind="int8"), P, 4 * P) == P + 4
    comp = CompressionConfig(kind="topk", topk_frac=0.01)
    assert wire_plane_bytes(comp, P, 4 * P) == 10 * 8
    # top-k only sparsifies the delta stream; other wire planes ride f32
    assert uplink_bytes_per_client(comp, ("delta", "state_delta"), P, 4 * P) \
        == 10 * 8 + 4 * P
    assert uplink_bytes_per_client(
        CompressionConfig(kind="int8"), ("delta", "extra"), P, 4 * P
    ) == 2 * (P + 4)


def test_round_keys_are_plane_and_round_distinct():
    comp = CompressionConfig(kind="int8", seed=3)
    k2, k3 = round_key(comp, 2), round_key(comp, 3)
    assert not np.array_equal(np.asarray(k2), np.asarray(k3))
    kd, ks = plane_key(k2, "delta"), plane_key(k2, "state_delta")
    assert not np.array_equal(np.asarray(kd), np.asarray(ks))


def test_validate_compression_rejects_malformed():
    with pytest.raises(ValueError, match="unknown compression kind"):
        validate_compression(CompressionConfig(kind="int4"))
    with pytest.raises(ValueError, match="topk_frac"):
        validate_compression(CompressionConfig(kind="topk", topk_frac=0.0))
    with pytest.raises(ValueError, match="topk_frac"):
        validate_compression(CompressionConfig(kind="topk", topk_frac=1.5))


# ---------------------------------------------------------------------------
# 2. fused dequant kernel vs reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C,P", [(3, 129), (8, 1000)])
@pytest.mark.parametrize("kind", ["int8", "bf16"])
def test_dequant_kernel_matches_ref(C, P, kind):
    """The fused dequantize→mean→EMA→step pass equals the jnp reference
    AND the dense fused_server_step over the pre-dequantized plane."""
    from repro.kernels.server_update.ops import dequant_server_step, fused_server_step
    from repro.kernels.server_update.ref import dequant_server_update_ref

    plane = jnp.asarray(RNG.normal(size=(C, P)), jnp.float32)
    if kind == "int8":
        rep = quantize_int8(plane, jax.random.PRNGKey(1))
    else:
        rep = as_qplane(plane.astype(jnp.bfloat16))
    wn = jnp.full((C,), 1.0 / C, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    m = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    out = dequant_server_step(rep.q, rep.scale, wn, x, m, 0.9, 0.1, -2.0)
    coefs = jnp.asarray([0.9, 0.1, -2.0, 1.0], jnp.float32)
    ref = dequant_server_update_ref(rep.q, rep.scale, wn, x, m, coefs)
    dense = fused_server_step(dequantize(rep), wn, x, m, 0.9, 0.1, -2.0)
    for o, r, d in zip(out, ref, dense):
        assert o.shape == (P,)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(o), np.asarray(d),
                                   rtol=2e-5, atol=2e-6)


def test_dequant_kernel_masked_client_contributes_nothing():
    from repro.kernels.server_update.ops import dequant_server_step

    C, P = 4, 257
    plane = jnp.asarray(RNG.normal(size=(C, P)), jnp.float32)
    w = jnp.asarray([1.0, 1.0, 1.0, 0.0])
    wn = w / jnp.sum(w)
    rep = quantize_int8(plane, jax.random.PRNGKey(2))
    x = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    m = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    out = dequant_server_step(rep.q, rep.scale, wn, x, m, 0.9, 0.1, -2.0)
    garbage = QPlane(q=rep.q.at[-1].set(127), scale=rep.scale.at[-1].set(1e9))
    out_g = dequant_server_step(garbage.q, garbage.scale, wn, x, m, 0.9, 0.1, -2.0)
    for o, og in zip(out, out_g):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(og))


# ---------------------------------------------------------------------------
# 3. registry validation
# ---------------------------------------------------------------------------

def test_registry_rejects_topk_without_residual_stream():
    spec = get_algorithm("fedcm")
    with pytest.raises(ValueError, match="needs_residual"):
        _validate(spec._replace(uplink_compression="topk"))
    # scaffold's client_state rides the wire — declaring lossy compression
    # on it without the residual stream must be refused the same way
    sc = get_algorithm("scaffold")
    assert sc.client_state_uplink
    with pytest.raises(ValueError, match="needs_residual"):
        _validate(sc._replace(uplink_compression="topk"))
    with pytest.raises(ValueError, match="only 'topk' carries residuals"):
        _validate(spec._replace(needs_residual=True, uplink_compression="int8"))
    with pytest.raises(ValueError, match="unknown uplink_compression"):
        _validate(spec._replace(uplink_compression="int4"))
    # the valid declaration passes
    _validate(spec._replace(uplink_compression="topk", needs_residual=True))


def test_engine_requires_flat_plane_for_compression():
    with pytest.raises(ValueError, match="flat"):
        _setup("fedcm", compression=CompressionConfig(kind="int8"),
               use_flat_plane=False)


# ---------------------------------------------------------------------------
# 4. engine end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["int8", "bf16"])
def test_compressed_run_close_to_uncompressed_oracle(kind):
    """Quantization noise is bounded: a compressed 3-round trajectory
    through the fused dequant fold stays within a per-round-noise
    tolerance of the f32 tree oracle, and the uncompressed flat run agrees
    with that oracle to kernel noise."""
    comp = CompressionConfig(kind=kind, seed=0)
    _, eng_tree, data, model = _setup("fedcm", use_flat_plane=False)
    st_tree, _ = eng_tree.run_rounds(_fresh_state(eng_tree, model), data, 3)
    _, eng_f32, _, _ = _setup("fedcm")
    st_f32, _ = eng_f32.run_rounds(_fresh_state(eng_f32, model), data, 3)
    _assert_trees_close(st_f32.params, st_tree.params, rtol=2e-5, atol=2e-6)
    _, eng, data_c, _ = _setup("fedcm", compression=comp)
    st, ms = eng.run_rounds(_fresh_state(eng, model), data_c, 3)
    # loose bound: per-round rounding noise ~ scale·eta ≪ 1e-2 here
    _assert_trees_close(st.params, st_tree.params, rtol=0.0, atol=5e-3)


def test_compression_accounting_reaches_metrics():
    P = 212  # mlp (8, 16, 4) plane
    comp = CompressionConfig(kind="int8")
    _, eng, data, model = _setup("fedcm", compression=comp)
    st, ms = eng.run_rounds(_fresh_state(eng, model), data, 2)
    per_client = int(np.asarray(ms.bytes_up)[-1]) / int(np.asarray(ms.n_active)[-1])
    assert per_client == P + 4  # int8 byte/elem + one f32 row scale
    assert eng.payload_bytes(st.params)["up_per_client"] == P + 4


def test_async_ring_carries_compression():
    """The async engine folds compressed in-flight cohorts: at depth 1 the
    ring's int8 run matches the sync int8 run (the same wire and fold), and
    at depth 2 int8 stays near the f32 async oracle."""
    outs = {}
    for kind in (None, "int8"):
        comp = None if kind is None else CompressionConfig(kind=kind)
        _, eng, data, model = _setup("fedcm", compression=comp)
        st, _ = eng.run_rounds_async(_fresh_state(eng, model), data, 4,
                                     pipeline_depth=2, staleness=1)
        outs[kind] = st
    _, eng, data, model = _setup("fedcm", compression=CompressionConfig(kind="int8"))
    st_sync, _ = eng.run_rounds(_fresh_state(eng, model), data, 4)
    st_d1, _ = eng.run_rounds_async(_fresh_state(eng, model), data, 4,
                                    pipeline_depth=1, staleness=0)
    _assert_trees_close(st_d1.params, st_sync.params, rtol=2e-5, atol=2e-6)
    _assert_trees_close(outs["int8"].params, outs[None].params,
                        rtol=0.0, atol=5e-3)


def test_topk_residuals_initialized_and_updated():
    comp = CompressionConfig(kind="topk", topk_frac=0.1)
    _, eng, data, model = _setup("fedcm", compression=comp)
    st = _fresh_state(eng, model)
    # one residual row per client, in the engine's plane layout
    # (212 parameters and the zero tail up to the kernels' block)
    plane = eng._flat_spec(st.params).plane_size
    assert st.residuals is not None and st.residuals.shape == (10, plane)
    np.testing.assert_array_equal(np.asarray(st.residuals), 0.0)
    st, _ = eng.run_rounds(st, data, 2)
    # the sampled cohort's rows accumulated unsent mass; others stayed zero
    assert np.any(np.asarray(st.residuals) != 0.0)


def test_residuals_roundtrip_save_fed_run_resident(tmp_path):
    comp = CompressionConfig(kind="topk", topk_frac=0.1)
    _, eng, data, model = _setup("fedcm", compression=comp)
    st, _ = eng.run_rounds(_fresh_state(eng, model), data, 2)
    save_fed_run(str(tmp_path), 2, st)
    restored, pop, res, meta = load_fed_run(str(tmp_path), 2, st)
    assert pop is None and res is None  # resident: rides the state template
    _assert_trees_equal(st, restored)
    np.testing.assert_array_equal(np.asarray(st.residuals),
                                  np.asarray(restored.residuals))


def _store_setup(algo, comp, num_clients=64, **kw):
    cfg = FedConfig(algo=algo, num_clients=num_clients, cohort_size=8,
                    local_steps=2, population_store="host",
                    compression=comp, **kw)
    data = StreamingClientData(num_clients, dim=8, n_classes=4, seed=0)
    model = mlp_classifier((8, 16, 4))
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
    st = eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
    return eng, data, st


def test_residuals_roundtrip_save_fed_run_host_store(tmp_path):
    comp = CompressionConfig(kind="topk", topk_frac=0.1)
    eng_a, data, st_a = _store_setup("fedcm", comp)
    assert eng_a.residual_population is not None
    st_a, _ = eng_a.run_rounds_store(st_a, data, 4)
    save_fed_run(str(tmp_path), 2, st_a,
                 population=eng_a.population,
                 residuals=eng_a.residual_population)
    eng_b, _, st_b = _store_setup("fedcm", comp)
    st_b, pop, res, meta = load_fed_run(str(tmp_path), None, st_b,
                                        num_clients=64)
    assert meta["step"] == 2 and res is not None
    np.testing.assert_array_equal(
        np.asarray(res.to_pytree()["rows"]),
        np.asarray(eng_a.residual_population.to_pytree()["rows"]))


@pytest.mark.parametrize("kind", ["int8", "topk"])
def test_kill_and_resume_is_bitwise_with_compression(kind):
    """4 straight rounds == 2 + snapshot + restore + 2 with compression on:
    the per-round rounding keys are absolute-round-keyed, and the EF
    residual plane rides the snapshot, so the trajectory continues bitwise
    through the checkpoint boundary."""
    import tempfile

    comp = CompressionConfig(kind=kind, topk_frac=0.1, seed=5)
    _, eng, data, model = _setup("fedcm", compression=comp)
    st_full, _ = eng.run_rounds(_fresh_state(eng, model), data, 2)
    st_full, _ = eng.run_rounds(st_full, data, 2)

    st_half, _ = eng.run_rounds(_fresh_state(eng, model), data, 2)
    with tempfile.TemporaryDirectory() as d:
        save_fed_run(d, 2, st_half)
        st_resumed, _pop, _res, _ = load_fed_run(d, None, st_half)
    st_resumed, _ = eng.run_rounds(st_resumed, data, 2)
    _assert_trees_equal(st_full, st_resumed)


@pytest.mark.parametrize("comp", [None,
                                  CompressionConfig(kind="int8"),
                                  CompressionConfig(kind="topk", topk_frac=0.1)])
def test_store_prefetch_loop_is_bitwise(comp):
    """The double-buffered host-store loop (store_prefetch) is bitwise the
    synchronous loop: final params, population rows, and EF residual rows
    all match exactly — the prefetched sample is provably the same draw."""
    finals = {}
    for pf in (False, True):
        eng, data, st = _store_setup("scaffold", comp, store_prefetch=pf)
        st, _ = eng.run_rounds_store(st, data, 5)
        finals[pf] = (st, eng)
    _assert_trees_equal(finals[False][0].params, finals[True][0].params)
    np.testing.assert_array_equal(
        np.asarray(finals[False][1].population.to_pytree()["rows"]),
        np.asarray(finals[True][1].population.to_pytree()["rows"]))
    if comp is not None and comp.kind == "topk":
        np.testing.assert_array_equal(
            np.asarray(finals[False][1].residual_population.to_pytree()["rows"]),
            np.asarray(finals[True][1].residual_population.to_pytree()["rows"]))


def test_store_async_launch_with_compression():
    comp = CompressionConfig(kind="topk", topk_frac=0.1)
    eng, data, st = _store_setup("scaffold", comp)
    st, ms = eng.run_rounds_store_async(st, data, 4, pipeline_depth=2,
                                        staleness=1)
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in _leaves(st.params))
    rows = np.asarray(eng.residual_population.to_pytree()["rows"])
    assert rows.size and np.any(rows != 0.0)
