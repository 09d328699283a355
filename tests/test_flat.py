"""Flat parameter-plane engine (core/flat.py + engine flat path) tests.

* FlatSpec: ravel/unravel round-trips (shapes, dtypes, scalar leaves,
  stacked leading axes), view_leaf addressing, nbytes accounting, hashing.
* Engine equivalence: the flat-plane trajectory must match the tree-path
  oracle bitwise-close (well inside the atol ≤ 1e-5 acceptance bar) for
  EVERY algorithm, stateful ones included.
* Donation: run_rounds donates its input state; the returned trajectory
  must be stable when the donated buffers get recycled by later calls.
* Mixed bf16/f32 trees survive the flat round trip.
* The engine's aligned plane: a zero tail that ``ravel`` writes,
  ``unravel`` ignores and every algorithm keeps exactly zero, while
  payload accounting charges the leaves alone.
* The gradient's return into the plane: ``unravel``'s transpose is one
  concatenate, and gives the numbers the slices' own transpose (a pad of
  each leaf to the plane, summed) gives, down to the engine's planes.
"""
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import CompressionConfig, FedConfig
from repro.core import FederatedEngine, FlatSpec, get_algorithm, list_algorithms
from repro.core.compress import uplink_bytes_per_client
from repro.data import FederatedData, make_synthetic_classification
from repro.models.small import classification_loss, mlp_classifier
from repro.utils.trees import split_flat

RNG = np.random.default_rng(0)


# ----------------------------------------------------------------------
# FlatSpec unit tests
# ----------------------------------------------------------------------


def _mixed_tree():
    return {
        "a": jnp.asarray(RNG.normal(size=(13, 7)), jnp.float32),
        "b": [
            jnp.asarray(RNG.normal(size=(5,)), jnp.float32),
            jnp.asarray(RNG.normal(size=(2, 3)), jnp.bfloat16),
        ],
        "scalar": jnp.float32(3.5),
    }


def test_flatspec_roundtrip_shapes_dtypes():
    tree = _mixed_tree()
    spec = FlatSpec.from_tree(tree)
    assert spec.size == 13 * 7 + 5 + 6 + 1
    flat = spec.ravel(tree)
    assert flat.shape == (spec.size,) and flat.dtype == jnp.float32
    back = spec.unravel(flat)
    for o, r in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(back)):
        assert o.shape == r.shape and o.dtype == r.dtype
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(r, np.float32), rtol=1e-2, atol=1e-2
        )
    # f32 leaves round-trip bitwise
    np.testing.assert_array_equal(np.asarray(tree["a"]), np.asarray(back["a"]))


def test_flatspec_stacked_batch_dims():
    tree = {"w": jnp.asarray(RNG.normal(size=(4, 3, 2)), jnp.float32),
            "b": jnp.asarray(RNG.normal(size=(4, 5)), jnp.float32)}
    # leading axis 4 = stacked clients; plane covers (3,2) and (5,)
    per_client = {"w": jax.ShapeDtypeStruct((3, 2), jnp.float32),
                  "b": jax.ShapeDtypeStruct((5,), jnp.float32)}
    spec = FlatSpec.from_tree(per_client)
    plane = spec.ravel(tree, batch_dims=1)
    assert plane.shape == (4, 11)
    back = spec.unravel(plane)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(tree["w"]))
    np.testing.assert_array_equal(np.asarray(back["b"]), np.asarray(tree["b"]))


def test_flatspec_view_leaf_by_index_and_path():
    tree = _mixed_tree()
    spec = FlatSpec.from_tree(tree)
    flat = spec.ravel(tree)
    np.testing.assert_array_equal(np.asarray(spec.view_leaf(flat, 0)),
                                  np.asarray(tree["a"]))
    path = spec.leaves[0].path
    np.testing.assert_array_equal(np.asarray(spec.view_leaf(flat, path)),
                                  np.asarray(tree["a"]))
    with pytest.raises(KeyError):
        spec.view_leaf(flat, "nope")


def test_flatspec_nbytes_matches_tree_bytes():
    from repro.utils.trees import tree_bytes

    tree = _mixed_tree()
    assert FlatSpec.from_tree(tree).nbytes == tree_bytes(tree)


def test_flatspec_rejects_int_leaves():
    with pytest.raises(TypeError):
        FlatSpec.from_tree({"i": jnp.arange(3)})


def test_flatspec_hashable_and_eq():
    t1, t2 = _mixed_tree(), _mixed_tree()
    s1, s2 = FlatSpec.from_tree(t1), FlatSpec.from_tree(t2)
    assert s1 == s2 and hash(s1) == hash(s2)
    s3 = FlatSpec.from_tree({"a": t1["a"]})
    assert s1 != s3


def test_flatspec_empty_tree():
    spec = FlatSpec.from_tree({})
    assert spec.size == 0
    assert spec.ravel({}).shape == (0,)


def test_flatspec_aligned_plane_roundtrips_with_zero_tail():
    tree = _mixed_tree()
    plain = FlatSpec.from_tree(tree)
    spec = plain.aligned(64)
    assert (spec.size, spec.plane_size) == (103, 128)
    assert plain.plane_size == plain.size and plain.aligned(1) == plain
    assert spec != plain and hash(spec) == hash(plain.aligned(64))
    flat = spec.ravel(tree)
    assert flat.shape == (128,)
    np.testing.assert_array_equal(np.asarray(flat[:103]), np.asarray(plain.ravel(tree)))
    np.testing.assert_array_equal(np.asarray(flat[103:]), 0.0)
    # the tail is never read: whatever it holds, the leaves come back
    back = spec.unravel(flat.at[103:].set(7.0))
    for o, r in zip(jax.tree_util.tree_leaves(plain.unravel(plain.ravel(tree))),
                    jax.tree_util.tree_leaves(back)):
        assert o.dtype == r.dtype
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
    np.testing.assert_array_equal(np.asarray(spec.view_leaf(flat, 0)),
                                  np.asarray(tree["a"]))
    # stacked leading axes carry the tail on every row
    per_client = {"w": jax.ShapeDtypeStruct((3, 2), jnp.float32)}
    stacked = {"w": jnp.asarray(RNG.normal(size=(4, 3, 2)), jnp.float32)}
    rows = FlatSpec.from_tree(per_client).aligned(8).ravel(stacked, batch_dims=1)
    assert rows.shape == (4, 8)
    np.testing.assert_array_equal(np.asarray(rows[:, 6:]), 0.0)


# ----------------------------------------------------------------------
# engine: flat plane vs tree-path oracle
# ----------------------------------------------------------------------

N_ROUNDS = 3


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


def _fresh(eng, model):
    return eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))


def _assert_close(a, b, atol=1e-5, rtol=1e-5):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=rtol, atol=atol)


@pytest.mark.parametrize("algo", list_algorithms())
def test_flat_plane_matches_tree_oracle(algo):
    """EVERY registered algorithm (the registry is the parametrization —
    a newly registered spec is held to this automatically)."""
    cfg, eng_flat, data, model = _setup(algo)
    assert cfg.use_flat_plane  # flat is the default engine
    eng_tree = FederatedEngine(
        replace(cfg, use_flat_plane=False), eng_flat.loss_fn, batch_size=8
    )
    s_flat, m_flat = eng_flat.run_rounds(_fresh(eng_flat, model), data, N_ROUNDS)
    s_tree, m_tree = eng_tree.run_rounds(_fresh(eng_tree, model), data, N_ROUNDS)
    _assert_close(s_flat.params, s_tree.params)
    _assert_close(s_flat.server.momentum, s_tree.server.momentum)
    _assert_close(s_flat.server.second_moment, s_tree.server.second_moment)
    if s_tree.client_states is not None:
        _assert_close(s_flat.client_states, s_tree.client_states)
        # treedef restored too: the flat engine must hand back a real tree
        assert jax.tree_util.tree_structure(
            s_flat.client_states
        ) == jax.tree_util.tree_structure(s_tree.client_states)
    np.testing.assert_allclose(np.asarray(m_flat.loss), np.asarray(m_tree.loss),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(m_flat.delta_norm),
                               np.asarray(m_tree.delta_norm), rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(m_flat.n_active),
                                  np.asarray(m_tree.n_active))
    np.testing.assert_array_equal(np.asarray(m_flat.bytes_down),
                                  np.asarray(m_tree.bytes_down))


def test_flat_plane_per_round_matches_fused():
    """ravel-per-round (run_round) and ravel-once (run_rounds) must agree:
    the f32 plane round-trips through the tree losslessly between rounds."""
    _, eng, data, model = _setup("scaffold")
    st = _fresh(eng, model)
    for _ in range(N_ROUNDS):
        st, _ = eng.run_round(st, data)
    fused, _ = eng.run_rounds(_fresh(eng, model), data, N_ROUNDS)
    _assert_close(st.params, fused.params, atol=1e-6, rtol=2e-5)
    _assert_close(st.client_states, fused.client_states, atol=1e-6, rtol=2e-5)


def test_run_rounds_donation_safety():
    """run_rounds donates its input: once the trajectory is returned, later
    calls recycling those buffers must not corrupt it, and the returned
    state must itself be usable as the next donated input."""
    _, eng, data, model = _setup("fedcm")
    out1, _ = eng.run_rounds(_fresh(eng, model), data, N_ROUNDS)
    snap = [np.array(l) for l in jax.tree_util.tree_leaves(out1.params)]
    # same shapes → jax may reuse the donated buffers of this second call
    out2, _ = eng.run_rounds(_fresh(eng, model), data, N_ROUNDS)
    for s, l in zip(snap, jax.tree_util.tree_leaves(out1.params)):
        np.testing.assert_array_equal(s, np.asarray(l))
    # identical seeds → identical trajectories
    for a, b in zip(jax.tree_util.tree_leaves(out1.params),
                    jax.tree_util.tree_leaves(out2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # chaining off the returned (donated-in) state works
    out3, m3 = eng.run_rounds(out2, data, 2)
    assert int(out3.server.round) == N_ROUNDS + 2
    assert np.all(np.isfinite(np.asarray(m3.loss)))


def test_flat_engine_bf16_mixed_param_tree():
    """A params tree mixing bf16 and f32 leaves runs on the flat plane and
    stays close to the tree path (bf16 tolerance: the plane carries f32
    across local steps, the tree path re-rounds each step)."""

    def loss_fn(params, batch):
        d = params["w"].astype(jnp.float32) - batch["c"]
        return 0.5 * jnp.mean(jnp.sum(d**2, -1)) + 0.5 * jnp.mean(
            params["b"].astype(jnp.float32) ** 2
        )

    cfg = FedConfig(algo="fedcm", num_clients=4, cohort_size=2, local_steps=2,
                    participation="fixed", weight_decay=0.0)
    params = {
        "w": jnp.asarray(RNG.normal(size=(6,)), jnp.bfloat16),
        "b": jnp.asarray(RNG.normal(size=(3,)), jnp.float32),
    }
    eng = FederatedEngine(cfg, loss_fn, batch_size=2)
    engt = FederatedEngine(replace(cfg, use_flat_plane=False), loss_fn, batch_size=2)

    centers = jnp.asarray(RNG.normal(size=(4, 2, 6)), jnp.float32)  # (C, B, 6)
    batches = {"c": jnp.broadcast_to(centers[:, None], (4, 2, 2, 6))}
    ids, mask = jnp.arange(2), jnp.ones(2, bool)
    st = eng.init(params, jax.random.PRNGKey(0))
    stt = engt.init(params, jax.random.PRNGKey(0))
    b2 = jax.tree_util.tree_map(lambda a: a[:2], batches)
    new, _ = eng.round_step(st, b2, ids, mask)
    newt, _ = engt.round_step(stt, b2, ids, mask)
    assert new.params["w"].dtype == jnp.bfloat16
    assert new.params["b"].dtype == jnp.float32
    _assert_close(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), new.params),
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), newt.params),
        atol=2e-2, rtol=2e-2,
    )


# ----------------------------------------------------------------------
# the gradient through the plane view
# ----------------------------------------------------------------------


def _sliced_view(spec, flat, dtype=None):
    """The view as plain slices, whose autodiff transpose pads each leaf's
    cotangent to the whole plane and sums the pads: the oracle of
    ``FlatSpec.unravel``'s own transpose."""
    dtypes = [dtype or l.dtype for l in spec.leaves]
    leaves = split_flat(flat, [l.shape for l in spec.leaves], dtypes)
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def _leaf_loss(tree):
    """Weighs every leaf differently; leaf "unused" gets a zero cotangent."""
    total = 0.0
    for i, (path, leaf) in enumerate(jax.tree_util.tree_leaves_with_path(tree)):
        if "unused" not in jax.tree_util.keystr(path):
            total += jnp.sum(jnp.sin(leaf.astype(jnp.float32) * (i + 1)) * leaf)
    return total


_VIEW_CASES = {
    "mixed": dict(lead=(), batch_dims=0, vmap=False, dtype=None),
    "batch_dims": dict(lead=(3,), batch_dims=1, vmap=False, dtype=None),
    "vmap": dict(lead=(3,), batch_dims=1, vmap=True, dtype=None),
    "dtype-override": dict(lead=(), batch_dims=0, vmap=False, dtype=jnp.bfloat16),
    "vmap-dtype-override": dict(lead=(2,), batch_dims=1, vmap=True, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_VIEW_CASES))
def test_unravel_gradient_equals_the_slices_transpose(case):
    """Leaves of mixed shape and dtype (f32, bf16, a scalar, one the loss
    does not read) on a plane with a zero tail: ``jax.grad`` through
    ``unravel`` equals the gradient through the plain slices exactly (only
    the sign of a zero may differ, which ``==`` ignores), with leading
    batch axes, under ``vmap`` and with the ``dtype=`` override."""
    c = _VIEW_CASES[case]
    per_leaf = {**_mixed_tree(), "unused": jnp.ones((4, 2), jnp.float32)}
    spec = FlatSpec.from_tree(per_leaf).aligned(64)
    assert spec.plane_size > spec.size
    tree = jax.tree_util.tree_map(
        lambda l: jnp.asarray(RNG.normal(size=c["lead"] + l.shape), l.dtype), per_leaf)
    flat = spec.ravel(tree, batch_dims=c["batch_dims"])

    def grad_of(view):
        g = jax.grad(lambda f: _leaf_loss(view(f)))
        return jax.vmap(g) if c["vmap"] else g

    got = grad_of(lambda f: spec.unravel(f, dtype=c["dtype"]))(flat)
    want = grad_of(lambda f: _sliced_view(spec, f, dtype=c["dtype"]))(flat)
    assert got.shape == flat.shape and got.dtype == flat.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.any(np.asarray(got)[..., :spec.size] != 0)
    np.testing.assert_array_equal(np.asarray(got)[..., spec.size:], 0.0)


@pytest.mark.parametrize("algo", ["fedcm", "mimelite"])
def test_kernel_path_planes_match_the_slices_transpose(algo, monkeypatch):
    """A ``run_rounds`` on a multi-leaf model with weight decay
    (MimeLite adds a full-batch gradient) gives the planes, momentum and
    losses that the view's plain slices give, bit for bit."""
    runs = []
    for view in (FlatSpec.unravel, _sliced_view):
        monkeypatch.setattr(FlatSpec, "unravel", view)
        _, eng, data, model = _setup(algo, weight_decay=1e-3)
        runs.append(eng.run_rounds(_fresh(eng, model), data, N_ROUNDS))
    (got, m_got), (want, m_want) = runs
    for a, b in ((got.params, want.params), (got.server.momentum, want.server.momentum)):
        assert len(jax.tree_util.tree_leaves(a)) == 4
        for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    np.testing.assert_array_equal(np.asarray(m_got.loss), np.asarray(m_want.loss))


# ----------------------------------------------------------------------
# the engine's aligned plane
# ----------------------------------------------------------------------


def _tails(fstate, P):
    """The zero tails of every plane a flat state carries."""
    planes = {"x": fstate.params, "m": fstate.server.momentum,
              "v": fstate.server.second_moment, "c_i": fstate.client_states,
              "residual": fstate.residuals}
    return {k: np.asarray(v)[..., P:] for k, v in planes.items() if v is not None}


_TAIL_CASES = [pytest.param(a, {}, "sync", id=a) for a in list_algorithms()] + [
    pytest.param("scaffold", dict(cohort_shard=1), "sync", id="scaffold-sharded"),
    pytest.param("scaffold", dict(compression=CompressionConfig(kind="int8")),
                 "sync", id="scaffold-int8"),
    pytest.param("fedcm", dict(compression=CompressionConfig(kind="topk", topk_frac=0.1)),
                 "sync", id="fedcm-topk"),
    pytest.param("scaffold", {}, "async", id="scaffold-async"),
]


@pytest.mark.parametrize("algo,kw,mode", _TAIL_CASES)
def test_kernel_path_plane_tail_stays_zero(algo, kw, mode):
    """Every plane the flat engine carries keeps its zero tail exactly
    zero through several rounds: x, m, the client state, the top-k
    residuals and, on the async ring, the in-flight uplinks."""
    _, eng, data, model = _setup(algo, **kw)
    st = _fresh(eng, model)
    spec = eng._flat_spec(st.params)
    assert spec.plane_size == 4096 > spec.size  # 212 leaves, one tile
    eng._unravel_state = lambda fstate, spec: fstate  # hand back the planes
    if mode == "sync":
        fstate, _ = eng.run_rounds(st, data, 4)
        pending = ()
    else:
        fstate, pending, _ = eng._run_rounds_async(
            st, data.client_x, data.client_y, None, None, None, n_rounds=4,
            pipeline_depth=2, staleness=1, eval_every=0, predict_fn=None)
    assert fstate.params.shape == (spec.plane_size,)
    for name, tail in _tails(fstate, spec.size).items():
        np.testing.assert_array_equal(tail, 0.0, err_msg=name)
    for entry in pending:
        for name in ("delta", "state_delta", "extra"):
            v = getattr(entry, name)
            if v is not None:
                np.testing.assert_array_equal(np.asarray(v)[..., spec.size:], 0.0,
                                              err_msg=name)


@pytest.mark.parametrize("kind", [None, "int8", "topk"])
def test_aligned_plane_charges_the_leaves(kind):
    """Payload accounting charges the leaves' P, not the aligned plane's
    P': scaffold sends x and c down and two wire planes up."""
    comp = None if kind is None else CompressionConfig(kind=kind, topk_frac=0.1)
    _, eng, data, model = _setup("scaffold", compression=comp)
    st = _fresh(eng, model)
    leaves = FlatSpec.from_tree(st.params)
    assert eng._flat_spec(st.params).plane_size > leaves.size
    wire = get_algorithm("scaffold").wire_uplink_planes
    up = (len(wire) * leaves.nbytes if comp is None else
          uplink_bytes_per_client(comp, wire, leaves.size, leaves.nbytes))
    down = 2 * leaves.nbytes
    assert eng.payload_bytes(st.params) == {"down_per_client": down, "up_per_client": up}
    _, ms = eng.run_rounds(st, data, 1)
    n = float(ms.n_active[0])
    assert (float(ms.bytes_up[0]), float(ms.bytes_down[0])) == (n * up, n * down)


# ----------------------------------------------------------------------
# CohortUplink ring (async pipelined engine's in-flight cohort store)
# ----------------------------------------------------------------------


def _uplink(C, P, val, with_state=True):
    from repro.core import CohortUplink

    return CohortUplink(
        delta=jnp.full((C, P), val, jnp.float32),
        state_delta=jnp.full((C, P), 2 * val, jnp.float32) if with_state else None,
        extra=None,
        ids=jnp.arange(C, dtype=jnp.int32),
        w=jnp.ones((C,), jnp.float32),
        eta_l=jnp.float32(0.1 * val),
    )


def test_ring_push_rotates_oldest_first():
    from repro.core import ring_push

    C, P = 4, 11
    pending = (_uplink(C, P, 1.0), _uplink(C, P, 2.0))  # depth 3 ring: D-1 pending
    oldest, pending = ring_push(pending, _uplink(C, P, 3.0))
    np.testing.assert_array_equal(np.asarray(oldest.delta), 1.0)
    np.testing.assert_array_equal(np.asarray(oldest.state_delta), 2.0)
    assert len(pending) == 2
    np.testing.assert_array_equal(np.asarray(pending[0].delta), 2.0)
    np.testing.assert_array_equal(np.asarray(pending[1].delta), 3.0)
    # depth 1 (sync schedule): the entry folds the round it launches
    oldest, empty = ring_push((), _uplink(C, P, 9.0, with_state=False))
    assert empty == () and oldest.state_delta is None and oldest.extra is None
    np.testing.assert_array_equal(np.asarray(oldest.delta), 9.0)


def test_ring_push_is_scan_carry_compatible():
    """The rotated tuple must hold its treedef across scan iterations (the
    steady scan carries it) and work as pure dataflow under jit."""
    from repro.core import ring_push

    C, P = 2, 5

    def body(carry, x):
        pending = carry
        entry = _uplink(C, P, 0.0, with_state=False)._replace(
            delta=jnp.full((C, P), x, jnp.float32))
        oldest, pending = ring_push(pending, entry)
        return pending, jnp.max(oldest.delta)

    init = (_uplink(C, P, -2.0, with_state=False),
            _uplink(C, P, -1.0, with_state=False))  # depth 3
    pending, folded = jax.lax.scan(body, init, jnp.arange(5, dtype=jnp.float32))
    # folds see entries in launch order, D-1 = 2 rounds late
    np.testing.assert_array_equal(np.asarray(folded), [-2.0, -1.0, 0.0, 1.0, 2.0])
    # the final pending entries are the last two launches (the drain's input)
    np.testing.assert_array_equal(np.asarray(pending[0].delta), 3.0)
    np.testing.assert_array_equal(np.asarray(pending[1].delta), 4.0)
