"""Tests for the jax routing shims (repro.utils.compat).

The shims run for real on the installed jax, and monkeypatched top-level
``jax`` attributes check that each one forwards to the jax symbol it
routes (resolved per call, so a patch is seen).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.utils import compat


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


# ----------------------------------------------------------------------
# executed on the installed jax
# ----------------------------------------------------------------------


def test_set_mesh_context_enters_and_exits():
    mesh = _one_device_mesh()
    with compat.set_mesh(mesh):
        # a trivial lowering under the ambient mesh must work
        out = jax.jit(lambda x: x + 1)(jnp.zeros((4,)))
    np.testing.assert_array_equal(np.asarray(out), np.ones((4,)))


def test_shard_map_runs_with_check_vma_kwarg():
    mesh = _one_device_mesh()

    def body(x):
        return jax.lax.psum(x, "data")  # 1-device axis: identity

    f = compat.shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=P(), check_vma=False
    )
    out = f(jnp.arange(4, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.arange(4, dtype=np.float32))


def test_shard_map_psum_value():
    mesh = _one_device_mesh()

    def body(x):
        return jnp.sum(x, keepdims=True)

    f = compat.shard_map(
        body, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"), check_vma=False
    )
    out = f(jnp.arange(4, dtype=jnp.float32))
    assert float(out[0]) == pytest.approx(6.0)


def test_make_mesh_axes_are_auto():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2


def test_make_mesh_accepts_sharding_constraints():
    """Explicit axes (jax.make_mesh's default) refuse
    ``with_sharding_constraint``; every model layer relies on it."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    sh = jax.sharding.NamedSharding(mesh, P("data", "model"))
    f = jax.jit(lambda x: jax.lax.with_sharding_constraint(x * 2, sh))
    np.testing.assert_array_equal(np.asarray(f(jnp.ones((2, 2)))),
                                  2 * np.ones((2, 2)))


# ----------------------------------------------------------------------
# forwarding (monkeypatched top-level jax.set_mesh / jax.shard_map)
# ----------------------------------------------------------------------


def test_set_mesh_prefers_toplevel_api(monkeypatch):
    sentinel = object()
    calls = []

    def fake_set_mesh(mesh):
        calls.append(mesh)
        return sentinel

    monkeypatch.setattr(jax, "set_mesh", fake_set_mesh, raising=False)
    mesh = _one_device_mesh()
    assert compat.set_mesh(mesh) is sentinel
    assert calls == [mesh]


def test_shard_map_prefers_toplevel_api_and_passes_check_vma(monkeypatch):
    seen = {}

    def fake_shard_map(f, *, mesh, in_specs, out_specs, **kwargs):
        seen.update(kwargs, mesh=mesh)
        return lambda *a: "new-path"

    monkeypatch.setattr(jax, "shard_map", fake_shard_map, raising=False)
    mesh = _one_device_mesh()
    f = compat.shard_map(
        lambda x: x, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False
    )
    assert f(jnp.zeros(())) == "new-path"
    assert seen["check_vma"] is False
    assert seen["mesh"] is mesh
