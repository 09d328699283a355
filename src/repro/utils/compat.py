"""The one routing point for the jax mesh and ``shard_map`` APIs.

The repo is written against the installed jax (0.9.0).  Every mesh
constructor, ambient-mesh context and ``shard_map`` call goes through this
module instead of jax directly, so the next API move lands in one place:

* ``set_mesh(mesh)`` — ``jax.set_mesh``: context manager making ``mesh``
  the ambient mesh.
* ``make_mesh(axis_shapes, axis_names)`` — ``jax.make_mesh`` with every
  axis ``AxisType.Auto``.  jax's default is ``Explicit`` axes, under which
  the models' ``with_sharding_constraint`` calls are refused ("can only
  refer to Auto axes").
* ``device_mesh(devices, axis_names)`` — the explicit-device-list ``Mesh``
  constructor.
* ``shard_map(f, mesh=..., in_specs=..., out_specs=..., check_vma=...)`` —
  ``jax.shard_map``.

The REP002 lint rule (``repro.analysis.lint``) enforces the routing: any
direct call to the symbols above outside this module is a finding.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def set_mesh(mesh):
    """Context manager setting ``mesh`` as the ambient mesh.

    Usage::

        with set_mesh(mesh):
            compiled = fn.lower(...).compile()
    """
    return jax.set_mesh(mesh)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` (device order that favors the backend's collective
    topology) with ``Auto`` axes, so sharding constraints may name them."""
    return jax.make_mesh(
        axis_shapes, axis_names, axis_types=(AxisType.Auto,) * len(axis_names)
    )


def device_mesh(devices, axis_names):
    """Build a ``Mesh`` over an explicit device array/list.

    The thin-but-deliberate routing point for the raw ``Mesh``
    constructor: all mesh construction in the repo goes through this
    module, so a future constructor change (e.g. ``AbstractMesh``
    plumbing) lands in one place.
    """
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), axis_names)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kwargs):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default.  All other
    kwargs pass through untouched."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         **kwargs)
