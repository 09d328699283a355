"""The flat parameter plane: ravel a pytree ONCE, compute on one buffer.

Every update FedCM (and each registered algorithm — see
``repro.core.registry``) performs — the client blend
``v = α·g + (1−α)·Δ_t``, SCAFFOLD's ``g − c_i + c``, the masked cohort
mean, the server momentum/param step — is elementwise over the parameter
vector.  The pytree structure only matters to the *loss function*; carrying
it through the update phase costs a tree_map dispatch per leaf per op and,
around the fused kernels, a full concatenate/split round-trip per local
step.  ``FlatSpec`` fixes the representation instead:

* ``ravel(tree)``      → ONE contiguous ``(P,)`` buffer (default f32),
* ``unravel(flat)``    → the original tree (shapes AND dtypes restored) —
  leaves are slices of the buffer, essentially free under jit,
* ``view_leaf(flat, key)`` → a single leaf without materializing the tree.

The layout is the static offset table ``spec.leaves`` (path, shape, dtype,
offset, size) in treedef order, with no padding between leaves.  The
plane may end in a zero tail: ``spec.size`` is the leaves' total P,
``spec.plane_size`` the plane's length P' ≥ P, a multiple of the
``align`` the spec was built with (``FlatSpec.aligned``).  The engine
aligns its plane to the kernels' block length
(``repro.kernels.plane_alignment``), so no launch pads its operands or
slices its outputs; ``ravel`` writes the tail as zeros, ``unravel`` and
``view_leaf`` never read it, and every affine direction and fold row
keeps it zero (see ``src/repro/kernels/README.md``).  Buffers with
leading batch axes reuse the same table: a cohort delta plane is
``(C, P')``, stacked per-client control variates are ``(N, P')``;
``unravel`` restores ``(..., *shape)`` leaves.

``FederatedEngine`` ravels params/momentum/client-state once per
``run_rounds`` call and carries the planes through the local-step scan, the
cohort vmap, aggregation, and the server update (``cfg.use_flat_plane``).
That is its one flat path; the tree path (``use_flat_plane=False``)
remains as the numerical oracle.

``CohortUplink`` is the in-flight cohort store of the async pipelined
engine (``FederatedEngine.run_rounds_async``): a static depth-D ring of
uplink planes plus per-cohort metadata, carried through the pipelined
``lax.scan`` as a python tuple the body rotates (``ring_push``).  An
uplink launched at round t is folded D−1 rounds later when the server
folds the (by then stale) cohort in — the ``(C, P)`` slot layout is the same layout a cohort-axis reduce-scatter wants, which is
what makes the ring the natural seam for multi-host cohort sharding.

Under the out-of-core population store (``cfg.population_store="host"``,
see ``repro.data.population``) the ring's client-state planes are the
host-gathered ``(C, P)`` cohort rows — device memory never holds an
``(N, ·)`` per-client plane; the population axis exists only in the host
store's sparse row map.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.trees import ravel_leaves, split_flat

# Name scope of every conversion between the plane and its leaf views
# (``FlatSpec.ravel`` / ``unravel``).  It reaches the compiled program's
# ``op_name`` metadata, the gradient's return into the plane included
# (``unravel``'s backward is a ``ravel``), so a device trace can attribute
# the plane's copies.
PLANE_VIEW_SCOPE = "fedcm.plane_view"


class LeafSpec(NamedTuple):
    """Static layout of one leaf inside the flat plane."""

    path: str  # jax.tree_util.keystr of the leaf's key path
    shape: Tuple[int, ...]
    dtype: Any  # numpy dtype (hashable)
    offset: int  # first element in the plane
    size: int  # number of elements


class FlatSpec:
    """Static per-leaf offset/shape/dtype table for one pytree structure.

    Hashable and comparable so it can serve as (part of) a jit cache key;
    building one is pure python and happens at trace time.
    """

    __slots__ = ("treedef", "leaves", "size", "plane_size")

    def __init__(self, treedef, leaves: Tuple[LeafSpec, ...], align: int = 1):
        self.treedef = treedef
        self.leaves = leaves
        # the leaves' total P: what payload accounting charges
        self.size = (leaves[-1].offset + leaves[-1].size) if leaves else 0
        # the plane's length P': P rounded up to ``align``, the tail zero
        self.plane_size = -(-self.size // align) * align

    def aligned(self, align: int) -> "FlatSpec":
        """The same layout on a plane whose length is a multiple of
        ``align`` (a zero tail after the last leaf)."""
        return FlatSpec(self.treedef, self.leaves, align)

    # ------------------------------------------------------------- build
    @classmethod
    def from_tree(cls, tree, require_float: bool = True) -> "FlatSpec":
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs, off = [], 0
        for path, leaf in flat:
            dt = np.dtype(leaf.dtype)
            if require_float and not jnp.issubdtype(dt, jnp.floating):
                raise TypeError(
                    f"flat plane requires floating leaves; "
                    f"{jax.tree_util.keystr(path)} has dtype {dt} "
                    f"(set cfg.use_flat_plane=False for non-float params)"
                )
            size = math.prod(leaf.shape)
            specs.append(
                LeafSpec(jax.tree_util.keystr(path), tuple(leaf.shape), dt, off, size)
            )
            off += size
        return cls(treedef, tuple(specs))

    # ------------------------------------------------------------- ravel
    def ravel(self, tree, dtype=jnp.float32, batch_dims: int = 0) -> jax.Array:
        """Tree → one contiguous ``(*lead, P')`` buffer in ``dtype``.

        ``batch_dims`` leading axes of every leaf (e.g. the stacked-client
        axis of ``(N, *shape)`` state) are preserved in front of the plane
        axis.  This is the ONE concatenate of the flat engine — everything
        downstream operates on the buffer.  The zero tail is one more
        operand of that concatenate.
        """
        leaves = self.treedef.flatten_up_to(tree)
        tail = self.plane_size - self.size
        if tail:
            lead = leaves[0].shape[:batch_dims]
            leaves = [*leaves, jnp.zeros((*lead, tail), dtype)]
        with jax.named_scope(PLANE_VIEW_SCOPE):
            return ravel_leaves(leaves, dtype=dtype, batch_dims=batch_dims)

    def unravel(self, flat: jax.Array, dtype=None):
        """Buffer ``(*lead, P')`` → tree of ``(*lead, *shape)`` leaves (the
        tail is never read).

        Leaf dtypes are restored from the table (pass ``dtype`` to override,
        e.g. a uniform momentum dtype).  Under jit the slices fuse into
        their consumers — no per-step copy.

        The view's transpose is ``ravel``: the gradient returns to the plane
        as ONE concatenate of the leaf cotangents (cast to the plane's
        dtype, the zero tail last), where autodiff of the slices would pad
        each leaf's cotangent to the whole plane and sum the pads — a pass
        whose cost grows with leaves × P'.  The values are the same: each
        element is its leaf's cotangent either way (up to the sign of a
        zero).
        """
        dtypes = [dtype or l.dtype for l in self.leaves]
        plane_dtype, batch_dims = flat.dtype, flat.ndim - 1

        @jax.custom_vjp
        def view(flat):
            with jax.named_scope(PLANE_VIEW_SCOPE):
                leaves = split_flat(flat, [l.shape for l in self.leaves], dtypes)
            return jax.tree_util.tree_unflatten(self.treedef, leaves)

        def view_bwd(_, ct):
            return (self.ravel(ct, dtype=plane_dtype, batch_dims=batch_dims),)

        view.defvjp(lambda flat: (view(flat), None), view_bwd)
        return view(flat)

    def view_leaf(self, flat: jax.Array, key: Union[int, str], dtype=None):
        """One leaf of the plane by index or key path, without the tree."""
        if isinstance(key, str):
            matches = [i for i, l in enumerate(self.leaves) if l.path == key]
            if not matches:
                raise KeyError(f"no leaf {key!r}; paths: {[l.path for l in self.leaves]}")
            key = matches[0]
        spec = self.leaves[key]
        seg = jax.lax.slice_in_dim(flat, spec.offset, spec.offset + spec.size, axis=-1)
        seg = seg.reshape(*flat.shape[:-1], *spec.shape)
        return seg.astype(dtype or spec.dtype)

    # ------------------------------------------------------------- misc
    @property
    def nbytes(self) -> int:
        """Bytes of the ORIGINAL tree (per-leaf dtypes) — payload accounting
        must charge the wire format, not the f32 compute plane."""
        return sum(l.size * l.dtype.itemsize for l in self.leaves)

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, FlatSpec)
            and self.treedef == other.treedef
            and self.leaves == other.leaves
            and self.plane_size == other.plane_size
        )

    def __hash__(self) -> int:
        return hash((self.treedef, self.leaves, self.plane_size))

    def __repr__(self) -> str:
        return (f"FlatSpec(n_leaves={len(self.leaves)}, size={self.size}, "
                f"plane_size={self.plane_size})")


# ----------------------------------------------------------------------
# in-flight cohort ring (async pipelined engine)
# ----------------------------------------------------------------------


class CohortUplink(NamedTuple):
    """ONE in-flight cohort's uplink on the flat plane — the unit the
    async engine's depth-D ring carries (a python tuple of D−1 pending
    uplinks in the scan carry; the D-th is the one being launched).

    Every plane is a raw ``(C, P)`` cohort plane: the fused server kernel
    folds mean + EMA + param step in ONE streaming pass over the cohort
    axis at fold time, and the client-state scatter is per-client.  Under
    compression a plane may ride as its wire representation (``QPlane``,
    ``TopKPlane``) until the fold.  ``state_delta``/``extra`` are ``None``
    for algorithms without client state / full-batch gradients — never
    allocated, never copied.
    """

    delta: jax.Array  # (C, P)
    state_delta: Optional[jax.Array]  # (C, P) or None (SCAFFOLD/FedDyn)
    extra: Optional[jax.Array]  # (C, P) or None (MimeLite)
    ids: jax.Array  # (C,) int32 sampled client ids
    w: jax.Array  # (C,) f32 active-mask weights
    eta_l: jax.Array  # f32 η_l at launch (the fold must reuse it)


def pad_cohort(tree, target: int, mode: str = "edge"):
    """Pad the leading (cohort) axis of every leaf to ``target`` rows.

    The cohort-parallel engine pads the sampled cohort to a multiple of the
    ``"clients"`` mesh axis AFTER the minibatch/state gathers (so the rng
    stream and every real client's data are bitwise those of the unsharded
    round) and gives the pad rows zero weight: a trailing ``+ 0.0`` in the
    masked fold is exact, which is what keeps the ragged-cohort case
    bitwise against the unsharded oracle.  ``None`` passes through.

    ``mode="edge"`` (default, for DATA: batches, gathered client states,
    ids) repeats the last real row — the pad clients then run their local
    steps on a real client's finite inputs, so a loss_fn that is
    non-finite on all-zero input (batch-statistic normalizers) cannot
    poison the fold through ``0 · NaN = NaN``.  ``mode="zero"`` is for
    the WEIGHT row, whose pad entries must stay exactly 0.
    """
    if tree is None:
        return None

    def p(a):
        C = a.shape[0]
        if C >= target:
            return a
        widths = [(0, target - C)] + [(0, 0)] * (a.ndim - 1)
        if mode == "edge":
            return jnp.pad(a, widths, mode="edge")
        return jnp.pad(a, widths)

    return jax.tree_util.tree_map(p, tree)


def cohort_to_columns(plane, axis_name: str, n_shards: int):
    """Clients-sharded ``(C, P)`` plane → plane-column shards, INSIDE
    ``shard_map``: pad the plane axis to a multiple of ``n_shards`` and
    ``all_to_all`` so each device holds ``(C, ceil(P/n_shards))`` — the
    COMPLETE cohort for its columns.  This is the reduce-scatter's first
    half, decomposed so the subsequent device-local reduce runs over all
    C clients in the unsharded reduction order (a ``psum_scatter`` would
    pre-reduce per device and re-associate the f32 sum — the bitwise
    oracle breaks).  Shared by every scattered reduction
    (``cohort_mean_scatter`` here, ``scatter_fold`` in the server kernel
    ops) — the decomposition is load-bearing, keep it in one place."""
    Pn = plane.shape[-1]
    chunk = -(-Pn // n_shards)
    plane = jnp.pad(plane, ((0, 0), (0, chunk * n_shards - Pn)))
    return jax.lax.all_to_all(plane, axis_name, split_axis=1, concat_axis=0,
                              tiled=True)


def plane_chunk(vec, axis_name: str, n_shards: int):
    """This device's column chunk of a replicated ``(P,)`` plane (the
    slice aligned with ``cohort_to_columns``'s layout)."""
    Pn = vec.shape[-1]
    chunk = -(-Pn // n_shards)
    idx = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice(jnp.pad(vec, (0, chunk * n_shards - Pn)),
                                 (idx * chunk,), (chunk,))


def gather_plane(vec, axis_name: str, n: int):
    """Inverse of ``plane_chunk``: all_gather the per-device column chunks
    back to the replicated ``(n,)`` plane (pad columns dropped)."""
    return jax.lax.all_gather(vec, axis_name, tiled=True)[:n]


def cohort_mean_scatter(plane, w, n_active, axis_name: str, n_shards: int,
                        agg_dtype=jnp.float32):
    """Masked cohort mean of one ``(C, P)`` plane, lowered as an explicit
    reduce-scatter + all-gather — call INSIDE ``shard_map`` with ``plane``
    sharded over ``axis_name`` (local view ``(C/n_shards, P)``) and ``w``
    replicated.

    The reduce-scatter is decomposed as ``cohort_to_columns`` (cohort
    shards → plane-column shards) followed by a device-local full-cohort
    contraction: every device then reduces over the COMPLETE client axis
    for its plane columns, in exactly the reduction order (and with
    exactly the ``aggregate_dtype`` quantization) of the unsharded
    ``_masked_pmean``.  The trailing ``gather_plane`` rebuilds the
    replicated ``(P,)`` mean.
    """
    Pn = plane.shape[-1]
    cols = cohort_to_columns(plane, axis_name, n_shards)
    # max(n, 1) guards the empty cohort (0/0 would NaN-poison the fold);
    # exact for n ≥ 1, so non-empty rounds stay bitwise
    mean = (
        jnp.tensordot(w.astype(agg_dtype), cols.astype(agg_dtype), axes=(0, 0))
        .astype(jnp.float32) / jnp.maximum(n_active, 1.0)
    )
    return gather_plane(mean, axis_name, Pn)


def ring_push(pending: Tuple[CohortUplink, ...], entry: CohortUplink):
    """Rotate the static-depth ring: append the just-launched uplink, pop
    the OLDEST for folding.  Returns ``(oldest, new_pending)``.

    The ring is a python tuple because depth is small and STATIC: rotating
    positions at trace time gives XLA direct carry dataflow — the fold
    reads a while-loop carry buffer, no per-round
    ``dynamic_update_slice``/``dynamic_slice`` materialization.  (A
    stacked ``(D, …)`` buffer with traced slot indices measured ~10%
    slower per round on the update-bound benchmark; a traced-depth ring —
    and the cohort-axis reduce-scatter of the multi-host roadmap item —
    would bring the stacked form back.)

    ``pending`` holds D−1 uplinks in launch order (oldest first); with
    D = 1 it is empty and the entry folds the round it launches — the
    sync schedule.
    """
    fifo = (*pending, entry)
    return fifo[0], fifo[1:]
