"""Uplink compression as pure plane transforms (config/spec data).

At production scale the network, not the FLOPs, is the budget: an
uncompressed cohort round moves full-precision f32 ``(C, P)`` uplink
planes, and the async engine keeps ``pipeline_depth`` of them in flight.
This module realizes :class:`repro.configs.base.CompressionConfig` as
pure plane transforms spliced between client launch and server fold on
EVERY execution path — sync jnp/kernel, async ring (ring entries carry
the compressed representation, 4–8x less in-flight memory at depth D),
cohort-sharded (``all_to_all`` moves int8/bf16 payloads instead of f32),
and the host-store loop.  ``compression=None`` traces none of this.

Representations
---------------
* ``"int8"`` → :class:`QPlane`: per-row absmax scaling
  (``scale = max|row| / 127``, zero rows get scale 1) + stochastic
  rounding ``q = clip(floor(x/scale + U[0,1)), −127, 127)``.  Unbiased:
  ``E[q·scale] = x`` elementwise (the clip never binds — ``|x/scale| ≤
  127`` by construction, and ``floor(±127 + u) = ±127`` for ``u < 1``).
  1 byte/element + one f32 scale per client row on the wire.
* ``"bf16"`` → a plain bfloat16 ``(C, P)`` array (round-to-nearest-even;
  2 bytes/element).  The fused dequant fold consumes it with unit scale.
* ``"topk"`` → :class:`TopKPlane`: per-row magnitude top-k of the DELTA
  plane (``k = max(1, round(topk_frac · P))``) with error feedback —
  the unsent remainder accumulates in a per-client residual plane
  (resident ``(N, P)`` or a host-store row stream) and is added to that
  client's next uplink, the standard fix for sparsification bias
  (memory/EF-SGD).  8 bytes/kept element (f32 value + int32 index).
  Non-delta wire planes (SCAFFOLD's control-variate deltas, MimeLite's
  full-batch grads) stay f32 under top-k: sparsifying a *state* stream
  without its own residual would bias the stored state itself — the
  registry refuses specs that declare it (see
  ``repro.core.registry._validate``).

Seeding: the stochastic-rounding draw is keyed
``fold_in(PRNGKey(comp.seed), absolute round t)`` then ``fold_in`` by a
static per-plane index — reproducible and kill/resume-stable.
Compression runs OUTSIDE ``shard_map`` on the full cohort plane (per-row
scales involve no cross-row reduction), so sharded and unsharded runs of
the same cohort draw identically whenever the cohort shape matches —
i.e. when ``cohort_size`` divides the mesh; a padded cohort changes the
draw SHAPE and therefore the realized rounding noise (still unbiased,
just a different sample).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# plane-name → static fold_in index for the per-plane rounding streams
PLANE_STREAMS = {"delta": 0, "state_delta": 1, "extra": 2}

INT8_LEVELS = 127.0
KINDS = ("int8", "bf16", "topk")


class QPlane(NamedTuple):
    """Stochastic-rounded int8 representation of an f32 ``(C, P)`` plane.

    Also the normalized carrier for bf16 compression inside the fused
    dequant fold: ``q`` may be a bf16 plane with ``scale`` all-ones (an
    f32 multiply by 1.0 is exact, so the bf16 path shares the kernel).
    """

    q: jax.Array  # int8 (or bf16) (C, P)
    scale: jax.Array  # f32 (C, 1) per-row dequant scale


class TopKPlane(NamedTuple):
    """Top-k sparsified representation of an f32 ``(C, P)`` plane."""

    values: jax.Array  # f32 (C, k)
    idx: jax.Array  # int32 (C, k) element indices into the plane


def validate_compression(comp) -> None:
    """Raise ValueError on a malformed CompressionConfig."""
    if comp.kind not in KINDS:
        raise ValueError(
            f"unknown compression kind {comp.kind!r} — expected one of {KINDS}"
        )
    if comp.kind == "topk" and not (0.0 < comp.topk_frac <= 1.0):
        raise ValueError(
            f"topk_frac must be in (0, 1], got {comp.topk_frac}"
        )


def topk_k(comp, n: int) -> int:
    """Static kept-elements-per-row under ``kind='topk'``."""
    return max(1, min(n, int(round(comp.topk_frac * n))))


def round_key(comp, t):
    """Per-round stochastic-rounding key: (seed, absolute round t)."""
    return jax.random.fold_in(jax.random.PRNGKey(comp.seed), t)


def plane_key(key, name: str):
    """Per-plane sub-stream of a round key (static plane index)."""
    return jax.random.fold_in(key, PLANE_STREAMS[name])


# ---------------------------------------------------------------- int8


def quantize_int8(plane, key, size: Optional[int] = None) -> QPlane:
    """Per-row absmax-scaled stochastic rounding to int8 (unbiased).

    Only the first ``size`` elements of each row (default: all) draw a
    rounding offset, so a plane's zero tail (``FlatSpec.plane_size``)
    neither shifts the draws of the parameters nor rounds away from 0."""
    amax = jnp.max(jnp.abs(plane), axis=-1, keepdims=True)
    # zero rows (dropped/quarantined clients) get scale 1 → q stays 0
    scale = jnp.where(amax > 0, amax / INT8_LEVELS, 1.0).astype(jnp.float32)
    n = plane.shape[-1]
    size = n if size is None else size
    u = jax.random.uniform(key, (*plane.shape[:-1], size), jnp.float32)
    if size < n:
        u = jnp.pad(u, [(0, 0)] * (plane.ndim - 1) + [(0, n - size)])
    q = jnp.clip(jnp.floor(plane / scale + u), -INT8_LEVELS, INT8_LEVELS)
    return QPlane(q=q.astype(jnp.int8), scale=scale)


def dequantize(rep: QPlane):
    """QPlane → dense f32 (the jnp oracle of the fused dequant kernel)."""
    return rep.q.astype(jnp.float32) * rep.scale


def quantize_bf16(plane):
    """Round-to-nearest-even bfloat16 (2 bytes/element on the wire)."""
    return plane.astype(jnp.bfloat16)


def as_qplane(rep) -> QPlane:
    """Normalize a compressed dense-layout rep to a QPlane for the fused
    dequant kernel: bf16 planes get a unit scale (exact under f32)."""
    if isinstance(rep, QPlane):
        return rep
    return QPlane(q=rep, scale=jnp.ones((rep.shape[0], 1), jnp.float32))


# ---------------------------------------------------------------- topk


def sparsify_topk(plane, k: int) -> TopKPlane:
    """Per-row magnitude top-k (k static)."""
    _, idx = jax.lax.top_k(jnp.abs(plane), k)
    values = jnp.take_along_axis(plane, idx, axis=-1)
    return TopKPlane(values=values, idx=idx.astype(jnp.int32))


def densify_topk(rep: TopKPlane, n: int):
    """TopKPlane → dense f32 ``(C, n)`` (top_k indices never collide)."""
    C = rep.values.shape[0]
    out = jnp.zeros((C, n), jnp.float32)
    return out.at[jnp.arange(C)[:, None], rep.idx].set(rep.values)


def error_feedback_topk(comp, plane, residual_rows, active, size: int):
    """One error-feedback round for the cohort's delta plane.

    ``plane`` (C, n) is the raw uplink, ``residual_rows`` (C, n) the
    cohort's gathered residuals, ``active`` (C,) the post-fault weight
    row (a client that did not transmit keeps its residual untouched).
    ``size`` is the parameter count k is a fraction of; a plane may be
    longer (``FlatSpec.plane_size``), and its zero tail is only picked
    where a row holds fewer than k nonzeros, which keeps it zero.
    Returns ``(rep, recon, new_residual_rows)`` where ``recon`` is the
    dense plane the server folds (exactly what arrived on the wire) and
    ``new_residual_rows = accumulated − sent`` for active rows.
    """
    acc = plane + residual_rows
    rep = sparsify_topk(acc, topk_k(comp, size))
    recon = densify_topk(rep, plane.shape[-1])
    keep = (active > 0)[:, None]
    new_rows = jnp.where(keep, acc - recon, residual_rows)
    # inactive rows must fold as zeros, not as their stale accumulator
    recon = jnp.where(keep, recon, 0.0)
    return rep, recon, new_rows


# ------------------------------------------------------------ dispatch


def compress_plane(comp, plane, key, size: Optional[int] = None):
    """Dense f32 plane → wire representation (int8/bf16 kinds); ``size``
    as in ``quantize_int8``."""
    if comp.kind == "int8":
        return quantize_int8(plane, key, size)
    if comp.kind == "bf16":
        return quantize_bf16(plane)
    raise ValueError(f"compress_plane does not handle kind {comp.kind!r}")


def decompress_plane(rep, n: Optional[int] = None):
    """Wire representation → dense f32 plane (any kind)."""
    if isinstance(rep, QPlane):
        return dequantize(rep)
    if isinstance(rep, TopKPlane):
        assert n is not None, "densifying a TopKPlane needs the plane length"
        return densify_topk(rep, n)
    return rep.astype(jnp.float32)


def is_compressed(rep) -> bool:
    """True when ``rep`` is a wire representation rather than dense f32."""
    return (isinstance(rep, (QPlane, TopKPlane))
            or getattr(rep, "dtype", None) == jnp.bfloat16)


# ---------------------------------------------------------- accounting


def wire_plane_bytes(comp, size: int, nbytes: int) -> int:
    """Bytes one compressed ``(P,)`` uplink plane costs on the wire.

    ``size`` is the element count, ``nbytes`` the uncompressed byte count
    (which honors sub-f32 leaf dtypes — ``comp=None`` returns it
    verbatim, preserving the pre-compression accounting bitwise).
    """
    if comp is None:
        return nbytes
    if comp.kind == "bf16":
        return 2 * size
    if comp.kind == "int8":
        return size + 4  # 1 byte/elem + one f32 row scale
    if comp.kind == "topk":
        return topk_k(comp, size) * 8  # f32 value + int32 index per kept
    raise ValueError(f"unknown compression kind {comp.kind!r}")


def uplink_bytes_per_client(comp, wire_planes, size: int, nbytes: int) -> int:
    """Total uplink bytes/client/round over a spec's wire planes.

    Under ``topk`` only the ``"delta"`` stream sparsifies (see module
    docstring); other wire planes ride f32.
    """
    total = 0
    for name in wire_planes:
        if comp is not None and comp.kind == "topk" and name != "delta":
            total += nbytes
        else:
            total += wire_plane_bytes(comp, size, nbytes)
    return total
