"""jit'd public wrappers for the fused server round-close kernel.

``fused_server_step`` launches one coefficient-row pass; ``fused_fold``
executes ALL of an ``AlgorithmSpec``'s declarative fold rows
(``repro.core.registry.FoldPass``) against the cohort's uplink planes —
the registry-driven replacement for the old per-algorithm dispatch.

``scatter_fold`` is the shard_map form of ``fused_fold`` for the
cohort-parallel engine: called INSIDE a ``shard_map`` over the
``"clients"`` mesh axis, it lowers the masked cohort mean to an explicit
reduce-scatter (``all_to_all`` to plane-column shards + device-local
full-cohort reduce — NOT ``psum_scatter``, whose per-device partial sums
would re-associate the f32 reduction and break bitwise equality with the
unsharded fold), runs the fold rows as kernel launches over each device's
``(C, P/num_shards)`` column block, and ``all_gather``s the updated
planes back to replicated form.

Launches are shard_map-compatible by construction — each device launches
on its LOCAL shapes — but interpret-mode bitwise stability across shard
counts needs one extra care: ``_auto_block`` floors the block size so the
grid loop keeps ≥ 2 steps whenever the plane allows it.  A single-step
grid gets its loop collapsed and re-fused into the surrounding program,
where XLA:CPU is free to contract the EMA's mul+add chains into FMAs
differently per program — a 1-ulp divergence between the sharded and
unsharded launches of the SAME math (measured); a real multi-step loop
body compiles shape-identically on both."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.server_update.kernel import (
    DEFAULT_BLOCK, LANE, dequant_update_flat, server_update_flat,
)


# Mosaic tiles a block's last two dims in (sublanes, LANE) units: 8
# sublanes for f32, 16 for bf16, 32 for int8.  A block of whole int8 tiles
# is whole tiles of the wider dtypes too.
TILE = 32 * LANE


def _auto_block(n: int, default: int = DEFAULT_BLOCK) -> int:
    """Largest TILE-multiple block ≤ ``default`` giving a ≥ 2-step grid.

    Keeps the interpret-mode grid loop a REAL loop for every plane length
    that allows it (n ≥ 2·TILE): the loop body then compiles as its own
    shape-stable computation, and sharded / unsharded launches of the same
    fold stay bitwise (see module docstring).  Shorter planes take one
    TILE (the launch's grid floor of 2 pads them).  A block that is not
    whole tiles compiles in interpret mode but Mosaic refuses it."""
    half = (n // (2 * TILE)) * TILE
    return max(TILE, min(default, half))


def fused_server_step(deltas, wn, x, m, c_mm, c_md, c_xd, m_dtype=None,
                      discount=1.0, write_x=True, write_m=True):
    """Masked cohort mean + momentum EMA + param step, one pass over (C, P).

    deltas (C, P), wn (C,) = mask/|S|, x (P,), m (P,).  Coefficients may be
    traced per-round scalars.  ``discount`` is the staleness weight γ the
    async engine applies to folded in-flight cohorts (rides SMEM with the
    other coefficients; 1.0 = sync, exact).  Returns
    (new_x, new_m, mean_delta) with mean_delta UNdiscounted; a statically
    dropped output (``write_x``/``write_m`` False) comes back ``None`` and
    costs no plane traffic.

    Block size is ``_auto_block`` of the plane length, so the launch's
    grid loop keeps ≥ 2 steps — the same fold launched on a plane-column
    SHARD (cohort-parallel engine) then compiles bitwise-identically to
    the full-plane launch.
    """
    coefs = jnp.stack([
        jnp.asarray(c_mm, jnp.float32),
        jnp.asarray(c_md, jnp.float32),
        jnp.asarray(c_xd, jnp.float32),
        jnp.asarray(discount, jnp.float32),
    ])
    return server_update_flat(
        deltas, wn, x, m, coefs, m_dtype=m_dtype, interpret=kernels.interpret_mode(),
        block_elems=_auto_block(deltas.shape[-1]),
        write_x=write_x, write_m=write_m,
    )


def dequant_server_step(q, scale, wn, x, m, c_mm, c_md, c_xd, m_dtype=None,
                        discount=1.0, write_x=True, write_m=True):
    """``fused_server_step`` over a COMPRESSED plane: dequantize (int8/bf16
    ``q`` × per-row ``scale``) → masked mean → EMA/step, one fused pass —
    the f32 ``(C, P)`` plane never materializes outside VMEM.  Contract
    otherwise identical to ``fused_server_step`` (same ``_auto_block``
    ≥2-step grid, so sharded column launches stay bitwise vs unsharded)."""
    coefs = jnp.stack([
        jnp.asarray(c_mm, jnp.float32),
        jnp.asarray(c_md, jnp.float32),
        jnp.asarray(c_xd, jnp.float32),
        jnp.asarray(discount, jnp.float32),
    ])
    return dequant_update_flat(
        q, scale, wn, x, m, coefs, m_dtype=m_dtype, interpret=kernels.interpret_mode(),
        block_elems=_auto_block(q.shape[-1]),
        write_x=write_x, write_m=write_m,
    )


def fused_fold(spec, cfg, planes, wn, n_active, x, m, eta_l, discount=1.0):
    """Execute an ``AlgorithmSpec``'s fold rows as fused kernel passes.

    ``planes`` maps plane names ("delta"/"state_delta"/"extra") to the
    cohort's raw ``(C, P)`` uplink planes; ``wn`` = mask/|S|.  Each
    ``FoldPass`` becomes one ``fused_server_step`` launch; statically-zero
    coefficients skip the corresponding state adoption (a pass with
    ``c_xd == 0.0`` never rewrites params, a pass with ``c_md == 0.0,
    c_mm == 1.0`` never re-rounds the momentum buffer) — the same
    structural skips the jnp interpreter (``AlgorithmSpec.server_update``)
    applies, so the two routes stay step-for-step comparable.

    Honors ``cfg.aggregate_dtype`` exactly like the jnp reductions: uplink
    planes are quantized BEFORE the reduction (the kernel body then
    accumulates in f32); only the reduction inputs are cast — the
    client-state scatter keeps the unquantized plane, as the tree oracle
    does.  Returns ``(new_x, new_m, mean_delta)`` with ``mean_delta`` the
    UNdiscounted mean of the "delta" pass (metrics + post-steps consume
    it).
    """
    # deferred import: repro.core.engine imports this module at package
    # init, so a module-level registry import would be circular
    from repro.core.compress import QPlane
    from repro.core.registry import _fold_coef, _is_static_one, _is_static_zero

    agg_dt = jnp.dtype(getattr(cfg, "aggregate_dtype", "float32"))

    def q(plane):
        return plane if agg_dt == jnp.float32 else plane.astype(agg_dt)

    m_dt = (jnp.dtype(getattr(cfg, "momentum_dtype", "float32"))
            if spec.momentum_store == "momentum_dtype" else jnp.float32)
    mean_delta = None
    for p in spec.fold:
        c_mm = _fold_coef(p.c_mm, cfg, eta_l, n_active)
        c_md = _fold_coef(p.c_md, cfg, eta_l, n_active)
        c_xd = _fold_coef(p.c_xd, cfg, eta_l, n_active)
        adopt_x = not _is_static_zero(p.c_xd)
        adopt_m = not (_is_static_zero(p.c_md) and _is_static_one(p.c_mm))
        pv = planes[p.plane]
        if isinstance(pv, QPlane):
            # compressed uplink (repro.core.compress): the fused dequant
            # fold consumes the int8/bf16 representation directly — the
            # f32 (C, P) plane never materializes (aggregate_dtype
            # quantization does not compose; the rep IS the quantization)
            new_x, new_m, mean = dequant_server_step(
                pv.q, pv.scale, wn, x, m, c_mm, c_md, c_xd,
                m_dtype=m_dt, discount=discount,
                write_x=adopt_x, write_m=adopt_m,
            )
        else:
            new_x, new_m, mean = fused_server_step(
                q(pv), wn, x, m, c_mm, c_md, c_xd,
                m_dtype=m_dt, discount=discount,
                write_x=adopt_x, write_m=adopt_m,
            )
        if p.plane == "delta":
            mean_delta = mean
        if adopt_x:
            x = new_x
        if adopt_m:
            m = new_m
    return x, m, mean_delta


def scatter_fold(spec, cfg, planes, wn, n_active, x, m, eta_l, discount=1.0,
                 *, axis_name: str, n_shards: int):
    """``fused_fold`` under cohort sharding — call INSIDE ``shard_map``.

    ``planes`` maps plane names to the device-LOCAL ``(C/n_shards, P)``
    shards of the cohort uplink (each device computed its own clients
    end-to-end); ``wn`` is the full replicated ``(C,)`` mask/|S| row; ``x``
    and ``m`` are the replicated ``(P,)`` server planes.  Three steps:

    1. reduce-scatter, decomposed bitwise-safely: ``all_to_all`` turns
       client-sharding into plane-column sharding — each device now holds
       ``(C, P/n_shards)``, the COMPLETE cohort for its columns — so the
       fold's masked reduce runs device-locally in exactly the unsharded
       reduction order.  The D−1 rounds of latency the async ring gives
       this collective are what hide it behind the next cohort's compute.
    2. the spec's fold rows execute as ``fused_fold`` kernel launches over
       the column block, updating each device's ``x``/``m`` chunk.
    3. ``all_gather`` rebuilds the replicated ``(P,)`` planes (the next
       round broadcasts them to every client anyway).

    Returns ``(new_x, new_m, mean_delta)`` — replicated, ``mean_delta``
    UNdiscounted, exactly ``fused_fold``'s contract.  The collective
    decomposition lives in ``repro.core.flat`` (``cohort_to_columns`` /
    ``plane_chunk`` / ``gather_plane``) — shared with the scattered-mean
    path so the bitwise-load-bearing layout has one definition.
    """
    from repro.core.compress import QPlane
    from repro.core.flat import cohort_to_columns, gather_plane, plane_chunk

    def to_cols(v):
        if isinstance(v, QPlane):
            # the all_to_all moves the COMPRESSED payload (int8/bf16) —
            # the cross-device wire win of this whole PR; the per-row f32
            # scales (C/n_shards, 1) all_gather to the full (C, 1) row
            # every column shard's dequant needs (C·4 bytes, negligible)
            return QPlane(
                q=cohort_to_columns(v.q, axis_name, n_shards),
                scale=jax.lax.all_gather(v.scale, axis_name, tiled=True),
            )
        return cohort_to_columns(v, axis_name, n_shards)

    Pn = x.shape[-1]
    cols = {k: to_cols(v)
            for k, v in planes.items() if k in spec.fold_planes}
    new_x, new_m, mean = fused_fold(
        spec, cfg, cols, wn, n_active,
        plane_chunk(x, axis_name, n_shards),
        plane_chunk(m, axis_name, n_shards),
        eta_l, discount=discount,
    )
    return (gather_plane(new_x, axis_name, Pn),
            gather_plane(new_m, axis_name, Pn),
            gather_plane(mean, axis_name, Pn))
