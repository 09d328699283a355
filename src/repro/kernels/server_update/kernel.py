"""Pallas TPU kernel: fused server update over the (C, P) delta plane.

The server's round-close is three chained reductions/maps over
cohort-stacked flat planes:

    mean  = Σ_c wn_c · Δ_c            (masked cohort mean; wn = mask/|S|)
    m'    = c_mm·m + c_md·mean        (momentum EMA / pseudo-grad store)
    x'    = x + c_xd·mean             (server param step)

Unfused that is one pass over the (C, P) plane for the mean plus two more
params-sized read/write pairs with the mean materialized in between; this
kernel streams the plane once per element-column, keeps the mean in VMEM,
and writes (x', m', mean) in the same pass — the whole server phase becomes
one roofline-memory-term trip over C+2 reads and 3 writes per plane column.

A fourth SMEM scalar γ (``staleness discount``, FedACG-style lookahead
weighting) scales the folded mean before the EMA/step consume it:

    m'    = c_mm·m + c_md·(γ·mean)
    x'    = x + c_xd·(γ·mean)

The async pipelined engine (``FederatedEngine.run_rounds_async``) folds
cohorts whose deltas are ``pipeline_depth − 1`` rounds stale and passes
γ = staleness_discount^(depth−1); the sync path passes γ = 1.0 (exact —
a f32 multiply by 1.0 is the identity).  The emitted ``mean`` output stays
UNdiscounted so delta-norm metrics report the cohort's actual update.

Coefficient mapping (see core/engine.py):
* fedavg/fedcm : c_mm=0, c_md=−1/(η_l·K), c_xd=η_g      (m' := Δ_{t+1})
* scaffold     : params pass (1, 0, η_g) over Δ, then the c-EMA pass
  (1, |S|/N, 0) over Δc — the x/m slots carry whichever buffer updates.
* mimelite     : params pass (1, 0, η_g) over Δ, momentum pass
  (1−α, α, 0) over the full-batch-grad plane.

Tiling: planes are padded to a multiple of ``block_elems`` (a width-0 pad
for the engine's aligned plane, ``FlatSpec.plane_size``, whose column
chunks are whole blocks too) and viewed as (padded//LANE, LANE); the delta plane blocks as (C, rows, LANE) — the whole
cohort column is resident per grid step (C is a cohort, 8–64, so a block is
C·256 KiB of VMEM at the default; shrink ``block_elems`` for huge cohorts).
``wn`` is lane-padded to (C, LANE) (column 0 live) instead of an unaligned
(C, 1) operand; coefficients ride in SMEM as a (1, 4) row
(c_mm, c_md, c_xd, γ) since several of them are traced per-round values.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK = 16 * 1024  # per-client elements per grid step


def _make_kernel(write_x: bool, write_m: bool):
    """Kernel body emitting only the adopted outputs (and reading only the
    buffers they need): a pass with a statically-zero param step never
    reads x or writes x' — the skip is a real HBM-bandwidth skip, not a
    discarded output XLA can't DCE out of a pallas_call."""

    def kernel(coef_ref, wn_ref, d_ref, *refs):
        c_mm = coef_ref[0, 0]
        c_md = coef_ref[0, 1]
        c_xd = coef_ref[0, 2]
        gamma = coef_ref[0, 3]  # staleness discount on the folded mean
        wn = wn_ref[...][:, 0].astype(jnp.float32)  # (C,) mask/|S| weights
        d = d_ref[...].astype(jnp.float32)  # (C, rows, LANE)
        mean = jnp.sum(d * wn[:, None, None], axis=0)  # (rows, LANE)
        dmean = gamma * mean
        refs = list(refs)
        x_ref = refs.pop(0) if write_x else None
        m_ref = refs.pop(0) if write_m else None
        if write_x:
            newx_ref = refs.pop(0)
        if write_m:
            newm_ref = refs.pop(0)
        mean_ref = refs.pop(0)
        if write_x:
            x = x_ref[...].astype(jnp.float32)
            newx_ref[...] = (x + c_xd * dmean).astype(newx_ref.dtype)
        if write_m:
            m = m_ref[...].astype(jnp.float32)
            newm_ref[...] = (c_mm * m + c_md * dmean).astype(newm_ref.dtype)
        mean_ref[...] = mean

    return kernel


@partial(jax.jit, static_argnames=("m_dtype", "block_elems", "interpret",
                                   "write_x", "write_m"))
def server_update_flat(deltas, wn, x, m, coefs, *, m_dtype=None,
                       block_elems: int = DEFAULT_BLOCK, interpret: bool = True,
                       write_x: bool = True, write_m: bool = True):
    """deltas: (C, P); wn: (C,) premultiplied mask/|S| weights; x, m: (P,);
    coefs: (4,) f32 (c_mm, c_md, c_xd, γ) where γ is the staleness
    discount applied to the mean before the EMA/step (1.0 = sync exact).
    Returns (new_x, new_m, mean) with new_m in ``m_dtype`` (default
    m.dtype) and mean in f32 (UNdiscounted).

    ``write_x``/``write_m`` (static) drop the param-step / momentum-EMA
    outputs — AND their input reads — from the launch entirely; the
    corresponding return slot is ``None``.  Multi-pass folds (scaffold's
    c-EMA pass, the post-step algorithms' c_xd=0 passes) use this so a
    structurally-skipped update costs zero plane traffic."""
    C, n = deltas.shape
    m_dt = jnp.dtype(m_dtype) if m_dtype is not None else m.dtype
    rows = block_elems // LANE
    # grid floor of 2: a single-step grid gets its loop collapsed and
    # re-fused into the surrounding program, where XLA:CPU may contract
    # the EMA's mul+add chains into FMAs differently per calling program —
    # a 1-ulp divergence between e.g. the sharded (plane-column chunk) and
    # unsharded launches of the SAME fold (measured; the cohort-parallel
    # bitwise tests pin it).  A ≥2-step grid keeps the body an isolated,
    # shape-stable loop computation; the extra block is pure padding.
    nblocks = max(2, pl.cdiv(n, block_elems))
    padded = nblocks * block_elems
    pad = padded - n

    def prep(a):
        a = jnp.pad(a, (0, pad))
        return a.reshape(padded // LANE, LANE)

    dr = jnp.pad(deltas, ((0, 0), (0, pad))).reshape(C, padded // LANE, LANE)
    wn_l = jnp.zeros((C, LANE), jnp.float32).at[:, 0].set(wn.astype(jnp.float32))

    vec = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
    plane = pl.BlockSpec((C, rows, LANE), lambda i: (0, i, 0))
    smem = pl.BlockSpec((1, 4), lambda i: (0, 0))
    wspec = pl.BlockSpec((C, LANE), lambda i: (0, 0))
    operands = [coefs.astype(jnp.float32).reshape(1, 4), wn_l, dr]
    in_specs = [smem, wspec, plane]
    out_specs, out_shape = [], []
    if write_x:
        xr = prep(x)
        operands.append(xr)
        in_specs.append(vec)
        out_specs.append(vec)
        out_shape.append(jax.ShapeDtypeStruct(xr.shape, x.dtype))
    if write_m:
        mr = prep(m)
        operands.append(mr)
        in_specs.append(vec)
        out_specs.append(vec)
        out_shape.append(jax.ShapeDtypeStruct(mr.shape, m_dt))
    out_specs.append(vec)
    out_shape.append(jax.ShapeDtypeStruct((padded // LANE, LANE), jnp.float32))
    outs = pl.pallas_call(
        _make_kernel(write_x, write_m),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    outs = [o.reshape(padded)[:n] for o in outs]
    new_x = outs.pop(0) if write_x else None
    new_m = outs.pop(0) if write_m else None
    return new_x, new_m, outs[0]


def _make_dequant_kernel(write_x: bool, write_m: bool):
    """The compressed-uplink fold: dequantize → masked-weighted accumulate
    → EMA/param step, fused in ONE pass over the compressed plane.

        d_c   = scale_c · q_c              (per-row dequant, in VMEM)
        mean  = Σ_c wn_c · d_c
        m'    = c_mm·m + c_md·(γ·mean)
        x'    = x + c_xd·(γ·mean)

    The f32 ``(C, P)`` cohort plane NEVER exists in HBM — the kernel
    streams the int8/bf16 blocks and dequantizes in registers, so the
    fold's plane traffic shrinks 4x (int8) / 2x (bf16) with it.  ``q``
    may be int8 (stochastic-rounded, scale = absmax/127) or bf16
    (scale ≡ 1.0, exact under f32).  Same grid/output structure as
    ``_make_kernel`` — the uncompressed kernel stays byte-identical, and
    the ≥2-step grid floor that makes sharded column launches bitwise
    applies unchanged."""

    def kernel(coef_ref, wn_ref, sc_ref, q_ref, *refs):
        c_mm = coef_ref[0, 0]
        c_md = coef_ref[0, 1]
        c_xd = coef_ref[0, 2]
        gamma = coef_ref[0, 3]  # staleness discount on the folded mean
        wn = wn_ref[...][:, 0].astype(jnp.float32)  # (C,) mask/|S| weights
        sc = sc_ref[...][:, 0].astype(jnp.float32)  # (C,) dequant scales
        # dequantize in-register: (C, rows, LANE) f32 exists only in VMEM
        d = q_ref[...].astype(jnp.float32) * sc[:, None, None]
        mean = jnp.sum(d * wn[:, None, None], axis=0)  # (rows, LANE)
        dmean = gamma * mean
        refs = list(refs)
        x_ref = refs.pop(0) if write_x else None
        m_ref = refs.pop(0) if write_m else None
        if write_x:
            newx_ref = refs.pop(0)
        if write_m:
            newm_ref = refs.pop(0)
        mean_ref = refs.pop(0)
        if write_x:
            x = x_ref[...].astype(jnp.float32)
            newx_ref[...] = (x + c_xd * dmean).astype(newx_ref.dtype)
        if write_m:
            m = m_ref[...].astype(jnp.float32)
            newm_ref[...] = (c_mm * m + c_md * dmean).astype(newm_ref.dtype)
        mean_ref[...] = mean

    return kernel


@partial(jax.jit, static_argnames=("m_dtype", "block_elems", "interpret",
                                   "write_x", "write_m"))
def dequant_update_flat(q, scale, wn, x, m, coefs, *, m_dtype=None,
                        block_elems: int = DEFAULT_BLOCK,
                        interpret: bool = True,
                        write_x: bool = True, write_m: bool = True):
    """Fused dequantize-fold launch: ``q`` (C, P) int8 or bf16, ``scale``
    (C,) or (C, 1) per-row f32 dequant scales, the rest exactly
    ``server_update_flat``'s contract (wn premultiplied mask/|S|, coefs =
    (c_mm, c_md, c_xd, γ)).  Returns (new_x, new_m, mean) with the mean
    of the DEQUANTIZED plane, f32, undiscounted.

    Layout matches the uncompressed launch: the compressed plane blocks
    as (C, rows, LANE) with the whole cohort column resident per grid
    step, and ``scale`` rides lane-padded (C, LANE) next to ``wn``
    instead of an unaligned (C, 1) operand.  (On real TPUs int8 tiles
    want (32, 128) minimum — the ``rows``-sized second axis satisfies it
    for every block_elems ≥ 32·LANE; interpret mode is layout-agnostic.)
    """
    C, n = q.shape
    m_dt = jnp.dtype(m_dtype) if m_dtype is not None else m.dtype
    rows = block_elems // LANE
    # same ≥2-step grid floor as server_update_flat (bitwise rationale
    # in that docstring: a collapsed 1-step grid re-fuses per-program)
    nblocks = max(2, pl.cdiv(n, block_elems))
    padded = nblocks * block_elems
    pad = padded - n

    def prep(a):
        a = jnp.pad(a, (0, pad))
        return a.reshape(padded // LANE, LANE)

    qr = jnp.pad(q, ((0, 0), (0, pad))).reshape(C, padded // LANE, LANE)
    wn_l = jnp.zeros((C, LANE), jnp.float32).at[:, 0].set(wn.astype(jnp.float32))
    sc_l = jnp.zeros((C, LANE), jnp.float32).at[:, 0].set(
        scale.astype(jnp.float32).reshape(C)
    )

    vec = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
    plane = pl.BlockSpec((C, rows, LANE), lambda i: (0, i, 0))
    smem = pl.BlockSpec((1, 4), lambda i: (0, 0))
    wspec = pl.BlockSpec((C, LANE), lambda i: (0, 0))
    operands = [coefs.astype(jnp.float32).reshape(1, 4), wn_l, sc_l, qr]
    in_specs = [smem, wspec, wspec, plane]
    out_specs, out_shape = [], []
    if write_x:
        xr = prep(x)
        operands.append(xr)
        in_specs.append(vec)
        out_specs.append(vec)
        out_shape.append(jax.ShapeDtypeStruct(xr.shape, x.dtype))
    if write_m:
        mr = prep(m)
        operands.append(mr)
        in_specs.append(vec)
        out_specs.append(vec)
        out_shape.append(jax.ShapeDtypeStruct(mr.shape, m_dt))
    out_specs.append(vec)
    out_shape.append(jax.ShapeDtypeStruct((padded // LANE, LANE), jnp.float32))
    outs = pl.pallas_call(
        _make_dequant_kernel(write_x, write_m),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    outs = [o.reshape(padded)[:n] for o in outs]
    new_x = outs.pop(0) if write_x else None
    new_m = outs.pop(0) if write_m else None
    return new_x, new_m, outs[0]
