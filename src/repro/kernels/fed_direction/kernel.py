"""Pallas TPU kernel: generalized fused federated local step.

Every algorithm's per-local-step direction is an affine combination of the
same streams — the minibatch gradient g, the current iterate x, and up to
two broadcast/per-client buffers (momentum Δ_t, control variates c_i/c, the
round anchor x_t):

    v = c_g·g + c_x·x + Σ_j c_j·aux_j          x ← x − η_l·v

* fedcm / mimelite : aux = (Δ_t,)      v = α·g + (1−α)·Δ_t
* scaffold         : aux = (c_i, c)    v = g − c_i + c
* feddyn           : aux = (λ_i, x_t)  v = g + a·x − λ_i − a·x_t
* fedavg / fedadam : aux = ()          v = g

One kernel body per aux arity streams each operand through VMEM exactly
once and writes x once — 3 + n_aux HBM transfers/element total, the
roofline floor for the op (AI ≈ 0.5 flop/byte; it is purely memory-bound).

Tiling: the flat plane is padded to a multiple
of ``block_elems`` and viewed as (padded//LANE, LANE) so every BlockSpec
tile is a VMEM-resident (rows, 128) slab.  The engine's plane already is
such a multiple (``FlatSpec.plane_size``), so there the pad has width 0,
the output slice is the whole plane, and neither copies; they stay for
callers with any other length.  The coefficient vector
(η_l, c_g, c_x, c_aux...) rides in SMEM as a (1, 3+n_aux) row — η_l decays
per round and several coefficients are traced, so baking them as python
constants would force a recompile per round.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK = 64 * 1024  # elements per grid step: 64k f32 = 256 KiB/input


def _make_kernel(n_aux: int):
    def kernel(coef_ref, x_ref, g_ref, *refs):
        aux_refs, out_ref = refs[:n_aux], refs[n_aux]
        eta = coef_ref[0, 0]
        x = x_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32)
        v = coef_ref[0, 1] * g + coef_ref[0, 2] * x
        for j in range(n_aux):
            v = v + coef_ref[0, 3 + j] * aux_refs[j][...].astype(jnp.float32)
        out_ref[...] = (x - eta * v).astype(out_ref.dtype)

    return kernel


@partial(jax.jit, static_argnames=("block_elems", "interpret"))
def fed_direction_flat(x, g, auxes, coefs, *, block_elems: int = DEFAULT_BLOCK,
                       interpret: bool = True):
    """x, g, auxes[j]: 1-D arrays of equal length; coefs: (3 + len(auxes),)
    f32 vector (η_l, c_g, c_x, c_aux...).  Returns updated x (x.dtype)."""
    n = x.shape[0]
    rows = block_elems // LANE
    padded = pl.cdiv(n, block_elems) * block_elems
    pad = padded - n

    def prep(a):
        a = jnp.pad(a, (0, pad))
        return a.reshape(padded // LANE, LANE)

    xr, gr = prep(x), prep(g)
    aux_r = [prep(a) for a in auxes]
    nblocks = padded // block_elems

    spec = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
    smem = pl.BlockSpec((1, 3 + len(auxes)), lambda i: (0, 0))
    out = pl.pallas_call(
        _make_kernel(len(auxes)),
        grid=(nblocks,),
        in_specs=[smem, spec, spec] + [spec] * len(auxes),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(xr.shape, x.dtype),
        interpret=interpret,
    )(coefs.astype(jnp.float32).reshape(1, -1), xr, gr, *aux_r)
    return out.reshape(padded)[:n]
