"""Pallas TPU kernels for the compute hot spots (DESIGN.md §3).

* ``fed_direction``   — generalized fused local step (affine family covers
  fedcm/mimelite blend, scaffold, feddyn, plain SGD; coefficients in SMEM)
* ``server_update``   — fused round-close: masked (C,)·(C,P) cohort mean +
  staleness-discounted momentum EMA + param step in one pass
* ``flash_attention`` — blocked online-softmax attention (GQA, sliding window)
* ``ssd_scan``        — chunked Mamba2 SSD scan with VMEM-carried state

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper; ``interpret=interpret_mode()``), ref.py (pure-jnp oracle used by
tests).
"""
from __future__ import annotations

import math

import jax


def interpret_mode() -> bool:
    """The ``interpret`` flag of every kernel launch, decided when the launch
    is traced (never at import, so importing the kernels starts no backend).

    TPU: False — Mosaic compiles the kernel.  CPU: True — the Pallas
    interpreter runs the body (the test suite's backend).  Any other
    platform raises rather than interpreting in silence: a run that lands
    there would otherwise report kernel results it never compiled.
    A compile rehearsal for a described TPU (whose tracing backend is the
    CPU) monkeypatches this function to return False.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU (Mosaic) or interpret on CPU; "
        f"the {platform!r} backend has neither"
    )


def plane_alignment(size: int, n_shards: int) -> int:
    """The length the engine's flat plane is a multiple of
    (``FlatSpec.aligned``), so that no launch pads or slices it.

    A plane of ``size`` elements or more than one ``fed_direction`` block
    aligns to that block and to ``server_update``'s block on each of the
    ``n_shards`` plane-column chunks of the scattered fold: 65,536 for 1,
    2 or 4 shards.  A shorter plane aligns to the fold's tile on each
    chunk instead, and ``fed_direction`` launches it as one block.
    """
    from repro.kernels.fed_direction.kernel import DEFAULT_BLOCK as DIRECTION_BLOCK
    from repro.kernels.server_update.kernel import DEFAULT_BLOCK as FOLD_BLOCK
    from repro.kernels.server_update.ops import TILE

    if size < DIRECTION_BLOCK:
        return TILE * n_shards
    return math.lcm(DIRECTION_BLOCK, FOLD_BLOCK * n_shards)
