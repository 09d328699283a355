"""Pallas TPU kernels for the compute hot spots (DESIGN.md §3).

* ``fed_direction``   — generalized fused local step (affine family covers
  fedcm/mimelite blend, scaffold, feddyn, plain SGD; coefficients in SMEM)
* ``server_update``   — fused round-close: masked (C,)·(C,P) cohort mean +
  staleness-discounted momentum EMA + param step in one pass
* ``flash_attention`` — blocked online-softmax attention (GQA, sliding window)
* ``ssd_scan``        — chunked Mamba2 SSD scan with VMEM-carried state
* ``fedcm_update``    — RETIRED to oracle-only: ref.py pins the FedCM blend

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper; ``interpret=interpret_mode()``), ref.py (pure-jnp oracle used by
tests).
"""
from __future__ import annotations

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
