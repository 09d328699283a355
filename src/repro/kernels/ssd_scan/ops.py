"""Public SSD wrapper (matches repro.models.mamba2.ssd_chunked's contract)."""
from __future__ import annotations

from repro import kernels
from repro.kernels.ssd_scan.kernel import ssd_chunked_pallas


def ssd(x, dt, A, Bm, Cm, chunk: int = 128):
    """x (B,S,H,P); dt (B,S,H) post-softplus; A (H,); Bm/Cm (B,S,N).

    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    return ssd_chunked_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=kernels.interpret_mode())
