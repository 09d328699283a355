"""Public flash attention wrapper: (B, S, H, hd) layout, GQA, causal/window."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro import kernels
from repro.kernels.flash_attention.kernel import flash_attention_bhsd


def flash_attention(
    q,  # (B, Sq, H, hd)
    k,  # (B, Skv, Hkv, hd)
    v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    bq: int = 128,
    bkv: int = 128,
):
    qt = jnp.swapaxes(q, 1, 2)  # (B, H, Sq, hd)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, scale=scale,
        q_offset=q_offset, bq=bq, bkv=bkv, interpret=kernels.interpret_mode(),
    )
    return jnp.swapaxes(out, 1, 2)
