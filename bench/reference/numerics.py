"""The precisions the plain references compute in.

``f32`` is the reference itself: float32 everywhere and every matmul at
``Precision.HIGHEST``.  ``fp8`` is the control of a bfloat16
configuration, the reference computed one precision below it: the
activations that the program keeps in bfloat16, and their cotangents,
rounded to float8 e4m3 with a scale per tensor instead; parameters and
updates float32.  The rounding is emulated in float32 arithmetic, so it
compiles wherever float32 does.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _identity(x):
    return x


def _fp8_e4m3(x):
    """Round to float8 e4m3 with one scale per tensor (its largest
    magnitude maps to 448, e4m3's largest value), as fp8 training scales
    each tensor; 3 mantissa bits, smallest normal 2^-6 of the scale."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    a = jnp.abs(x) / scale
    quantum = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -6))) - 3.0)
    return jnp.sign(x) * jnp.round(a / quantum) * quantum * scale


@jax.custom_vjp
def _round_fp8_e4m3(x):
    """``_fp8_e4m3`` forward; the cotangent is rounded the same way, as a
    backward pass in that precision would."""
    return _fp8_e4m3(x)


_round_fp8_e4m3.defvjp(lambda x: (_fp8_e4m3(x), None),
                       lambda _, ct: (_fp8_e4m3(ct),))


class Numerics(NamedTuple):
    act: Callable  # rounding applied where the program's activations are rounded


MODES = {
    "f32": Numerics(_identity),
    "fp8": Numerics(_round_fp8_e4m3),
}


def numerics(mode: str) -> Numerics:
    if mode not in MODES:
        raise ValueError(f"unknown reference precision {mode!r}; known: {sorted(MODES)}")
    return MODES[mode]
