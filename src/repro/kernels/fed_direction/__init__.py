"""Generalized fused local-step kernel: x ← x − η_l·v(algo) on the flat plane."""
from repro.kernels.fed_direction.ops import flat_direction_step
from repro.kernels.fed_direction.ref import fed_direction_ref

__all__ = ["flat_direction_step", "fed_direction_ref"]
