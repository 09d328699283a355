"""Kernel micro-benchmarks: bytes/FLOPs accounting + CPU sanity timings.

On this container the Pallas kernels execute in interpret mode, so
wall-clock numbers are NOT TPU performance — the value here is (a) the
analytic bytes/FLOPs table (what the fusion saves on the roofline's memory
term) and (b) a correctness-at-size smoke.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.fed_direction.kernel import fed_direction_flat
from repro.kernels.fed_direction.ref import fedcm_step_ref


def fedcm_update_accounting(n_params: int) -> dict:
    """HBM traffic for one local step over n_params (f32)."""
    b = 4 * n_params
    unfused = {  # v = αg + (1−α)Δ ; x = x − ηv  as two ops
        "reads": 2 * b + 2 * b,  # (g, Δ) then (x, v)
        "writes": b + b,  # v then x
    }
    fused = {"reads": 3 * b, "writes": b}
    return {
        "n_params": n_params,
        "unfused_bytes": unfused["reads"] + unfused["writes"],
        "fused_bytes": fused["reads"] + fused["writes"],
        "saving": 1 - (fused["reads"] + fused["writes"]) / (unfused["reads"] + unfused["writes"]),
    }


def main() -> int:
    print("### fused local-step accounting (fed_direction blend, per local step)")
    for n in [1_000_000, 11_000_000, 390_000_000]:  # ~ResNet18 / ~llama3.2 emb / llama4
        acc = fedcm_update_accounting(n)
        print(f"  n={n:>11,d}  unfused={acc['unfused_bytes']/1e9:7.2f} GB  "
              f"fused={acc['fused_bytes']/1e9:7.2f} GB  saving={acc['saving']:.0%}")

    print("\n### correctness at size (interpret mode)")
    # the FedCM blend launches through the generalized fed_direction
    # kernel; coefficients (η, α, 0, 1−α) select the blend form
    rng = np.random.default_rng(0)
    n = 4_000_000
    x = jnp.asarray(rng.normal(size=n), jnp.float32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    d = jnp.asarray(rng.normal(size=n), jnp.float32)
    coefs = jnp.asarray([0.05, 0.1, 0.0, 0.9], jnp.float32)
    t0 = time.time()
    out = jax.block_until_ready(fed_direction_flat(x, g, (d,), coefs))
    t_k = time.time() - t0
    ref = fedcm_step_ref(x, g, d, 0.1, 0.05)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"  n={n:,}: max|err|={err:.2e}  (interpret-mode wall {t_k*1e3:.0f} ms — not TPU perf)")
    assert err < 1e-6
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
