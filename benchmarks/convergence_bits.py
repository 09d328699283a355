"""Convergence-vs-bits curves: accuracy as the uplink wire narrows.

Sweeps the uplink compression kind {f32, bf16, int8, topk} over fedcm and
scaffold — the paper's momentum method and the stateful baseline whose
``state_delta`` plane stresses the multi-plane wire — on the heterogeneous
toy split (dirichlet α=0.6) and records final test accuracy plus the
TOTAL uplink bytes the run actually billed (summed from the engine's
per-round ``bytes_up`` accounting, which the wire encoders reprice).  The
question the curve answers: how many bits does client-level momentum need
on the wire — int8 (≈4×) should sit within 1% of f32, and top-k with
error feedback documents how far a 10× squeeze drifts.

Compression rides the engine as pure ``CompressionConfig`` data (seeded
stochastic rounding keyed by absolute round × plane, so every cell is
reproducible); the f32 cell runs with ``compression=None`` — the
bitwise-preserved baseline engine.

The artifact is rev-stamped; ``benchmarks/fused_rounds.py`` folds the
rows into the top-level ``BENCH_fused_rounds.json`` trajectory summary
when the revs match.

    PYTHONPATH=src python -m benchmarks.convergence_bits [--rounds 40]
"""
from __future__ import annotations

import argparse

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import git_rev, print_table, save_artifact
from repro.configs.base import CompressionConfig, FedConfig
from repro.core import FederatedEngine, make_eval_fn
from repro.data import FederatedData, make_synthetic_classification
from repro.models.small import classification_loss, mlp_classifier

ALGOS = ["fedcm", "scaffold"]
KINDS = [None, "bf16", "int8", "topk"]
TOPK_FRAC = 0.05

DIM, N_CLASSES, HIDDEN = 32, 10, 64
N_CLIENTS, COHORT, LOCAL_STEPS, BATCH = 100, 10, 5, 20


def run_cell(algo: str, kind, rounds: int, seed: int = 0) -> dict:
    comp = None
    if kind is not None:
        comp = CompressionConfig(kind=kind, topk_frac=TOPK_FRAC, seed=seed)
    cfg = FedConfig(
        algo=algo, num_clients=N_CLIENTS, cohort_size=COHORT,
        local_steps=LOCAL_STEPS, alpha=0.1, eta_l=0.05, eta_g=1.0,
        participation="bernoulli", rounds=rounds, seed=seed,
        compression=comp,
    )
    x_tr, y_tr, x_te, y_te = make_synthetic_classification(
        n_classes=N_CLASSES, dim=DIM, n_train=20_000, n_test=2_000, seed=seed)
    data = FederatedData(x_tr, y_tr, N_CLIENTS, dirichlet_alpha=0.6, seed=seed)
    model = mlp_classifier((DIM, HIDDEN, HIDDEN, N_CLASSES))
    eng = FederatedEngine(cfg, classification_loss(model.apply),
                          batch_size=BATCH)
    state = eng.init(model.init(jax.random.PRNGKey(seed)),
                     jax.random.PRNGKey(seed + 1))
    state, ms = eng.run_rounds(state, data, rounds)
    evaluate = make_eval_fn(model.apply)
    acc = evaluate(state.params, jnp.asarray(x_te), jnp.asarray(y_te))
    # RoundMetrics.bytes_up = n_active × per-client wire bytes (the round's
    # cohort-total uplink); recover the per-client price from the last round
    bytes_up = np.asarray(ms.bytes_up, dtype=np.float64)
    n_active = np.asarray(ms.n_active, dtype=np.float64)
    per_client = bytes_up[-1] / max(n_active[-1], 1.0)
    return {
        "algo": algo,
        "kind": kind or "f32",
        "topk_frac": TOPK_FRAC if kind == "topk" else None,
        "acc_final": round(float(acc), 4),
        "uplink_bytes_per_client": int(per_client),
        "total_uplink_mb": round(float(bytes_up.sum()) / 2**20, 3),
        "params_finite": all(bool(jnp.all(jnp.isfinite(l)))
                             for l in jax.tree_util.tree_leaves(state.params)),
    }


def main(rounds: int = 40, seed: int = 0) -> list:
    rows = []
    for algo in ALGOS:
        base = None
        for kind in KINDS:
            row = run_cell(algo, kind, rounds, seed=seed)
            if kind is None:
                base = row
            row["reduction_x"] = round(
                base["uplink_bytes_per_client"]
                / max(row["uplink_bytes_per_client"], 1), 2)
            row["acc_vs_f32"] = round(row["acc_final"] - base["acc_final"], 4)
            rows.append(row)
            print(f"  {algo:9s} {row['kind']:<5} acc={row['acc_final']:.4f} "
                  f"(Δf32={row['acc_vs_f32']:+.4f}) "
                  f"{row['uplink_bytes_per_client']} B/client "
                  f"({row['reduction_x']}x) "
                  f"total={row['total_uplink_mb']} MiB")
    save_artifact("convergence_bits", {"rev": git_rev(), "rows": rows})
    print_table("Convergence vs uplink bits (dirichlet α=0.6 toy)",
                rows, ["algo", "kind", "acc_final", "acc_vs_f32",
                       "uplink_bytes_per_client", "reduction_x",
                       "total_uplink_mb", "params_finite"])
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    main(a.rounds, a.seed)
