"""Round-throughput: per-round dispatch vs fused scan vs flat-plane engine.

The paper's experiments are hundreds-to-thousands of *cheap* rounds
(Table 1: 4000 rounds of a small CNN), so per-round overheads — jit
dispatch, host-side cohort sampling, per-leaf tree_map op chains in the
aggregate/server phase — dominate wall clock.  This benchmark measures the
same trajectory three ways:

* sequential: ``engine.run_round`` × N (one jit dispatch per round),
* tree-fused (the PR-1 engine, ``use_flat_plane=False``): ONE lax.scan
  program, but the whole update phase is per-leaf tree_map chains — one
  masked tensordot per leaf per uplink plane (including the zeros
  state/extra planes stateless algorithms still materialize), per-leaf
  server updates, per-leaf metric norms,
* flat-fused (the default engine): the local steps carry ONE ravelled
  (P,) buffer through the ``fed_direction`` kernel and every round-scope
  reduction lands on it — the fused ``server_update`` fold, flat norms,
  and no zeros planes at all.  On a CPU host the kernels run in the
  Pallas interpreter.

Three workloads, all in the artifact:

* ``update_bound`` (headline): deep-narrow MLP — 202 parameter leaves, the
  leaf census of a ResNet/transformer-class model — with K=1 local step.
  The round is
  round-machinery-bound (broadcast → 1 grad → aggregate → server), which
  is the regime the flat plane targets: for production-scale models the
  update phase is HBM-bandwidth-bound at any K, and on CPU this leaf-rich
  shape is its faithful stand-in.  The acceptance bar (flat ≥ 1.3× the
  PR-1 tree path) is measured here.
* ``paper_scaled`` (PR-1's original shape): 3-layer MLP, K=5, B=32 —
  local-grad-bound, where the plane has little to win.
* ``async_pipeline``: the update-bound shape through the overlapping-cohort
  engine (``run_rounds_async``, ``scan_unroll=2`` — the ring boundary
  amortizes across an unrolled pair; the sync scan has no such boundary)
  at pipeline depth D ∈ {1, 2, 4} vs the sync ``run_rounds`` scan.  On
  one device the pipeline cannot overlap anything physically — the number
  documents that carrying the depth-D ring of in-flight cohort uplinks
  costs ~nothing per round (the acceptance bar: D=2 no slower than sync,
  judged on the drift-robust ``*_vs_sync_median`` pairwise ratio — on a
  shared 2-core container single ratios swing ±8%), so the mode is free
  until a multi-host mesh gives the overlap something to hide.
* ``algo_sweep``: rounds/s for EVERY registered algorithm
  (``repro.core.list_algorithms``) on the flat engine — the per-PR
  record that each spec's declarative routing (direction row →
  ``fed_direction``, fold rows → ``server_update``, pure post-steps)
  actually executes, and what each costs relative to fedcm.  A spec that
  silently falls off the kernel route shows up here as an outlier.
* ``uplink_compression``: rounds/s + wire accounting per uplink
  compression kind (none/int8/bf16/topk) on the fused dequant-fold
  route — per-client bytes/round, the f32-relative reduction, and the
  async ring's per-slot in-flight bytes (the ring carries the compressed
  representation, so in-flight memory shrinks with the wire).
* ``store_prefetch``: the host-store loop synchronous vs double-buffered
  (``cfg.store_prefetch``) — what overlapping the next cohort's store
  gather + host batch build with the current round's device step buys.

Timing is interleaved min-of-N (alternating engines) so slow drift on a
shared host cannot bias one path.  Artifact:
benchmarks/artifacts/fused_rounds.json with per-path seconds, rounds/s,
the fused-vs-sequential speedup, and the flat-vs-tree speedup per
workload.  Every run also appends a rounds/s-per-workload row (keyed by
git rev, folding in benchmarks/cohort_sharded.py's artifact when present
— that sweep needs its own multi-device process) to the TOP-LEVEL
``BENCH_fused_rounds.json`` — the per-PR perf trajectory CI uploads.
Run via ``python -m benchmarks.run`` or directly:
``PYTHONPATH=src python -m benchmarks.fused_rounds [--rounds N]``.
"""
from __future__ import annotations

import argparse
import json
import time
from dataclasses import replace
from pathlib import Path

import jax

from repro.configs.base import FedConfig
from repro.core import FederatedEngine, list_algorithms
from repro.data import FederatedData, make_synthetic_classification
from repro.models.small import classification_loss, mlp_classifier

ARTIFACT = Path(__file__).resolve().parent / "artifacts" / "fused_rounds.json"
#: the cohort-parallel sweep writes its own artifact (it needs a multi-
#: device process of its own, started with XLA_FLAGS set on a CPU host);
#: when present it is folded into the trajectory summary below
COHORT_ARTIFACT = Path(__file__).resolve().parent / "artifacts" / "cohort_sharded.json"
#: the participation scenario harness (host-store population engine) also
#: writes a rev-stamped artifact; folded into the trajectory when current
PARTICIPATION_ARTIFACT = (
    Path(__file__).resolve().parent / "artifacts" / "participation_robustness.json"
)
#: convergence-vs-fault-rate curves (fault-injected engine, PR-7); folded
#: into the trajectory when current
FAULT_ARTIFACT = (
    Path(__file__).resolve().parent / "artifacts" / "fault_tolerance.json"
)
#: convergence-vs-uplink-bits curves (compressed wire engine); folded
#: into the trajectory when current
BITS_ARTIFACT = (
    Path(__file__).resolve().parent / "artifacts" / "convergence_bits.json"
)
#: the fleet-smoke job's per-round telemetry JSONL (repro.fleet): when a
#: `fed_train --serve` run at this rev wrote one here, its per-round
#: rounds/s series + hot-swap summary fold into the trajectory
FLEET_ARTIFACT = (
    Path(__file__).resolve().parent / "artifacts" / "fleet_telemetry.jsonl"
)
#: top-level per-PR perf trajectory: rounds/s per workload, one entry per
#: commit — the diffable history CI uploads (and the repo carries)
BENCH_SUMMARY = Path(__file__).resolve().parents[1] / "BENCH_fused_rounds.json"

WORKLOADS = {
    # dims, cohort, local_steps, batch — see module docstring
    "update_bound": dict(dims=(32,) + (16,) * 100 + (10,), cohort=16, K=1, B=8),
    "paper_scaled": dict(dims=(32, 64, 64, 10), cohort=8, K=5, B=32),
}


def _block(state):
    jax.block_until_ready(jax.tree_util.tree_leaves(state.params))


def _measure(name, dims, cohort, K, B, rounds, alts, quiet):
    cfg = FedConfig(algo="fedcm", num_clients=64, cohort_size=cohort,
                    local_steps=K, participation="fixed")
    x, y, *_ = make_synthetic_classification(
        n_classes=10, dim=dims[0], n_train=6400, n_test=10
    )
    data = FederatedData(x, y, cfg.num_clients, seed=0)
    model = mlp_classifier(dims)
    loss_fn = classification_loss(model.apply)
    eng_flat = FederatedEngine(cfg, loss_fn, batch_size=B)
    eng_tree = FederatedEngine(replace(cfg, use_flat_plane=False), loss_fn,
                               batch_size=B)

    def fresh(eng):
        return eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))

    # --- warm every path (compile outside the timed region) ---
    st, _ = eng_flat.run_round(fresh(eng_flat), data)
    _block(st)
    for e in (eng_flat, eng_tree):
        st, _ = e.run_rounds(fresh(e), data, rounds)
        _block(st)

    # --- sequential: one dispatch per round (timed once; its gap is 2×+) ---
    t0 = time.perf_counter()
    st = fresh(eng_flat)
    for _ in range(rounds):
        st, _ = eng_flat.run_round(st, data)
    _block(st)
    seq_s = time.perf_counter() - t0

    # --- fused paths: interleaved min-of-N, drift-robust ---
    times = {"flat": [], "tree": []}
    for _ in range(alts):
        for key, e in (("flat", eng_flat), ("tree", eng_tree)):
            t0 = time.perf_counter()
            st, _ = e.run_rounds(fresh(e), data, rounds)
            _block(st)
            times[key].append(time.perf_counter() - t0)
    flat_s, tree_s = min(times["flat"]), min(times["tree"])

    result = {
        "workload": {
            "algo": cfg.algo, "num_clients": cfg.num_clients,
            "cohort_size": cohort, "local_steps": K, "batch_size": B,
            "model": f"mlp {len(dims) - 1} layers ({2 * (len(dims) - 1)} leaves)",
            "rounds": rounds, "timing": f"interleaved min of {alts}",
        },
        "sequential_s": round(seq_s, 4),
        "tree_fused_s": round(tree_s, 4),
        "flat_fused_s": round(flat_s, 4),
        "sequential_rounds_per_s": round(rounds / seq_s, 2),
        "tree_fused_rounds_per_s": round(rounds / tree_s, 2),
        "flat_fused_rounds_per_s": round(rounds / flat_s, 2),
        "speedup": round(seq_s / flat_s, 2),
        "flat_vs_tree_speedup": round(tree_s / flat_s, 2),
    }
    if not quiet:
        print(f"== {name} ({result['workload']['model']}, C={cohort}, K={K}) ==")
        print(f"  sequential:  {seq_s:.3f}s  ({result['sequential_rounds_per_s']} rounds/s)")
        print(f"  tree-fused:  {tree_s:.3f}s  ({result['tree_fused_rounds_per_s']} rounds/s)")
        print(f"  flat-fused:  {flat_s:.3f}s  ({result['flat_fused_rounds_per_s']} rounds/s)")
        print(f"  fused vs sequential: {result['speedup']}x   "
              f"flat vs tree: {result['flat_vs_tree_speedup']}x")
    return result


def _measure_async(rounds, alts, quiet, depths=(1, 2, 4), scan_unroll=2):
    """Sync run_rounds vs run_rounds_async at D ∈ depths, update-bound shape.

    Reports two ratios per depth: ``*_vs_sync`` from interleaved min-of-N
    (comparable to the other workloads) and ``*_vs_sync_median`` — the
    median of per-alternation sync/async PAIRWISE ratios, which cancels
    the slow load drift of a shared host much better (each alternation
    measures the two back-to-back) and is the acceptance-bar number.
    """
    wl = WORKLOADS["update_bound"]
    dims, cohort, K, B = wl["dims"], wl["cohort"], wl["K"], wl["B"]
    cfg = FedConfig(algo="fedcm", num_clients=64, cohort_size=cohort,
                    local_steps=K, participation="fixed")
    x, y, *_ = make_synthetic_classification(
        n_classes=10, dim=dims[0], n_train=6400, n_test=10
    )
    data = FederatedData(x, y, cfg.num_clients, seed=0)
    model = mlp_classifier(dims)
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=B)

    def fresh():
        return eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))

    runners = {"sync": lambda: eng.run_rounds(fresh(), data, rounds)}
    for d in depths:
        runners[f"async_d{d}"] = (
            lambda d=d: eng.run_rounds_async(fresh(), data, rounds,
                                             pipeline_depth=d,
                                             scan_unroll=scan_unroll)
        )
    for r in runners.values():  # warm/compile outside the timed region
        st, _ = r()
        _block(st)
    times = {k: [] for k in runners}
    for _ in range(alts):  # interleaved, drift-robust
        for k, r in runners.items():
            t0 = time.perf_counter()
            st, _ = r()
            _block(st)
            times[k].append(time.perf_counter() - t0)
    best = {k: min(v) for k, v in times.items()}
    result = {
        "workload": {
            "algo": cfg.algo, "num_clients": cfg.num_clients,
            "cohort_size": cohort, "local_steps": K, "batch_size": B,
            "model": f"mlp {len(dims) - 1} layers ({2 * (len(dims) - 1)} leaves)",
            "rounds": rounds, "timing": f"interleaved min/median-pairwise of {alts}",
            "pipeline_depths": list(depths), "scan_unroll": scan_unroll,
        },
        "sync_s": round(best["sync"], 4),
        "sync_rounds_per_s": round(rounds / best["sync"], 2),
    }
    for d in depths:
        s = best[f"async_d{d}"]
        pairwise = sorted(sy / a for sy, a in zip(times["sync"], times[f"async_d{d}"]))
        med = pairwise[len(pairwise) // 2]
        result[f"async_d{d}_s"] = round(s, 4)
        result[f"async_d{d}_rounds_per_s"] = round(rounds / s, 2)
        result[f"async_d{d}_vs_sync"] = round(best["sync"] / s, 2)
        result[f"async_d{d}_vs_sync_median"] = round(med, 2)
    if not quiet:
        print(f"== async_pipeline ({result['workload']['model']}, C={cohort}, "
              f"K={K}, unroll={scan_unroll}) ==")
        print(f"  sync:        {best['sync']:.3f}s  ({result['sync_rounds_per_s']} rounds/s)")
        for d in depths:
            print(f"  async D={d}:   {best[f'async_d{d}']:.3f}s  "
                  f"({result[f'async_d{d}_rounds_per_s']} rounds/s, "
                  f"{result[f'async_d{d}_vs_sync']}x min / "
                  f"{result[f'async_d{d}_vs_sync_median']}x median vs sync)")
    return result


def _measure_algo_sweep(rounds, quiet, dims=(32, 64, 64, 10), cohort=8, K=2, B=16):
    """rounds/s per REGISTERED algorithm, flat plane + fused kernels.

    One timed fused scan per algorithm (compile excluded) on a small
    shared shape — the point is per-algorithm relative cost and that the
    registry-driven kernel routing executes for every spec, not absolute
    throughput (the other workloads own that).  Emits rounds/s per
    algorithm plus each one's ratio to fedcm."""
    x, y, *_ = make_synthetic_classification(
        n_classes=10, dim=dims[0], n_train=6400, n_test=10
    )
    model = mlp_classifier(dims)
    loss_fn = classification_loss(model.apply)
    result = {"workload": {
        "num_clients": 64, "cohort_size": cohort, "local_steps": K,
        "batch_size": B, "rounds": rounds,
        "model": f"mlp {len(dims) - 1} layers ({2 * (len(dims) - 1)} leaves)",
        "path": "flat + fused kernels",
    }, "rounds_per_s": {}}
    for algo in list_algorithms():
        cfg = FedConfig(algo=algo, num_clients=64, cohort_size=cohort,
                        local_steps=K, participation="fixed")
        eng = FederatedEngine(cfg, loss_fn, batch_size=B)
        data = FederatedData(x, y, cfg.num_clients, seed=0)

        def fresh():
            return eng.init(model.init(jax.random.PRNGKey(0)),
                            jax.random.PRNGKey(1))

        st, _ = eng.run_rounds(fresh(), data, rounds)  # warm/compile
        _block(st)
        t0 = time.perf_counter()
        st, _ = eng.run_rounds(fresh(), data, rounds)
        _block(st)
        dt = time.perf_counter() - t0
        result["rounds_per_s"][algo] = round(rounds / dt, 2)
    base = result["rounds_per_s"].get("fedcm") or 1.0
    result["vs_fedcm"] = {
        a: round(r / base, 2) for a, r in result["rounds_per_s"].items()
    }
    if not quiet:
        print(f"== algo_sweep ({result['workload']['model']}, C={cohort}, "
              f"K={K}, kernel path) ==")
        for a, r in sorted(result["rounds_per_s"].items()):
            print(f"  {a:<12} {r:>8} rounds/s  ({result['vs_fedcm'][a]}x fedcm)")
    return result


def _measure_compression(rounds, quiet, kinds=("none", "int8", "bf16", "topk")):
    """rounds/s + wire accounting per uplink compression kind.

    fedcm on the paper_scaled shape, flat + fused kernel (the dequant-fold
    route), one timed fused scan per kind.  Three numbers per kind, all
    from the SAME accounting the engine bills at runtime
    (``repro.core.compress``): per-client uplink bytes/round (from the
    run's ``bytes_up`` metric), the f32-relative reduction, and the async
    ring's per-slot in-flight bytes for the wire planes at this cohort —
    the D×cohort ring carries the COMPRESSED representation, so in-flight
    memory shrinks by the same factor the wire does."""
    import numpy as np

    from repro.configs.base import CompressionConfig
    from repro.core.compress import uplink_bytes_per_client
    from repro.core.registry import get_algorithm

    wl = WORKLOADS["paper_scaled"]
    dims, cohort, K, B = wl["dims"], wl["cohort"], wl["K"], wl["B"]
    x, y, *_ = make_synthetic_classification(
        n_classes=10, dim=dims[0], n_train=6400, n_test=10
    )
    model = mlp_classifier(dims)
    loss_fn = classification_loss(model.apply)
    spec_wire = get_algorithm("fedcm").wire_uplink_planes
    result = {"workload": {
        "algo": "fedcm", "num_clients": 64, "cohort_size": cohort,
        "local_steps": K, "batch_size": B, "rounds": rounds,
        "model": f"mlp {len(dims) - 1} layers ({2 * (len(dims) - 1)} leaves)",
        "path": "flat + fused kernels (dequant fold for int8/bf16)",
    }, "kinds": {}}
    base_bytes = None
    for kind in kinds:
        comp = (None if kind == "none"
                else CompressionConfig(kind=kind, topk_frac=0.05))
        cfg = FedConfig(algo="fedcm", num_clients=64, cohort_size=cohort,
                        local_steps=K, participation="fixed",
                        compression=comp)
        eng = FederatedEngine(cfg, loss_fn, batch_size=B)
        data = FederatedData(x, y, cfg.num_clients, seed=0)

        def fresh():
            return eng.init(model.init(jax.random.PRNGKey(0)),
                            jax.random.PRNGKey(1))

        st, ms = eng.run_rounds(fresh(), data, rounds)  # warm/compile
        _block(st)
        t0 = time.perf_counter()
        st, ms = eng.run_rounds(fresh(), data, rounds)
        _block(st)
        dt = time.perf_counter() - t0
        # bytes_up = n_active × per-client wire bytes; fixed participation
        # here, so n_active == cohort every round
        up = int(np.asarray(ms.bytes_up)[-1]) // cohort
        if base_bytes is None:
            base_bytes = up
        # ring slot = the wire planes of one in-flight cohort, as stored
        # (compressed on the kernel path) — size from the same pricing fn
        size = sum(int(l.size) for l in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))))
        ring = cohort * uplink_bytes_per_client(comp, spec_wire, size, size * 4)
        result["kinds"][kind] = {
            "rounds_per_s": round(rounds / dt, 2),
            "uplink_bytes_per_client": up,
            "reduction_x": round(base_bytes / up, 2),
            "ring_bytes_per_slot": ring,
        }
    f32_ring = result["kinds"][kinds[0]]["ring_bytes_per_slot"]
    for k in result["kinds"]:
        result["kinds"][k]["ring_reduction_x"] = round(
            f32_ring / result["kinds"][k]["ring_bytes_per_slot"], 2)
    if not quiet:
        print(f"== uplink_compression ({result['workload']['model']}, "
              f"C={cohort}, K={K}, kernel path) ==")
        for k, r in result["kinds"].items():
            print(f"  {k:<5} {r['rounds_per_s']:>8} rounds/s  "
                  f"{r['uplink_bytes_per_client']:>7} B/client "
                  f"({r['reduction_x']}x)  ring/slot "
                  f"{r['ring_bytes_per_slot']:>8} B ({r['ring_reduction_x']}x)")
    return result


def _measure_store_prefetch(rounds, alts, quiet, n_clients=256, cohort=16):
    """Host-store loop: synchronous vs double-buffered (store_prefetch).

    scaffold (client state makes the store gather/scatter real work) on the
    paper_scaled shape through ``run_rounds_store``; the prefetch loop
    overlaps the NEXT cohort's store gather + host batch build with the
    current round's device step.  The two loops are bitwise-identical by
    contract (tests assert it); this records what the overlap buys in
    rounds/s — interleaved min-of-N, plus the drift-robust median of
    pairwise per-alternation ratios."""
    wl = WORKLOADS["paper_scaled"]
    dims, K, B = wl["dims"], wl["K"], wl["B"]
    x, y, *_ = make_synthetic_classification(
        n_classes=10, dim=dims[0], n_train=6400, n_test=10
    )
    model = mlp_classifier(dims)
    loss_fn = classification_loss(model.apply)
    engines = {}
    for key, pf in (("sync", False), ("prefetch", True)):
        cfg = FedConfig(algo="scaffold", num_clients=n_clients,
                        cohort_size=cohort, local_steps=K,
                        participation="fixed",
                        population_store="host", store_prefetch=pf)
        engines[key] = FederatedEngine(cfg, loss_fn, batch_size=B)
    data = FederatedData(x, y, n_clients, seed=0)

    def run(eng):
        st = eng.init(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1))
        st, _ = eng.run_rounds(st, data, rounds)
        _block(st)

    for e in engines.values():  # warm/compile
        run(e)
    times = {k: [] for k in engines}
    for _ in range(alts):
        for k, e in engines.items():
            t0 = time.perf_counter()
            run(e)
            times[k].append(time.perf_counter() - t0)
    best = {k: min(v) for k, v in times.items()}
    pairwise = sorted(s / p for s, p in zip(times["sync"], times["prefetch"]))
    result = {
        "workload": {
            "algo": "scaffold", "num_clients": n_clients,
            "cohort_size": cohort, "local_steps": K, "batch_size": B,
            "rounds": rounds, "population_store": "host",
            "timing": f"interleaved min/median-pairwise of {alts}",
        },
        "sync_s": round(best["sync"], 4),
        "prefetch_s": round(best["prefetch"], 4),
        "sync_rounds_per_s": round(rounds / best["sync"], 2),
        "prefetch_rounds_per_s": round(rounds / best["prefetch"], 2),
        "prefetch_vs_sync": round(best["sync"] / best["prefetch"], 2),
        "prefetch_vs_sync_median": round(pairwise[len(pairwise) // 2], 2),
    }
    if not quiet:
        print(f"== store_prefetch (scaffold host store, N={n_clients}, "
              f"C={cohort}) ==")
        print(f"  sync loop:     {best['sync']:.3f}s  "
              f"({result['sync_rounds_per_s']} rounds/s)")
        print(f"  prefetch loop: {best['prefetch']:.3f}s  "
              f"({result['prefetch_rounds_per_s']} rounds/s, "
              f"{result['prefetch_vs_sync']}x min / "
              f"{result['prefetch_vs_sync_median']}x median vs sync)")
    return result


def write_trajectory_summary(result: dict) -> dict:
    """Append this run's rounds/s-per-workload row to the top-level
    ``BENCH_fused_rounds.json`` trajectory (one entry per commit — an
    existing entry for the same rev is replaced, so re-runs update in
    place).  Folds in the cohort-parallel sweep's artifact when
    ``benchmarks/cohort_sharded.py`` has run in this checkout AT THIS
    REV — a stale (checked-in, earlier-commit) artifact is flagged, not
    attributed to the current rev."""
    from benchmarks.common import git_rev

    entry = {
        "rev": git_rev(),
        "rounds_per_s": {
            "sequential": result["sequential_rounds_per_s"],
            "update_bound_tree": result["update_bound"]["tree_fused_rounds_per_s"],
            "update_bound_flat": result["update_bound"]["flat_fused_rounds_per_s"],
            "paper_scaled_flat": result["paper_scaled"]["flat_fused_rounds_per_s"],
            "async_d2": result["async_pipeline"]["async_d2_rounds_per_s"],
            "algo_sweep": result["algo_sweep"]["rounds_per_s"],
            "store_prefetch": result["store_prefetch"]["prefetch_rounds_per_s"],
            "store_sync": result["store_prefetch"]["sync_rounds_per_s"],
        },
        # wire accounting per compression kind (bytes/client, f32-relative
        # reduction, async ring in-flight bytes/slot) + kernel-path rounds/s
        "uplink_compression": result["uplink_compression"]["kinds"],
    }
    if COHORT_ARTIFACT.exists():
        cs = json.loads(COHORT_ARTIFACT.read_text())
        if cs.get("rev") == entry["rev"]:
            entry["cohort_sharded"] = {
                "devices_visible": cs.get("devices_visible"),
                "cpu_count": cs.get("cpu_count"),
            }
            for wl in ("update_bound", "update_bound_c64", "cohort_scaled"):
                if wl in cs:
                    row = cs[wl]
                    entry["cohort_sharded"][wl] = {
                        k: v for k, v in row.items()
                        if k.endswith(("rounds_per_s", "speedup"))
                    }
        else:
            entry["cohort_sharded"] = {"stale_rev": cs.get("rev")}
    if PARTICIPATION_ARTIFACT.exists():
        pr = json.loads(PARTICIPATION_ARTIFACT.read_text())
        if isinstance(pr, dict) and pr.get("rev") == entry["rev"]:
            # per-(N, regime, algo) accuracy + rounds/s of the host-store
            # population engine — the scenario harness's headline numbers
            entry["participation"] = [
                {k: row[k] for k in ("num_clients", "availability", "algo",
                                     "acc_final", "rounds_per_s")}
                for row in pr.get("rows", [])
            ]
        else:
            entry["participation"] = {
                "stale_rev": pr.get("rev") if isinstance(pr, dict) else "pre-harness"
            }
    if FAULT_ARTIFACT.exists():
        ft = json.loads(FAULT_ARTIFACT.read_text())
        if isinstance(ft, dict) and ft.get("rev") == entry["rev"]:
            # convergence-vs-fault-rate: acc per (algo, drop rate) plus the
            # degradation counters — the fault harness's headline numbers
            entry["fault_tolerance"] = [
                {k: row[k] for k in ("algo", "drop_rate", "acc_final",
                                     "params_finite", "n_dropped",
                                     "n_quarantined", "quorum_skipped")}
                for row in ft.get("rows", [])
            ]
        else:
            entry["fault_tolerance"] = {
                "stale_rev": ft.get("rev") if isinstance(ft, dict) else "pre-harness"
            }
    if BITS_ARTIFACT.exists():
        cb = json.loads(BITS_ARTIFACT.read_text())
        if isinstance(cb, dict) and cb.get("rev") == entry["rev"]:
            # convergence-vs-bits: acc per (algo, kind) + wire accounting —
            # the compressed-uplink harness's headline numbers
            entry["convergence_bits"] = [
                {k: row[k] for k in ("algo", "kind", "acc_final",
                                     "acc_vs_f32", "uplink_bytes_per_client",
                                     "reduction_x")}
                for row in cb.get("rows", [])
            ]
        else:
            entry["convergence_bits"] = {
                "stale_rev": cb.get("rev") if isinstance(cb, dict) else "pre-harness"
            }
    if FLEET_ARTIFACT.exists():
        from repro.fleet.telemetry import events, replay, round_rows

        try:
            header, rows, _ = replay(FLEET_ARTIFACT)
        except ValueError:
            header, rows = {"meta": {}}, []
        if header.get("meta", {}).get("rev") == entry["rev"]:
            # the --serve run's per-round record: throughput series with
            # eval points, plus the serving thread's swap/health summary
            rnds = round_rows(rows)
            summaries = events(rows, "serve_summary")
            probes = events(rows, "health_probe")
            entry["fleet"] = {
                "rounds": len(rnds),
                "rounds_per_s": [r["rounds_per_s"] for r in rnds],
                "eval_acc": [
                    {"round": r["round"], "acc": r["eval_acc"]}
                    for r in rnds if r.get("eval_acc") is not None
                ],
                "serve": ({k: summaries[-1].get(k) for k in
                           ("steps", "swaps", "swaps_mid_session",
                            "served_version")} if summaries else None),
                "health_status": probes[-1].get("status") if probes else None,
            }
        else:
            entry["fleet"] = {"stale_rev": header.get("meta", {}).get("rev")}
    data = {"trajectory": []}
    if BENCH_SUMMARY.exists():
        try:
            data = json.loads(BENCH_SUMMARY.read_text())
        except json.JSONDecodeError:
            pass
    traj = [e for e in data.get("trajectory", []) if e.get("rev") != entry["rev"]]
    traj.append(entry)
    data = {"trajectory": traj, "latest": entry}
    BENCH_SUMMARY.write_text(json.dumps(data, indent=1))
    return entry


def main(rounds: int = 60, alts: int = 8, quiet: bool = False) -> dict:
    result = {
        name: _measure(name, rounds=rounds, alts=alts, quiet=quiet, **wl)
        for name, wl in WORKLOADS.items()
    }
    result["async_pipeline"] = _measure_async(rounds, alts, quiet)
    result["algo_sweep"] = _measure_algo_sweep(rounds, quiet)
    result["uplink_compression"] = _measure_compression(rounds, quiet)
    result["store_prefetch"] = _measure_store_prefetch(
        rounds, max(2, alts // 2), quiet
    )
    # legacy top-level keys mirror the headline workload
    head = result["update_bound"]
    for k in ("sequential_s", "flat_fused_s", "tree_fused_s", "speedup",
              "flat_vs_tree_speedup"):
        result[k] = head[k]
    result["fused_s"] = head["flat_fused_s"]
    result["sequential_rounds_per_s"] = head["sequential_rounds_per_s"]
    result["fused_rounds_per_s"] = head["flat_fused_rounds_per_s"]
    ARTIFACT.parent.mkdir(parents=True, exist_ok=True)
    ARTIFACT.write_text(json.dumps(result, indent=1))
    write_trajectory_summary(result)
    if not quiet:
        print(f"  (artifact: {ARTIFACT.name}; trajectory: {BENCH_SUMMARY.name})")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--alts", type=int, default=8,
                    help="interleaved timing repetitions per path")
    args = ap.parse_args()
    main(rounds=args.rounds, alts=args.alts)
